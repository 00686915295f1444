"""Required operations and bytes of one training round of ResNet-18
(CIFAR variant), from the configuration's shapes alone.

A convolution with a k x k kernel, Cin inputs, Cout outputs and an
Ho x Wo output map needs Ho*Wo*k*k*Cin*Cout multiply-accumulates a
sample. Training needs the forward pass, the gradient to the input and
the gradient to the filter: three times the forward operations, two
FLOPs a multiply-accumulate. Real samples only: no padded slot, no
recomputation. GroupNorm, ReLU, the pooling and the loss are left out
of the count (under 1 % of it).
"""

BYTES = 2  # a bfloat16 operand


def conv_layers(config: dict) -> list:
    """``(name, out_px, kernel, c_in, c_out, stride)`` of every convolution, in
    order: stem, then two 3x3 convs a basic block and a 1x1 projection
    where a stage changes width or stride (models/resnet.py)."""
    px = config["image_px"] // config["stem_stride"]
    width = config["stage_widths"][0]
    layers = [("stem", px, config["stem_kernel"], config["image_channels"],
               width, config["stem_stride"])]
    c_in = width
    for s, (n_blocks, c_out) in enumerate(
            zip(config["blocks_per_stage"], config["stage_widths"])):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            px //= stride
            k = config["kernel"]
            layers.append((f"s{s}b{b}.conv1", px, k, c_in, c_out, stride))
            layers.append((f"s{s}b{b}.conv2", px, k, c_out, c_out, 1))
            if stride != 1 or c_in != c_out:
                layers.append((f"s{s}b{b}.proj", px, 1, c_in, c_out, stride))
            c_in = c_out
    return layers


def forward_macs(config: dict) -> dict:
    """Forward multiply-accumulates of one sample, by part."""
    conv = sum(px * px * k * k * ci * co
               for _, px, k, ci, co, _ in conv_layers(config))
    return {"conv": conv,
            "fc": config["stage_widths"][-1] * config["num_classes"]}


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``. Returns the round's required FLOPs, and for the
    ``conv`` kernel category its FLOPs and its least bytes: each pass
    reads its two operands and writes its result once, activations a
    real sample, filters once a client and local step."""
    macs = forward_macs(config)
    samples = sum(job["n_samples"]) * job["local_epochs"]
    steps = sum(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    act = filt = 0
    for _, px, k, ci, co, stride in conv_layers(config):
        act += ((px * stride) ** 2 * ci + px * px * co) * BYTES
        filt += k * k * ci * co * BYTES
    return {
        "flops_per_sample": 6 * (macs["conv"] + macs["fc"]),
        "flops_per_round": 6 * (macs["conv"] + macs["fc"]) * samples,
        "kernel": "conv",
        "kernel_flops_per_round": 6 * macs["conv"] * samples,
        # forward, input-gradient and filter-gradient passes each move
        # one activation pair and one filter
        "kernel_bytes_per_round": 3 * (act * samples + filt * steps),
        "forward_macs_per_sample": macs,
    }
