"""Required operations and bytes of one training round of the
``olmo_hybrid_7b`` stage under LoRA, from the configuration's shapes
alone; real tokens only, no recomputation.

Per token, in multiply-accumulates:

- a **frozen product** (the base's projections, the 4-tap convolutions,
  the head) runs forward and for the gradient of its input; its weight
  gradient is never needed: 2 passes, 4 FLOPs a multiply-accumulate;
- an **adapter** ``(x A) B`` of rank r on a ``[d_in, d_out]`` projection
  is ``r (d_in + d_out)`` and trains: 3 passes, 6 FLOPs;
- **full attention**'s score and value products are ``2 L H`` at
  sequence length L (all heads together, the whole square as the
  program and the reference compute it); both operands are activations:
  3 passes;
- the **recurrence** of a linear-attention layer, per head and token as
  its five lines are written (``S k``: d_k d_v; the rank-one update:
  d_k d_v; ``S q``: d_k d_v; the decay of the state: half a
  multiply-accumulate an entry) is ``3.5 d_k d_v``, 3 passes. Its
  **least bytes** are its operands once a pass in bfloat16 with the
  gates in float32, the state never leaving the chip: forward reads q,
  k, v, alpha, beta and writes o; backward reads those and o's gradient
  and writes five gradients.

The embedding is a lookup and counts 0. ``kernel`` is ``matmul``: every
counted part but the recurrence's elementwise half is a matrix product.
"""

BYTES = 2  # a bfloat16 operand
TAPS = "linear_conv_kernel_dim"


def _layers(config: dict) -> tuple:
    """``(linear, full)``: how many layers of each kind are run."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    linear = sum(k == "linear_attention" for k in kinds)
    return linear, len(kinds) - linear


def per_token_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one token, by part."""
    h, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    heads = config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    qk, vv = heads * d_k, heads * d_v
    r = config["lora_rank"]
    linear, full = _layers(config)
    # [d_in, d_out] of every adapted projection, by kind of sub-layer
    lin_proj = [(h, qk), (h, qk), (h, vv), (h, vv), (vv, h)]
    full_proj = [(h, h)] * 4
    mlp_proj = [(h, f), (h, f), (f, h)]

    def frozen(shapes):
        return sum(a * b for a, b in shapes)

    def adapters(shapes):
        return sum(r * (a + b) for a, b in shapes)

    return {
        "frozen": linear * (frozen(lin_proj) + 2 * h * heads
                            + config[TAPS] * (2 * qk + vv))
        + full * frozen(full_proj)
        + (linear + full) * frozen(mlp_proj),
        "head": h * v,
        "adapters": linear * adapters(lin_proj) + full * adapters(full_proj)
        + (linear + full) * adapters(mlp_proj),
        "attention": full * 2 * seq_len * h,
        "scan": linear * heads * 3.5 * d_k * d_v,
    }


def scan_bytes_per_token(config: dict) -> float:
    """Least bytes of the recurrence, a token over all linear layers."""
    heads = config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    forward = BYTES * (2 * d_k + 2 * d_v) + 4 * 2
    backward = BYTES * (2 * d_k + 3 * d_v) + 4 * 2 \
        + BYTES * (2 * d_k + d_v) + 4 * 2
    return _layers(config)[0] * heads * (forward + backward)


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``, ``seq_len``. The matmul kernel's least bytes: the
    frozen weights read once a pass and local step (the clients of a
    wave share one product), each product's activations in and out once
    a pass and real token."""
    seq = job["seq_len"]
    macs = per_token_macs(config, seq)
    flops_per_token = (4 * (macs["frozen"] + macs["head"])
                       + 6 * (macs["adapters"] + macs["attention"]
                              + macs["scan"]))
    samples = sum(job["n_samples"]) * job["local_epochs"]
    tokens = samples * seq
    steps = max(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    h, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    heads = config["linear_num_value_heads"]
    vv = heads * config["linear_value_head_dim"]
    qk = heads * config["linear_key_head_dim"]
    linear, full = _layers(config)
    weights = macs["frozen"] + macs["head"]
    act = (linear * (5 * h + 2 * qk + 3 * vv) + full * 8 * h
           + (linear + full) * (3 * h + 3 * f) + h + v)
    scan_flops = 6 * macs["scan"] * tokens
    return {
        "flops_per_sample": flops_per_token * seq,
        "flops_per_token": flops_per_token,
        "flops_per_round": flops_per_token * tokens,
        "kernel": "matmul",
        "kernel_flops_per_round": flops_per_token * tokens - scan_flops / 2,
        "kernel_bytes_per_round": 2 * BYTES * (weights * steps
                                               + act * tokens),
        "scan_flops_per_round": scan_flops,
        "scan_bytes_per_round": scan_bytes_per_token(config) * tokens,
        "forward_macs_per_token": macs,
    }
