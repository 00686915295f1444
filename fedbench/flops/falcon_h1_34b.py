"""Required operations and bytes of one training round of the
``falcon_h1_34b`` stage under LoRA, from the configuration's shapes
alone; real tokens only, no recomputation. The conventions are
``fedbench/flops/olmo_hybrid_7b.py``'s.

Per token, in multiply-accumulates, by part:

- a **frozen product** runs forward and for the gradient of its input:
  2 passes, 4 FLOPs a multiply-accumulate. ``ssm_proj`` is the
  state-space branch's ``in_proj`` and ``out_proj`` with its 4-tap
  convolution, ``attn_proj`` the attention branch's four projections,
  ``mlp`` the three of the MLP, ``head`` the untied head over the held
  slice. The multipliers, the gates, the norms and the rotation are
  elementwise and count 0;
- an **adapter** ``(x A) B`` of rank r on a ``[d_in, d_out]`` projection
  is ``r (d_in + d_out)`` and trains: 3 passes, 6 FLOPs;
- the **attention core** under the causal mask: each of the 20 query
  heads against ``(L + 1) / 2`` keys on average, 128 channels of scores
  and 128 of values; both operands are activations: 3 passes;
- the **recurrence** as its lines are written, whatever implements it,
  per head and token: the state's decay half a multiply-accumulate an
  entry, the rank-one update ``delta B xs^T`` one and the read-out ``C^T
  S`` one, ``2.5 x 256 x 128``, and ``D xs``, 128; 3 passes. Its
  **least bytes**, a pass: ``xs`` read and ``y`` written in bfloat16,
  ``B`` and ``C`` read once a group (2, not once a head), ``delta`` and
  the decay ``a`` read in float32, the state never leaving the chip.

The embedding is a lookup and counts 0. ``kernel`` is ``matmul``: every
counted part but the recurrence's elementwise half is a matrix product.
Least bytes of that kernel: weights once a pass and local step (the
wave's clients share one product), each product's activations in and
out once a pass and real token.
"""

BYTES = 2  # a bfloat16 operand


def _projections(config: dict) -> dict:
    """``[d_in, d_out]`` of the nine adapted projections, by part."""
    h, f, d = (config["hidden_size"], config["intermediate_size"],
               config["head_dim"])
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    d_ssm = config["mamba_n_heads"] * config["mamba_d_head"]
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    return {
        "ssm_proj": [(h, 2 * d_ssm + 2 * bc + config["mamba_n_heads"]),
                     (d_ssm, h)],
        "attn_proj": [(h, q), (h, kv), (h, kv), (q, h)],
        "mlp": [(h, f), (h, f), (f, h)],
    }


def per_token_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one token, by part."""
    layers, r = config["num_hidden_layers"], config["lora_rank"]
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    states = config["mamba_d_state"]
    conv = config["mamba_d_conv"] * (
        heads * width + 2 * config["mamba_n_groups"] * states)
    macs = {part: layers * sum(a * b for a, b in shapes)
            for part, shapes in _projections(config).items()}
    macs["ssm_proj"] += layers * conv
    macs.update({
        "head": config["hidden_size"] * config["vocab_size"],
        "adapters": layers * sum(
            r * (a + b) for shapes in _projections(config).values()
            for a, b in shapes),
        "attention": layers * config["num_attention_heads"] * 2
        * config["head_dim"] * (seq_len + 1) / 2,
        "scan": layers * heads * (2.5 * states * width + width),
    })
    return macs


def scan_bytes_per_token(config: dict) -> float:
    """Least bytes of the recurrence, a token and pass over all layers."""
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    return config["num_hidden_layers"] * (
        BYTES * (2 * heads * width + 2 * bc) + 4 * 2 * heads)


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``, ``seq_len``."""
    seq = job["seq_len"]
    macs = per_token_macs(config, seq)
    frozen = macs["ssm_proj"] + macs["attn_proj"] + macs["mlp"] + macs["head"]
    flops_per_token = 4 * frozen + 6 * (
        macs["adapters"] + macs["attention"] + macs["scan"])
    samples = sum(job["n_samples"]) * job["local_epochs"]
    tokens = samples * seq
    steps = max(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    layers = config["num_hidden_layers"]
    # activations in and out of every product, a token and pass
    act = (layers * sum(a + b for shapes in _projections(config).values()
                        for a, b in shapes)
           + config["hidden_size"] + config["vocab_size"])
    scan_flops = 6 * macs["scan"] * tokens
    # the state's decay and ``D xs`` are no matrix products
    elementwise = layers * config["mamba_n_heads"] * config["mamba_d_head"] \
        * (0.5 * config["mamba_d_state"] + 1)
    return {
        "flops_per_sample": flops_per_token * seq,
        "flops_per_token": flops_per_token,
        "flops_per_round": flops_per_token * tokens,
        "kernel": "matmul",
        "kernel_flops_per_round": (flops_per_token - 6 * elementwise)
        * tokens,
        "kernel_bytes_per_round": 2 * BYTES * (frozen * steps + act * tokens),
        "ssd_scan_flops_per_round": scan_flops,
        "ssd_scan_bytes_per_round": 3 * scan_bytes_per_token(config) * tokens,
        "forward_macs_per_token": macs,
    }
