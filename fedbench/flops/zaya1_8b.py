"""Required operations and bytes of one training round of the
``zaya1_8b`` stage under LoRA, from the configuration's shapes alone;
real tokens only, no recomputation. The conventions are
``fedbench/flops/sarvam_105b.py``'s.

Per token, in multiply-accumulates:

- a **frozen product** (the mixer's five projections, the second
  convolution, which is a ``[256, 128]`` product a head, the router's
  down-projection and MLP, the routed experts, the head) runs forward
  and for the gradient of its input: 2 passes, 4 FLOPs a
  multiply-accumulate. The first convolution (two products a channel),
  the q-k mean, the norms, the rotation, the value shift and the merges
  are elementwise and count 0;
- an **adapter** ``(x A) B`` of rank r on a ``[d_in, d_out]`` projection
  is ``r (d_in + d_out)`` and trains: 3 passes, 6 FLOPs;
- the **attention core** under the causal mask: each of the 8 query
  heads against ``(L + 1) / 2`` keys on average, 128 channels of scores
  and 128 of values; both operands are activations: 3 passes;
- the **routed experts** by expectation, over the rows whose choice is
  not the skip: ``required(config, job)`` sees no routing, so a token's
  one choice falls on one of the 16 experts with probability 16 / 17,
  what a uniform router over the 17 outputs gives. The configuration's
  draw of the router is balanced to that (``assumed.router_init``: the
  skip took 5.3 to 6.5 % of the tokens against 1 / 17 = 5.9 %), and
  ``chip_smoke.py``'s ``cca_lora`` phase prints a seed's share a layer.
  Every expert is held, so no choice falls elsewhere.

The embedding is a lookup and counts 0; the head is the same table and
counts once, as a product. ``kernel`` is ``matmul``. Least bytes:
weights once a pass and local step (the wave's clients share one
product), each product's activations in and out once a pass; the
core's are the 8 query heads in and the 8 output heads out, and the
keys and values once a key-value head (2 of them, not once a query
head), a pass.
"""

BYTES = 2  # a bfloat16 operand


def _routed_rows_per_token(config: dict) -> float:
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_outputs"]


def _mixer_projections(config: dict) -> list:
    """``[d_in, d_out]`` of the five adapted projections."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return [(h, q), (h, kv), (h, d), (h, d), (q, h)]


def per_token_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one token, by part."""
    h, v = config["hidden_size"], config["vocab_size"]
    fe, d = config["moe_intermediate_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    rh, outputs = config["router_hidden_size"], config["router_outputs"]
    layers, r = config["num_hidden_layers"], config["lora_rank"]
    proj = _mixer_projections(config)
    conv = (hq + hkv) * config["cca_time1"] * d * d
    router = h * rh + 2 * rh * rh + rh * outputs
    return {
        "frozen": layers * (sum(a * b for a, b in proj) + conv + router),
        "experts": layers * _routed_rows_per_token(config) * 3 * h * fe,
        "head": h * v,
        "adapters": layers * sum(r * (a + b) for a, b in proj),
        "attention": layers * hq * 2 * d * (seq_len + 1) / 2,
    }


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``, ``seq_len``."""
    seq = job["seq_len"]
    macs = per_token_macs(config, seq)
    flops_per_token = (4 * (macs["frozen"] + macs["experts"] + macs["head"])
                       + 6 * (macs["adapters"] + macs["attention"]))
    samples = sum(job["n_samples"]) * job["local_epochs"]
    tokens = samples * seq
    steps = max(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    h, v = config["hidden_size"], config["vocab_size"]
    fe, d = config["moe_intermediate_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    rh, outputs = config["router_hidden_size"], config["router_outputs"]
    layers = config["num_hidden_layers"]
    rows = _routed_rows_per_token(config)
    stacks = layers * config["num_experts"] * 3 * h * fe
    weights = macs["frozen"] + macs["head"] + stacks
    latent = (hq + hkv) * d
    # activations in and out of every product, a token and pass
    act = (layers * (sum(a + b for a, b in _mixer_projections(config))
                     + (config["cca_time1"] + 1) * latent
                     + h + 4 * rh + outputs
                     + rows * (3 * h + 3 * fe))
           + h + v)
    # forward q, k, v in and o out; backward those and o's gradient in,
    # three gradients out: six passes over the 8 query heads' width and
    # six over the 2 key-value heads'
    core_bytes = BYTES * layers * 6 * latent * tokens
    return {
        "flops_per_sample": flops_per_token * seq,
        "flops_per_token": flops_per_token,
        "flops_per_round": flops_per_token * tokens,
        "kernel": "matmul",
        "kernel_flops_per_round": flops_per_token * tokens,
        "kernel_bytes_per_round": 2 * BYTES * (weights * steps
                                               + act * tokens),
        "expert_flops_per_round": 4 * macs["experts"] * tokens,
        "expert_bytes_per_round": 2 * BYTES * (
            stacks * steps + layers * rows * (3 * h + 3 * fe) * tokens),
        "cca_core_flops_per_round": 6 * macs["attention"] * tokens,
        "cca_core_bytes_per_round": core_bytes,
        "skip_share": 1.0 - config["num_experts"] / outputs,
        "forward_macs_per_token": macs,
    }
