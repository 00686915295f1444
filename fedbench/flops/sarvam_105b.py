"""Required operations and bytes of one training round of the
``sarvam_105b`` stage under LoRA, from the configuration's shapes
alone; real tokens only, no recomputation.

Per token, in multiply-accumulates:

- a **frozen product** (the base's projections, the router, the routed
  and shared experts, the head) runs forward and for the gradient of
  its input; its weight gradient is never needed: 2 passes, 4 FLOPs a
  multiply-accumulate;
- an **adapter** ``(x A) B`` of rank r on a ``[d_in, d_out]`` projection
  is ``r (d_in + d_out)`` and trains: 3 passes, 6 FLOPs;
- the **attention core** of a latent-attention layer under the causal
  mask: a query of 192 channels against ``(L + 1) / 2`` keys on average
  and their values of 128, all heads; both operands are activations:
  3 passes;
- the **routed experts**: ``required(config, job)`` sees no routing, so
  it takes the expectation: a token's ``num_experts_per_tok`` choices
  fall on the ``num_experts`` held of ``num_experts_published`` with
  probability held / published each, ``T * 8 * 16 / 128`` rows of three
  ``[4096, 2048]`` products a layer. ``chip_smoke.py``'s
  ``moe_mla_lora`` phase prints how far a seed's share lies from it.

The embedding is a lookup and counts 0. ``kernel`` is ``matmul``: every
counted part is a matrix product. The grouped products' least bytes are
the held stacks once a pass and local step (the wave's clients share
one product) and each routed row's activations in and out once a pass;
the attention core's are q, k, v and the output once a pass.
"""

BYTES = 2  # a bfloat16 operand


def _layers(config: dict) -> tuple:
    """``(dense, expert)``: how many layers of each kind are run."""
    dense = min(config["first_k_dense_replace"], config["num_hidden_layers"])
    return dense, config["num_hidden_layers"] - dense


def _routed_rows_per_token(config: dict) -> float:
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_experts_published"])


def per_token_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one token, by part."""
    h, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    fe = config["moe_intermediate_size"]
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    d_v, rank = config["v_head_dim"], config["kv_lora_rank"]
    r = config["lora_rank"]
    dense, expert = _layers(config)
    shared = config["num_shared_experts"] * fe
    # [d_in, d_out] of every adapted projection, by kind of sub-layer
    mla_proj = [(h, heads * qk), (h, rank + config["qk_rope_head_dim"]),
                (rank, heads * (config["qk_nope_head_dim"] + d_v)),
                (heads * d_v, h)]
    mlp_proj = [(h, f), (h, f), (f, h)]
    shared_proj = [(h, shared), (h, shared), (shared, h)]

    def frozen(shapes):
        return sum(a * b for a, b in shapes)

    def adapters(shapes):
        return sum(r * (a + b) for a, b in shapes)

    return {
        "frozen": (dense + expert) * frozen(mla_proj)
        + dense * frozen(mlp_proj)
        + expert * (frozen(shared_proj) + h * config["num_experts_published"]),
        "experts": expert * _routed_rows_per_token(config) * 3 * h * fe,
        "head": h * v,
        "adapters": (dense + expert) * adapters(mla_proj)
        + dense * adapters(mlp_proj) + expert * adapters(shared_proj),
        "attention": (dense + expert) * heads * (qk + d_v)
        * (seq_len + 1) / 2,
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
    h, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    fe = config["moe_intermediate_size"]
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    d_v, rank = config["v_head_dim"], config["kv_lora_rank"]
    dense, expert = _layers(config)
    layers = dense + expert
    rows = _routed_rows_per_token(config)
    stacks = expert * config["num_experts"] * 3 * h * fe
    weights = macs["frozen"] + macs["head"] + stacks
    shared = config["num_shared_experts"] * fe
    # activations in and out of every product, a token and pass
    act = (layers * (2 * h + heads * qk + rank + config["qk_rope_head_dim"]
                     + rank + heads * (config["qk_nope_head_dim"] + d_v)
                     + heads * d_v + h)
           + dense * (3 * h + 3 * f)
           + expert * (3 * h + 3 * shared + h
                       + config["num_experts_published"]
                       + rows * (3 * h + 3 * fe))
           + h + v)
    expert_flops = 4 * macs["experts"] * tokens
    core_flops = 6 * macs["attention"] * tokens
    # q, k, v in and o out forward; those, o's gradient in and three
    # gradients out backward
    core_bytes = BYTES * layers * heads * (
        (2 * qk + 2 * d_v) + (2 * qk + 2 * d_v) + (2 * qk + d_v)) * tokens
    return {
        "flops_per_sample": flops_per_token * seq,
        "flops_per_token": flops_per_token,
        "flops_per_round": flops_per_token * tokens,
        "kernel": "matmul",
        "kernel_flops_per_round": flops_per_token * tokens,
        "kernel_bytes_per_round": 2 * BYTES * (weights * steps
                                               + act * tokens),
        "expert_flops_per_round": expert_flops,
        "expert_bytes_per_round": 2 * BYTES * (
            stacks * steps + expert * rows * (3 * h + 3 * fe) * tokens),
        "mla_core_flops_per_round": core_flops,
        "mla_core_bytes_per_round": core_bytes,
        "forward_macs_per_token": macs,
    }
