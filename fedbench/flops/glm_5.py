"""Required operations and bytes of one training round of the ``glm_5``
stage under LoRA, from the configuration's shapes alone; real tokens
only, no recomputation. The conventions are
``fedbench/flops/sarvam_105b.py``'s.

Per token, in multiply-accumulates:

- a **frozen product** (the base's projections, the router, the routed
  and shared experts, the head) runs forward and for the gradient of
  its input: 2 passes, 4 FLOPs a multiply-accumulate;
- an **adapter** ``(x A) B`` of rank r on a ``[d_in, d_out]`` projection
  is ``r (d_in + d_out)`` and trains: 3 passes, 6 FLOPs;
- the **attention core over the chosen keys alone**: a query at
  position ``t`` attends ``min(t + 1, index_topk)`` keys, on average
  over a sequence of ``L`` ``(topk (topk + 1) / 2 + (L - topk) topk) /
  L`` (1,792.125 at 8,192 and 2,048: ``selected_share`` 43.75 % of the
  ``(L + 1) / 2`` a full causal core sees), 256 channels of scores and
  256 of values, all heads; both operands are activations: 3 passes. A
  kernel that visits every causal pair and masks is held to the same
  count as one that gathers;
- the **indexer**: its three projections (from the queries' latent and
  from the block's input) and ``index_n_heads x index_head_dim`` a
  causal pair for the scores; nothing differentiates it: 1 pass, 2
  FLOPs. The selection itself is comparisons and counts 0;
- the **routed experts** by expectation: a token's 8 choices fall on
  the 8 held of 256 with probability held / published each.

The embedding is a lookup and counts 0. ``kernel`` is ``matmul``. Least
bytes: weights once a pass and local step, each product's activations
in and out once a pass; the core's are q, k, v and the output once a
pass (the choice, a byte a pair where a mask carries it, is the
kernel's own and counts 0); the indexer's are its inputs, its queries,
keys and weights, and its own weights once a step (the scores, which a
fused kernel would not write, count 0).
"""

BYTES = 2  # a bfloat16 operand


def _layers(config: dict) -> tuple:
    """``(dense, expert)``: how many layers of each kind are run."""
    dense = min(config["first_k_dense_replace"], config["num_hidden_layers"])
    return dense, config["num_hidden_layers"] - dense


def _routed_rows_per_token(config: dict) -> float:
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["n_routed_experts_published"])


def keys_per_query(seq_len: int, topk: int) -> float:
    """Mean over a sequence's queries of ``min(t + 1, topk)``."""
    k = min(topk, seq_len)
    return (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len


def per_token_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one token, by part."""
    h, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    fe = config["moe_intermediate_size"]
    heads = config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    qk, d_v = nope + rot, config["v_head_dim"]
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    ih, idim = config["index_n_heads"], config["index_head_dim"]
    r = config["lora_rank"]
    dense, expert = _layers(config)
    layers = dense + expert
    shared = config["n_shared_experts"] * fe
    # [d_in, d_out] of every adapted projection, by kind of sub-layer
    mla_proj = [(h, q_rank), (q_rank, heads * qk), (h, rank + rot),
                (rank, heads * (nope + d_v)), (heads * d_v, h)]
    mlp_proj = [(h, f), (h, f), (f, h)]
    shared_proj = [(h, shared), (h, shared), (shared, h)]
    index_proj = [(q_rank, ih * idim), (h, idim), (h, ih)]

    def frozen(shapes):
        return sum(a * b for a, b in shapes)

    def adapters(shapes):
        return sum(r * (a + b) for a, b in shapes)

    return {
        "frozen": layers * frozen(mla_proj) + dense * frozen(mlp_proj)
        + expert * (frozen(shared_proj)
                    + h * config["n_routed_experts_published"]),
        "experts": expert * _routed_rows_per_token(config) * 3 * h * fe,
        "head": h * v,
        "adapters": layers * adapters(mla_proj) + dense * adapters(mlp_proj)
        + expert * adapters(shared_proj),
        "attention": layers * heads * (qk + d_v)
        * keys_per_query(seq_len, config["index_topk"]),
        "indexer": layers * (frozen(index_proj)
                             + ih * idim * (seq_len + 1) / 2),
    }


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``, ``seq_len``."""
    seq = job["seq_len"]
    macs = per_token_macs(config, seq)
    flops_per_token = (4 * (macs["frozen"] + macs["experts"] + macs["head"])
                       + 6 * (macs["adapters"] + macs["attention"])
                       + 2 * macs["indexer"])
    samples = sum(job["n_samples"]) * job["local_epochs"]
    tokens = samples * seq
    steps = max(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    h, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    fe = config["moe_intermediate_size"]
    heads = config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    qk, d_v = nope + rot, config["v_head_dim"]
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    ih, idim = config["index_n_heads"], config["index_head_dim"]
    dense, expert = _layers(config)
    layers = dense + expert
    rows = _routed_rows_per_token(config)
    stacks = expert * config["n_routed_experts"] * 3 * h * fe
    index_weights = layers * (q_rank * ih * idim + h * idim + h * ih)
    weights = macs["frozen"] + macs["head"] + stacks
    shared = config["n_shared_experts"] * fe
    # activations in and out of every product, a token and pass
    act = (layers * (h + q_rank + q_rank + heads * qk + h + rank + rot
                     + rank + heads * (nope + d_v) + heads * d_v + h)
           + dense * (3 * h + 3 * f)
           + expert * (3 * h + 3 * shared + h
                       + config["n_routed_experts_published"]
                       + rows * (3 * h + 3 * fe))
           + h + v)
    # q, k, v in and o out forward; those, o's gradient in and three
    # gradients out backward
    core_bytes = BYTES * layers * heads * (
        (2 * qk + 2 * d_v) + (2 * qk + 2 * d_v) + (2 * qk + d_v)) * tokens
    index_act = layers * (q_rank + 2 * h + 2 * ih * idim + 2 * idim + 2 * ih)
    return {
        "flops_per_sample": flops_per_token * seq,
        "flops_per_token": flops_per_token,
        "flops_per_round": flops_per_token * tokens,
        "kernel": "matmul",
        "kernel_flops_per_round": flops_per_token * tokens,
        "kernel_bytes_per_round": BYTES * (
            2 * (weights * steps + act * tokens)
            + index_weights * steps + index_act * tokens),
        "expert_flops_per_round": 4 * macs["experts"] * tokens,
        "expert_bytes_per_round": 2 * BYTES * (
            stacks * steps + expert * rows * (3 * h + 3 * fe) * tokens),
        "sparse_core_flops_per_round": 6 * macs["attention"] * tokens,
        "sparse_core_bytes_per_round": core_bytes,
        "indexer_flops_per_round": 2 * macs["indexer"] * tokens,
        "indexer_bytes_per_round": BYTES * (index_weights * steps
                                            + index_act * tokens),
        "selected_share": keys_per_query(seq, config["index_topk"])
        / ((seq + 1) / 2),
        "forward_macs_per_token": macs,
    }
