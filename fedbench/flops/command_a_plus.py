"""Required operations and bytes of one training round of the
``command_a_plus`` rank under LoRA, from the configuration's shapes
alone; real tokens only, no recomputation of a layer. The conventions are
``fedbench/flops/mellum2_12b.py``'s and ``sarvam_105b.py``'s.

Per token, in multiply-accumulates:

- a **frozen product** (the four attention projections of the heads
  held, the router, the routed and the shared experts, the tied head)
  runs forward and for the gradient of its input: 2 passes, 4 FLOPs a
  multiply-accumulate;
- an **adapter** ``(x A) B`` of rank r on a ``[d_in, d_out]`` projection
  is ``r (d_in + d_out)`` and trains: 3 passes, 6 FLOPs. The shared
  experts' three are on the wide matrices, ``[4096, 16384]`` twice and
  ``[16384, 4096]``: one adapter each, not four;
- the **attention cores** by the pairs of a query and a key it sees
  (:func:`pairs_seen`): a full layer's query at position ``t`` sees ``t +
  1`` keys, a sliding layer's ``min(t + 1, sliding_window)``: 33,558,528
  and 25,167,872 a head and sequence of 8,192 (75.0 %). A pair is a
  multiply-accumulate over the head's 128 channels in each of seven
  products (``mellum2_12b.py`` has which): 3.5 x 4 x 128 = 1,792 FLOPs;
- the **routed experts**: ``required(config, job)`` sees no routing, so
  it takes the expectation: a token's ``num_experts_per_tok`` choices
  fall on the ``num_experts`` held of ``num_experts_published`` with
  probability held / published each: half a row of three ``[4096,
  4096]`` products a token and layer;
- the **shared experts**: every token through all
  ``num_shared_experts`` of them, three ``[4096, 4096]`` products each.
  The mean over the four is a scale and counts 0.

The embedding is a lookup and counts 0. ``kernel`` is ``matmul``: every
counted part is a matrix product. Least bytes: weights once a pass and
local step (the wave's clients share one product), each product's
activations in and out once a pass; a core's are q, k, v and the output
and the gradients of the four, each moved once: the query heads' width
four times and the key-value heads' four times, a token (the gradients
of k and v are written once a key-value head, whatever the kernel
writes).
"""

BYTES = 2  # a bfloat16 operand
PASSES_A_PAIR = 3.5  # of scores-and-values: forward 1, backward 2.5


def pairs_seen(seq_len: int, window=None) -> int:
    """Pairs (query, key) of one head over one sequence in which the
    query sees the key: every key up to itself, or with ``window`` itself
    and the ``window - 1`` before it."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _layers(config: dict) -> tuple:
    """``(sliding, full)``: how many layers of each kind are run."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    sliding = sum(k == "sliding_attention" for k in kinds)
    return sliding, len(kinds) - sliding


def _projections(config: dict) -> list:
    """``[d_in, d_out]`` of the four adapted attention projections."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return [(h, q), (h, kv), (h, kv), (q, h)]


def _shared_projections(config: dict) -> list:
    """``[d_in, d_out]`` of the shared experts' three wide matrices."""
    h = config["hidden_size"]
    wide = config["num_shared_experts"] * config["intermediate_size"]
    return [(h, wide), (h, wide), (wide, h)]


def _routed_rows_per_token(config: dict) -> float:
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_experts_published"])


def per_token_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one token, by part; the cores' by
    the mean number of keys a query sees."""
    h, v = config["hidden_size"], config["vocab_size"]
    fe, d = config["intermediate_size"], config["head_dim"]
    hq = config["num_attention_heads"]
    layers, r = config["num_hidden_layers"], config["lora_rank"]
    sliding, full = _layers(config)
    proj, shared = _projections(config), _shared_projections(config)
    window = config["sliding_window"]
    return {
        "frozen": layers * (sum(a * b for a, b in proj)
                            + h * config["num_experts_published"]),
        "shared": layers * sum(a * b for a, b in shared),
        "experts": layers * _routed_rows_per_token(config) * 3 * h * fe,
        "head": h * v,
        "adapters": layers * sum(r * (a + b) for a, b in proj),
        "shared_adapters": layers * sum(r * (a + b) for a, b in shared),
        "window_core": sliding * hq * 2 * d
        * pairs_seen(seq_len, window) / seq_len,
        "full_core": full * hq * 2 * d * pairs_seen(seq_len) / seq_len,
    }


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``, ``seq_len``."""
    seq = job["seq_len"]
    macs = per_token_macs(config, seq)
    core = 2 * PASSES_A_PAIR  # FLOPs a multiply-accumulate of a core
    flops_per_token = (
        4 * (macs["frozen"] + macs["shared"] + macs["experts"] + macs["head"])
        + 6 * (macs["adapters"] + macs["shared_adapters"])
        + core * (macs["window_core"] + macs["full_core"]))
    samples = sum(job["n_samples"]) * job["local_epochs"]
    tokens = samples * seq
    steps = max(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    h, v = config["hidden_size"], config["vocab_size"]
    fe, d = config["intermediate_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    sliding, full = _layers(config)
    rows = _routed_rows_per_token(config)
    wide = config["num_shared_experts"] * fe
    stacks = layers * config["num_experts"] * 3 * h * fe
    weights = macs["frozen"] + macs["shared"] + macs["head"] + stacks
    # activations in and out of every product, a token and pass
    shared_act = layers * (3 * h + 3 * wide)
    act = (layers * (sum(a + b for a, b in _projections(config))
                     + h + config["num_experts_published"]
                     + rows * (3 * h + 3 * fe))
           + shared_act + h + v)
    # q, k, v, the output and the gradients of the four, each once
    core_bytes_a_token = BYTES * 4 * (hq + hkv) * d
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
        "shared_expert_flops_per_round": (
            4 * macs["shared"] + 6 * macs["shared_adapters"]) * tokens,
        "shared_expert_bytes_per_round": 2 * BYTES * (
            macs["shared"] * steps + shared_act * tokens),
        "window_core_flops_per_round": core * macs["window_core"] * tokens,
        "window_core_bytes_per_round": sliding * core_bytes_a_token * tokens,
        "full_core_flops_per_round": core * macs["full_core"] * tokens,
        "full_core_bytes_per_round": full * core_bytes_a_token * tokens,
        "forward_macs_per_token": macs,
    }
