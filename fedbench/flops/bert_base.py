"""Required operations and bytes of one training round of the BERT-base
classifier, from the configuration's shapes alone.

Per token and layer: Q, K, V and output projections (4 H^2
multiply-accumulates), the feed-forward pair (2 H F), and attention's
score and value products (2 L H at sequence length L, all heads
together). Per sample: the pooler (H^2) and the head (H * classes). The
token and position embeddings are table lookups: they are no matrix
product and count 0 — ``6 * n_params * tokens`` would count the 30,522 x
768 table as one (ROADMAP S3's "24 % gap"). Training is three times
the forward operations, two FLOPs a multiply-accumulate; real samples
only, no recomputation.
"""

BYTES = 2  # a bfloat16 operand


def forward_macs(config: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates of one sample of ``seq_len`` tokens,
    by part."""
    h, f = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    return {
        "embeddings": 0,
        "blocks": layers * seq_len * (4 * h * h + 2 * h * f),
        "attention": layers * 2 * seq_len * seq_len * h,
        "pooler": h * h,
        "head": h * config["num_labels"],
    }


def required(config: dict, job: dict) -> dict:
    """``job``: ``n_samples`` (list, one a client), ``batch``,
    ``local_epochs``, ``seq_len``. Every counted part is a matrix
    product, so the ``matmul`` kernel category's FLOPs are the round's.
    Its least bytes: weights read twice and their gradient written once
    a client and local step; each product's activations read and
    written once a pass and real token."""
    seq = job["seq_len"]
    macs = forward_macs(config, seq)
    total = sum(macs.values())
    samples = sum(job["n_samples"]) * job["local_epochs"]
    steps = sum(-(-n // job["batch"]) for n in job["n_samples"]) \
        * job["local_epochs"]
    h, f = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    weights = layers * (4 * h * h + 2 * h * f) + h * h \
        + h * config["num_labels"]
    # per token and layer: in and out of four H->H products, H->F and
    # F->H, and the two attention products' operands
    act = layers * seq * (4 * 2 * h + 2 * (h + f) + 2 * 3 * h)
    return {
        "flops_per_sample": 6 * total,
        "flops_per_token": 6 * total / seq,
        "flops_per_round": 6 * total * samples,
        "kernel": "matmul",
        "kernel_flops_per_round": 6 * total * samples,
        "kernel_bytes_per_round": 3 * BYTES * (act * samples
                                               + weights * steps),
        "forward_macs_per_sample": macs,
    }
