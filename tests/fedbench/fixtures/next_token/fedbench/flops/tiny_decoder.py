"""Required operations of the fixture decoder: per token and layer the
attention projections (with grouped key and value heads), the SwiGLU
feed-forward's three products and attention's two; per token the output
head. Training is three times the forward, two FLOPs a
multiply-accumulate."""


def required(config: dict, job: dict) -> dict:
    h, f = config["hidden_size"], config["intermediate_size"]
    kv = h * config["num_key_value_heads"] // config["num_attention_heads"]
    seq = job["seq_len"]
    per_token = config["num_hidden_layers"] * (
        2 * h * h + 2 * h * kv + 3 * h * f + 2 * seq * h
    ) + h * config["vocab_size"]
    samples = sum(job["n_samples"]) * job["local_epochs"]
    flops = 6 * per_token * seq
    return {"flops_per_sample": flops, "flops_per_round": flops * samples,
            "kernel": "matmul", "kernel_flops_per_round": flops * samples,
            "kernel_bytes_per_round": 0}
