"""BASELINE config 4: Llama-class LoRA federated instruction-tune.

Each client trains ONLY low-rank adapters on the attention projections
(:func:`llama_lora_target`); the frozen base is replicated once and
never ships per-client, so client state and the FedAvg aggregate are
both tiny (rank·(d_in+d_out) per target matrix instead of d_in·d_out).
``trainable=lora_trainable`` makes the engine train and aggregate the
adapter sub-pytree only — base weights stay byte-identical across
rounds (asserted below).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.models.llama import LlamaConfig, llama_lm_model, llama_lora_target
from baton_tpu.models.lora import lora_trainable, lora_wrap, merge_lora_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim


def make_data(rng, cfg, n_clients, n_per_client):
    """Instruction-tune stand-in: token sequences with the 'prompt' half
    masked out of the loss (loss_mask 0) and the 'response' half kept."""
    datasets = []
    half = cfg.max_len // 2
    for _ in range(n_clients):
        toks = rng.integers(
            0, cfg.vocab_size, size=(n_per_client, cfg.max_len)
        ).astype(np.int32)
        mask = np.concatenate([
            np.zeros((n_per_client, half), np.float32),
            np.ones((n_per_client, cfg.max_len - half), np.float32),
        ], axis=1)
        datasets.append({"x": toks, "y": toks, "loss_mask": mask})
    return datasets


def run(n_clients=4, n_per_client=8, n_rounds=2, n_epochs=1, batch_size=4,
        rank=4, config=None, seed=0):
    cfg = config or LlamaConfig.tiny()
    rng = np.random.default_rng(seed)
    data, n_samples = stack_client_datasets(
        make_data(rng, cfg, n_clients, n_per_client), batch_size=batch_size
    )
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    base = llama_lm_model(cfg)
    model = lora_wrap(base, rank=rank, target=llama_lora_target)
    sim = FedSim(model, batch_size=batch_size, learning_rate=1e-2,
                 trainable=lora_trainable)
    params = sim.init(jax.random.key(seed))
    base_before = jax.tree_util.tree_leaves(params["base"])

    params, history = sim.run_rounds(
        params, data, n_samples, jax.random.key(seed + 1),
        n_rounds=n_rounds, n_epochs=n_epochs,
    )
    for a, b in zip(jax.tree_util.tree_leaves(params["base"]), base_before):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n_adapter = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(params["lora"])
    )
    n_base = sum(int(np.prod(np.asarray(l).shape)) for l in base_before)
    print(f"LoRA rank={rank}: {n_adapter:,} trainable / {n_base:,} frozen "
          f"params ({100 * n_adapter / n_base:.2f}%)")
    print(f"loss: {history[0]:.4f} -> {history[-1]:.4f}")

    # deploy: fold adapters into the base weights (zero inference cost)
    merged_params = merge_lora_model(model, params)
    return history, merged_params


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    args = p.parse_args()
    if args.scale == "full":
        # Llama-3-8B-shaped config, 64 clients (BASELINE config 4) —
        # needs a pod slice; adapters-only keeps per-client state ~MB
        run(n_clients=64, n_per_client=512, n_rounds=10, batch_size=8,
            rank=16,
            config=LlamaConfig(vocab_size=128_256, d_model=4096,
                               n_layers=32, n_heads=32, n_kv_heads=8,
                               d_ff=14336, max_len=1024))
    else:
        history, _ = run()
        assert history[-1] < history[0], "loss should fall"
