"""Char-LSTM federated language modeling — the FedAvg-paper Shakespeare
workload shape.

The original FedAvg paper's canonical non-vision benchmark: each client
is one speaking role's text, the model is a stacked character LSTM, and
rounds average the whole model. Here the roles are synthetic per-client
Markov "styles" (data/synthetic.py::synthetic_char_clients) so the
recipe runs offline; swap in real Shakespeare shards by replacing the
data call. The recurrence is a ``lax.scan`` (models/lstm.py), so the
multi-epoch local run still compiles into the engine's single round
program and vmaps over the client axis.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.data.synthetic import synthetic_char_clients
from baton_tpu.models.lstm import LSTMConfig, lstm_lm_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim


def run(n_clients=8, n_per_client=16, n_rounds=4, n_epochs=2, batch_size=8,
        seq_len=24, config=None, seed=0):
    cfg = config or LSTMConfig.tiny(vocab_size=16)
    rng = np.random.default_rng(seed)
    shards = synthetic_char_clients(
        rng, n_clients, n_per_client=n_per_client, seq_len=seq_len,
        vocab_size=cfg.vocab_size, order=1,
    )
    data, n_samples = stack_client_datasets(shards, batch_size=batch_size)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    model = lstm_lm_model(cfg)
    sim = FedSim(model, batch_size=batch_size, learning_rate=0.5)
    params = sim.init(jax.random.key(seed))
    params, history = sim.run_rounds(
        params, data, n_samples, jax.random.key(seed + 1),
        n_rounds=n_rounds, n_epochs=n_epochs,
    )
    metrics = sim.evaluate_round(params, data, n_samples)
    chance = float(np.log(cfg.vocab_size))
    print(f"char-LSTM FedAvg: loss {history[0]:.4f} -> {history[-1]:.4f} "
          f"(chance {chance:.4f}); eval loss {metrics['loss']:.4f}")
    return history, metrics


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    args = p.parse_args()
    if args.scale == "full":
        # FedAvg-paper shape: 2x256 LSTM over a 90-char alphabet
        run(n_clients=64, n_per_client=256, n_rounds=50, n_epochs=1,
            batch_size=32, seq_len=80, config=LSTMConfig.shakespeare())
    else:
        history, _ = run()
        assert history[-1] < history[0], "loss should fall"
