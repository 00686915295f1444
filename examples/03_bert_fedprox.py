"""BASELINE config 3: BERT federated text-classification fine-tune with
FedProx.

Non-IID text clients drift apart during multi-epoch local training;
FedProx adds a proximal term ``mu/2 · ||w − w_global||²`` to each
client's local objective (a pluggable regularizer on the jitted train
step — core/regularizers.py), keeping local updates anchored to the
broadcast round model. AG-News stands in as 4-class sequences of token
ids; swap ``make_data`` for a real tokenized loader.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.core.regularizers import fedprox
from baton_tpu.data.datasets import load_ag_news
from baton_tpu.data.partition import dirichlet_partition
from baton_tpu.models.bert import BertConfig, bert_classifier_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim


def make_ag_news_data(rng, cfg, n_clients, n_per_client, alpha=0.3,
                      data_dir=None):
    """Real AG-News (byte-tokenized) when the CSVs are cached, else the
    labelled synthetic surrogate; Dirichlet label-skew shards either way.
    Requires ``cfg.vocab_size >= 257`` (byte vocab)."""
    train, _test, info = load_ag_news(
        data_dir=data_dir, max_len=cfg.max_len, fallback="synthetic",
        seed=int(rng.integers(1 << 31)),
    )
    print(f"dataset: ag_news (synthetic={info['synthetic']})")
    n_keep = min(n_clients * n_per_client, len(train["y"]))
    sel = rng.permutation(len(train["y"]))[:n_keep]
    return dirichlet_partition({k: v[sel] for k, v in train.items()},
                               n_clients, rng, alpha=alpha)


def make_data(rng, cfg, n_clients, n_per_client):
    """Class-correlated token sequences: each class has a 'topic'
    distribution over the vocabulary; each client is skewed toward two
    classes (label heterogeneity, the FedProx setting)."""
    topics = rng.dirichlet(np.full(cfg.vocab_size, 0.1), size=cfg.n_classes)
    datasets = []
    for c in range(n_clients):
        fav = rng.choice(cfg.n_classes, size=2, replace=False)
        y = rng.choice(fav, size=n_per_client).astype(np.int32)
        x = np.stack([
            rng.choice(cfg.vocab_size, size=cfg.max_len, p=topics[label])
            for label in y
        ]).astype(np.int32)
        datasets.append({"x": x, "y": y})
    return datasets


def run(n_clients=8, n_per_client=24, n_rounds=3, n_epochs=2,
        batch_size=8, mu=0.1, config=None, seed=0,
        real_data=False, data_dir=None, remat=False):
    cfg = config or BertConfig.tiny(n_classes=4)
    if real_data and cfg.vocab_size < 257:
        # byte-level tokenizer emits ids 0..256 (PAD=256); a smaller
        # embedding table would silently clamp half the vocabulary
        # (JAX gathers clamp out-of-range indices rather than raise)
        import dataclasses as _dc

        cfg = _dc.replace(cfg, vocab_size=257)
    rng = np.random.default_rng(seed)
    shards = (
        make_ag_news_data(rng, cfg, n_clients, n_per_client, data_dir=data_dir)
        if real_data
        else make_data(rng, cfg, n_clients, n_per_client)
    )
    data, n_samples = stack_client_datasets(shards, batch_size=batch_size)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    # remat: recompute encoder-block activations in the backward pass —
    # what lets long-sequence full-scale cohorts fit HBM (models/bert.py)
    model = bert_classifier_model(cfg, remat=remat)
    sim = FedSim(model, batch_size=batch_size, learning_rate=5e-3,
                 regularizer=fedprox(mu=mu) if mu else None)
    params = sim.init(jax.random.key(seed))
    params, history = sim.run_rounds(
        params, data, n_samples, jax.random.key(seed + 1),
        n_rounds=n_rounds, n_epochs=n_epochs,
    )
    metrics = sim.evaluate_round(params, data, n_samples)
    print(f"FedProx(mu={mu}): loss {history[0]:.4f} -> {history[-1]:.4f}, "
          f"eval accuracy {metrics['accuracy']:.3f}")
    return history, metrics


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    p.add_argument("--mu", type=float, default=0.1)
    p.add_argument("--data-dir", default=None,
                   help="directory holding AG-News train.csv/test.csv")
    p.add_argument("--remat", action="store_true",
                   help="recompute encoder activations in backward (fits "
                        "bigger cohorts/sequences in HBM)")
    args = p.parse_args()
    if args.scale == "full":
        # byte-level vocab (257) needs vocab_size >= 257 on the model
        run(n_clients=64, n_per_client=1875, n_rounds=30, n_epochs=2,
            batch_size=32, mu=args.mu, real_data=True,
            data_dir=args.data_dir, remat=args.remat,
            config=BertConfig.base(n_classes=4, vocab_size=512))  # AG-News: 120k/64
    else:
        history, _ = run(mu=args.mu, real_data=bool(args.data_dir),
                         data_dir=args.data_dir, remat=args.remat)
        assert history[-1] < history[0], "loss should fall"
