"""BASELINE config 5: ViT cross-silo federation with DP-SGD and secure
aggregation.

Two privacy layers compose:

* **DP-SGD inside each silo** (``dp=DPConfig(...)`` on the engine):
  per-example gradients are clipped to ``clip_norm`` and Gaussian noise
  is added every local step — all inside the jitted train step via
  vmapped per-example grads (ops/privacy.py). The RDP accountant
  reports the resulting (epsilon, delta).
* **Secure aggregation across silos** (ops/secure_agg.py): each silo's
  update is quantized to a modular integer ring and masked with
  pairwise-cancelling noise, so the server only ever sees the SUM —
  demonstrated here by masking each client's round delta and checking
  the unmasked sum matches plain FedAvg.

This recipe runs the *offline* masking primitives against a simulated
cohort. For real multi-process federations, the HTTP control plane
speaks the full Bonawitz double-masking protocol — key agreement,
Shamir-shared self masks, threshold unmasking with dropout recovery —
via ``Experiment(secure_agg=True)`` (baton_tpu/server/secure.py;
driven end-to-end in tests/test_secure_http.py).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.models.vit import ViTConfig, vit_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.ops.privacy import (
    DPConfig,
    poisson_sample,
    rdp_epsilon,
    subsampled_rdp_epsilon,
)
from baton_tpu.ops.secure_agg import aggregate_masked, mask_update
from baton_tpu.parallel.engine import FedSim


def make_data(rng, cfg, n_clients, n_per_client):
    protos = rng.standard_normal(
        (cfg.n_classes, cfg.image_size, cfg.image_size, 3)
    ).astype(np.float32)
    datasets = []
    for _ in range(n_clients):
        y = rng.integers(0, cfg.n_classes, size=n_per_client).astype(np.int32)
        x = protos[y] + 0.5 * rng.standard_normal(
            (n_per_client, cfg.image_size, cfg.image_size, 3)
        ).astype(np.float32)
        datasets.append({"x": x, "y": y})
    return datasets


def run(n_clients=4, n_per_client=16, n_rounds=2, n_epochs=1, batch_size=8,
        clip_norm=1.0, noise_multiplier=0.5, delta=1e-5, config=None,
        seed=0, remat=False):
    cfg = config or ViTConfig.tiny()
    rng = np.random.default_rng(seed)
    data, n_samples = stack_client_datasets(
        make_data(rng, cfg, n_clients, n_per_client), batch_size=batch_size
    )
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    dp = DPConfig(clip_norm=clip_norm, noise_multiplier=noise_multiplier)
    # remat matters doubly under DP: per-example gradients multiply
    # activation memory by the batch, so recompute-not-store is often
    # the difference between fitting and OOM (models/vit.py)
    model = vit_model(cfg, remat=remat)
    sim = FedSim(model, batch_size=batch_size, learning_rate=1e-2, dp=dp)
    params = sim.init(jax.random.key(seed))

    # Poisson client sampling each round: amplification-by-subsampling
    # needs the cohort drawn independently per round, not a fixed schedule
    cohort_rate = 1.0 if n_clients <= 2 else 0.75
    history = []
    for r in range(n_rounds):
        cohort = poisson_sample(rng, n_clients, cohort_rate)
        if cohort.size == 0:  # empty cohort: round is a no-op
            continue
        res = sim.run_round(params, data, n_samples,
                            jax.random.fold_in(jax.random.key(seed + 1), r),
                            n_epochs=n_epochs,
                            client_indices=cohort)
        params = res.params
        history.extend(float(x) for x in res.loss_history)

    steps = n_rounds * n_epochs * (int(data["x"].shape[1]) // batch_size)
    eps = rdp_epsilon(noise_multiplier, steps, delta)
    # Amplified bound: each local step touches a batch_size/n_per_client
    # Poisson fraction of a silo's examples (the standard DP-SGD
    # accounting approximation for shuffled batches)
    q = batch_size / n_per_client
    eps_amp = subsampled_rdp_epsilon(noise_multiplier, steps, delta, q)
    print(f"DP-SGD: clip {clip_norm}, noise x{noise_multiplier} -> "
          f"epsilon {eps:.2f} at delta={delta} after {steps} local steps "
          f"({eps_amp:.2f} with subsampling amplification at q={q:.3f})")
    print(f"loss: {history[0]:.4f} -> {history[-1]:.4f}")

    # --- secure aggregation of one round's client deltas -------------
    seed_key = jax.random.key(seed + 7)
    flat = lambda t: jax.tree_util.tree_leaves(t)
    deltas = []
    for c in range(n_clients):
        client = {k: v[c] for k, v in data.items()}
        one, n1 = jax.tree_util.tree_map(lambda a: a[None], client), n_samples[c:c + 1]
        res = sim.run_round(params, one, n1, jax.random.key(100 + c),
                            n_epochs=1, collect_client_losses=False)
        deltas.append(jax.tree_util.tree_map(
            lambda new, old: np.asarray(new, np.float32) - np.asarray(old, np.float32),
            res.params, params,
        ))
    masked = [mask_update(d, seed_key, i, n_clients)
              for i, d in enumerate(deltas)]
    unmasked_sum = aggregate_masked(masked)
    plain_sum = jax.tree_util.tree_map(
        lambda *xs: sum(np.asarray(x, np.float64) for x in xs), *deltas
    )
    err = max(
        float(np.max(np.abs(np.asarray(a, np.float64) - b)))
        for a, b in zip(flat(unmasked_sum), flat(plain_sum))
    )
    print(f"secure agg: masked-sum error vs plain sum {err:.2e} "
          f"(server never saw an individual update)")
    assert err < 1e-3
    return history, eps


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    p.add_argument("--remat", action="store_true",
                   help="recompute encoder activations in backward (per-"
                        "example DP grads make this the HBM lever)")
    args = p.parse_args()
    if args.scale == "full":
        run(n_clients=16, n_per_client=4096, n_rounds=20, batch_size=64,
            config=ViTConfig.b16(), remat=args.remat)
    else:
        history, _ = run(remat=args.remat)
        assert np.isfinite(history[-1])
