"""Advanced aggregation modes in one tour: robust, async, personalized,
clustered.

The reference has exactly one aggregation story — synchronous
sample-weighted FedAvg over every reporting client (reference
manager.py:109-132). This recipe shows the standard departures the
framework adds, on one shared non-IID setup:

1. **Byzantine robustness** (``aggregator="median"``): one poisoned
   client wrecks the weighted mean but not the coordinate median.
2. **Asynchronous FedBuff** (:class:`baton_tpu.parallel.FedBuff`):
   overlapping clients, buffered staleness-discounted updates — no
   round barrier at all.
3. **Partial personalization** (:class:`baton_tpu.parallel.FedPer`):
   label-permuted shards where one global head is impossible but
   per-client heads are trivial.
4. **Clustered FL** (:class:`baton_tpu.parallel.ClusteredFedSim`,
   IFCA): a two-population mixture separates into its K=2 models.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.data.synthetic import DEMO_COEF, linear_client_data
from baton_tpu.models.linear import linear_regression_model
from baton_tpu.models.mlp import mlp_classifier_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel import ClusteredFedSim, FedBuff, FedPer, FedSim


def run(n_clients=8, n_rounds=6, seed=0):
    rng = np.random.default_rng(seed)
    out = {}

    # shared linear setup (the reference demo's data distribution)
    data, n = stack_client_datasets(
        [linear_client_data(rng) for _ in range(n_clients)], batch_size=32
    )
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n = jnp.asarray(n)
    model = linear_regression_model(10)

    # -- 1. robust aggregation under poisoning --------------------------
    poisoned = dict(data)
    poisoned["y"] = poisoned["y"].at[0].mul(1e5)
    for spec in ("mean", "median"):
        sim = FedSim(model, batch_size=32, learning_rate=0.02,
                     aggregator=spec)
        p = sim.init(jax.random.key(seed))
        for r in range(n_rounds):
            p = sim.run_round(
                p, poisoned, n, jax.random.fold_in(jax.random.key(1), r),
                n_epochs=4,
            ).params
        err = float(np.max(np.abs(np.asarray(p["w"]).ravel() - DEMO_COEF)))
        out[f"poisoned_{spec}_err"] = err
        print(f"1. poisoned cohort, aggregator={spec:7s}: coef error {err:.3g}")

    # -- 2. asynchronous FedBuff ---------------------------------------
    sim = FedSim(model, batch_size=32, learning_rate=0.02)
    fb = FedBuff(sim, buffer_size=2, concurrency=n_clients, alpha=0.5)
    res = fb.run(sim.init(jax.random.key(seed)), data, n,
                 jax.random.key(2), n_steps=n_rounds * 8, n_epochs=2)
    err = float(np.max(np.abs(np.asarray(res.params["w"]).ravel() - DEMO_COEF)))
    out["fedbuff_err"] = err
    out["fedbuff_staleness"] = res.mean_staleness
    print(f"2. FedBuff async: mean staleness {res.mean_staleness:.2f}, "
          f"coef error {err:.3g}")

    # -- 3. personalization on label-permuted shards -------------------
    k, d = 4, 8
    protos = rng.normal(size=(k, d)).astype(np.float32) * 3.0
    shards = []
    for _ in range(n_clients):
        perm = rng.permutation(k)
        y = rng.integers(0, k, size=64).astype(np.int32)
        x = protos[y] + 0.3 * rng.normal(size=(64, d)).astype(np.float32)
        shards.append({"x": x, "y": perm[y].astype(np.int32)})
    pdata, pn = stack_client_datasets(shards, batch_size=16)
    pdata = {kk: jnp.asarray(v) for kk, v in pdata.items()}
    pn = jnp.asarray(pn)

    mlp = mlp_classifier_model(d, (16,), k)
    sim = FedSim(mlp, batch_size=16, learning_rate=0.1)
    params = sim.init(jax.random.key(seed))

    pg = params
    for r in range(n_rounds + 4):
        pg = sim.run_round(pg, pdata, pn,
                           jax.random.fold_in(jax.random.key(3), r),
                           n_epochs=2).params
    acc_glob = sim.evaluate_round(pg, pdata, pn)["accuracy"]

    fp = FedPer(sim, personal=lambda path, leaf: path.startswith("1/"))
    p, pers = params, None
    for r in range(n_rounds + 4):
        rr = fp.run_round(p, pers, pdata, pn,
                          jax.random.fold_in(jax.random.key(3), r),
                          n_epochs=2)
        p, pers = rr.params, rr.personal_state
    acc_pers = fp.evaluate(p, pers, pdata, pn)["accuracy"]
    out["global_acc"] = float(acc_glob)
    out["personalized_acc"] = float(acc_pers)
    print(f"3. label-permuted shards: global acc {acc_glob:.3f}, "
          f"personalized acc {acc_pers:.3f}")

    # -- 4. clustered FL on a two-population mixture --------------------
    coef_b = -DEMO_COEF
    shards2, pops = [], []
    # IFCA needs a few clients per population to break symmetry from a
    # random init — keep at least 4 per population regardless of scale
    per_pop = max(n_clients // 2, 4)
    for pop, coef in ((0, DEMO_COEF), (1, coef_b)):
        for _ in range(per_pop):
            xx = rng.normal(size=(64, 10)).astype(np.float32)
            yy = (xx @ coef + 0.1 * rng.normal(size=64)).astype(np.float32)
            shards2.append({"x": xx, "y": yy})
            pops.append(pop)
    cdata, cn = stack_client_datasets(shards2, batch_size=32)
    cdata = {kk: jnp.asarray(v) for kk, v in cdata.items()}
    cn = jnp.asarray(cn)
    csim = FedSim(model, batch_size=32, learning_rate=0.05)
    cf = ClusteredFedSim(csim, n_clusters=2)
    clusters = cf.init_clusters(jax.random.key(seed))
    for r in range(n_rounds + 8):
        rr = cf.run_round(clusters, cdata, cn,
                          jax.random.fold_in(jax.random.key(4), r),
                          n_epochs=2)
        clusters = rr.cluster_params
    pops = np.asarray(pops)
    sep = bool(np.all(rr.assignments == pops)
               or np.all(rr.assignments == 1 - pops))
    out["clusters_separated"] = sep
    out["clustered_loss"] = cf.evaluate(clusters, cdata, cn)["loss"]
    print(f"4. two-population mixture: clusters separated={sep}, "
          f"clustered eval loss {out['clustered_loss']:.4f}")
    return out


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    args = p.parse_args()
    if args.scale == "full":
        out = run(n_clients=32, n_rounds=20)
    else:
        out = run()
    assert out["poisoned_median_err"] < 1.0 < out["poisoned_mean_err"]
    assert out["fedbuff_err"] < 1.0
    assert out["personalized_acc"] > out["global_acc"]
    assert out["clusters_separated"] and out["clustered_loss"] < 1.0
