"""FedSim engine: vmap / shard_map / wave equivalence + convergence.

The three execution modes must produce the same round output (the
weighted mean is associative in its sums), and federated training of the
demo-parity linear model must converge to the generating coefficients —
the TPU-native analogue of watching demo.py losses fall (SURVEY §4).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from baton_tpu.data.synthetic import linear_client_data, DEMO_COEF
from baton_tpu.models.linear import linear_regression_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim, _acc_tree_add
from baton_tpu.parallel.mesh import (client_sharding, make_mesh,
                                     shard_client_arrays)


@pytest.fixture
def linear_setup(nprng):
    model = linear_regression_model(10)
    datasets = [
        linear_client_data(nprng, min_batches=2, max_batches=4) for _ in range(8)
    ]
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    params = model.init(jax.random.key(0))
    return model, params, data, jnp.asarray(n_samples)


def test_round_matches_manual_fedavg(linear_setup):
    """One engine round == manually training each client and applying the
    reference weighted-mean formula (manager.py:119-126 oracle)."""
    model, params, data, n_samples = linear_setup
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    res = sim.run_round(params, data, n_samples, jax.random.key(7), n_epochs=2)

    # manual: per-client training with the same per-client rngs
    rngs = jax.random.split(jax.random.key(7), int(n_samples.shape[0]))
    client_params = []
    client_losses = []
    for i in range(int(n_samples.shape[0])):
        d = {k: v[i] for k, v in data.items()}
        p, _, l = sim.trainer.train(params, d, n_samples[i], rngs[i], 2)
        client_params.append(p)
        client_losses.append(np.asarray(l))
    w = np.asarray(n_samples, np.float64)
    want_w = sum(
        np.asarray(p["w"], np.float64) * wi for p, wi in zip(client_params, w)
    ) / w.sum()
    np.testing.assert_allclose(np.asarray(res.params["w"]), want_w, rtol=1e-5)
    want_loss = sum(l * wi for l, wi in zip(client_losses, w)) / w.sum()
    np.testing.assert_allclose(np.asarray(res.loss_history), want_loss, rtol=1e-5)
    assert res.client_losses.shape == (8, 2)


def test_wave_scheduling_equals_single_wave(linear_setup):
    model, params, data, n_samples = linear_setup
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    full = sim.run_round(params, data, n_samples, jax.random.key(3), n_epochs=1)
    waved = sim.run_round(
        params, data, n_samples, jax.random.key(3), n_epochs=1, wave_size=3
    )
    np.testing.assert_allclose(
        np.asarray(full.params["w"]), np.asarray(waved.params["w"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(full.loss_history), np.asarray(waved.loss_history), rtol=1e-5
    )




def test_short_final_wave_smaller_than_pad(nprng):
    """Regression: 5 clients with wave_size=4 leaves a 1-client final wave
    needing 3 phantom clients — more than it has real rngs to slice."""
    model = linear_regression_model(10)
    datasets = [linear_client_data(nprng, min_batches=2, max_batches=2) for _ in range(5)]
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)
    params = model.init(jax.random.key(0))
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    full = sim.run_round(params, data, n_samples, jax.random.key(3), n_epochs=1)
    waved = sim.run_round(
        params, data, n_samples, jax.random.key(3), n_epochs=1, wave_size=4
    )
    np.testing.assert_allclose(
        np.asarray(full.params["w"]), np.asarray(waved.params["w"]), rtol=1e-5
    )


def test_client_sampling(linear_setup):
    model, params, data, n_samples = linear_setup
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    idx = np.asarray([0, 3, 5])
    res = sim.run_round(
        params, data, n_samples, jax.random.key(2), n_epochs=1, client_indices=idx
    )
    assert res.client_losses.shape == (3, 1)
    assert float(res.n_samples_total) == float(np.asarray(n_samples)[idx].sum())


def test_federated_convergence_to_true_coefficients(nprng):
    """Multi-round FedAvg recovers the demo's generating vector
    (the reference's implicit success criterion, demo.py:52-59)."""
    model = linear_regression_model(10)
    datasets = [linear_client_data(nprng, min_batches=3, max_batches=6) for _ in range(4)]
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    sim = FedSim(model, batch_size=32, learning_rate=0.02)
    params = model.init(jax.random.key(0))
    params, history = sim.run_rounds(
        params, data, jnp.asarray(n_samples), jax.random.key(1), n_rounds=10, n_epochs=4
    )
    assert history[-1] < history[0] * 0.01
    np.testing.assert_allclose(
        np.asarray(params["w"]).ravel(), DEMO_COEF, atol=0.5
    )


def test_server_optimizer_fedavg_identity(linear_setup):
    """FedOpt with sgd(1.0) must reduce exactly to FedAvg assignment."""
    model, params, data, n_samples = linear_setup
    plain = FedSim(model, batch_size=32, learning_rate=0.01)
    fedopt = FedSim(
        model, batch_size=32, learning_rate=0.01, server_optimizer=optax.sgd(1.0)
    )
    r1 = plain.run_round(params, data, n_samples, jax.random.key(4), n_epochs=1)
    r2 = fedopt.run_round(params, data, n_samples, jax.random.key(4), n_epochs=1)
    np.testing.assert_allclose(
        np.asarray(r1.params["w"]), np.asarray(r2.params["w"]), rtol=1e-5
    )


def test_run_round_progress_fn_reports_each_wave(linear_setup):
    """progress_fn (the simulated-cohort mid-round heartbeat) fires once
    per completed wave with (waves_done, n_waves), in order."""
    model, params, data, n_samples = linear_setup
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    calls = []
    res = sim.run_round(params, data, n_samples, jax.random.key(3),
                        n_epochs=1, wave_size=3,
                        progress_fn=lambda d, t: calls.append((d, t)))
    assert calls == [(1, 3), (2, 3), (3, 3)], calls
    assert np.isfinite(float(res.loss_history[-1]))


def test_robust_aggregators_match_manual_oracle(linear_setup):
    """aggregator="trimmed:r"/"median" == manually training each client
    and applying ops/aggregation's order statistic (unweighted, real
    participants only)."""
    model, params, data, n_samples = linear_setup
    c = int(n_samples.shape[0])
    rngs = jax.random.split(jax.random.key(7), c)
    sim0 = FedSim(model, batch_size=32, learning_rate=0.01)
    client_params = []
    for i in range(c):
        d = {k: v[i] for k, v in data.items()}
        p, _, _ = sim0.trainer.train(params, d, n_samples[i], rngs[i], 1)
        client_params.append(p)
    stacked = {
        "w": jnp.stack([p["w"] for p in client_params]),
        "b": jnp.stack([p["b"] for p in client_params]),
    }
    from baton_tpu.ops import aggregation as agg

    for spec, oracle in (
        ("trimmed:0.2", lambda s: agg.trimmed_mean(s, 0.2)),
        ("median", agg.coordinate_median),
    ):
        sim = FedSim(model, batch_size=32, learning_rate=0.01,
                     aggregator=spec)
        res = sim.run_round(params, data, n_samples, jax.random.key(7),
                            n_epochs=1, wave_size=3)
        want = oracle(stacked)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(res.params[k]), np.asarray(want[k]), rtol=1e-5,
                atol=1e-6,
            )


def test_robust_aggregator_survives_poisoned_client(linear_setup):
    """One client's data scaled by 1e4 wrecks the weighted mean but not
    the coordinate median."""
    model, params, data, n_samples = linear_setup
    data = dict(data)
    data["y"] = data["y"].at[0].mul(1e4)  # client 0 trains on garbage

    res_mean = FedSim(model, batch_size=32, learning_rate=0.01).run_round(
        params, data, n_samples, jax.random.key(3), n_epochs=1)
    res_med = FedSim(model, batch_size=32, learning_rate=0.01,
                     aggregator="median").run_round(
        params, data, n_samples, jax.random.key(3), n_epochs=1)

    from baton_tpu.data.synthetic import DEMO_COEF

    err_mean = float(np.max(np.abs(np.asarray(res_mean.params["w"]).ravel()
                                   - DEMO_COEF)))
    err_med = float(np.max(np.abs(np.asarray(res_med.params["w"]).ravel()
                                  - DEMO_COEF)))
    assert err_med < 15.0 < err_mean, (err_med, err_mean)


def test_bad_aggregator_spec_rejected(linear_setup):
    import pytest

    model, *_ = linear_setup
    for bad in ("trimmed:0.5", "trimmed:-0.1", "krum", ""):
        with pytest.raises(ValueError):
            FedSim(model, aggregator=bad)



def test_evaluate_clients_fairness(linear_setup):
    """Per-client eval: weighted recombination matches evaluate_round,
    zero-sample clients are NaN, fairness block is consistent."""
    model, params, data, n_samples = linear_setup
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    n0 = np.asarray(n_samples).copy()
    n0[3] = 0  # client 3 contributes nothing
    out = sim.evaluate_clients(params, data, jnp.asarray(n0),
                               jax.random.key(0), wave_size=3)
    pc = out["per_client"]
    assert pc["loss"].shape == (8,)
    assert np.isnan(pc["loss"][3]) and np.isfinite(pc["loss"][0])
    # example-weighted recombination == the aggregate eval
    agg_eval = sim.evaluate_round(params, data, jnp.asarray(n0),
                                  jax.random.key(0))
    valid = pc["n"] > 0
    recombined = float(np.sum(pc["loss"][valid] * pc["n"][valid])
                       / np.sum(pc["n"][valid]))
    np.testing.assert_allclose(recombined, agg_eval["loss"], rtol=1e-5)
    f = out["fairness"]
    assert f["n_clients"] == 7 and f["metric"] == "loss"
    # loss: "worst" is the HIGHEST loss (direction-aware tail)
    assert f["worst"] == float(np.nanmax(pc["loss"]))
    assert f["worst_decile"] <= f["worst"]
    assert f["worst"] >= f["mean"]


def test_auto_wave_size_from_memory_plan(nprng, monkeypatch):
    """wave_size="auto" productizes the OOM guard: the wave size comes
    from XLA's static memory plan vs the device budget, halving until
    it fits, with per-shape caching on the run_round path."""
    from baton_tpu.models.linear import linear_regression_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.utils import profiling

    model = linear_regression_model(6)
    datasets = [{
        "x": nprng.normal(size=(8, 6)).astype(np.float32),
        "y": nprng.normal(size=(8,)).astype(np.float32),
    } for _ in range(8)]
    data, n = stack_client_datasets(datasets, batch_size=8)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    sim = FedSim(model, batch_size=8, learning_rate=0.1)
    params = sim.init(jax.random.key(0))

    # a generous budget: the whole cohort fits in one wave
    assert sim.auto_wave_size(params, data, n, budget_gb=64.0) is None

    # a budget under the full-cohort plan but above the halved plans:
    # auto must halve at least once and return a smaller wave
    full_plan = sim.wave_plan_gb(params, data, n, jax.random.key(0))
    if full_plan is not None:  # CPU surfaces memory analysis today
        w = sim.auto_wave_size(params, data, n,
                               budget_gb=full_plan * 0.9)
        assert w is not None and w < 8

    # nothing fits: refuse rather than risk the OOM (only assertable
    # where the backend surfaces memory analysis at all)
    if full_plan is not None:
        with pytest.raises(RuntimeError, match="no wave size"):
            sim.auto_wave_size(params, data, n, budget_gb=1e-12)

    # robust aggregators execute a different (params-stacking) kernel:
    # sizing from the sums kernel would lie, so auto refuses
    sim_robust = FedSim(model, batch_size=8, learning_rate=0.1,
                        aggregator="median")
    with pytest.raises(NotImplementedError, match="wave_size"):
        sim_robust.auto_wave_size(params, data, n, budget_gb=64.0)

    # the budget table holds accelerators only: on a device it does not
    # know, "auto" asks for a budget instead of guessing one
    with pytest.raises(ValueError, match="no HBM budget"):
        sim.run_round(params, data, jnp.asarray(n), jax.random.key(1),
                      wave_size="auto")
    monkeypatch.setitem(profiling.HBM_BUDGET_GB, "cpu", 64.0)

    # end-to-end through run_round, decision cached per cohort shape
    res = sim.run_round(params, data, jnp.asarray(n), jax.random.key(1),
                        wave_size="auto")
    assert np.isfinite(float(res.loss_history[-1]))
    assert len(sim._auto_wave_cache) == 1
    sim.run_round(res.params, data, jnp.asarray(n), jax.random.key(2),
                  wave_size="auto")
    assert len(sim._auto_wave_cache) == 1  # same shapes -> cache hit


def test_auto_wave_size_mesh_and_fused(nprng, monkeypatch):
    """"auto" composes with a clients mesh (the probe lowers the
    per-shard program) and with run_rounds_fused."""
    from baton_tpu.models.linear import linear_regression_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.mesh import make_mesh
    from baton_tpu.utils import profiling

    monkeypatch.setitem(profiling.HBM_BUDGET_GB, "cpu", 64.0)

    model = linear_regression_model(6)
    datasets = [{
        "x": nprng.normal(size=(8, 6)).astype(np.float32),
        "y": nprng.normal(size=(8,)).astype(np.float32),
    } for _ in range(16)]
    data, n = stack_client_datasets(datasets, batch_size=8)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    sim = FedSim(model, batch_size=8, learning_rate=0.1, mesh=make_mesh(8))
    params = sim.init(jax.random.key(0))

    assert sim.auto_wave_size(params, data, n, budget_gb=64.0) is None
    p2, hist = sim.run_rounds_fused(params, data, jnp.asarray(n),
                                    jax.random.key(1), n_rounds=2,
                                    wave_size="auto")
    assert np.isfinite(float(hist[-1]))


# ----------------------------------------------------------------------
# the mean fold as one device program (ISSUE 28)
def _eager_round(sim, params, data, n_samples, rng, wave_size,
                 server_opt_state):
    """The round with the fold it had before it was one program: the wave
    loop through the sim's own wave program, then every expression of the
    fold and of the server update dispatched one by one, outside any
    ``jit``. Returns ``(params, loss_history, n_samples_total,
    server_opt_state)``."""
    trainable, frozen = sim._split(params)
    n_samples = jnp.asarray(n_samples)
    c = int(n_samples.shape[0])
    rngs = jax.random.split(rng, c)
    wave_size = sim._resolve_wave_size(wave_size, c)
    program, bind = sim._wave_program(1, robust=False)
    in_shard = client_sharding(sim.mesh) if sim.mesh is not None else None
    psum_acc = lsum_acc = w_acc = None
    for start in range(0, c, wave_size):
        d, n, r = sim._stage_wave(data, n_samples, rngs, start,
                                  min(start + wave_size, c), wave_size,
                                  in_shard)
        psum, lsum, wtot, _ = program(*bind(trainable, frozen, d, n, r))
        psum_acc = psum if psum_acc is None else _acc_tree_add(psum_acc, psum)
        lsum_acc = lsum if lsum_acc is None else lsum_acc + lsum
        w_acc = wtot if w_acc is None else w_acc + wtot

    denom = jnp.maximum(w_acc, 1e-9)
    aggregate = jax.tree_util.tree_map(
        lambda s, ref: (s / denom).astype(ref.dtype), psum_acc, trainable)
    loss_history = lsum_acc / denom
    new = aggregate
    if sim.server_optimizer is not None:
        if server_opt_state is None:
            server_opt_state = sim.server_optimizer.init(trainable)
        pseudo_grad = jax.tree_util.tree_map(
            lambda g, a: (g.astype(jnp.float32)
                          - a.astype(jnp.float32)).astype(g.dtype),
            trainable, aggregate)
        updates, server_opt_state = sim.server_optimizer.update(
            pseudo_grad, server_opt_state, trainable)
        new = optax.apply_updates(trainable, updates)
    if sim.partition is not None:
        new = sim.partition.merge(new, frozen)
    return new, loss_history, w_acc, server_opt_state


def _bfloat16_bias(model):
    """The linear model with its bias kept in bfloat16: the fold's cast."""

    def init(rng):
        p = model.init(rng)
        return {**p, "b": p["b"].astype(jnp.bfloat16)}

    def apply(params, batch, rng):
        return model.apply(
            {**params, "b": params["b"].astype(jnp.float32)}, batch, rng)

    def per_example_loss(params, batch, rng):
        return model.per_example_loss(
            {**params, "b": params["b"].astype(jnp.float32)}, batch, rng)

    return dataclasses.replace(model, init=init, apply=apply,
                               per_example_loss=per_example_loss)


# (FedSim arguments, wave size, bit-equal?). One program lets XLA contract
# a multiply and the add that takes it into one rounding, which the same
# expressions dispatched one by one cannot have (the fused runner has
# always computed them so): exact where every product is (the mean's
# divide and cast, a server step of 1.0 or a power of two), to half a
# unit in the last place through a moment's `decay * m + g`.
FOLD_CASES = {
    "one_wave": ({}, None, True),
    "waves_short_last": ({}, 3, True),
    "bfloat16_leaf": ({"model": _bfloat16_bias}, 3, True),
    "server_sgd": ({"server_optimizer": optax.sgd(1.0)}, 3, True),
    # stateful: the step count picks the second round's rate
    "server_scheduled": ({"server_optimizer": optax.sgd(
        optax.piecewise_constant_schedule(1.0, {1: 0.5}))}, 3, True),
    "server_adam": ({"server_optimizer": optax.adam(0.05)}, 3, False),
    "server_momentum": (
        {"server_optimizer": optax.sgd(0.7, momentum=0.9)}, None, False),
    "trainable": ({"trainable": lambda path, leaf: path.endswith("w")}, 3,
                  True),
    "mesh2": ({"mesh": 2}, 4, True),
}


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_fold_program_is_bit_equal_to_the_eager_fold(linear_setup, case):
    """Two rounds (the second fed the first's parameters and server
    state): ``run_round``'s one fold program against the eager
    expressions it replaced, bit for bit (``FOLD_CASES`` says where a
    contracted multiply-add allows the last place)."""
    sim_kw, wave_size, exact = FOLD_CASES[case]
    sim_kw = dict(sim_kw)
    model, _, data, n_samples = linear_setup
    if "model" in sim_kw:
        model = sim_kw.pop("model")(model)
    if "mesh" in sim_kw:
        sim_kw["mesh"] = make_mesh(sim_kw["mesh"])
        data = shard_client_arrays(data, sim_kw["mesh"])
    sim = FedSim(model, batch_size=32, learning_rate=0.01, **sim_kw)
    params = model.init(jax.random.key(0))
    want_p, want_state = params, None
    got_p, got_state = params, None
    for i in range(2):
        rng = jax.random.key(10 + i)
        want_p, want_loss, want_n, want_state = _eager_round(
            sim, want_p, data, n_samples, rng, wave_size, want_state)
        before = got_p
        res = sim.run_round(got_p, data, n_samples, rng, wave_size=wave_size,
                            server_opt_state=got_state)
        got_p, got_state = res.params, res.server_opt_state
        got = (got_p, res.loss_history, res.n_samples_total, got_state)
        want = (want_p, want_loss, want_n, want_state)
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for x, y in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert x.dtype == y.dtype and x.shape == y.shape
            if exact:
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            else:
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=2e-6, atol=1e-9)
        if case == "trainable":  # a frozen leaf is the array it was
            assert got_p["b"] is before["b"]
    assert not np.array_equal(np.asarray(got_p["w"]),
                              np.asarray(params["w"]))
    if case == "bfloat16_leaf":
        assert got_p["b"].dtype == jnp.bfloat16
