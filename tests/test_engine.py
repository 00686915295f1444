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


def test_rounds_learn_classification(nprng):
    """The MLP classifier over rounds to an accuracy: the only test that
    trains a non-linear model through ``run_rounds`` to a metric."""
    from baton_tpu.data.synthetic import synthetic_classification_clients
    from baton_tpu.models.mlp import mlp_classifier_model

    datasets, _ = synthetic_classification_clients(nprng, 8)
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    model = mlp_classifier_model(32, (64,), 10)
    sim = FedSim(model, batch_size=32, learning_rate=0.3)
    params = sim.init(jax.random.key(0))
    params, history = sim.run_rounds(
        params, data, jnp.asarray(n_samples), jax.random.key(1),
        n_rounds=10, n_epochs=2,
    )
    assert history[-1] < history[0] * 0.5
    metrics = sim.evaluate_round(params, data, jnp.asarray(n_samples))
    assert metrics["accuracy"] > 0.7


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


def test_auto_wave_size_mesh_and_rounds(nprng, monkeypatch):
    """"auto" composes with a clients mesh (the probe lowers the
    per-shard program) and with run_rounds."""
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
    p2, hist = sim.run_rounds(params, data, jnp.asarray(n),
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
    rows, _ = sim._rows_to_stage(data, np.asarray(n_samples))
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
                                  in_shard, rows)
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
# expressions dispatched one by one cannot have: exact where every product is (the mean's
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


# ----------------------------------------------------------------------
# a round computes the rows its cohort holds (ISSUE 30)
def _padded_cohort(sizes, capacity=64, dim=10, seed=0):
    """Clients of ``sizes`` real rows in ``capacity``; the rows past a
    client's own are poisoned, so a row that is read shows."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(len(sizes), capacity, dim)).astype(np.float32)
    y = x @ np.asarray(DEMO_COEF, np.float32)[:dim]
    for c, n in enumerate(sizes):
        x[c, n:], y[c, n:] = 1e4, -1e4
    return ({"x": jnp.asarray(x), "y": jnp.asarray(y)},
            np.asarray(sizes, np.int32))


def _cut(data, rows):
    return jax.tree_util.tree_map(lambda a: a[:, :rows], data)


def _assert_trees_equal(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _staged(monkeypatch):
    """The attributes of every ``baton.round.stage`` span opened from
    here on."""
    from baton_tpu.parallel import engine
    stages = []
    annotate = engine.annotate

    def recording(name, **attrs):
        if name == "baton.round.stage":
            stages.append(attrs)
        return annotate(name, **attrs)

    monkeypatch.setattr(engine, "annotate", recording)
    return stages


# (FedSim arguments, run_round arguments)
TRIM_CASES = {
    "one_wave": ({}, {}),
    "waves_short_last": ({}, {"wave_size": 4}),
    "client_indices": ({}, {"client_indices": np.asarray([5, 0, 3]),
                            "wave_size": 2}),
    "trainable": ({"trainable": lambda path, leaf: path.endswith("w")},
                  {"wave_size": 4}),
    "mesh2": ({"mesh": 2}, {}),
    "robust": ({"aggregator": "median"}, {"wave_size": 4}),
    "momentum_two_epochs": ({"optimizer": optax.sgd(0.01, momentum=0.9)},
                            {"n_epochs": 2}),
    "numpy_arrays": ({"numpy": True}, {"wave_size": 4}),
}


@pytest.mark.parametrize("case", sorted(TRIM_CASES))
def test_round_on_padded_rows_equals_round_on_the_rows_cut_by_the_caller(
        case, monkeypatch):
    """6 clients of 48 real rows handed over in 64 (a batch multiple, as
    ``stack_client_datasets`` pads them) at batch 32: the engine stages
    48 rows, and the round is the one the caller gets by cutting the
    arrays to 48 rows first, bit for bit. The trainer takes two steps
    of 24."""
    sim_kw, round_kw = TRIM_CASES[case]
    sim_kw = dict(sim_kw)
    data, n = _padded_cohort([48] * 6)
    cut = _cut(data, 48)
    if sim_kw.pop("numpy", False):
        data = {k: np.asarray(v) for k, v in data.items()}
    if "mesh" in sim_kw:
        sim_kw["mesh"] = make_mesh(sim_kw["mesh"])
        data = shard_client_arrays(data, sim_kw["mesh"])
        cut = shard_client_arrays(cut, sim_kw["mesh"])
    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32, learning_rate=0.01, **sim_kw)
    params = model.init(jax.random.key(0))
    stages = _staged(monkeypatch)
    got = sim.run_round(params, data, n, jax.random.key(1), **round_kw)
    assert stages and all(
        s["rows"] == 48 and s["capacity"] == 64 for s in stages)
    del stages[:]
    want = sim.run_round(params, cut, n, jax.random.key(1), **round_kw)
    assert all(s["rows"] == 48 and s["capacity"] == 48 for s in stages)
    _assert_trees_equal(
        (got.params, got.loss_history, got.client_losses,
         got.n_samples_total),
        (want.params, want.loss_history, want.client_losses,
         want.n_samples_total))
    assert np.isfinite(np.asarray(got.loss_history)).all()
    assert sim.last_compute["steps"] == (
        len(round_kw.get("client_indices", n)) * round_kw.get("n_epochs", 1)
        * 2)


@pytest.mark.parametrize("sizes,rows", [
    ((5, 17, 33, 48), 48),   # the largest client decides
    ((5, 17, 33, 41), 48),   # rounded up to a quarter of the batch
    ((5, 17, 33, 40), 40),
    ((64, 1, 1, 1), 64),     # never more than the caller handed over
    ((3, 1, 0, 2), 8),       # fewer rows than a batch: one step of 8
    ((0, 0, 0, 0), 8),       # an empty cohort still stages a granule
])
def test_a_ragged_cohort_stages_its_largest_client_in_quarter_batches(
        sizes, rows, monkeypatch):
    data, n = _padded_cohort(sizes)
    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    assert sim._rows_to_stage(data, n) == (rows, 64)
    stages = _staged(monkeypatch)
    params = model.init(jax.random.key(0))
    got = sim.run_round(params, data, n, jax.random.key(1), wave_size=2)
    assert [(s["rows"], s["capacity"]) for s in stages] == [(rows, 64)] * 2
    want = sim.run_round(params, _cut(data, rows), n, jax.random.key(1),
                         wave_size=2)
    _assert_trees_equal((got.params, got.loss_history),
                        (want.params, want.loss_history))
    assert float(got.n_samples_total) == sum(sizes)


def test_a_smaller_largest_client_in_the_same_granule_compiles_nothing():
    """Largest client 48, then 41: both rounds stage 48 rows and run one
    wave program (compile events as ``tests/test_round_spans.py`` and
    ``fedbench/run.py`` count them)."""
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32, learning_rate=0.01)
    params = model.init(jax.random.key(0))
    data, n = _padded_cohort((5, 17, 33, 48))
    first = sim.run_round(params, data, n, jax.random.key(1), wave_size=2)
    data, n = _padded_cohort((5, 17, 33, 41), seed=1)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        jax.jit(lambda x: x * 5 + 2)(jnp.zeros(3))  # a compile is heard
        heard = len(compiles)
        second = sim.run_round(first.params, data, n, jax.random.key(2),
                               wave_size=2)
        jax.block_until_ready(second.params)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert heard >= 1 and len(compiles) == heard
    assert sim.last_compute["cache_hit"]  # the record's signature too


@pytest.mark.parametrize("mesh", [None, 2])
def test_evaluators_score_the_staged_rows_and_return_what_they_did(mesh):
    """``evaluate_round`` and ``evaluate_clients`` on 48 real rows in 64
    equal the same calls on the arrays cut to 48 rows, and the masked
    means written out over all 64."""
    data, n = _padded_cohort((48, 17, 33, 48))
    cut = _cut(data, 48)
    full = data
    model = linear_regression_model(10)
    if mesh:
        mesh = make_mesh(mesh)
        data, cut = (shard_client_arrays(d, mesh) for d in (data, cut))
    sim = FedSim(model, batch_size=32, mesh=mesh)
    params = model.init(jax.random.key(3))
    got = sim.evaluate_round(params, data, n, wave_size=2)
    assert got == sim.evaluate_round(params, cut, n, wave_size=2)
    per = sim.evaluate_clients(params, data, n, wave_size=2)
    per_cut = sim.evaluate_clients(params, cut, n, wave_size=2)
    assert per["fairness"] == per_cut["fairness"]
    _assert_trees_equal(per["per_client"], per_cut["per_client"])
    losses = np.stack([
        np.asarray(model.per_example_loss(
            params, {k: v[c] for k, v in full.items()}, jax.random.key(0)))
        for c in range(4)])
    mask = np.arange(64)[None, :] < n[:, None]
    np.testing.assert_allclose(
        per["per_client"]["loss"], (losses * mask).sum(1) / n, rtol=1e-6)
    np.testing.assert_allclose(
        got["loss"], (losses * mask).sum() / n.sum(), rtol=1e-6)
    assert got["n"] == n.sum()


def test_wave_plan_and_lowering_see_the_staged_rows():
    data, n = _padded_cohort([48] * 4)
    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32)
    params = model.init(jax.random.key(0))
    _, args = sim._first_wave(params, data, n, jax.random.key(1), 1, None)
    staged = args[3]  # (sim, params, frozen, data, n_samples, rngs, epochs)
    assert staged["x"].shape == (4, 48, 10) and staged["y"].shape == (4, 48)
    text = sim.lower_wave(params, data, n, jax.random.key(1)).as_text()
    assert "4x48x10" in text and "4x64x10" not in text
    assert sim.wave_plan_gb(params, data, n, jax.random.key(1)) == (
        sim.wave_plan_gb(params, _cut(data, 48), n, jax.random.key(1)))
