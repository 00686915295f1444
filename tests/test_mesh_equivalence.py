"""Unified-path equivalence tests for the sharded algorithm paths.

These replace the retired mesh-vs-single-device equivalence tests.
Those tests compared a vmap-of-scan program against a shard_map (or
GSPMD-placed) program running the same math; XLA compiles the two
differently, per-batch loss sums differ by exact multiples of 2^-10
(float reassociation — the single-SGD-step programs agree bitwise), and
the noise compounds through SGD to ~1e-3..1e-1 relative after 1-2
epochs, far past any honest tolerance. What those tests actually pinned
down decomposes into properties that ARE stable, tested here:

* spec-equality — every sharded path's shard_map layout comes verbatim
  from ``partition.kernel_specs`` (asserted against the intended
  layouts; the no-ad-hoc-PartitionSpec check in test_partition_rules
  keeps construction out of the call sites);
* fold-equivalence — the psum aggregation fold equals the float64
  oracle on identical trained client contributions (training factored
  out; see also test_aggregation's psum-vs-oracle tests);
* exact phantom invariance — inside ONE compiled sharded kernel,
  zero-weight phantom rows cannot perturb the aggregate no matter what
  values/rngs they carry (bitwise assertion, no cross-compilation);
* exact discrete bookkeeping — outputs that don't compound float noise
  (cluster assignments, buffer versions, staleness) still match the
  single-device path exactly;
* loose semantic guardrails — cross-layout comparisons at a 5e-2 band:
  reassociation noise is ~1e-2, semantic bugs (wrong fold, dropped
  weights, bad padding) are order-1, so the band still catches real
  breakage without asserting bitwise stability XLA never promised.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from baton_tpu.data.synthetic import DEMO_COEF, linear_client_data
from baton_tpu.models.linear import linear_regression_model
from baton_tpu.models.lora import lora_trainable, lora_wrap
from baton_tpu.models.mlp import mlp_classifier_model
from baton_tpu.ops import aggregation as agg
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim
from baton_tpu.parallel.mesh import CLIENT_AXIS, make_mesh
from baton_tpu.parallel.partition import (
    client_spec,
    kernel_specs,
    replicated_spec,
)


def _linear_setup(nprng, n_clients=8):
    datasets = [linear_client_data(nprng, min_batches=2, max_batches=3)
                for _ in range(n_clients)]
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    return data, jnp.asarray(n_samples)


def _tree_close(a, b, rtol, atol=0.0):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# spec-equality: the kernel layout table IS the intended layout
# ---------------------------------------------------------------------------

def test_kernel_spec_table_is_the_partition_layout():
    """Every shard_map kernel's in/out specs come from the one table in
    partition.py, and the table says exactly what the layout contract
    docstring promises: per-client stacked state rides the clients
    axis, broadcast/aggregated state is replicated."""
    cli, rep = P(CLIENT_AXIS), P()
    assert client_spec() == cli and replicated_spec() == rep
    want = {
        "engine.wave_sums": ((rep, rep, cli, cli, cli),
                             (rep, rep, rep, cli)),
        "engine.wave_params": ((rep, rep, cli, cli, cli), (cli, cli)),
        "fedbuff.train": ((cli, cli, cli, cli, rep), (cli, cli)),
        "clustered.round": ((rep, cli, cli, cli), (rep, cli, cli)),
        "stateful.round": ((rep, cli, cli, cli, cli),
                           (rep, cli, rep, cli)),
        "personalization.round": ((cli, rep, cli, cli, cli),
                                  (cli, rep, rep, rep, cli)),
    }
    for name, specs in want.items():
        assert kernel_specs(name) == specs, name
    # a custom client axis threads through every entry
    ins, outs = kernel_specs("engine.wave_sums", axis="workers")
    assert ins[2] == P("workers") and outs[3] == P("workers")


# ---------------------------------------------------------------------------
# fold-equivalence: train once, fold twice
# ---------------------------------------------------------------------------

def test_engine_fold_equivalence_on_trained_contributions(nprng):
    """The engine's sharded aggregation fold (per-shard weighted sums +
    psum over the clients axis, engine.wave_sums) equals the float64
    oracle on the SAME trained client params — training happens once on
    the vmap path, so only the fold itself is under test."""
    data, n_samples = _linear_setup(nprng)
    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32, learning_rate=0.02)
    params = sim.init(jax.random.key(0))
    rngs = jax.random.split(jax.random.key(1), 8)

    client_params, _ = sim._wave_params_vmap(
        params, None, data, n_samples, rngs, 1
    )
    w = n_samples.astype(jnp.float32)

    # oracle: float64 weighted mean of the stacked contributions
    w64 = np.asarray(w, np.float64)
    oracle = jax.tree_util.tree_map(
        lambda l: np.tensordot(w64, np.asarray(l, np.float64),
                               axes=(0, 0)) / w64.sum(),
        client_params,
    )

    # the sharded fold, laid out per the kernel table (stacked inputs
    # ride the clients axis, the aggregate comes back replicated)
    mesh = make_mesh(8)

    def fold(cp, wv):
        ps = jax.lax.psum(agg.weighted_tree_sum(cp, wv), CLIENT_AXIS)
        wt = jax.lax.psum(jnp.sum(wv), CLIENT_AXIS)
        return jax.tree_util.tree_map(lambda s: s / wt, ps)

    cli = client_spec()
    mesh_mean = jax.jit(jax.shard_map(
        fold, mesh=mesh, in_specs=(cli, cli),
        out_specs=replicated_spec(), check_vma=False,
    ))(client_params, w)

    vmap_mean = agg.weighted_tree_mean(client_params, w)
    _tree_close(mesh_mean, oracle, rtol=1e-5)
    _tree_close(vmap_mean, oracle, rtol=1e-5)


# ---------------------------------------------------------------------------
# exact phantom invariance, inside one compiled kernel
# ---------------------------------------------------------------------------

def test_engine_sharded_wave_phantom_rows_cannot_perturb(nprng):
    """Zero-sample phantom rows must contribute EXACTLY nothing to the
    sharded wave aggregate: run the same compiled kernel twice with
    wildly different phantom data/rng fills — psum, loss sum, weight
    sum, and the real clients' losses must be bit-identical."""
    data6, n6 = _linear_setup(nprng, n_clients=6)
    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32, learning_rate=0.02,
                 mesh=make_mesh(8))
    params = sim.init(jax.random.key(0))
    rngs6 = jax.random.split(jax.random.key(1), 6)
    kernel = sim._make_wave_sums_sharded(1)

    def padded(fill_key):
        fill = jax.random.split(fill_key, 3)
        data = jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [a, jax.random.normal(
                    fill[0], (2,) + a.shape[1:]).astype(a.dtype)]
                if jnp.issubdtype(a.dtype, jnp.floating)
                else [a, jnp.zeros((2,) + a.shape[1:], a.dtype)],
                axis=0),
            data6,
        )
        n = jnp.concatenate([n6, jnp.zeros(2, n6.dtype)])
        rngs = jnp.concatenate([rngs6, jax.random.split(fill[1], 2)])
        return data, n, rngs

    outs = [kernel(params, None, *padded(k))
            for k in (jax.random.key(10), jax.random.key(99))]
    for a, b in zip(jax.tree_util.tree_leaves(outs[0][:3]),
                    jax.tree_util.tree_leaves(outs[1][:3])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(outs[0][3][:6]),
                                  np.asarray(outs[1][3][:6]))


def test_fedper_sharded_kernel_phantom_rows_cannot_perturb(nprng):
    """Same exactness for FedPer's sharded kernel: phantom personal
    rows carry arbitrary values but weight 0 and mask 0, so the shared
    aggregate, warm-start personal mean, and loss history must be
    bit-identical across phantom fills."""
    from baton_tpu.parallel.personalization import FedPer
    from test_personalization import _clients_with_permuted_labels, _head

    model = mlp_classifier_model(8, (16,), 4)
    datasets, _ = _clients_with_permuted_labels(nprng, n_clients=6)
    data6, n6 = stack_client_datasets(datasets, batch_size=16)
    data6 = {k: jnp.asarray(v) for k, v in data6.items()}
    n6 = jnp.asarray(n6)
    sim = FedSim(model, batch_size=16, learning_rate=0.1,
                 mesh=make_mesh(8))
    fp = FedPer(sim, personal=_head)
    params = FedSim(model, batch_size=16).init(jax.random.key(0))
    fp._ensure_partition(params)
    pers6 = fp.init_personal(params, 6)
    _, shared = fp.partition.split(params)
    rngs6 = jax.random.split(jax.random.key(2), 6)
    kernel = fp._round_fn_sharded(1)

    def padded(fill_key):
        fill = jax.random.split(fill_key, 3)
        pad_f = lambda key: lambda a: jnp.concatenate(
            [a, jax.random.normal(
                key, (2,) + a.shape[1:]).astype(a.dtype)]
            if jnp.issubdtype(a.dtype, jnp.floating)
            else [a, jnp.zeros((2,) + a.shape[1:], a.dtype)],
            axis=0)
        pers = jax.tree_util.tree_map(pad_f(fill[0]), pers6)
        data = jax.tree_util.tree_map(pad_f(fill[1]), data6)
        n = jnp.concatenate([n6, jnp.zeros(2, n6.dtype)])
        rngs = jnp.concatenate([rngs6, jax.random.split(fill[2], 2)])
        return pers, shared, data, n, rngs

    outs = [kernel(*padded(k))
            for k in (jax.random.key(11), jax.random.key(77))]
    # shared_agg, pers_mean, loss_hist: exactly phantom-independent
    for i in (1, 2, 3):
        for a, b in zip(jax.tree_util.tree_leaves(outs[0][i]),
                        jax.tree_util.tree_leaves(outs[1][i])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # real clients' personal rows and losses too
    for a, b in zip(jax.tree_util.tree_leaves(outs[0][0]),
                    jax.tree_util.tree_leaves(outs[1][0])):
        np.testing.assert_array_equal(np.asarray(a)[:6],
                                      np.asarray(b)[:6])
    np.testing.assert_array_equal(np.asarray(outs[0][4])[:6],
                                  np.asarray(outs[1][4])[:6])


# ---------------------------------------------------------------------------
# layout + weights on the real sharded round
# ---------------------------------------------------------------------------

def test_engine_sharded_round_layout_and_weights(nprng):
    """The mesh round's outputs carry the kernel table's layout (the
    aggregate comes back replicated) and the exact FedAvg weight
    accounting, including on an unaligned auto-padded cohort."""
    data, n_samples = _linear_setup(nprng)
    model = linear_regression_model(10)
    sim = FedSim(model, batch_size=32, learning_rate=0.01,
                 mesh=make_mesh(8))
    params = sim.init(jax.random.key(0))
    res = sim.run_round(params, data, n_samples, jax.random.key(5),
                        n_epochs=2)
    for leaf in jax.tree_util.tree_leaves(res.params):
        assert leaf.sharding.is_fully_replicated, leaf.sharding
    assert res.client_losses.shape == (8, 2)
    assert np.all(np.isfinite(np.asarray(res.loss_history)))
    np.testing.assert_array_equal(np.asarray(res.n_samples_total),
                                  np.asarray(n_samples).sum())

    # unaligned cohort: 6 clients auto-pad to the 8-device mesh; the
    # phantoms' zero weight is visible in the EXACT total
    data6 = {k: v[:6] for k, v in data.items()}
    n6 = n_samples[:6]
    res6 = sim.run_round(params, data6, n6, jax.random.key(5),
                         n_epochs=1)
    assert res6.client_losses.shape == (6, 1)
    np.testing.assert_array_equal(np.asarray(res6.n_samples_total),
                                  np.asarray(n6).sum())
    for leaf in jax.tree_util.tree_leaves(res6.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_robust_aggregator_on_mesh_rejects_byzantine(nprng):
    """The mesh robust path (per-client params gathered client-sharded,
    engine.wave_params, trimmed on host): a poisoned client must be
    rejected on the mesh exactly as on one device — the property the
    robust aggregator exists for, stable under reassociation noise."""
    data, n_samples = _linear_setup(nprng)
    poisoned = dict(data)
    poisoned["y"] = data["y"].at[0].set(data["y"][0] * 1e3)
    model = linear_regression_model(10)
    params = model.init(jax.random.key(0))
    kw = dict(batch_size=32, learning_rate=0.05, mesh=make_mesh(8))

    def err(aggregator):
        sim = FedSim(model, aggregator=aggregator, **kw)
        res = sim.run_round(params, poisoned, n_samples,
                            jax.random.key(5), n_epochs=4)
        w = np.asarray(res.params["w"]).ravel()
        return float(np.max(np.abs(w - DEMO_COEF)))

    err_trimmed, err_mean = err("trimmed:0.2"), err("mean")
    assert err_trimmed < 15.0 < err_mean, (err_trimmed, err_mean)


def test_lora_sharded_round_keeps_frozen_base_untouched(nprng):
    """On the mesh LoRA path the frozen base must come back BITWISE
    identical (partition.merge reinserts the frozen leaves; only
    adapters train and fold), and the adapters must actually move."""
    from test_lora_fedprox import _classif_data

    base_model = mlp_classifier_model(8, (16,), 4)
    model = lora_wrap(base_model, rank=2)
    params = model.init(jax.random.key(0))
    data, n_samples = _classif_data(nprng, n_clients=8)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    sim = FedSim(model, batch_size=16, learning_rate=0.1,
                 trainable=lora_trainable, mesh=make_mesh(8))
    res = sim.run_round(params, data, jnp.asarray(n_samples),
                        jax.random.key(3), n_epochs=1)
    flat_in = jax.tree_util.tree_flatten(params["base"])[0]
    flat_out = jax.tree_util.tree_flatten(res.params["base"])[0]
    for a, b in zip(flat_in, flat_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(params["lora"]),
                        jax.tree_util.tree_leaves(res.params["lora"]))
    )
    assert moved
    assert np.all(np.isfinite(np.asarray(res.loss_history)))


# ---------------------------------------------------------------------------
# exact discrete bookkeeping across paths
# ---------------------------------------------------------------------------

def test_clustered_mesh_assignments_match_single_device_exactly(nprng):
    """IFCA's cluster assignments are argmins over well-separated
    losses — discrete, so reassociation noise cannot flip them: the
    mesh round must assign every client exactly like the single-device
    round, aligned and auto-padded, and the mesh path alone must
    recover the generating populations."""
    from baton_tpu.parallel.clustered import ClusteredFedSim
    from test_clustered import _mixture

    datasets, pops = _mixture(nprng)
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)
    model = linear_regression_model(10)
    cf1 = ClusteredFedSim(
        FedSim(model, batch_size=32, learning_rate=0.05), n_clusters=2)
    cf8 = ClusteredFedSim(
        FedSim(model, batch_size=32, learning_rate=0.05,
               mesh=make_mesh(8)), n_clusters=2)
    clusters = cf1.init_clusters(jax.random.key(0))

    r1 = cf1.run_round(clusters, data, n_samples, jax.random.key(1),
                       n_epochs=2)
    r8 = cf8.run_round(clusters, data, n_samples, jax.random.key(1),
                       n_epochs=2)
    np.testing.assert_array_equal(r1.assignments, r8.assignments)
    _tree_close(r1.cluster_params, r8.cluster_params, rtol=5e-2,
                atol=5e-2)

    # unaligned: 6 clients auto-pad on the 8-mesh, unpadded outputs
    data6 = {k: v[:6] for k, v in data.items()}
    r1b = cf1.run_round(clusters, data6, n_samples[:6],
                        jax.random.key(2), n_epochs=1)
    r8b = cf8.run_round(clusters, data6, n_samples[:6],
                        jax.random.key(2), n_epochs=1)
    assert r8b.assignments.shape == (6,)
    np.testing.assert_array_equal(r1b.assignments, r8b.assignments)

    # the mesh path alone separates the populations (semantics, not
    # cross-compilation numerics)
    cl = cf8.init_clusters(jax.random.key(0))
    for r in range(12):
        res = cf8.run_round(cl, data, n_samples,
                            jax.random.fold_in(jax.random.key(1), r),
                            n_epochs=2)
        cl = res.cluster_params
    a = np.asarray(res.assignments)
    assert np.all(a == pops) or np.all(a == 1 - pops), (a, pops)


def test_fedbuff_mesh_bookkeeping_matches_single_device_exactly(nprng):
    """FedBuff's buffer/staleness machinery is host-side integer
    bookkeeping — the mesh run must match the single-device run
    EXACTLY on versions and staleness, and stay within the semantic
    band on the float outputs."""
    from baton_tpu.parallel.fedbuff import FedBuff

    model = linear_regression_model(10)
    datasets = [linear_client_data(nprng) for _ in range(8)]
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)
    sim_1d = FedSim(model, batch_size=32, learning_rate=0.02)
    sim_mesh = FedSim(model, batch_size=32, learning_rate=0.02,
                      mesh=make_mesh(4))
    params = sim_1d.init(jax.random.key(0))
    out = {}
    for name, sim in [("single", sim_1d), ("mesh", sim_mesh)]:
        fb = FedBuff(sim, buffer_size=4, concurrency=8, alpha=0.5)
        out[name] = fb.run(params, data, n_samples, jax.random.key(7),
                           n_steps=6, n_epochs=2)
    assert out["mesh"].version == out["single"].version
    assert out["mesh"].mean_staleness == out["single"].mean_staleness
    losses = np.asarray(out["mesh"].loss_history)
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    np.testing.assert_allclose(losses,
                               np.asarray(out["single"].loss_history),
                               rtol=5e-2)
    _tree_close(out["mesh"].params, out["single"].params, rtol=5e-2,
                atol=5e-2)


def test_stateful_mesh_threads_state_and_learns(nprng):
    """The mesh stateful path must thread per-client optimizer states
    across rounds (round 2 with threaded momentum differs from a
    fresh-state round 2), return them unpadded and client-stacked, and
    converge on its own trajectory."""
    from baton_tpu.parallel.stateful import StatefulClients

    model = linear_regression_model(10)
    datasets = [linear_client_data(nprng, min_batches=2, max_batches=3)
                for _ in range(6)]
    data, n_samples = stack_client_datasets(datasets, batch_size=32)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)
    sim = FedSim(model, batch_size=32,
                 optimizer=optax.sgd(0.01, momentum=0.9),
                 mesh=make_mesh(8))
    params = sim.init(jax.random.key(0))
    sc = StatefulClients(sim)

    p, opt = params, None
    for r in range(2):
        key = jax.random.fold_in(jax.random.key(1), r)
        res = sc.run_round(p, opt, data, n_samples, key, n_epochs=1)
        p, opt = res.params, res.opt_states
    # opt states come back unpadded, stacked over the 6 real clients
    assert all(l.shape[0] == 6
               for l in jax.tree_util.tree_leaves(opt))
    # threading is real: replaying round 2 with RESET states diverges
    key = jax.random.fold_in(jax.random.key(1), 1)
    res_threaded = res
    res_reset = sc.run_round(res_threaded.params, None, data, n_samples,
                             key, n_epochs=1)
    # (res_threaded used the threaded opt from round 1 at the same key)
    assert not np.allclose(np.asarray(res_threaded.params["w"]),
                           np.asarray(res_reset.params["w"]))
    # and the mesh trajectory converges by itself
    p, opt = params, None
    for r in range(12):
        key = jax.random.fold_in(jax.random.key(1), r)
        res = sc.run_round(p, opt, data, n_samples, key, n_epochs=1)
        p, opt = res.params, res.opt_states
    err = float(np.max(np.abs(np.asarray(p["w"]).ravel() - DEMO_COEF)))
    assert err < 2.0, err


def test_fedper_mesh_round_layout_and_warm_start(nprng):
    """The mesh FedPer round returns unpadded per-client personal
    state, finite losses, and a warm-start personal mean that equals
    the mask-weighted float64 oracle over the returned personal rows
    (the fold re-checked on the real round output)."""
    from baton_tpu.parallel.personalization import FedPer
    from test_personalization import _clients_with_permuted_labels, _head

    model = mlp_classifier_model(8, (16,), 4)
    datasets, _ = _clients_with_permuted_labels(nprng, n_clients=6)
    data, n_samples = stack_client_datasets(datasets, batch_size=16)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)
    fp = FedPer(FedSim(model, batch_size=16, learning_rate=0.1,
                       mesh=make_mesh(8)), personal=_head)
    params = FedSim(model, batch_size=16).init(jax.random.key(0))
    res = fp.run_round(params, None, data, n_samples,
                       jax.random.key(2), n_epochs=1)
    assert all(l.shape[0] == 6
               for l in jax.tree_util.tree_leaves(res.personal_state))
    assert res.client_losses.shape == (6, 1)
    assert np.all(np.isfinite(np.asarray(res.loss_history)))
    # warm start == float64 mean of the returned real personal rows
    pers_mean, _ = fp.partition.split(res.params)
    want = jax.tree_util.tree_map(
        lambda l: np.asarray(l, np.float64).mean(axis=0),
        res.personal_state,
    )
    _tree_close(pers_mean, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# loose semantic guardrails across layouts
# ---------------------------------------------------------------------------

def test_hybrid_round_semantic_guardrail():
    """Hybrid clients x model GSPMD vs the 1-D clients mesh: identical
    math in different layouts. Reassociation noise between the two
    compilations measures ~1e-2 relative; the 5e-2 band still catches
    order-1 semantic breakage (dropped weights, wrong collectives)."""
    from test_hybrid_tp import _hybrid_mesh, _tiny_lora_setup

    model, params, data, n_samples = _tiny_lora_setup()
    kw = dict(batch_size=4, learning_rate=0.05, trainable=lora_trainable)
    res_1d = FedSim(model, mesh=make_mesh(8), **kw).run_round(
        params, data, n_samples, jax.random.key(1), n_epochs=1)
    res_h = FedSim(model, mesh=_hybrid_mesh(4, 2), **kw).run_round(
        params, data, n_samples, jax.random.key(1), n_epochs=1)
    _tree_close(res_1d.params, res_h.params, rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(res_1d.loss_history),
                               np.asarray(res_h.loss_history),
                               rtol=5e-2)


def test_rounds_phantom_padding_semantic_guardrail(nprng):
    """``run_rounds`` auto-pads a 5-client cohort on the 8-device
    mesh; the padded mesh program must stay in the semantic band of the
    unpadded vmap program (phantom weightlessness is asserted exactly,
    per compiled kernel, in test_engine_sharded_wave_phantom_rows_*)."""
    data, n_samples = _linear_setup(nprng, n_clients=5)
    model = linear_regression_model(10)
    sim_m = FedSim(model, batch_size=32, learning_rate=0.02,
                   mesh=make_mesh(8))
    sim_v = FedSim(model, batch_size=32, learning_rate=0.02)
    params = sim_v.init(jax.random.key(0))
    p_m, h_m = sim_m.run_rounds(params, data, n_samples,
                                jax.random.key(1), n_rounds=2)
    p_v, h_v = sim_v.run_rounds(params, data, n_samples,
                                jax.random.key(1), n_rounds=2)
    _tree_close(p_m, p_v, rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(h_m, h_v, rtol=5e-2)
