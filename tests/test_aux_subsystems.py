"""Aux-subsystem tests (SURVEY §5/§7 step 7 — all new capabilities):
checkpoint/resume, metrics, profiling, fault injection.

The reference had none of these; the test strategy follows SURVEY §4:
pure-core unit tests plus in-process aiohttp integration for the
HTTP-visible parts.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from baton_tpu.models.linear import linear_regression_model
from baton_tpu.server.http_manager import Manager
from baton_tpu.server.state import params_to_state_dict
from baton_tpu.utils.checkpoint import Checkpointer
from baton_tpu.utils.faults import FaultInjector
from baton_tpu.utils.metrics import Metrics
from baton_tpu.utils.profiling import timed


def run(coro):
    return asyncio.run(coro)


# ----------------------------------------------------------------------
# checkpoint/resume


def test_checkpoint_roundtrip(tmp_path):
    model = linear_regression_model(6)
    params = model.init(jax.random.key(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    with Checkpointer(str(tmp_path / "ckpt")) as ck:
        ck.save(3, params, server_opt_state=opt_state,
                meta={"n_rounds": 3, "loss_history": [1.0, 0.5]})
        assert ck.latest_step() == 3

        template = jax.tree_util.tree_map(jnp.zeros_like, params)
        restored = ck.restore(template, server_opt_template=opt.init(template))
        assert restored is not None and restored.step == 3
        for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert restored.meta["loss_history"] == [1.0, 0.5]
        # optimizer state roundtrips leaf-for-leaf (FedOpt resume)
        for a, b in zip(jax.tree_util.tree_leaves(restored.server_opt_state),
                        jax.tree_util.tree_leaves(opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restore_empty_dir(tmp_path):
    with Checkpointer(str(tmp_path / "empty")) as ck:
        assert ck.latest_step() is None
        assert ck.restore({"w": jnp.zeros(2)}) is None


def test_checkpoint_max_to_keep(tmp_path):
    params = {"w": jnp.arange(4.0)}
    with Checkpointer(str(tmp_path / "gc"), max_to_keep=2) as ck:
        for step in range(5):
            ck.save(step, params, meta={})
        assert ck.all_steps() == [3, 4]


def _fake_round(exp, n_epoch=2, scale=0.5):
    """Drive one complete round through the round machine directly, with
    a single synthetic client reporting scaled params."""
    exp.rounds.start_round(n_epoch=n_epoch)
    exp.rounds.client_start("c0")
    state = {
        k: v * scale for k, v in params_to_state_dict(exp.params).items()
    }
    exp.rounds.client_end("c0", {
        "state_dict": state,
        "n_samples": 8.0,
        "loss_history": [float(e) for e in range(n_epoch)],
    })
    exp.end_round()


def test_experiment_checkpoint_resume(tmp_path):
    ckdir = str(tmp_path / "exp_ck")
    model = linear_regression_model(4)

    app = web.Application()
    exp = Manager(app).register_experiment(
        model, name="exp", start_background_tasks=False, checkpoint_dir=ckdir
    )
    _fake_round(exp)
    _fake_round(exp)
    saved_params = params_to_state_dict(exp.params)
    saved_losses = [float(x) for x in exp.rounds.loss_history]
    assert exp.rounds.n_rounds == 2
    exp.checkpointer.close()

    # "manager restart": a brand-new process state restores everything
    app2 = web.Application()
    exp2 = Manager(app2).register_experiment(
        model, name="exp", start_background_tasks=False, checkpoint_dir=ckdir
    )
    assert exp2.rounds.n_rounds == 2
    assert [float(x) for x in exp2.rounds.loss_history] == saved_losses
    for k, v in params_to_state_dict(exp2.params).items():
        np.testing.assert_array_equal(v, saved_params[k])
    # and the round machine is usable (round names continue the sequence)
    name = exp2.rounds.start_round(n_epoch=1)
    assert name.endswith("00002")
    exp2.rounds.abort_round()
    exp2.checkpointer.close()


# ----------------------------------------------------------------------
# metrics


def test_metrics_counters_gauges_timers():
    m = Metrics()
    m.inc("updates")
    m.inc("updates", 2)
    m.set_gauge("clients", 5)
    m.observe("round_s", 1.0)
    m.observe("round_s", 3.0)
    with m.timer("round_s"):
        pass
    snap = m.snapshot()
    assert snap["counters"]["updates"] == 3
    assert snap["gauges"]["clients"] == 5.0
    t = snap["timers"]["round_s"]
    assert t["count"] == 3
    assert t["max_s"] == 3.0
    assert t["min_s"] >= 0.0
    assert abs(t["total_s"] - (4.0 + t["last_s"])) < 1e-6


def test_manager_metrics_endpoint():
    async def main():
        app = web.Application()
        exp = Manager(app).register_experiment(
            linear_regression_model(4), name="exp", start_background_tasks=False
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        _fake_round(exp, n_epoch=1)
        resp = await client.get("/exp/metrics")
        assert resp.status == 200
        snap = await resp.json()
        assert snap["gauges"]["rounds_completed"] == 1.0
        assert snap["counters"]["rounds_finished"] == 1.0
        assert snap["timers"]["round_s"]["count"] == 1
        await client.close()

    run(main())


# ----------------------------------------------------------------------
# profiling


def test_timed_blocks_on_device_work():
    x = jnp.ones((64, 64))
    out, secs = timed(lambda a: a @ a, x)
    assert out.shape == (64, 64)
    assert secs >= 0.0


# ----------------------------------------------------------------------
# fault injection


def test_fault_injector_error_delay_expiry():
    async def main():
        inj = FaultInjector()
        app = web.Application(middlewares=[inj.middleware])

        async def ok(request):
            return web.json_response("OK")

        app.router.add_get("/exp/heartbeat", ok)
        client = TestClient(TestServer(app))
        await client.start_server()

        rule = inj.error("heartbeat", status=503, times=2)
        assert (await client.get("/exp/heartbeat")).status == 503
        assert (await client.get("/exp/heartbeat")).status == 503
        # rule exhausted → traffic flows again (recovery path testable)
        assert (await client.get("/exp/heartbeat")).status == 200
        assert rule.hits == 2

        inj.clear()
        inj.delay("heartbeat", seconds=0.05, times=1)
        t0 = asyncio.get_event_loop().time()
        assert (await client.get("/exp/heartbeat")).status == 200
        assert asyncio.get_event_loop().time() - t0 >= 0.05
        await client.close()

    run(main())


def test_fault_injector_drop_aborts_connection():
    async def main():
        inj = FaultInjector()
        app = web.Application(middlewares=[inj.middleware])

        async def ok(request):
            return web.json_response("OK")

        app.router.add_get("/exp/register", ok)
        client = TestClient(TestServer(app))
        await client.start_server()
        inj.drop("register", times=1)
        with pytest.raises(Exception):  # connection reset surfaces client-side
            await client.get("/exp/register")
        # next attempt succeeds — models a transient network fault
        assert (await client.get("/exp/register")).status == 200
        await client.close()

    run(main())


def test_checkpoint_extra_pytree_roundtrip(tmp_path, nprng):
    """The `extra` slot checkpoints federation-mode state (FedPer
    personal stacks, stateful-client optimizer states): a personalized
    federation resumed from disk continues bit-identically."""
    import jax
    import jax.numpy as jnp

    from baton_tpu.models.mlp import mlp_classifier_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim
    from baton_tpu.parallel.personalization import FedPer
    from baton_tpu.utils.checkpoint import Checkpointer

    model = mlp_classifier_model(6, (8,), 3)
    datasets = [{
        "x": nprng.normal(size=(16, 6)).astype(np.float32),
        "y": nprng.integers(0, 3, size=16).astype(np.int32),
    } for _ in range(3)]
    data, n_samples = stack_client_datasets(datasets, batch_size=8)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    sim = FedSim(model, batch_size=8, learning_rate=0.05)
    fp = FedPer(sim, personal=lambda p, l: p.startswith("1/"))
    params = sim.init(jax.random.key(0))
    res = fp.run_round(params, None, data, n_samples, jax.random.key(1))

    with Checkpointer(str(tmp_path / "ck")) as ck:
        ck.save(1, res.params, extra=res.personal_state,
                meta={"mode": "fedper"})
        restored = ck.restore(res.params, extra_template=res.personal_state)
    assert restored.step == 1 and restored.meta["mode"] == "fedper"
    assert restored.extra is not None
    got = jax.tree_util.tree_leaves(restored.extra)
    want = jax.tree_util.tree_leaves(res.personal_state)
    assert len(got) == len(want) and len(want) > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # resuming from the restored state continues identically to never
    # having checkpointed
    r_direct = fp.run_round(res.params, res.personal_state, data, n_samples,
                            jax.random.key(2))
    r_resumed = fp.run_round(restored.params, restored.extra, data,
                             n_samples, jax.random.key(2))
    for a, b in zip(jax.tree_util.tree_leaves(r_direct.params),
                    jax.tree_util.tree_leaves(r_resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a checkpoint WITHOUT extra restores cleanly with extra=None
    with Checkpointer(str(tmp_path / "ck2")) as ck2:
        ck2.save(1, res.params)
        r2 = ck2.restore(res.params, extra_template=res.personal_state)
    assert r2.extra is None


def test_peak_hbm_reads_the_allocator_only():
    """peak_hbm_gb is a measurement: live arrays plus the running
    program's reserved temporaries, both from the runtime allocator
    (the TPU runtime counts them apart). A backend without allocator
    statistics gives None — never XLA's static plan under this name."""
    from baton_tpu.utils.profiling import peak_hbm_gb

    class Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    # the shape the v5e runtime returned in the PR 21 chip run
    gb = peak_hbm_gb(Dev({"peak_bytes_in_use": 2**30,
                          "peak_bytes_reserved": 2 * 2**30,
                          "bytes_in_use": 5}))
    assert gb == 3.0
    assert peak_hbm_gb(Dev({"peak_bytes_in_use": 2**29})) == 0.5
    assert peak_hbm_gb(Dev(None)) is None
    # the CPU test backend keeps no allocator statistics
    assert peak_hbm_gb(jax.devices()[0]) is None


def test_hbm_budget_device_mapping():
    from baton_tpu.utils.profiling import hbm_budget_gb

    class D:
        def __init__(self, kind):
            self.device_kind = kind

    # capacity minus headroom, by device-kind prefix
    assert hbm_budget_gb(D("TPU v5 lite")) == 13.5
    assert hbm_budget_gb(D("TPU v5e")) == 13.5
    assert hbm_budget_gb(D("TPU v4")) == 29.0
    assert hbm_budget_gb(D("TPU v5p")) == 90.0
    # a device the table does not hold is refused, never defaulted
    with pytest.raises(ValueError, match="weird accelerator"):
        hbm_budget_gb(D("weird accelerator"))
    with pytest.raises(ValueError, match="'cpu'"):
        hbm_budget_gb(jax.devices()[0])


def test_is_oom_error_requires_memory_corroboration():
    """gRPC/transport reuse RESOURCE_EXHAUSTED for quota, rate-limit and
    message-size failures; classifying those as device OOM turns them
    into a definitive plan=inf skip. Genuine TPU OOMs always carry
    memory/compile evidence."""
    from baton_tpu.utils.profiling import is_oom_error

    genuine = [
        RuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile permanent "
                     "error. Ran out of memory in memory space hbm"),
        RuntimeError("Allocation type: HLO temp; Size: 256.00M"),
        RuntimeError("out of memory allocating 123 bytes"),
    ]
    for e in genuine:
        assert is_oom_error(e), e
    transport = [
        RuntimeError("RESOURCE_EXHAUSTED: received message larger than "
                     "max (20971520 vs. 4194304)"),
        RuntimeError("RESOURCE_EXHAUSTED: quota exceeded for requests"),
        RuntimeError("RESOURCE_EXHAUSTED: rate limit"),
        RuntimeError("tracing error"),
    ]
    for e in transport:
        assert not is_oom_error(e), e


def test_plan_gb_treats_compile_oom_as_infinite(monkeypatch):
    """A compile-time RESOURCE_EXHAUSTED is XLA *proving* the program
    exceeds HBM. FedSim.wave_plan_gb must report it as over-any-budget,
    not as missing analysis that waves the config through."""
    from baton_tpu.models.linear import linear_regression_model
    from baton_tpu.parallel.engine import FedSim
    from baton_tpu.utils import profiling

    oom = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm; Allocation type: HLO temp")
    other = RuntimeError("memory_analysis unsupported")
    assert profiling.is_oom_error(oom)
    assert not profiling.is_oom_error(RuntimeError("tracing error"))

    class _Raises:
        def __init__(self, error):
            self.error = error

        def lower(self, *a):
            raise self.error

    assert profiling._plan_gb_of(_Raises(oom), ()) == float("inf")
    assert profiling._plan_gb_of(_Raises(other), ()) is None

    # the engine's probe keeps the contract, whether the wave program's
    # compile or the staging before it is what raises
    sim = FedSim(linear_regression_model(3), batch_size=4)
    params = sim.init(jax.random.key(0))
    data = {"x": jnp.zeros((2, 4, 3)), "y": jnp.zeros((2, 4))}
    n = jnp.array([4, 4])
    plan = sim.wave_plan_gb(params, data, n, jax.random.key(1))
    assert plan is None or plan > 0
    for error, want in ((oom, float("inf")), (other, None)):
        monkeypatch.setattr(
            sim, "_wave_program",
            lambda *a, error=error, **k: (_Raises(error), lambda *w: w))
        assert sim.wave_plan_gb(params, data, n, jax.random.key(1)) == want

        def stage_raises(*a, error=error, **k):
            raise error

        monkeypatch.setattr(sim, "_stage_wave", stage_raises)
        assert sim.wave_plan_gb(params, data, n, jax.random.key(1)) == want
        monkeypatch.undo()


def test_attention_sweep_never_clobbers_recorded_artifact(tmp_path):
    """A sweep without one TPU timing must not overwrite an artifact
    containing real hardware measurements."""
    import importlib.util
    import json
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "attention_sweep_under_test",
        pathlib.Path(__file__).resolve().parent.parent
        / "benchmarks" / "attention_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)

    out = tmp_path / "sweep.json"
    failed_path = str(out.with_name("sweep_failed.json"))
    good = {"platform": "tpu",
            "results": [{"L": 1024, "flash": {"256x256": 0.9}}]}
    smoke = {"platform": "cpu",
             "results": [{"L": 1024, "dense_ms": 5.0, "flash": {}}]}
    bad = {"platform": "tpu",
           "results": [{"L": 1024, "dense_error": "timeout", "flash": {}}]}
    mixed = {"platform": "tpu", "results": good["results"] + bad["results"]}

    # no prior artifact: failures may write to the primary path
    assert sweep.resolve_artifact_path(str(out), bad) == str(out)
    # prior artifact with TPU numbers: failures are diverted...
    out.write_text(json.dumps(good))
    assert sweep.resolve_artifact_path(str(out), bad) == failed_path
    # ...and so is a CPU smoke run (plausible numbers, wrong platform)
    assert sweep.resolve_artifact_path(str(out), smoke) == failed_path
    # a run with a TPU success always takes the primary path
    assert sweep.resolve_artifact_path(str(out), mixed) == str(out)
    # prior artifact that was itself TPU-less: overwrite is fine
    for prior in (bad, smoke):
        out.write_text(json.dumps(prior))
        assert sweep.resolve_artifact_path(str(out), bad) == str(out)
    # an unreadable prior is clobber-safe
    out.write_text("not json")
    assert sweep.resolve_artifact_path(str(out), bad) == str(out)
