"""Compute-plane observability (obs/compute.py) and its wiring.

Covers the probe itself (FLOPs/MFU accounting, compile tracking, the
null-with-reason record invariant), the manager-side sanitizer, the
fleet ledger's degrading-MFU classification, the ``compute:*`` SLO
derivation with its skip carve-out, ``Metrics.history(since=)``, and an
end-to-end federation round asserting the record flows worker ->
manager -> rounds.jsonl -> fleet ledger.
"""

import asyncio
import json
import socket

import numpy as np
import pytest
from aiohttp import web

from baton_tpu.obs.compute import (
    RECOMPILE_STORM_THRESHOLD,
    TPU_PEAK_FLOPS,
    TRAIN_FLOPS_PER_IMG,
    CompileTracker,
    ComputeProbe,
    build_record,
    compute_mfu,
    model_family_of,
    peak_flops_for,
    register_model_flops,
    summarize_round,
    train_flops_per_sample,
    validate_record,
)


# ----------------------------------------------------------------------
# FLOPs / MFU accounting (the one shared implementation)


def test_model_family_resolution():
    class M:
        name = "resnet18_cifar10"

    fam, why = model_family_of(M())
    assert fam == "resnet18_cifar" and why is None
    fam, why = model_family_of("lineartest")
    assert fam is None and "lineartest" in why
    fam, why = model_family_of(object())
    assert fam is None and "no name" in why


def test_train_flops_and_peak_lookup():
    flops, why = train_flops_per_sample("resnet18_cifar")
    assert flops == TRAIN_FLOPS_PER_IMG and why is None
    flops, why = train_flops_per_sample(None)
    assert flops is None and why
    flops, why = train_flops_per_sample("unknown_family")
    assert flops is None and "unknown_family" in why

    peak, why = peak_flops_for("TPU v5 lite chip 0")  # prefix match
    assert peak == TPU_PEAK_FLOPS["TPU v5 lite"] and why is None
    peak, why = peak_flops_for("cpu")
    assert peak is None and "cpu" in why


def test_mfu_formula_matches_bench_headline():
    mfu, why = compute_mfu(100.0, TRAIN_FLOPS_PER_IMG, "TPU v5e")
    assert why is None
    assert mfu == pytest.approx(100.0 * TRAIN_FLOPS_PER_IMG / 197e12)
    # every unavailable input becomes a reason, never a bare None
    for args in [(None, 1e9, "TPU v4"), (1.0, None, "TPU v4"),
                 (1.0, 1e9, "cpu")]:
        mfu, why = compute_mfu(*args)
        assert mfu is None and isinstance(why, str) and why


def test_register_model_flops_roundtrip():
    register_model_flops("toynet_test", 123.0, name_prefixes=["toynet"])
    assert model_family_of("toynet_v2") == ("toynet_test", None)
    assert train_flops_per_sample("toynet_test") == (123.0, None)
    with pytest.raises(ValueError):
        register_model_flops("badnet", 0.0)


# ----------------------------------------------------------------------
# compile tracking


def test_compile_tracker_hit_miss_and_storm():
    t = CompileTracker()
    first = t.observe("train", ("sig", 1))
    assert first["cache_hit"] is False
    # the tracker owns no time: a build's seconds are the ledger's
    assert set(first) == {"cache_hit", "recompiles", "recompile_storm"}
    assert first["recompiles"] == 0
    assert first["recompile_storm"] is False
    assert t.seen("train", ("sig", 1)) and not t.seen("train", ("sig", 2))

    hit = t.observe("train", ("sig", 1))
    assert hit["cache_hit"] is True
    assert hit["recompiles"] == 0

    # shape churn: enough NEW signatures in the window flips the flag
    out = {}
    for i in range(2, 2 + RECOMPILE_STORM_THRESHOLD):
        out = t.observe("train", ("sig", i))
    assert out["recompile_storm"] is True
    assert out["recompiles"] == RECOMPILE_STORM_THRESHOLD


# ----------------------------------------------------------------------
# record building + the null-with-reason invariant


def test_validate_record_flags_bare_and_self_nulls():
    assert validate_record({"mfu": 0.4}) == []
    assert validate_record({"mfu": None, "mfu_reason": "why"}) == []
    assert validate_record({"mfu": None, "mfu_source": "s"}) == []
    bad = validate_record({"mfu": None})
    assert bad and "mfu" in bad[0]
    bad = validate_record({"mfu": None, "mfu_reason": None})
    assert len(bad) == 2  # the null AND the null reason field


def test_build_record_tpu_path_measures_everything():
    rec = build_record(
        train_s=2.0, n_samples=400.0, n_epochs=1, steps=8,
        device_kind="TPU v5e", n_chips=4,
        model_family="resnet18_cifar",
        compile_fields={"cache_hit": True, "recompiles": 0,
                        "recompile_storm": False, "compile_s": 0.0,
                        "compile_s_source": "cache_hit"},
        peak_hbm_gb=3.5, peak_hbm_source="allocator",
    )
    assert validate_record(rec) == []
    assert rec["samples_per_sec"] == 200.0
    assert rec["samples_per_sec_per_chip"] == 50.0
    assert rec["mfu"] == pytest.approx(
        50.0 * TRAIN_FLOPS_PER_IMG / 197e12, abs=5e-7)
    assert rec["peak_hbm_gb"] == 3.5
    assert rec["peak_hbm_gb_source"] == "allocator"


def test_build_record_unknowns_are_null_with_reason():
    rec = build_record(train_s=0.0, n_samples=0.0, device_kind="cpu")
    assert validate_record(rec) == []
    assert rec["samples_per_sec"] is None
    assert rec["samples_per_sec_reason"] == "no samples"
    assert rec["mfu"] is None and rec["mfu_reason"]
    assert rec["model_family"] is None and rec["model_family_reason"]
    assert rec["peak_hbm_gb"] is None and rec["peak_hbm_gb_reason"]
    assert rec["compile_s"] is None and rec["compile_s_reason"]


def test_probe_record_round_on_cpu():
    import time

    import jax
    import jax.numpy as jnp

    from baton_tpu.obs.compute import builds

    probe = ComputeProbe(model="lineartest")
    # a round that builds a program: what JAX says the build took is
    # the round's compile_s, and it lies inside the round's wall time
    t0 = time.perf_counter()
    jax.block_until_ready(
        jax.jit(lambda x: jnp.tanh(x) * 0.25 + 1.5)(jnp.ones(7)))
    built_s = sum(b.seconds for b in builds().since(t0))
    rec = probe.record_round(
        key="train", signature=("s", 1),
        train_s=time.perf_counter() - t0, n_samples=64.0,
        n_epochs=2, steps=4,
    )
    assert validate_record(rec) == []
    assert rec["steps"] == 4
    assert rec["samples_per_sec"] == pytest.approx(
        128.0 / rec["train_s"], rel=1e-3)
    assert rec["cache_hit"] is False
    assert rec["compile_s_source"] == "jax_monitoring"
    assert 0 < rec["compile_s"] <= rec["train_s"]
    assert rec["compile_s"] == pytest.approx(built_s, abs=2e-6)
    assert 0 <= rec["compile_cold_s"] <= rec["compile_s"]
    # CPU smoke: MFU + HBM are unmeasurable, and each says why
    assert rec["mfu"] is None and rec["mfu_reason"]
    assert rec["peak_hbm_gb"] is None and rec["peak_hbm_gb_reason"]
    # a second identical call in which nothing was built is a cache hit
    rec2 = probe.record_round(
        key="train", signature=("s", 1), train_s=0.0, n_samples=64.0,
    )
    assert rec2["cache_hit"] is True and rec2["compile_s"] == 0.0
    assert rec2["compile_s_source"] == "cache_hit"
    assert rec2["compile_cold_s"] == 0.0


def test_summarize_round_aggregates_and_keeps_reasons():
    r1 = build_record(
        train_s=2.0, n_samples=400.0, steps=8, device_kind="TPU v5e",
        model_family="resnet18_cifar",
        compile_fields={"cache_hit": False, "recompiles": 1,
                        "recompile_storm": True, "compile_s": 1.5,
                        "compile_s_source": "jax_monitoring",
                        "compile_cold_s": 0.5},
        peak_hbm_gb=3.0, peak_hbm_source="allocator",
    )
    r2 = build_record(
        train_s=4.0, n_samples=400.0, steps=8, device_kind="TPU v5e",
        model_family="resnet18_cifar",
        compile_fields={"cache_hit": True, "recompiles": 1,
                        "recompile_storm": False, "compile_s": 0.0,
                        "compile_s_source": "cache_hit"},
        peak_hbm_gb=3.5, peak_hbm_source="allocator",
    )
    s = summarize_round([r1, r2, None])
    assert validate_record(s) == []
    assert s["reporters"] == 2
    assert s["compile_s"] == 1.5            # max
    # r2's record carries no cold seconds: the one that does is the max
    assert s["compile_cold_s"] == 0.5
    assert s["steps"] == 16                 # sum
    assert s["peak_hbm_gb"] == 3.5          # max
    assert s["recompile_storms"] == 1
    assert s["samples_per_sec_per_chip"] == pytest.approx(
        (200.0 + 100.0) / 2)

    empty = summarize_round([])
    assert validate_record(empty) == []
    assert empty["reporters"] == 0
    assert empty["mfu"] is None and empty["mfu_reason"]


# ----------------------------------------------------------------------
# manager-side sanitizer


def test_clean_compute_enforces_invariant_at_the_door():
    from baton_tpu.server.http_manager import _clean_compute

    assert _clean_compute(None) is None
    assert _clean_compute("nope") is None
    assert _clean_compute({}) is None

    raw = {
        "train_s": 1.5,
        "mfu": None, "mfu_reason": "no peak spec",
        "peak_hbm_gb": 2.0, "peak_hbm_gb_source": "allocator",
        "compile_s": None,              # bare null: must be DROPPED
        "steps": -3,                    # negative: dropped
        "samples_per_sec": float("inf"),  # non-finite: dropped
        "recompiles": True,             # bool is not a count: dropped
        "cache_hit": True,
        "recompile_storm": False,
        "device_kind": "x" * 1000,      # bounded
        "unknown_key": 7,               # not in schema: dropped
    }
    out = _clean_compute(raw)
    assert out["train_s"] == 1.5
    assert out["mfu"] is None and out["mfu_reason"] == "no peak spec"
    assert out["peak_hbm_gb"] == 2.0
    assert out["peak_hbm_gb_source"] == "allocator"
    assert "compile_s" not in out
    assert "steps" not in out
    assert "samples_per_sec" not in out
    assert "recompiles" not in out
    assert out["cache_hit"] is True and out["recompile_storm"] is False
    assert len(out["device_kind"]) == 256
    assert "unknown_key" not in out


def test_clean_compute_accepts_a_real_probe_record():
    from baton_tpu.server.http_manager import _clean_compute

    rec = ComputeProbe(model="lineartest").record_round(
        key="t", signature=1, train_s=0.2, n_samples=32.0)
    out = _clean_compute(rec)
    assert out is not None
    assert validate_record(out) == []
    assert out["train_s"] == rec["train_s"]
    assert out["mfu"] is None and out["mfu_reason"]


# ----------------------------------------------------------------------
# fleet ledger: degrading MFU


def test_classify_client_degrading_mfu():
    from baton_tpu.server.fleet import classify_client

    def obs(mfu):
        return {"outcome": "reported", "train_s": 1.0, "mfu": mfu}

    # wall time steady, delivered FLOPs collapsing: degrading
    window = [obs(0.40)] * 4 + [obs(0.10)] * 4
    status, reason = classify_client(window, [1.0])
    assert status == "degrading"
    assert "mfu" in reason

    # steady MFU stays healthy
    status, _ = classify_client([obs(0.40)] * 8, [1.0])
    assert status == "healthy"

    # clients that never report MFU (CPU smoke) are untouched
    status, _ = classify_client(
        [{"outcome": "reported", "train_s": 1.0}] * 8, [1.0])
    assert status == "healthy"


def test_ledger_record_round_folds_compute_into_observations():
    from baton_tpu.server.fleet import ClientLedger

    led = ClientLedger(window=8)
    led.record_round(
        "r1", ["w0"], ["w0"],
        {"w0": {"timings": {"train_s": 0.5},
                "compute": {"mfu": 0.33, "compile_s": 1.2,
                            "recompile_storm": True}}},
    )
    snap = led.health_snapshot()
    info = snap["clients"]["w0"]
    assert info["mfu"] == 0.33
    assert info["compile_s"] == 1.2


# ----------------------------------------------------------------------
# SLO derivation + skip carve-out


def _round_rec(name, compute):
    return {"round": name, "outcome": "completed", "duration_s": 1.0,
            "reporters": 2, "participants": 2, "compute": compute}


def test_derive_compute_metrics_measured_path():
    from baton_tpu.loadgen.slo import derive_compute_metrics

    recs = [
        _round_rec("r1", {"reporters": 2, "compile_s": 1.0, "steps": 8,
                          "samples_per_sec_per_chip": 100.0, "mfu": 0.3,
                          "peak_hbm_gb": 2.0, "recompile_storms": 0}),
        _round_rec("r2", {"reporters": 2, "compile_s": 0.0, "steps": 8,
                          "samples_per_sec_per_chip": 120.0, "mfu": 0.4,
                          "peak_hbm_gb": 2.5, "recompile_storms": 1}),
    ]
    metrics, skips = derive_compute_metrics(recs)
    assert skips == {}
    assert metrics["compute:rounds_with_compute"] == 2.0
    assert metrics["compute:compile_s_max"] == 1.0
    assert metrics["compute:compile_s_mean"] == 0.5
    assert metrics["compute:steps_total"] == 16
    assert metrics["compute:samples_per_sec_per_chip_mean"] == 110.0
    assert metrics["compute:mfu_mean"] == pytest.approx(0.35)
    assert metrics["compute:peak_hbm_gb_max"] == 2.5
    assert metrics["compute:recompile_storm_rounds"] == 1.0


def test_derive_compute_metrics_null_with_reason_becomes_skip():
    from baton_tpu.loadgen.slo import derive_compute_metrics

    recs = [_round_rec("r1", {
        "reporters": 1, "compile_s": 0.2, "steps": 4,
        "samples_per_sec_per_chip": 50.0,
        "mfu": None, "mfu_reason": "no peak-FLOPs spec for 'cpu'",
        "peak_hbm_gb": None,
        "peak_hbm_gb_reason": "no allocator stats on cpu",
        "recompile_storms": 0})]
    metrics, skips = derive_compute_metrics(recs)
    assert "compute:mfu_mean" not in metrics
    assert skips["compute:mfu_mean"] == "no peak-FLOPs spec for 'cpu'"
    assert skips["compute:peak_hbm_gb_max"] == "no allocator stats on cpu"
    # a value that vanished WITHOUT a reason is simply absent: the
    # baseline gate will regress it (the silent-drop class)
    recs[0]["compute"].pop("mfu_reason")
    _, skips = derive_compute_metrics(recs)
    assert "compute:mfu_mean" not in skips


def test_evaluate_slo_compute_gate_and_skip_carveout():
    from baton_tpu.loadgen.scenario import SLOSpec
    from baton_tpu.loadgen.slo import evaluate_slo

    recs = [_round_rec("r1", {
        "reporters": 1, "compile_s": 0.2, "steps": 4,
        "samples_per_sec_per_chip": 50.0,
        "mfu": None, "mfu_reason": "cpu smoke",
        "peak_hbm_gb": None, "peak_hbm_gb_reason": "cpu smoke",
        "recompile_storms": 0})]
    baseline = {"metrics": {
        "compute:compile_s_max": {"value": 0.2,
                                  "direction": "lower_is_better",
                                  "tolerance": 1.0},
        # measured on TPU hardware, excused on the CPU tier
        "compute:mfu_mean": {"value": 0.35,
                             "direction": "higher_is_better",
                             "tolerance": 0.2},
    }}
    report = evaluate_slo(SLOSpec(), recs, baseline=baseline)
    assert report["pass"] is True
    by_metric = {r["metric"]: r for r in report["baseline"]["results"]}
    assert by_metric["compute:compile_s_max"]["regression"] is False
    mfu_entry = by_metric["compute:mfu_mean"]
    assert mfu_entry["regression"] is False
    assert mfu_entry["note"] == "skipped: cpu smoke"
    assert report["compute_skips"]["compute:mfu_mean"] == "cpu smoke"

    # no reason recorded -> the regression is NOT excused
    recs[0]["compute"]["mfu_reason"] = ""
    report = evaluate_slo(SLOSpec(), recs, baseline=baseline)
    assert report["pass"] is False


# ----------------------------------------------------------------------
# metrics history delta


def test_metrics_history_since():
    from baton_tpu.utils.metrics import Metrics

    m = Metrics()
    m.inc("updates_received")
    m.record_history(ts=100.0)
    m.inc("updates_received")
    m.record_history(ts=200.0)
    full = m.history()
    assert len(full) == 2
    assert [s["ts"] for s in m.history(since=100.0)] == [200.0]
    assert m.history(since=200.0) == []
    assert len(m.history(since=0.0)) == 2


# ----------------------------------------------------------------------
# end to end: worker -> manager -> rounds.jsonl -> fleet ledger


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_end_to_end_compute_telemetry(tmp_path):
    from baton_tpu.core.training import make_local_trainer
    from baton_tpu.data.synthetic import linear_client_data
    from baton_tpu.models.linear import linear_regression_model
    from baton_tpu.server.http_manager import Manager
    from baton_tpu.server.http_worker import ExperimentWorker

    rounds_path = tmp_path / "rounds.jsonl"

    async def main():
        model = linear_regression_model(10, name="ctest")
        nprng = np.random.default_rng(3)
        mport = _free_port()

        mapp = web.Application()
        manager = Manager(mapp)
        exp = manager.register_experiment(
            model, name="ctest", round_timeout=60.0,
            rounds_log_path=str(rounds_path),
        )
        mrunner = web.AppRunner(mapp)
        await mrunner.setup()
        await web.TCPSite(mrunner, "127.0.0.1", mport).start()
        runners = [mrunner]

        for _ in range(2):
            wport = _free_port()
            data = linear_client_data(nprng, min_batches=2, max_batches=2)
            wapp = web.Application()
            ExperimentWorker(
                wapp, model, f"127.0.0.1:{mport}", port=wport,
                heartbeat_time=1.0,
                trainer=make_local_trainer(model, batch_size=32,
                                           learning_rate=0.02),
                get_data=lambda d=data: (d, d["x"].shape[0]),
            )
            wrunner = web.AppRunner(wapp)
            await wrunner.setup()
            await web.TCPSite(wrunner, "127.0.0.1", wport).start()
            runners.append(wrunner)

        for _ in range(100):
            if len(exp.registry) == 2:
                break
            await asyncio.sleep(0.05)
        assert len(exp.registry) == 2

        import aiohttp

        async with aiohttp.ClientSession() as session:
            for _ in range(2):
                async with session.get(
                    f"http://127.0.0.1:{mport}/ctest/start_round?n_epoch=2"
                ) as resp:
                    assert resp.status == 200
                for _ in range(200):
                    if not exp.rounds.in_progress:
                        break
                    await asyncio.sleep(0.05)
                assert not exp.rounds.in_progress
            async with session.get(
                f"http://127.0.0.1:{mport}/ctest/metrics"
            ) as resp:
                metrics = await resp.json()
            async with session.get(
                f"http://127.0.0.1:{mport}/ctest/metrics/history?since=0"
            ) as resp:
                assert resp.status == 200
            async with session.get(
                f"http://127.0.0.1:{mport}/ctest/metrics/history?since=bogus"
            ) as resp:
                assert resp.status == 400
            async with session.get(
                f"http://127.0.0.1:{mport}/ctest/fleet/health"
            ) as resp:
                health = await resp.json()

        for r in runners:
            await r.cleanup()
        return metrics, health

    metrics, health = asyncio.run(main())

    # rounds.jsonl: every round carries a valid compute section with the
    # CPU-measurable fields measured and the rest null-with-reason
    records = [json.loads(line) for line in
               rounds_path.read_text().splitlines()]
    assert len(records) == 2
    for rec in records:
        comp = rec["compute"]
        assert validate_record(comp) == []
        assert comp["reporters"] == 2
        assert comp["steps"] and comp["steps"] > 0
        assert comp["samples_per_sec_per_chip"] > 0
        assert comp["compile_s"] is not None
        # linear model on CPU: MFU/HBM unmeasurable, reasons mandatory
        assert comp["mfu"] is None and comp["mfu_reason"]
        assert comp["peak_hbm_gb"] is None and comp["peak_hbm_gb_reason"]
    # round 2 reuses round 1's jit cache: compile_s drops to the exact 0
    assert records[0]["compute"]["compile_s"] > 0.0
    assert records[1]["compute"]["compile_s"] == 0.0

    # the same values are exported as compute_* gauges for the console
    gauges = metrics["gauges"]
    assert gauges["compute_reporters"] == 2
    assert gauges["compute_steps"] == records[-1]["compute"]["steps"]
    assert gauges["compute_samples_per_sec_per_chip"] == pytest.approx(
        records[-1]["compute"]["samples_per_sec_per_chip"])
    assert gauges["compute_recompile_storm"] == 0.0

    # and the fleet ledger carries per-client compile_s observations
    infos = list(health["clients"].values())
    assert len(infos) == 2
    assert all(i.get("compile_s") is not None for i in infos)
