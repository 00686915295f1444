"""Scenario harness + SLO gate (baton_tpu.loadgen).

Three layers, matching the module split:

- **scenario.py** — pure config/curve math: strict parsing (unknown
  keys fail), availability curve shapes, phase lookup, deterministic
  speed assignment. No federation needed.
- **slo.py** — the evaluator over hand-built ``rounds.jsonl`` records
  and metrics snapshots: assertion pass/fail/missing, the counter
  absence-is-zero rule, baseline deltas in both directions, warm-up
  exclusion, torn-line tolerance.
- **engine.py** — two short end-to-end runs with a real manager +
  worker fleet on loopback: the availability curve must actually
  modulate per-round participation, and a heavily-churned fleet must
  never leave a round stuck (every record reaches a terminal outcome).
"""

import asyncio
import json
import os

import pytest

from baton_tpu.loadgen.scenario import (
    AvailabilitySpec,
    Scenario,
    ScenarioError,
    load_scenario,
    parse_scenario,
)
from baton_tpu.loadgen.slo import (
    SLOAssertion,
    _quantile,
    check_assertions,
    check_baseline,
    derive_metrics,
    evaluate_slo,
    load_baseline,
    resolve_metric,
)
from baton_tpu.loadgen.scenario import SLOSpec
from baton_tpu.utils.slog import RoundsLog, read_rounds_jsonl


# ----------------------------------------------------------------------
# scenario.py — parsing + curve math (pure)


def minimal_scenario(**overrides):
    d = {
        "name": "t",
        "phases": [
            {"duration_s": 4.0, "availability": {"kind": "step", "level": 1.0}}
        ],
    }
    d.update(overrides)
    return d


def test_parse_minimal_scenario_defaults():
    scn = parse_scenario(minimal_scenario())
    assert scn.name == "t"
    assert scn.workers.count == 8
    assert scn.rounds.interval_s == 2.0
    assert scn.total_s == 4.0
    assert scn.slo.assertions == ()
    assert scn.slo.baseline is None


def test_unknown_key_is_an_error_not_a_default():
    # the whole point of strict parsing: "availabilty" must fail loudly
    with pytest.raises(ScenarioError, match="unknown key"):
        parse_scenario(minimal_scenario(typo_key=1))
    bad_phase = minimal_scenario()
    bad_phase["phases"][0]["availabilty"] = {"kind": "step"}
    with pytest.raises(ScenarioError, match="availabilty"):
        parse_scenario(bad_phase)


def test_bad_values_rejected():
    with pytest.raises(ScenarioError, match="name"):
        parse_scenario(minimal_scenario(name="bad name with spaces"))
    with pytest.raises(ScenarioError, match="phases"):
        parse_scenario({"name": "t", "phases": []})
    with pytest.raises(ScenarioError, match="min > max"):
        AvailabilitySpec.parse(
            {"kind": "sine", "min": 0.9, "max": 0.2}, "x"
        )
    with pytest.raises(ScenarioError, match="op"):
        parse_scenario(minimal_scenario(slo={
            "assertions": [{"metric": "rounds.total", "op": "!=", "value": 1}]
        }))


def test_step_and_sine_curves():
    step = AvailabilitySpec.parse({"kind": "step", "level": 0.4}, "x")
    assert step.level_at(0.0) == step.level_at(99.0) == 0.4

    sine = AvailabilitySpec.parse(
        {"kind": "sine", "min": 0.2, "max": 1.0, "period_s": 20}, "x"
    )
    # phase=0.25 turns: starts at the peak, troughs mid-period
    assert sine.level_at(0.0) == pytest.approx(1.0)
    assert sine.level_at(10.0) == pytest.approx(0.2)
    assert sine.level_at(5.0) == pytest.approx(0.6)
    assert sine.level_at(20.0) == pytest.approx(1.0)
    for t in range(0, 40):
        assert 0.0 <= sine.level_at(t / 2.0) <= 1.0


def test_phase_at_walks_and_clamps():
    scn = parse_scenario(minimal_scenario(phases=[
        {"name": "a", "duration_s": 2.0},
        {"name": "b", "duration_s": 3.0},
    ]))
    assert scn.phase_at(0.0)[1].name == "a"
    assert scn.phase_at(1.99)[1].name == "a"
    assert scn.phase_at(2.0)[1].name == "b"
    idx, phase, t_in = scn.phase_at(99.0)   # past the end: stick to last
    assert (idx, phase.name) == (1, "b")
    assert scn.total_s == 5.0


def test_speed_for_is_deterministic_and_cycles():
    scn = parse_scenario(minimal_scenario(workers={
        "count": 8,
        "speeds": [{"scale": 20.0, "fraction": 0.25}],
    }))
    speeds = [scn.workers.speed_for(i) for i in range(8)]
    assert speeds.count(20.0) == 2
    assert speeds.count(1.0) == 6
    # a joiner with idx >= count lands on the same layout
    assert scn.workers.speed_for(8) == scn.workers.speed_for(0)
    with pytest.raises(ScenarioError, match="sum"):
        parse_scenario(minimal_scenario(workers={
            "speeds": [{"scale": 2.0, "fraction": 0.7},
                       {"scale": 3.0, "fraction": 0.7}],
        }))


def test_baseline_path_resolves_relative_to_scenario_file(tmp_path):
    sub = tmp_path / "scenarios"
    sub.mkdir()
    path = sub / "s.json"
    path.write_text(json.dumps(minimal_scenario(
        slo={"baseline": "baselines/s.json"}
    )))
    scn = load_scenario(str(path))
    assert scn.slo.baseline == str(sub / "baselines" / "s.json")


def test_committed_scenarios_parse():
    root = os.path.join(os.path.dirname(__file__), "..",
                        "benchmarks", "scenarios")
    for name in sorted(os.listdir(root)):
        if name.endswith(".json"):
            scn = load_scenario(os.path.join(root, name))
            assert scn.phases and scn.slo.assertions


# ----------------------------------------------------------------------
# slo.py — evaluator units (no federation)


def rec(round_name, outcome="completed", duration=1.0, participants=4,
        reporters=4, stragglers=(), **extra):
    r = {
        "round": round_name, "outcome": outcome, "duration_s": duration,
        "participants": participants, "reporters": reporters,
        "stragglers": list(stragglers),
        "bytes_uploaded": 100, "bytes_broadcast": 200,
    }
    r.update(extra)
    return r


SNAPSHOT = {
    "counters": {"updates_received": 12.0},
    "gauges": {"clients_registered": 4.0},
    "timers": {"round_s": {"count": 3, "mean_s": 1.0, "p50_s": 1.0,
                           "p95_s": 2.0, "p99_s": 2.5, "max_s": 3.0}},
}


def test_derive_metrics_namespace():
    records = [rec("r1"), rec("r2", duration=3.0),
               rec("r3", outcome="aborted", duration=9.0)]
    m = derive_metrics(records, SNAPSHOT,
                       loadgen_snapshot={"counters": {"scenario_rounds_started": 3},
                                         "gauges": {"scenario_availability": 0.5}},
                       fleet_snapshot={"counters": {"heartbeats_sent": 40},
                                       "gauges": {}, "timers": {}})
    assert m["rounds.total"] == 3.0
    assert m["rounds.completed"] == 2.0
    assert m["rounds.completion_rate"] == pytest.approx(2 / 3)
    # aborted rounds are excluded from duration stats
    assert m["rounds.duration_max"] == 3.0
    assert m["rounds.duration_mean"] == 2.0
    assert m["counter:updates_received"] == 12.0
    assert m["gauge:clients_registered"] == 4.0
    assert m["timer:round_s:p95"] == 2.0
    assert m["fleet:counter:heartbeats_sent"] == 40.0
    assert m["loadgen:scenario_rounds_started"] == 3.0
    assert m["loadgen:scenario_availability"] == 0.5


def test_straggler_rate_counts_id_lists():
    # `stragglers` is a LIST of client ids; `participants` is a count
    records = [rec("r1", participants=4, stragglers=["w1", "w2"]),
               rec("r2", participants=4, stragglers=[])]
    m = derive_metrics(records)
    assert m["rounds.straggler_rate"] == pytest.approx(2 / 8)


def test_quantile_exact_interpolation():
    vals = [1.0, 2.0, 3.0, 4.0]
    assert _quantile(vals, 0.0) == 1.0
    assert _quantile(vals, 1.0) == 4.0
    assert _quantile(vals, 0.5) == 2.5
    assert _quantile([7.0], 0.95) == 7.0


def test_counter_absence_is_zero_but_timers_and_gauges_are_not():
    m = {"timer:round_s:p95": 2.0}
    assert resolve_metric(m, "counter:never_touched") == 0.0
    assert resolve_metric(m, "fleet:counter:never_touched") == 0.0
    assert resolve_metric(m, "loadgen:scenario_rounds_refused_423") == 0.0
    assert resolve_metric(m, "timer:never_observed:p95") is None
    assert resolve_metric(m, "gauge:never_set") is None
    assert resolve_metric(m, "rounds.duration_p95") is None


def test_check_assertions_pass_fail_missing():
    m = {"rounds.total": 5.0, "rounds.completion_rate": 0.4}
    out = check_assertions([
        SLOAssertion("rounds.total", ">=", 3),
        SLOAssertion("rounds.completion_rate", ">=", 0.8),
        SLOAssertion("timer:round_s:p95", "<=", 1.0),
        SLOAssertion("counter:updates_refused_secure_downgrade", "==", 0),
    ], m)
    assert [a["status"] for a in out] == ["pass", "fail", "missing", "pass"]
    assert out[2]["observed"] is None


def test_evaluate_slo_verdicts(tmp_path):
    slo = SLOSpec(assertions=(SLOAssertion("rounds.total", ">=", 2),))
    records = [rec("warm"), rec("r1"), rec("r2")]
    report = evaluate_slo(slo, records, SNAPSHOT,
                          exclude_rounds=["warm"], scenario_name="t")
    assert report["pass"] is True
    assert report["rounds_evaluated"] == 2
    assert report["rounds_excluded_warmup"] == 1

    failing = SLOSpec(assertions=(SLOAssertion("rounds.total", ">=", 99),))
    assert evaluate_slo(failing, records, SNAPSHOT)["pass"] is False

    missing = SLOSpec(assertions=(SLOAssertion("timer:nope:p95", "<=", 1),))
    report = evaluate_slo(missing, records, SNAPSHOT)
    assert report["pass"] is False
    assert report["assertions"][0]["status"] == "missing"


def test_baseline_deltas_both_directions():
    baseline = {"metrics": {
        "rounds.completion_rate": {"value": 1.0,
                                   "direction": "higher_is_better",
                                   "tolerance": 0.1},
        "rounds.duration_p95": {"value": 1.0,
                                "direction": "lower_is_better",
                                "tolerance": 0.5, "tolerance_abs": 0.1},
        "timer:gone:p95": {"value": 0.5, "direction": "lower_is_better"},
    }}
    m = {"rounds.completion_rate": 0.5, "rounds.duration_p95": 1.55}
    results = {r["metric"]: r for r in check_baseline(baseline, m)}
    # 0.5 < 1.0 - 0.1 → regression in the higher-is-better direction
    assert results["rounds.completion_rate"]["regression"] is True
    # 1.55 <= 1.0 + (0.5 + 0.1) → within slack
    assert results["rounds.duration_p95"]["regression"] is False
    assert results["rounds.duration_p95"]["delta"] == pytest.approx(0.55)
    # a metric the run stopped producing IS a regression
    assert results["timer:gone:p95"]["regression"] is True
    assert "missing" in results["timer:gone:p95"]["note"]

    within = {"rounds.completion_rate": 0.95, "rounds.duration_p95": 0.2,
              "timer:gone:p95": 0.4}
    assert not any(r["regression"] for r in check_baseline(baseline, within))


def test_evaluate_slo_gates_on_baseline_regressions():
    slo = SLOSpec(assertions=(SLOAssertion("rounds.total", ">=", 1),))
    baseline = {"metrics": {
        "rounds.total": {"value": 10, "direction": "higher_is_better",
                         "tolerance": 0.1},
    }}
    report = evaluate_slo(slo, [rec("r1")], SNAPSHOT, baseline=baseline)
    assert report["pass"] is False           # assertion passed, baseline didn't
    assert report["baseline"]["regressions"] == 1


def test_load_baseline_validation(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"metrics": {"x": {"value": 1.0}}}))
    assert load_baseline(str(p))["metrics"]["x"]["value"] == 1.0
    p.write_text(json.dumps({"metrics": {}}))
    with pytest.raises(ScenarioError, match="non-empty"):
        load_baseline(str(p))
    p.write_text(json.dumps({"metrics": {"x": {"value": 1,
                                               "direction": "sideways"}}}))
    with pytest.raises(ScenarioError, match="direction"):
        load_baseline(str(p))


def test_torn_final_line_is_counted_not_fatal(tmp_path):
    path = str(tmp_path / "rounds.jsonl")
    log = RoundsLog(path)
    log.append(rec("r1"))
    log.append(rec("r2"))
    with open(path, "a", encoding="utf-8") as fh:   # crash mid-append
        fh.write('{"round": "r3", "outcome": "comp')
    records, n_torn = read_rounds_jsonl(path)
    assert [r["round"] for r in records] == ["r1", "r2"]
    assert n_torn == 1
    report = evaluate_slo(
        SLOSpec(assertions=(SLOAssertion("rounds.total", "==", 2),)),
        records, SNAPSHOT, n_torn=n_torn,
    )
    assert report["pass"] is True
    assert report["torn_lines"] == 1


def test_rounds_log_appends_are_single_line_records(tmp_path):
    path = str(tmp_path / "rounds.jsonl")
    log = RoundsLog(path)
    for i in range(5):
        log.append({"round": f"r{i}", "outcome": "completed"})
    lines = open(path, encoding="utf-8").read().splitlines()
    assert len(lines) == 5
    for line in lines:
        r = json.loads(line)
        assert "wall_ts" in r   # stamped by the writer


# ----------------------------------------------------------------------
# engine.py — short end-to-end federations (real manager + workers)


def run_engine(scenario_dict, tmp_path, tick_s=0.05):
    from baton_tpu.loadgen.engine import run_scenario
    scn = parse_scenario(scenario_dict)
    artifacts = str(tmp_path / "artifacts")
    summary = asyncio.run(run_scenario(scn, artifacts, tick_s=tick_s))
    return scn, artifacts, summary


def test_availability_curve_modulates_participation(tmp_path):
    scn, artifacts, summary = run_engine({
        "name": "avail_mod",
        "seed": 11,
        "model": {"dim": 6},
        "workers": {"count": 8, "heartbeat_time": 0.3,
                    "min_batches": 1, "max_batches": 1, "batch_size": 16},
        "manager": {"round_timeout": 3.0, "client_ttl": 6.0},
        "rounds": {"interval_s": 1.2, "warmup": 1},
        "phases": [
            {"name": "high", "duration_s": 3.5,
             "availability": {"kind": "step", "level": 1.0}},
            {"name": "low", "duration_s": 3.5,
             "availability": {"kind": "step", "level": 0.4}},
        ],
    }, tmp_path)

    rounds = [r for r in summary["rounds"] if not r["warmup"]]
    by_phase = {"high": [], "low": []}
    for r in rounds:
        if r["phase"] in by_phase and isinstance(r["participants"], int):
            by_phase[r["phase"]].append(r["participants"])
    assert by_phase["high"], f"no rounds landed in the high phase: {rounds}"
    assert by_phase["low"], f"no rounds landed in the low phase: {rounds}"
    high = sum(by_phase["high"]) / len(by_phase["high"])
    low = sum(by_phase["low"]) / len(by_phase["low"])
    # level 1.0 → all 8 broadcast targets; level 0.4 → round(0.4×8) = 3
    # (the other 5 answer the injected 503 and are excluded, not evicted)
    assert high > low + 1.5, (high, low, rounds)

    # the availability 503s were refusals, not evictions: the manager
    # still ended the run with the full fleet registered
    mm = json.load(open(os.path.join(artifacts, "manager_metrics.json")))
    assert mm["gauges"]["clients_registered"] == 8
    assert mm["counters"].get("broadcast_rejected_503", 0) > 0

    # warm-up is excluded from the evaluated set
    assert summary["warmup_round_names"]
    assert all(r["round"] not in summary["warmup_round_names"]
               for r in rounds)


def test_churned_fleet_leaves_no_stuck_rounds(tmp_path):
    scn, artifacts, summary = run_engine({
        "name": "churn_t",
        "seed": 5,
        "model": {"dim": 6},
        "workers": {"count": 5, "heartbeat_time": 0.3,
                    "min_batches": 1, "max_batches": 1, "batch_size": 16},
        "manager": {"round_timeout": 2.0, "client_ttl": 2.0},
        "rounds": {"interval_s": 1.2, "warmup": 1, "drain_grace_s": 8.0},
        "phases": [
            {"name": "churny", "duration_s": 5.0,
             "availability": {"kind": "step", "level": 1.0},
             "churn": {"leave_per_s": 0.6, "join_per_s": 0.6}},
        ],
    }, tmp_path)

    # churn actually happened (cold leaves + mid-run joins)
    assert summary["counters"].get("scenario_workers_left", 0) >= 1
    assert summary["counters"].get("scenario_workers_joined", 0) >= 1

    # every recorded round reached a terminal outcome — the watchdog
    # turns departed reporters into stragglers instead of a stuck round
    records, n_torn = read_rounds_jsonl(os.path.join(artifacts,
                                                     "rounds.jsonl"))
    assert n_torn == 0
    assert records, "no rounds recorded at all"
    for r in records:
        outcome = r.get("outcome") or ""
        assert outcome == "completed" or outcome.startswith("aborted:"), r
    # and the drain left nothing in flight
    assert summary["counters"].get("scenario_rounds_forced_end", 0) == 0
