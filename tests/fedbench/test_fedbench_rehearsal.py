"""Every cell's control flow on the CPU at its ``tiny`` sizes: one JSON
line with the contract's keys, counts only, no time under a device
metric's name; and no result at all without a TPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest, run  # noqa: E402

BENCH = manifest.load_manifest(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
COUNTS = {"count"}  # units of metrics a CPU run may report


def _last_line(capsys):
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    result = json.loads(lines[-1])
    # the same numbers are the last lines of standard error
    said = captured.err.strip().splitlines()[-len(result["compared"]):]
    assert [l.split()[2] for l in said] == list(result["compared"])
    assert all(l.startswith("fedbench compared: ") and "(limit " in l
               for l in said)
    return lines, result


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_the_contracts_line(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "3", "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu"])
    lines, result = _last_line(capsys)
    assert rc == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # each number that decided ``correct`` beside its limit, last in the
    # line; the probe's and the count of failed rounds in every run
    assert {"reference", "reference_l2", "loss_gap", "failed_rounds"} <= set(
        result["compared"])
    assert all(set(c) == {"value", "limit"}
               for c in result["compared"].values())
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "round_s",
                                      "setup_s"}
    for name, m in result["metrics"].items():
        assert m["value"] is None, f"a CPU run wrote {name}"
    # the probe's disagreement and the loss trajectory are on earlier lines
    assert any("probe: update disagreement" in l for l in lines)
    assert any("loss by round" in l for l in lines)
    # only the last line is JSON
    assert all(not l.startswith("{") for l in lines[:-1])


@pytest.mark.parametrize("cell", ["resnet18_c128_w32", "resnet18_c128_mesh4"])
def test_traced_rehearsal_reports_counts_only(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "4", "--seconds", "1",
                   "--trace", "1", "--rehearse-cpu"])
    _, result = _last_line(capsys)
    assert rc == 0 and result["correct"] is True
    workload = manifest.load_workload(ROOT, cell)
    assert result["attempted"] == workload["tiny"]["trace_rounds"]
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    wanted = {m["name"] for m in
              manifest.metrics_for(BENCH["per_layer"], cell)}
    assert set(result["metrics"]) == wanted
    sources = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    for name, m in result["metrics"].items():
        # a CPU trace has host spans and no device plane: whatever is
        # read from a span, a scope or the device trace is null, and only
        # a count is written
        if sources[name] in ("program_span", "device_trace"):
            assert m["value"] is None, f"a CPU run wrote {name}"
        if units[name] in COUNTS:
            assert m["value"] == 0  # compiles_in_window
        else:
            assert m["value"] is None, f"a CPU run wrote {name}"
    assert {"fold_idle_ms", "fwd_ms", "padded_slot_share"} <= wanted
    # no device plane: no busy time, no breakdown
    assert "busy_s" not in result["device"] and "breakdown" not in result


def test_without_a_tpu_there_is_no_result(capsys):
    rc = run.main(["--workload", "resnet18_c32_w1", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert captured.out.strip() == ""
    assert "No result" in captured.err


def test_window_stamps_follow_the_dispatch_rule():
    """Round i-2 is settled and stamped before round i is dispatched,
    the last two rounds are settled without a stamp, and a non-finite
    loss is a failed round."""
    import jax.numpy as jnp

    order = []

    class Result:
        def __init__(self, i):
            self.params = i
            self.loss_history = [jnp.asarray(float("nan") if i == 3 else 1.0)]

    class Sim:
        def run_round(self, params, data, n, key, **kw):
            order.append(("dispatch", len([o for o in order
                                           if o[0] == "dispatch"])))
            return Result(order[-1][1])

    job = {"local_epochs": 1, "wave_size": None}
    import jax

    _, stamps, losses, attempted, failed = run.run_rounds(
        Sim(), 0, None, None, jax.random.key(0), job, 0, lambda n: n < 5)
    assert attempted == 5 and len(losses) == 5 and len(stamps) == 3
    assert failed == 1
    assert stamps == sorted(stamps)
