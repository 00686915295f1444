"""``correct`` can fail: the control (the plain reference with every
matrix product's operands in float8, put in the program's place) comes
out over a limit the program stays under, and a run whose timed path is
broken underneath (a round that returns its state unchanged) ends with
``correct`` false. At ``tiny`` sizes on the CPU; PERF.md section 2 has
the chip's readings at the cells' own sizes."""

import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import control, manifest, run  # noqa: E402

BENCH = manifest.load_manifest(ROOT)


@pytest.mark.parametrize("cell", ["resnet18_c32_w1", "bert_base_c10_l128"])
def test_the_control_comes_out_not_correct(cell):
    config = manifest.load_config(
        ROOT, BENCH, manifest.cell_entry(BENCH, cell)["config"])
    limits = {"max": config["probe_tolerance"],
              "l2": config["probe_l2_tolerance"]}
    for seed in (21, 22, 23):
        got = control.readings(ROOT, cell, seed, tiny=True)
        assert got["program"]["reference"] <= limits["max"]
        assert got["program"]["reference_l2"] <= limits["l2"]
        # float8 has to fail one of the numbers, not each: the widest
        # entry swings by its nature, the norm over all parameters is
        # the steady one
        assert got["control"]["l2"] > limits["l2"], (seed, got)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_round_that_returns_its_state_unchanged_is_not_correct(
        trace, monkeypatch, capsys):
    """The harness's look for a chip skipped (a rehearsal), the rest of a
    run driven with ``FedSim.run_round`` broken underneath: it computes
    its losses and hands back the parameters it was given."""
    from baton_tpu.parallel.engine import FedSim

    sound = FedSim.run_round

    def unchanged(self, params, *args, **kwargs):
        return dataclasses.replace(sound(self, params, *args, **kwargs),
                                   params=params)

    monkeypatch.setattr(FedSim, "run_round", unchanged)
    rc = run.main(["--workload", "resnet18_c128_w32", "--seed", "9",
                   "--seconds", "1", "--trace", str(trace), "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0
    # the probe sees it: the update it compares is all zeros, every
    # disagreement 1 (the loss of a round still varies with its shuffle,
    # so the falling-loss rule alone would not)
    assert any("probe:" in l and "reference 1 " in l and "FAILED" in l
               for l in lines)
