"""BENCHMARK.json against the contract's limits, every named file in
place, and the proof that the harness is driven by data: a copy of the
tree gains a cell and a layer metric by added files and entries alone."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = ["resnet18_c32_w1", "resnet18_c128_w32", "resnet18_c128_mesh4",
         "bert_base_c10_l128"]
LAYER_METRICS = ["init_s", "first_round_s", "idle_ms_per_round",
                 "compiles_in_window", "wave_ms", "conv_roofline",
                 "matmul_roofline", "nonwave_device_ms", "collective_ms",
                 "device_idle_share", "peak_hbm_gib"]


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest(ROOT)


def test_manifest_has_exactly_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["fedbench", "tests/fedbench"]
    assert bench["command"] == ["python3", "fedbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_cells_and_metrics_of_issue_22_exist(bench):
    assert [w["name"] for w in bench["workloads"]] == CELLS
    assert [c["name"] for c in bench["configs"]] == ["resnet18_cifar10",
                                                     "bert_base"]
    assert [m["name"] for m in bench["end_to_end"]] == [
        "samples_per_s_per_chip", "round_s", "setup_s"]
    assert [m["name"] for m in bench["per_layer"]] == LAYER_METRICS


def test_names_units_and_whys_are_within_limits(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in bench[group]]
        assert len(seen) == len(set(seen)), f"duplicate name in {group}"
        names += seen
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds_and_chip_counts(bench):
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert {m["name"]: m["bound"] for m in bench["end_to_end"]}[
        "setup_s"] == 0.1
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["resnet18_c128_mesh4"]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_every_cells_and_configurations_files_exist(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("fedbench/configs/")
        config = manifest.load_config(ROOT, bench, c["name"])
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        # no width may be named as changed
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)
        for block in ("builder", "input", "tiny", "assumed"):
            assert block in config, (c["name"], block)
        assert hasattr(manifest.load_module(ROOT, "flops", c["name"]),
                       "required")
    used = set()
    for w in bench["workloads"]:
        workload = manifest.load_workload(ROOT, w["name"])
        assert workload["name"] == w["name"]
        assert workload["config"] == w["config"]
        assert workload["chips"] == w["chips"]
        kind = workload["samples_per_client"]["kind"]
        assert hasattr(manifest.load_module(ROOT, "cohorts", kind), "sizes")
        used.add(w["config"])
    assert used == {c["name"] for c in bench["configs"]}


def test_every_layer_metric_names_its_layer_cells_and_target(bench):
    cells = [w["name"] for w in bench["workloads"]]
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        reader = manifest.load_module(ROOT, "layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        # every cell reports every end-to-end metric, so the target is
        # reported wherever the layer metric is
        assert m["moves"] in end_to_end
        assert all(c in cells for c in m.get("workloads", cells))
        assert callable(reader.read)
        assert reader.read(None, {}, {"required": {"kernel": None}}) is None
    for cell in cells:
        assert manifest.metrics_for(bench["per_layer"], cell)
        assert len(manifest.metrics_for(bench["end_to_end"], cell)) == 3


def test_roofline_metrics_follow_the_naming_rule(bench):
    for m in bench["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_a_cell_and_a_layer_metric_are_added_by_files_alone(tmp_path):
    """Copy the benchmark, add one workload file, one layer-metric file
    and their BENCHMARK.json entries; the same harness code lists and
    loads them. No file that was there is edited."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "fedbench"),
                    os.path.join(root, "fedbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = manifest.load_manifest(ROOT)
    before = {}
    for folder, _, files in os.walk(root):
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()

    workload = manifest.load_workload(ROOT, "resnet18_c32_w1")
    workload.update(name="resnet18_c64_w16", clients=64, wave_size=16)
    with open(os.path.join(root, "fedbench", "workloads",
                           "resnet18_c64_w16.json"), "w") as f:
        json.dump(workload, f)
    with open(os.path.join(root, "fedbench", "layer_metrics",
                           "waves_per_round.py"), "w") as f:
        f.write('LAYER = "round loop"\nUNIT = "count"\n'
                'MOVES = "round_s"\nSOURCE = "program_counter"\n\n\n'
                'def read(reduced, counters, cell):\n'
                '    return counters.get("n_waves")\n')
    bench["workloads"].append({
        "name": "resnet18_c64_w16", "config": "resnet18_cifar10",
        "traffic": "c64x48_w16", "chips": 1, "why": "discovery test"})
    bench["per_layer"].append({
        "name": "waves_per_round", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "round loop",
        "moves": "round_s", "workloads": ["resnet18_c64_w16"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    seen = manifest.load_manifest(root)
    entry = manifest.cell_entry(seen, "resnet18_c64_w16")
    assert manifest.load_workload(root, entry["name"])["wave_size"] == 16
    assert manifest.load_config(root, seen, entry["config"])["name"] == \
        "resnet18_cifar10"
    new = [m["name"] for m in
           manifest.metrics_for(seen["per_layer"], "resnet18_c64_w16")]
    assert "waves_per_round" in new and "collective_ms" not in new
    assert "waves_per_round" not in [
        m["name"] for m in
        manifest.metrics_for(seen["per_layer"], "resnet18_c32_w1")]
    reader = manifest.load_module(root, "layer_metrics", "waves_per_round")
    assert reader.read(None, {"n_waves": 4}, {}) == 4
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, f"{path} was edited"


def test_unknown_names_are_errors():
    bench = manifest.load_manifest(ROOT)
    with pytest.raises(KeyError):
        manifest.cell_entry(bench, "no_such_cell")
    with pytest.raises(FileNotFoundError):
        manifest.load_module(ROOT, "layer_metrics", "no_such_metric")
