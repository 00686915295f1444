"""BENCHMARK.json against the contract's limits, every named file in
place, and the proof that the harness is driven by data: a copy of the
tree gains a cell and a layer metric, and then a whole next-token
configuration, by added files and entries alone."""

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
CONFIGS = ["resnet18_cifar10", "bert_base"]
LAYER_METRICS = ["init_s", "first_round_s", "idle_ms_per_round",
                 "compiles_in_window", "wave_ms", "conv_roofline",
                 "matmul_roofline", "nonwave_device_ms", "collective_ms",
                 "device_idle_share", "peak_hbm_gib"]
SPAN_AND_SCOPE_METRICS = ["fold_idle_ms", "stage_idle_ms",
                          "sync_record_idle_ms", "fwd_ms", "bwd_ms",
                          "optimizer_ms", "norm_ms", "padded_slot_share"]
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "next_token")

# What ``reduced`` may never name: a width. A hidden, intermediate,
# latent, state, projection or window size, a head size, a key that ends
# in ``_dim``, ``_rank`` or ``_width``, an expansion factor, the experts
# a token is routed to. Depth, counts of heads or of experts held here
# and the rows of the vocabulary may be the chip's share (the
# ``model-configs`` guide, section 4) and pass.
WIDTH_KEYS = {"d_model", "d_ff", "d_head", "d_state", "d_conv", "expand",
              "sliding_window", "kv_channels", "top_k"}
WIDTH_ENDINGS = ("_dim", "_rank", "_width", "head_dim", "headdim",
                 "hidden_size", "intermediate_size", "latent_size",
                 "state_size", "projection_size", "window_size", "_d_state",
                 "_expand", "expansion_factor", "per_tok", "per_token",
                 "_top_k", "_topk")
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "linear_key_head_dim", "linear_value_head_dim",
          "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "q_lora_rank",
          "linear_conv_kernel_dim", "stem_width", "sliding_window",
          "window_size", "ssm_state_size", "mamba_d_state", "mamba_expand",
          "mlp_expansion_factor", "num_experts_per_tok", "moe_top_k",
          "ffn_hidden_size", "moe_latent_size"]
NOT_WIDTHS = ["layers", "num_hidden_layers", "num_layers",
              "num_attention_heads", "num_key_value_heads",
              "linear_num_key_heads", "linear_num_value_heads",
              "n_routed_experts", "num_experts", "num_local_experts",
              "vocab_size", "hidden_act", "attention_bias", "layer_norm_eps",
              "max_window_layers", "first_k_dense_replace"]


def widths_named(reduced: list) -> list:
    """The keys of a ``reduced`` list that name a width: none may."""
    return [key for key in reduced
            if key in WIDTH_KEYS or key.endswith(WIDTH_ENDINGS)]


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
    """Issue 22's names are present, in order; later names may follow.
    A new end-to-end metric is a ``benchmark`` PR's business."""
    assert [w["name"] for w in bench["workloads"]][:len(CELLS)] == CELLS
    assert [c["name"] for c in bench["configs"]][:len(CONFIGS)] == CONFIGS
    assert [m["name"] for m in bench["end_to_end"]] == [
        "samples_per_s_per_chip", "round_s", "setup_s"]
    assert [m["name"] for m in bench["per_layer"]][
        :len(LAYER_METRICS)] == LAYER_METRICS


def test_the_layer_metrics_of_issue_26_exist(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[len(LAYER_METRICS):][:len(SPAN_AND_SCOPE_METRICS)] == \
        SPAN_AND_SCOPE_METRICS
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["norm_ms"]["workloads"] == CELLS[:3]
    assert {by_name[n]["source"] for n in SPAN_AND_SCOPE_METRICS[:3]} == {
        "program_span"}


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
    cells = bench["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert "resnet18_c128_mesh4" in four
    assert len(four) <= max(1, len(cells) // 4) and len(cells) <= 24
    assert all(w["chips"] in (1, 4) for w in cells)


def test_every_cells_and_configurations_files_exist(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("fedbench/configs/")
        config = manifest.load_config(ROOT, bench, c["name"])
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        # no width may be named as changed
        assert widths_named(c["reduced"]) == []
        for block in ("builder", "input", "tiny", "assumed"):
            assert block in config, (c["name"], block)
        assert hasattr(manifest.load_module(ROOT, "flops", c["name"]),
                       "required")
        assert hasattr(manifest.load_module(ROOT, "references", c["name"]),
                       "make_loss")
        assert hasattr(manifest.load_module(ROOT, "inputs",
                                            config["input"]["kind"]), "make")
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


@pytest.mark.parametrize("key,width", [(k, True) for k in WIDTHS]
                         + [(k, False) for k in NOT_WIDTHS])
def test_reduced_refuses_widths_and_nothing_else(key, width):
    """Among the cuts the guide allows, a width is still refused, and
    one more key that is no width is not."""
    cut = ["num_hidden_layers", "num_attention_heads", "vocab_size"]
    assert widths_named(cut + [key]) == ([key] if width else [])


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


def _copy_of_the_benchmark(tmp_path):
    """``(root, before)``: a copy of ``fedbench/`` and every file's
    bytes, to show afterwards that none was edited."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "fedbench"),
                    os.path.join(root, "fedbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {}
    for folder, _, files in os.walk(root):
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    return root, before


def _assert_no_file_was_edited(before):
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, f"{path} was edited"


def test_a_cell_and_a_layer_metric_are_added_by_files_alone(tmp_path):
    """Copy the benchmark, add one workload file, one layer-metric file
    and their BENCHMARK.json entries; the same harness code lists and
    loads them. No file that was there is edited."""
    root, before = _copy_of_the_benchmark(tmp_path)
    bench = manifest.load_manifest(ROOT)

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
    _assert_no_file_was_edited(before)


def _copy_with_the_fixture(tmp_path):
    """``(root, before, bench)``: a copy of the benchmark with
    ``tests/fedbench/fixtures/next_token`` laid over it, files and
    BENCHMARK.json entries; no file of the copy is replaced."""
    root, before = _copy_of_the_benchmark(tmp_path)
    added = []
    for folder, _, files in os.walk(os.path.join(FIXTURE, "fedbench")):
        for f in files:
            if f.endswith(".pyc"):
                continue
            src = os.path.join(folder, f)
            dst = os.path.join(root, os.path.relpath(src, FIXTURE))
            assert dst not in before, f"{dst} would replace a file"
            shutil.copy(src, dst)
            added.append(os.path.relpath(dst, root))
    assert sorted({a.split(os.sep)[1] for a in added}) == [
        "configs", "flops", "inputs", "layer_metrics", "references",
        "workloads"]
    bench = manifest.load_manifest(ROOT)
    with open(os.path.join(FIXTURE, "entries.json")) as f:
        for group, entries in json.load(f).items():
            bench[group] += entries
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before, bench


def _rehearse(root, cell, seed, trace, monkeypatch, capsys):
    """``(rc, lines, result)`` of ``run.main`` on the tree at ``root``."""
    from fedbench import run

    monkeypatch.setattr(manifest, "ROOT", root)
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace), "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, lines, json.loads(lines[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_a_configuration_is_added_by_files_alone(trace, tmp_path, monkeypatch,
                                                 capsys):
    """Copy the benchmark and lay ``tests/fedbench/fixtures/next_token``
    over it: a next-token decoder (labels ``y [n, l]``) with its
    configuration (an ``engine`` block whose ``trainable`` predicate
    freezes all but the attention projections, a ``scopes`` block),
    reference, input kind, FLOP count, cells and a layer metric, plus
    their BENCHMARK.json entries. ``run.main`` on the copy rehearses the
    new cell to the contract's line with ``correct`` true, untraced and
    traced, and no file that was there is edited."""
    root, before, bench = _copy_with_the_fixture(tmp_path)
    rc, lines, result = _rehearse(root, "tiny_decoder_c4", 5, trace,
                                  monkeypatch, capsys)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    # 2 layers of 9 leaves, two tables and a norm; 8 projections train
    assert any("probe: update disagreement" in l
               and "frozen leaves unchanged: 13 of 13: ok" in l
               for l in lines)
    assert result["compared"]["frozen_leaves_changed"] == {"value": 0,
                                                           "limit": 0}
    if trace:
        wanted = {m["name"] for m in manifest.metrics_for(
            bench["per_layer"], "tiny_decoder_c4")}
        assert set(result["metrics"]) == wanted
        assert "norm_ms" not in wanted and "fwd_ms" in wanted
        assert result["metrics"]["real_samples_per_round"]["value"] == 24
    else:
        assert set(result["metrics"]) == {"samples_per_s_per_chip", "round_s",
                                          "setup_s"}
    # the configuration's own names reach the reduction of a trace
    config = manifest.load_config(root, bench, "tiny_decoder")
    names = manifest.load_trace_names(root, config)
    assert "lm_head" in names["parts"] and "norm" in names["parts"]
    assert re.fullmatch(names["blocks"], "layer3")
    assert re.fullmatch(names["blocks"], "s0b1")
    # only attention projections are trainable: the engine block arrived
    engine = manifest.engine_args(config, {})
    assert engine["trainable"]("blocks/0/attn/wq", None)
    assert not engine["trainable"]("blocks/0/mlp/w_up", None)
    _assert_no_file_was_edited(before)


def test_the_fixture_rehearses_at_batch_1(tmp_path, monkeypatch, capsys):
    """A decoder of 7 B's widths trains at one or two sequences a batch:
    the fixture's second cell has batch 1, where a quarter of a batch is
    no sample. The probe cohort is then four clients of one sample each,
    none empty, and the run ends ``correct``."""
    from fedbench import run

    root, before, bench = _copy_with_the_fixture(tmp_path)
    rc, lines, result = _rehearse(root, "tiny_decoder_c4_b1", 6, 0,
                                  monkeypatch, capsys)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert any("frozen leaves unchanged: 13 of 13: ok" in l for l in lines)
    assert all(c["value"] == c["value"] for c in result["compared"].values())
    config = manifest.load_config(root, bench, "tiny_decoder")
    job = run.job_of(manifest.load_workload(root, "tiny_decoder_c4_b1"), True)
    data, sizes = run.probe_cohort(root, config, job, True, 6)
    assert list(sizes) == [1, 1, 1, 1] and data["x"].shape[:2] == (4, 1)
    _assert_no_file_was_edited(before)


def test_a_changed_frozen_leaf_is_not_correct(tmp_path, monkeypatch, capsys):
    """The timed path broken underneath: ``FedSim.run_round`` trains as it
    should and hands back one leaf that the ``trainable`` predicate
    rejects with one entry moved by one step of its own dtype. No
    disagreement over the trainable leaves sees it; the probe's count of
    frozen leaves does, and ``correct`` is false."""
    import dataclasses

    import jax.numpy as jnp

    from baton_tpu.parallel.engine import FedSim

    sound = FedSim.run_round

    def nudged(self, params, *args, **kwargs):
        res = sound(self, params, *args, **kwargs)
        table = res.params["tok_emb"]
        moved = dict(res.params, tok_emb=table.at[0, 0].set(
            jnp.nextafter(table[0, 0], jnp.inf)))
        return dataclasses.replace(res, params=moved)

    root, _, _ = _copy_with_the_fixture(tmp_path)
    monkeypatch.setattr(FedSim, "run_round", nudged)
    rc, lines, result = _rehearse(root, "tiny_decoder_c4", 7, 0, monkeypatch,
                                  capsys)
    assert rc == 0 and result["correct"] is False and result["failed"] == 0
    assert any("frozen leaves unchanged: 12 of 13: FAILED" in l
               for l in lines)
    compared = result["compared"]
    assert compared["frozen_leaves_changed"] == {"value": 1, "limit": 0}
    assert all(compared[k]["value"] <= compared[k]["limit"]
               for k in ("reference", "loss_gap"))


def test_unknown_names_are_errors():
    bench = manifest.load_manifest(ROOT)
    with pytest.raises(KeyError):
        manifest.cell_entry(bench, "no_such_cell")
    with pytest.raises(FileNotFoundError):
        manifest.load_module(ROOT, "layer_metrics", "no_such_metric")
