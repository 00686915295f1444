"""The ``olmo_hybrid_7b`` configuration and its cell at ``tiny`` sizes on
the CPU: the configuration file against what it promises (every catalog
key, the cut, the period kept), the FLOP and byte counts against the
shapes, the traced rehearsal with the cell's four layer metrics, the
float8 control over the limits and a changed frozen leaf not
``correct``. The untraced rehearsal, the reference against the program
(loss and every gradient leaf, a whole period) and the reference's
plainness run for every configuration in ``test_fedbench_rehearsal.py``
and ``test_fedbench_references.py``."""

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
CELL, CONFIG = "olmo_hybrid_c4_l1024", "olmo_hybrid_7b"
NEW_METRICS = ["linear_attn_ms", "delta_scan_ms", "lm_loss_ms",
               "delta_scan_roofline"]
# Olmo-Hybrid-7B's config.json as the model-configs catalog holds it
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(ROOT, BENCH, CONFIG)


def test_the_configuration_keeps_every_published_size(config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["num_hidden_layers_published"] == 32
    assert "four pipeline stages of 8 layers" in config["deployment"]
    # what the config.json does not give is listed with its reason
    for key in ("norm_placement", "qk_norm", "rotary", "a_log_and_dt_bias",
                "linear_chunk", "output_gate", "lora", "lora_b_std",
                "embed_std", "param_dtype"):
        assert len(config["assumed"][key]) > 40, key
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers"]


def test_the_cell_is_the_one_the_issue_names():
    entry = manifest.cell_entry(BENCH, CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "c4x2_l1024_b1", 1)
    job = manifest.load_workload(ROOT, CELL)
    assert (job["clients"], job["samples_per_client"], job["seq_len"],
            job["batch"], job["local_epochs"], job["wave_size"]) == (
                4, {"kind": "const", "n": 2}, 1024, 1, 1, None)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-4:] == NEW_METRICS
    for m in BENCH["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
    assert [m["name"] for m in manifest.metrics_for(
        BENCH["per_layer"], "bert_base_c10_l128")
        if m["name"] in NEW_METRICS] == []


def test_the_model_built_from_the_file_is_the_stage_it_states(config):
    """2,435 M frozen parameters in bfloat16 and 10.7 M adapter
    parameters in float32, from shapes alone."""
    import jax
    import jax.numpy as jnp

    model = manifest.build_model(config, tiny=False)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    base = jax.tree_util.tree_leaves(shapes["base"])
    lora = jax.tree_util.tree_leaves(shapes["lora"])
    matrices = sum(a.size for a in base if a.ndim >= 2)
    assert 2.43e9 < matrices < 2.44e9
    assert {a.dtype for a in base if a.ndim >= 2} == {jnp.dtype(jnp.bfloat16)}
    assert 10.6e6 < sum(a.size for a in lora) < 10.8e6
    assert {a.dtype for a in lora} == {jnp.dtype(jnp.float32)}
    kinds = ["linear_attn" if "linear_attn" in b else "attn"
             for b in shapes["base"]["blocks"]]
    assert kinds == (["linear_attn"] * 3 + ["attn"]) * 2
    engine = manifest.engine_args(config, {})
    assert engine["trainable"]("lora/blocks/0/mlp/w_up/a", None)
    assert not engine["trainable"]("base/blocks/0/mlp/w_up", None)
    # at tiny sizes the pattern stays: one whole period, all float32
    tiny = jax.eval_shape(manifest.build_model(config, tiny=True).init,
                          jax.random.key(0))
    assert len(tiny["base"]["blocks"]) == 4
    assert {a.dtype for a in jax.tree_util.tree_leaves(tiny)} == {
        jnp.dtype(jnp.float32)}


def test_required_work_follows_the_shapes(config):
    flops = manifest.load_module(ROOT, "flops", CONFIG)
    job = {"n_samples": [2, 2, 2, 2], "batch": 1, "local_epochs": 1,
           "seq_len": 1024}
    need = flops.required(config, job)
    macs = need["forward_macs_per_token"]
    h, f = 3840, 11008
    linear = (2 * h * 2880 + 3 * h * 5760 + 2 * h * 30
              + 4 * (2 * 2880 + 5760))
    assert macs["frozen"] == 6 * linear + 2 * 4 * h * h + 8 * 3 * h * f
    assert macs["head"] == h * 100352
    assert macs["adapters"] == 16 * (
        6 * (2 * (h + 2880) + 3 * (h + 5760)) + 2 * 4 * 2 * h
        + 8 * 3 * (h + f))
    assert macs["attention"] == 2 * 2 * 1024 * h
    assert macs["scan"] == 6 * 30 * 3.5 * 96 * 192
    # frozen products forward and input-gradient, the rest three passes
    per_token = 4 * (macs["frozen"] + macs["head"]) + 6 * (
        macs["adapters"] + macs["attention"] + macs["scan"])
    assert need["flops_per_token"] == per_token
    assert need["flops_per_round"] == per_token * 8 * 1024
    assert need["flops_per_sample"] == per_token * 1024
    assert need["scan_flops_per_round"] == 6 * macs["scan"] * 8192
    assert need["scan_bytes_per_round"] == 8192 * 6 * 30 * (
        2 * (2 * 96 + 2 * 192) + 8 + 2 * (2 * 96 + 3 * 192) + 8
        + 2 * (2 * 96 + 192) + 8)
    # twice the samples, twice the work; the recurrence is bound by bytes
    double = flops.required(config, dict(job, n_samples=[4, 4, 4, 4]))
    assert double["flops_per_round"] == 2 * need["flops_per_round"]
    from fedbench.roofline import least_seconds

    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    assert least_seconds(need["scan_flops_per_round"],
                         need["scan_bytes_per_round"], peaks)[1] == "memory"
    assert least_seconds(need["kernel_flops_per_round"],
                         need["kernel_bytes_per_round"], peaks)[1] == "compute"


def test_the_roofline_reader_divides_least_time_by_scope_time(config):
    reader = manifest.load_module(ROOT, "layer_metrics", "delta_scan_roofline")
    need = manifest.load_module(ROOT, "flops", CONFIG).required(
        config, {"n_samples": [2] * 4, "batch": 1, "local_epochs": 1,
                 "seq_len": 1024})
    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    cell = {"required": need, "peaks": peaks, "chips": 1}
    wave = {"runs": 2, "phase_part_s": {
        "forward": {"delta_scan": 0.030, "mlp": 0.2},
        "backward": {"delta_scan": 0.050, "linear_attention": 0.1}}}
    reduced = {"devices": {"/device:TPU:0": {"wave": wave}}, "n_rounds": 2}
    least = need["scan_bytes_per_round"] / peaks["hbm_bytes_per_s"]
    got = reader.read(reduced, {"n_waves": 1}, cell)
    assert got == pytest.approx(100 * least / 0.040)
    assert 0 < got < 100
    # a program without the scope, or a configuration without the count
    bare = {"devices": {"d": {"wave": {"runs": 1, "phase_part_s": {
        "forward": {"mlp": 0.1}}}}}}
    assert reader.read(bare, {"n_waves": 1}, cell) is None
    assert reader.read(reduced, {"n_waves": 1},
                       dict(cell, required={"kernel": "matmul"})) is None
    mixer = manifest.load_module(ROOT, "layer_metrics", "linear_attn_ms")
    assert mixer.read(reduced, {}, cell) == pytest.approx(1e3 * 0.18 / 2)
    assert mixer.read(bare, {}, cell) is None


def test_traced_rehearsal_reports_the_cells_layer_metrics(capsys):
    rc = run.main(["--workload", CELL, "--seed", "4", "--seconds", "1",
                   "--trace", "1", "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 2
    wanted = {m["name"] for m in manifest.metrics_for(BENCH["per_layer"],
                                                      CELL)}
    assert set(result["metrics"]) == wanted and set(NEW_METRICS) <= wanted
    assert not {"conv_roofline", "matmul_roofline", "norm_ms"} & wanted
    for name, m in result["metrics"].items():
        # a CPU trace has no device plane: only a count is written
        assert m["value"] == (0 if m["unit"] == "count" else None), name
    assert any("frozen leaves unchanged: 68 of 68: ok" in l for l in lines)
    assert result["compared"]["frozen_leaves_changed"] == {"value": 0,
                                                           "limit": 0}
    names = manifest.load_trace_names(
        ROOT, manifest.load_config(ROOT, BENCH, CONFIG))
    assert {"linear_attention", "delta_scan", "lm_loss", "attention",
            "mlp"} <= set(names["parts"])


def test_the_float8_control_comes_out_not_correct(config):
    limits = {"max": config["probe_tolerance"],
              "l2": config["probe_l2_tolerance"]}
    assert limits == {"max": 0.05, "l2": 0.15}
    for seed in (21, 22):
        got = control.readings(ROOT, CELL, seed, tiny=True)
        assert got["program"]["reference"] <= limits["max"]
        assert got["program"]["reference_l2"] <= limits["l2"]
        assert got["program"]["frozen_leaves_changed"] == 0
        assert got["control"]["l2"] > limits["l2"], (seed, got)


def test_a_changed_frozen_leaf_is_not_correct(monkeypatch, capsys):
    """``FedSim.run_round`` trains as it should and hands back one
    matrix of the frozen base with one entry moved by one step of its
    dtype: no disagreement over the adapters sees it, the count of
    frozen leaves does."""
    import jax.numpy as jnp

    from baton_tpu.parallel.engine import FedSim

    sound = FedSim.run_round

    def nudged(self, params, *args, **kwargs):
        res = sound(self, params, *args, **kwargs)
        base = dict(res.params["base"])
        head = base["lm_head"]
        base["lm_head"] = head.at[0, 0].set(jnp.nextafter(head[0, 0],
                                                          jnp.inf))
        return dataclasses.replace(res, params=dict(res.params, base=base))

    monkeypatch.setattr(FedSim, "run_round", nudged)
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is False and result["failed"] == 0
    assert any("frozen leaves unchanged: 67 of 68: FAILED" in l
               for l in lines)
    compared = result["compared"]
    assert compared["frozen_leaves_changed"] == {"value": 1, "limit": 0}
    assert all(compared[k]["value"] <= compared[k]["limit"]
               for k in ("reference", "reference_l2", "loss_gap"))
