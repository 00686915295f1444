"""The ``glm_5`` configuration and its cell at ``tiny`` sizes on the CPU:
the configuration file against what it promises (every catalog key, the
five cuts with the published counts, the deployment and every departure
beside them), the model its builder makes, the FLOP and byte counts
against the shapes (the core over the chosen keys alone), the traced
rehearsal with the cell's layer metrics, the whole configuration's
``FedSim.run_round`` against ``reference_round`` through the files the
harness loads with ``index_topk`` under the sequence, the reference's
own choice of keys, the float8 control over the limits, and the expert
shares of the rehearsal's sizes against the uncut layer. Every check of
``BENCHMARK.json`` is by membership, never by position. The untraced
rehearsal, the reference against the program (loss and every gradient
leaf) and the reference's plainness run for every configuration in
``test_fedbench_rehearsal.py`` and ``test_fedbench_references.py``."""

import ast
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import control, manifest, reference, run  # noqa: E402
from test_fedbench_manifest import widths_named  # noqa: E402

BENCH = manifest.load_manifest(ROOT)
CELL, CONFIG = "glm5_c4_l8192", "glm_5"
NEW_METRICS = ["indexer_ms", "index_select_ms", "sparse_core_ms",
               "sparse_core_roofline", "indexer_roofline"]
# zai-org/GLM-5's config.json as the model-configs catalog holds it, but
# for the five keys the cut changes
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
    "head_dim": 64, "hidden_size": 6144, "index_head_dim": 128,
    "index_n_heads": 32, "index_topk": 2048, "indexer_rope_interleave": True,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 202752, "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "model_type": "glm_moe_dsa", "n_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 64, "q_lora_rank": 2048,
    "qk_head_dim": 256, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256,
}
CUT = {"num_hidden_layers": (5, 78), "first_k_dense_replace": (1, 3),
       "n_routed_experts": (8, 256), "vocab_size": (19360, 154880),
       "num_nextn_predict_layers": (0, 1)}
JOB = {"n_samples": [1, 1, 1, 1], "batch": 1, "local_epochs": 1,
       "seq_len": 8192}


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(ROOT, BENCH, CONFIG)


def test_the_configuration_keeps_every_published_width(config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == list(CUT)
    for key, (held, published) in CUT.items():
        assert config[key] == held
        assert config[f"{key}_published"] == published
        assert len(config["reduced_why"][key]) > 40
    assert widths_named(config["reduced"]) == []
    assert widths_named(["index_head_dim"]) and widths_named(["q_lora_rank"])
    assert config["rope_theta"] == config["rope_parameters"]["rope_theta"]
    # the deployment: 32 expert ranks, 8 vocabulary ranks, the first stage
    for said in ("32 ways", "eight ways", "the first", "experts 0 to 7"):
        assert said in config["deployment"], said
    assert "5.03 GiB" in config["reduced_why"]["arithmetic"]
    assert "held_unchanged" in config["reduced_why"]["n_routed_experts"]
    # the floors of a model_config cut: four layers after the leading
    # dense one, at least 8 experts, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert 8 * config["vocab_size"] >= config["vocab_size_published"]
    for key in ("norm_placement", "indexer", "indexer_gradient",
                "rope_interleave", "rope_theta", "router_scoring",
                "router_bias", "head_dim", "lora", "lora_b_std", "embed_std",
                "param_dtype", "mla_block", "multi_token_prediction"):
        assert len(config["assumed"][key]) > 40, key
    for said in ("Hadamard", "FP8", "LayerNorm with bias", "float32 sums"):
        assert said in config["assumed"]["indexer"], said
    assert "stop_gradient" in config["assumed"]["indexer_gradient"]
    assert "none on the indexer" in config["assumed"]["lora"]
    assert "79th block" in config["reduced_why"]["num_nextn_predict_layers"]
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == f"fedbench/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    # the tiny sizes select: index_topk under the rehearsal's sequence,
    # and hold experts that do not start at 0
    tiny = config["tiny"]["sizes"]
    job = manifest.load_workload(ROOT, CELL)
    assert tiny["index_topk"] < job["tiny"]["seq_len"]
    assert tiny["first_expert_held"] > 0
    assert config["scopes"]["parts"] == [
        "latent_attention", "mla_core", "indexer", "index_select", "moe",
        "expert_matmul", "lm_loss"]


def test_the_cell_is_the_one_the_issue_names():
    entry = manifest.cell_entry(BENCH, CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "c4x1_l8192_b1", 1)
    assert len(entry["why"]) <= 200
    job = manifest.load_workload(ROOT, CELL)
    assert (job["clients"], job["samples_per_client"], job["seq_len"],
            job["batch"], job["local_epochs"], job["wave_size"],
            job["learning_rate"], job["warmup_rounds"],
            job["trace_rounds"], job["tiny"]) == (
                4, {"kind": "const", "n": 1}, 8192, 1, 1, None, 0.02, 2, 2,
                {"seq_len": 16, "trace_rounds": 2})
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        module = manifest.load_module(ROOT, "layer_metrics", name)
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            by_name[name]["layer"], by_name[name]["unit"],
            by_name[name]["moves"], by_name[name]["source"])
    assert {by_name[n]["moves"] for n in NEW_METRICS[:3]} == {"round_s"}
    assert {by_name[n]["unit"] for n in NEW_METRICS[3:]} == {"%"}
    # no list the benchmark had is joined, and no other cell reports
    # these five
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            assert not {m["name"] for m in manifest.metrics_for(
                BENCH["per_layer"], other["name"])} & set(NEW_METRICS)
    assert CELL not in [w for m in BENCH["per_layer"]
                        if m["name"] not in NEW_METRICS
                        for w in m.get("workloads", [])]


def test_the_model_built_from_the_file_is_the_stage_it_states(config):
    """2,701.6 M frozen parameters in bfloat16 (5.03 GiB), the router
    and its bias float32, adapters on 2-D projections alone and none on
    the indexer, from shapes."""
    import jax
    import jax.numpy as jnp

    model = manifest.build_model(config, tiny=False)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    base = jax.tree_util.tree_leaves(shapes["base"])
    matrices = sum(a.size for a in base if a.ndim >= 2)
    assert 2.7010e9 < matrices < 2.7022e9
    held = sum(a.size * a.dtype.itemsize for a in base)
    assert 5.03 < held / 2**30 < 5.05
    blocks = shapes["base"]["blocks"]
    assert len(blocks) == 5 and "router" not in blocks[0]["mlp"]
    assert blocks[0]["mlp"]["w_up"].shape == (6144, 12288)
    for b in blocks:
        mla = b["mla"]
        assert "wq" not in mla and "q_norm" not in mla
        assert mla["wq_a"].shape == (6144, 2048)
        assert mla["q_a_norm"]["scale"].shape == (2048,)
        assert mla["wq_b"].shape == (2048, 64 * 256)
        assert mla["wkv_a"].shape == (6144, 576)
        assert mla["wkv_b"].shape == (512, 64 * 448)
        assert mla["wo"].shape == (64 * 256, 6144)
        ix = mla["indexer"]
        assert ix["wq"].shape == (2048, 32 * 128)
        assert ix["wk"].shape == (6144, 128)
        assert ix["w_heads"].shape == (6144, 32)
        assert ix["k_norm"]["bias"].shape == (128,)
        assert ix["wq"].dtype == jnp.bfloat16
    for b in blocks[1:]:
        mlp = b["mlp"]
        assert mlp["router"].shape == (6144, 256)
        assert mlp["router"].dtype == mlp["router_bias"].dtype == jnp.float32
        assert mlp["w_gate"].shape == mlp["w_up"].shape == (8, 6144, 2048)
        assert mlp["w_down"].shape == (8, 2048, 6144)
        assert mlp["w_down"].dtype == jnp.bfloat16
        assert mlp["shared"]["w_up"].shape == (6144, 2048)
    assert shapes["base"]["tok_emb"].shape == (19360, 6144)
    assert shapes["base"]["lm_head"].shape == (6144, 19360)
    lora = shapes["lora"]
    assert {k.rsplit("/", 1)[-1] for k in lora} == {
        "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down"}
    assert not [k for k in lora if "indexer" in k]
    assert not [k for k in lora if "blocks/0" not in k and "/mlp/" in k
                and "/shared/" not in k]
    n_adapter = sum(a.size for a in jax.tree_util.tree_leaves(lora))
    assert f"{n_adapter / 1e6:.1f} M" in config["reduced_why"]["arithmetic"]
    engine = manifest.engine_args(config, {})
    assert engine["trainable"]("lora/blocks/1/mla/wq_b/a", None)
    assert not engine["trainable"]("base/blocks/1/mla/indexer/wq", None)
    decoder = manifest.resolve(config["builder"]["kwargs"]["config"], config)
    assert decoder.norm_eps == decoder.mla.norm_eps == 1e-5
    assert decoder.mla.q_rank == 2048 and decoder.mla.rope_theta == 1e6
    assert decoder.mla.softmax_scale == 256 ** -0.5
    ix = decoder.mla.indexer
    assert (ix.heads, ix.dim, ix.topk, ix.rope_dim) == (32, 128, 2048, 64)
    assert decoder.mla.selects(8192) and not decoder.mla.selects(2048)
    tiny = jax.eval_shape(manifest.build_model(config, tiny=True).init,
                          jax.random.key(0))
    assert len(tiny["base"]["blocks"]) == 3
    assert tiny["base"]["blocks"][1]["mlp"]["w_up"].shape == (4, 64, 32)
    assert {a.dtype for a in jax.tree_util.tree_leaves(tiny)} == {
        jnp.dtype(jnp.float32)}


def test_required_work_follows_the_shapes(config):
    flops = manifest.load_module(ROOT, "flops", CONFIG)
    assert flops.keys_per_query(8192, 2048) == 1792.125
    assert flops.keys_per_query(2048, 2048) == 2049 / 2
    assert flops.keys_per_query(16, 6) == (21 + 10 * 6) / 16
    need = flops.required(config, JOB)
    assert need["selected_share"] == pytest.approx(0.4375, abs=1e-4)
    assert need["selected_share"] == 1792.125 / 4096.5
    macs = need["forward_macs_per_token"]
    h = 6144
    mla = h * 2048 + 2048 * 64 * 256 + h * 576 + 512 * 64 * 448 \
        + 64 * 256 * h
    assert macs["frozen"] == 5 * mla + 3 * h * 12288 \
        + 4 * (3 * h * 2048 + h * 256)
    assert macs["experts"] == 4 * (8 * 8 / 256) * 3 * h * 2048
    assert macs["head"] == h * 19360
    assert macs["attention"] == 5 * 64 * (256 + 256) * 1792.125
    assert macs["indexer"] == 5 * (2048 * 32 * 128 + h * 128 + h * 32
                                   + 32 * 128 * 8193 / 2)
    assert macs["adapters"] == 16 * (
        5 * ((h + 2048) + (2048 + 64 * 256) + (h + 576) + (512 + 64 * 448)
             + (64 * 256 + h))
        + 3 * (h + 12288) + 4 * 3 * (h + 2048))
    per_token = 4 * (macs["frozen"] + macs["experts"] + macs["head"]) \
        + 6 * (macs["adapters"] + macs["attention"]) + 2 * macs["indexer"]
    assert need["flops_per_token"] == per_token
    assert need["flops_per_sample"] == per_token * 8192
    assert need["flops_per_round"] == per_token * 4 * 8192
    assert need["sparse_core_flops_per_round"] == \
        6 * macs["attention"] * 32768
    assert need["indexer_flops_per_round"] == 2 * macs["indexer"] * 32768
    assert need["expert_flops_per_round"] == 4 * macs["experts"] * 32768
    assert need["kernel"] == "matmul"
    assert "mla_core_flops_per_round" not in need
    double = flops.required(config, dict(JOB, n_samples=[2, 2, 2, 2]))
    assert double["flops_per_round"] == 2 * need["flops_per_round"]
    from fedbench.roofline import least_seconds

    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    for kernel in ("expert", "sparse_core", "indexer", "kernel"):
        assert least_seconds(need[f"{kernel}_flops_per_round"],
                             need[f"{kernel}_bytes_per_round"],
                             peaks)[1] == "compute"


def test_the_readers_divide_least_time_by_scope_time(config):
    need = manifest.load_module(ROOT, "flops", CONFIG).required(config, JOB)
    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    cell = {"required": need, "peaks": peaks, "chips": 1}
    wave = {"runs": 2, "phase_part_s": {
        "forward": {"indexer": 0.2, "index_select": 0.3, "mla_core": 1.0,
                    "latent_attention": 0.4},
        "backward": {"indexer": 0.2, "index_select": 0.1, "mla_core": 3.0}}}
    reduced = {"devices": {"/device:TPU:0": {"wave": wave}}, "n_rounds": 2}

    def read(name, seen=reduced, cell=cell):
        return manifest.load_module(ROOT, "layer_metrics", name).read(
            seen, {"n_waves": 1}, cell)

    assert read("indexer_ms") == pytest.approx(200.0)
    assert read("index_select_ms") == pytest.approx(200.0)
    assert read("sparse_core_ms") == pytest.approx(2000.0)
    least = need["sparse_core_flops_per_round"] / peaks["flops_per_s_bf16"]
    assert read("sparse_core_roofline") == pytest.approx(100 * least / 2.0)
    least = need["indexer_flops_per_round"] / peaks["flops_per_s_bf16"]
    assert read("indexer_roofline") == pytest.approx(100 * least / 0.2)
    assert 0 < read("sparse_core_roofline") < 100
    assert 0 < read("indexer_roofline") < 100
    # a program without the scopes (the parent's), or a configuration
    # without the counts: nothing, and no error
    bare = {"devices": {"d": {"wave": {"runs": 1, "phase_part_s": {
        "forward": {"mlp": 0.1}}}}}}
    for name in NEW_METRICS:
        assert read(name, bare) is None
        assert read(name, None) is None
    for name in ("sparse_core_roofline", "indexer_roofline"):
        assert read(name, cell=dict(cell, required={"kernel": "matmul"})) \
            is None


def test_traced_rehearsal_reports_the_cells_layer_metrics(capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "1", "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 2
    wanted = {m["name"] for m in manifest.metrics_for(BENCH["per_layer"],
                                                      CELL)}
    assert set(result["metrics"]) == wanted
    assert set(NEW_METRICS) <= wanted
    assert not {"conv_roofline", "matmul_roofline", "norm_ms", "mla_ms",
                "delta_scan_ms", "mla_core_roofline"} & wanted
    for name, m in result["metrics"].items():
        assert m["value"] == (0 if m["unit"] == "count" else None), name
    # 3 layers of 14 leaves of latent attention (7 of the mixer, 5 of
    # its indexer) and 2 norms, a dense MLP of 3, two expert layers of
    # 8, two tables and a norm
    assert any("frozen leaves unchanged: 64 of 64: ok" in l for l in lines)
    names = manifest.load_trace_names(
        ROOT, manifest.load_config(ROOT, BENCH, CONFIG))
    assert {"latent_attention", "mla_core", "indexer", "index_select", "moe",
            "expert_matmul", "lm_loss", "mlp", "norm"} <= set(names["parts"])


@pytest.mark.parametrize("seed", [5, 4294967311])
def test_a_round_of_the_program_is_the_reference_round(config, seed):
    """``FedSim.run_round`` on the probe cohort against
    ``reference_round`` with the loss of ``references/glm_5.py``, through
    the files the harness loads, at ``tiny`` sizes in float32, 16 tokens
    and 6 keys a query: the adapters agree and every frozen leaf, the
    indexer's among them, is the array that went in."""
    import jax

    job = run.job_of(manifest.load_workload(ROOT, CELL), True)
    assert job["seq_len"] > manifest.sized(config, True)["index_topk"]
    _, params, _, _, _, mesh, sim = run.build_cell(
        ROOT, config, job, 1, seed, True)
    ok, compared = run.probe(ROOT, config, job, True, seed, sim, params, mesh)
    assert ok
    assert compared["reference"][0] < 1e-4
    assert compared["reference_l2"][0] < 1e-4
    assert compared["loss_gap"][0] < 1e-5
    assert compared["frozen_leaves_changed"] == (0, 0)
    pdata, sizes = run.probe_cohort(ROOT, config, job, True, seed)
    loss = manifest.load_module(ROOT, "references", CONFIG).make_loss(
        manifest.sized(config, True))
    trainable = manifest.engine_args(config, job)["trainable"]
    want, _ = reference.reference_round(loss, params, pdata, sizes,
                                        job["learning_rate"], trainable)
    for a, b in zip(jax.tree_util.tree_leaves(want["base"]),
                    jax.tree_util.tree_leaves(params["base"])):
        assert a is b
    assert "indexer" in want["base"]["blocks"][1]["mla"]


def test_the_references_choice_moves_its_loss(config, monkeypatch):
    """The reference chooses keys by its own ``top_k``, a block of
    queries and a group of whole heads at a time: in blocks of 4 queries
    and groups of 2 heads it gives the loss it gives whole, and with
    ``index_topk`` past the sequence (every key chosen) another: the
    choice is not decoration."""
    import jax

    module = manifest.load_module(ROOT, "references", CONFIG)
    sized = manifest.sized(config, True)
    job = run.job_of(manifest.load_workload(ROOT, CELL), True)
    _, params, _, _, _, _, _ = run.build_cell(ROOT, config, job, 1, 3, True)
    pdata, _ = run.probe_cohort(ROOT, config, job, True, 3)
    x, y = pdata["x"][0], pdata["y"][0]
    mask = jax.numpy.ones((x.shape[0],))
    whole = float(module.make_loss(sized)(params, x, y, mask))
    monkeypatch.setattr(module, "QUERY_BLOCK", 4)
    monkeypatch.setattr(module, "HEAD_GROUP", 2)
    blocked = float(module.make_loss(sized)(params, x, y, mask))
    assert blocked == pytest.approx(whole, rel=1e-6)
    dense = float(module.make_loss(dict(sized, index_topk=64))(
        params, x, y, mask))
    assert abs(dense - whole) > 1e-4 * abs(whole)


def test_the_reference_is_plain():
    """No ``vmap``, no grouped product and no sort; it chooses keys by
    ``top_k`` and holds no ``[heads, L, L]`` scores whole at the cell's
    length (its query blocks are checkpointed)."""
    path = os.path.join(ROOT, "fedbench", "references", f"{CONFIG}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    called = {n.func.attr for n in ast.walk(tree)
              if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)}
    assert not called & {"vmap", "ragged_dot", "ragged_dot_general",
                         "argsort", "sort", "custom_vjp", "custom_jvp",
                         "stop_gradient"}
    assert "top_k" in called
    assert "checkpoint" in {n.attr for n in ast.walk(tree)
                            if isinstance(n, ast.Attribute)}


def test_the_float8_control_comes_out_not_correct(config):
    limits = {"max": config["probe_tolerance"],
              "l2": config["probe_l2_tolerance"]}
    for seed in (21, 22):
        got = control.readings(ROOT, CELL, seed, tiny=True)
        assert got["program"]["reference"] <= limits["max"]
        assert got["program"]["reference_l2"] <= limits["l2"]
        assert got["program"]["frozen_leaves_changed"] == 0
        assert got["control"]["l2"] > limits["l2"], (seed, got)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]
                                  if "first_expert_held" in manifest.
                                  load_config(ROOT, BENCH, c["name"])])
def test_the_expert_shares_add_up_to_the_uncut_layer(name):
    """PR 33's share test for every configuration that holds a share of
    its experts: at the ``tiny`` sizes, the ranks that together hold all
    the router's experts (each built as the file builds its own, from
    its first held expert on) compute parts whose sum, with the shared
    expert counted once, is the layer that holds every expert."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from baton_tpu.models import moe

    config = manifest.load_config(ROOT, BENCH, name)
    sized = manifest.sized(config, True)
    decoder = manifest.resolve(config["builder"]["kwargs"]["config"], sized)
    cfg = decoder.moe
    assert cfg.first_held > 0 and cfg.n_experts % cfg.held == 0
    whole_cfg = dataclasses.replace(cfg, experts_held=None, first_held=0)
    key = jax.random.key(4)
    d, f = decoder.d_model, decoder.d_ff
    whole = moe.moe_init(key, d, f, whole_cfg)
    x = jax.random.normal(jax.random.key(5), (2, 12, d), jnp.float32)
    want = moe.moe_apply(whole, x, whole_cfg)
    shared = moe.swiglu(whole["shared"], x)
    total = shared
    for first in range(0, cfg.n_experts, cfg.held):
        rank_cfg = dataclasses.replace(cfg, first_held=first)
        rank = moe.moe_init(key, d, f, rank_cfg)
        # a rank's stacks are the uncut layer's slice
        np.testing.assert_array_equal(
            np.asarray(rank["w_up"]),
            np.asarray(whole["w_up"][first:first + cfg.held]))
        total = total + moe.moe_apply(rank, x, rank_cfg) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
