"""The ``zaya1_8b`` configuration and its cell at ``tiny`` sizes on the
CPU: the configuration file against what it promises (every catalog
key, the two cuts with the published counts, every expert held, the
deployment and every assumption beside them), the model its builder
makes, the FLOP and byte counts against the shapes, the rehearsals
through ``fedbench/run.py``, the whole configuration's
``FedSim.run_round`` against ``reference_round`` through the files the
harness loads, the reference's own convolutions and skip, and the
float8 control over the limits. Every check of ``BENCHMARK.json`` is by
membership, never by position, so that the next PR's appended entries
fail nothing. The untraced rehearsal, the reference against the
program (loss and every gradient leaf) and the reference's plainness
run for every configuration in ``test_fedbench_rehearsal.py`` and
``test_fedbench_references.py``."""

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
CELL, CONFIG = "zaya1_c4_l8192", "zaya1_8b"
NEW_METRICS = ["cca_ms", "cca_mix_ms", "cca_core_ms", "zaya_router_ms",
               "cca_core_roofline"]
# Zyphra/ZAYA1-8B's config.json as the model-configs catalog holds it,
# but for the two keys the cut changes
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True,
}
CUT = {"num_hidden_layers": (10, 40), "vocab_size": (65568, 262272)}
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
    assert widths_named(["router_hidden_size"]) and widths_named(["head_dim"])
    # every expert is held, the router is one wider: the skip
    assert "num_experts" not in config["reduced"]
    assert config["experts_held"].startswith("16 of 16")
    assert config["router_outputs"] == config["num_experts"] + 1 == 17
    assert "first_expert_held" not in config
    assert config["rope_theta"] == \
        config["rope_parameters"]["hybrid"]["rope_theta"]
    assert config["decoder_layer_types"] == \
        ["compressed_attention"] * config["num_hidden_layers"]
    # the deployment: four stages, the vocabulary four ways, the first
    for said in ("four pipeline stages", "four ways", "the first stage",
                 "all 16 experts", "Yeh et al."):
        assert said in config["deployment"], said
    assert "4.13 GiB" in config["reduced_why"]["arithmetic"]
    assert "held_unchanged" in config["reduced_why"]["num_hidden_layers"]
    # the floors of a model_config cut: a whole period and four layers,
    # at least 8 experts, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert 8 * config["vocab_size"] >= config["vocab_size_published"]
    assert 4 * config["vocab_size"] == config["vocab_size_published"]
    assert 4 * config["num_hidden_layers"] == \
        config["num_hidden_layers_published"]
    for key in ("layer_types", "norm_placement", "residual_merge",
                "cca_convolutions", "cca_qk_mean", "cca_values", "cca_norm",
                "rope", "router", "router_init", "router_bias",
                "final_norm_scale", "lora", "lora_b_std", "embed_std",
                "param_dtype", "cca_block", "max_position_embeddings"):
        assert len(config["assumed"][key]) > 40, key
    for said in ("zaya_use_eda", "zaya_use_mod", "17", "not renormalised",
                 "erf"):
        assert said in config["assumed"]["router"], said
    assert "scale_residual_merge" in config["assumed"]["residual_merge"]
    assert "columns that sum to nothing" in config["assumed"]["router_init"]
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == f"fedbench/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert config["scopes"]["parts"] == [
        "compressed_attention", "cca_mix", "cca_core", "moe", "router",
        "expert_matmul", "lm_loss"]
    assert config["tiny"]["sizes"] == {
        "vocab_size": 96, "max_position_embeddings": 32, "hidden_size": 64,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
        "num_experts": 4, "router_hidden_size": 8, "lora_rank": 4,
        "lora_alpha": 8, "cca_block": 8}


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
    # the same job as glm5_c4_l8192's, to the token
    other = manifest.load_workload(ROOT, "glm5_c4_l8192")
    for key in ("clients", "samples_per_client", "seq_len", "batch",
                "local_epochs", "wave_size", "learning_rate"):
        assert job[key] == other[key], key
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["source"] == "device_trace"
        module = manifest.load_module(ROOT, "layer_metrics", name)
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            by_name[name]["layer"], by_name[name]["unit"],
            by_name[name]["moves"], by_name[name]["source"])
    assert {by_name[n]["moves"] for n in NEW_METRICS[:4]} == {"round_s"}
    assert by_name["cca_core_roofline"]["unit"] == "%"
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
    """2,210.1 M frozen parameters, 4.13 GiB, the router, every vector
    and the adapters float32, adapters on the mixer's five projections
    alone, from shapes."""
    import jax
    import jax.numpy as jnp

    model = manifest.build_model(config, tiny=False)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    base = jax.tree_util.tree_leaves(shapes["base"])
    assert sum(a.size for a in base) == 2_210_122_942
    held = sum(a.size * a.dtype.itemsize for a in base)
    assert held == 4_433_931_000 and 4.12 < held / 2**30 < 4.14
    assert "2,210.1 M" in config["reduced_why"]["arithmetic"]
    assert "207.58 M" in config["reduced_why"]["arithmetic"]
    assert set(shapes["base"]) == {"tok_emb", "blocks", "norm_f"}
    assert shapes["base"]["tok_emb"].shape == (65568, 2048)
    assert shapes["base"]["tok_emb"].dtype == jnp.bfloat16
    blocks = shapes["base"]["blocks"]
    assert len(blocks) == 10
    for b in blocks:
        assert set(b) == {"norm_attn", "cca", "merge_attn", "norm_mlp",
                          "mlp", "merge_mlp"}
        assert sum(a.size for a in jax.tree_util.tree_leaves(b)) \
            == 207_583_763
        cca = b["cca"]
        assert cca["linear_q"].shape == (2048, 1024)
        assert cca["linear_k"].shape == (2048, 256)
        assert cca["val_proj1"].shape == cca["val_proj2"].shape == (2048, 128)
        assert cca["o_proj"].shape == (1024, 2048)
        assert cca["conv0_w"].shape == (2, 1280)
        assert cca["conv1_w"].shape == (2, 10, 128, 128)
        assert cca["conv0_b"].shape == cca["conv1_b"].shape == (1280,)
        assert cca["temp"].shape == (2,)
        assert cca["o_proj"].dtype == cca["conv1_w"].dtype == jnp.bfloat16
        mlp = b["mlp"]
        assert mlp["w_gate"].shape == mlp["w_up"].shape == (16, 2048, 2048)
        assert mlp["w_down"].shape == (16, 2048, 2048)
        assert mlp["w_down"].dtype == jnp.bfloat16
        assert "shared" not in mlp
        router = mlp["router"]
        assert router["w_in"].shape == (2048, 256)
        assert router["w1"].shape == router["w2"].shape == (256, 256)
        assert router["w3"].shape == (256, 17)
        assert router["state_scale"].shape == router["norm"].shape == (256,)
        assert mlp["router_bias"].shape == (17,)
        assert {a.dtype for a in jax.tree_util.tree_leaves(
            (router, mlp["router_bias"], b["merge_attn"], b["merge_mlp"],
             cca["temp"], cca["conv0_b"]))} == {jnp.dtype(jnp.float32)}
        for merge in (b["merge_attn"], b["merge_mlp"]):
            assert {k: v.shape for k, v in merge.items()} == {
                k: (2048,) for k in ("a_x", "b_x", "a_y", "b_y")}
    lora = shapes["lora"]
    assert {k.rsplit("/", 1)[-1] for k in lora} == {
        "linear_q", "linear_k", "val_proj1", "val_proj2", "o_proj"}
    assert len(lora) == 50 and all("/cca/" in k for k in lora)
    n_adapter = sum(a.size for a in jax.tree_util.tree_leaves(lora))
    assert n_adapter == 10 * 204_800
    assert f"{n_adapter / 1e6:.2f} M" in config["reduced_why"]["arithmetic"]
    engine = manifest.engine_args(config, {})
    assert engine["trainable"]("lora/blocks/1/cca/o_proj/a", None)
    assert not engine["trainable"]("base/blocks/1/mlp/router/w1", None)
    decoder = manifest.resolve(config["builder"]["kwargs"]["config"], config)
    assert decoder.norm_eps == decoder.moe.router_norm_eps == 1e-5
    assert decoder.tie_embeddings and decoder.residual_merge
    cca = decoder.cca
    assert (cca.n_heads, cca.n_kv_heads, cca.head_dim, cca.time0, cca.time1,
            cca.rope_dim, cca.rope_theta, cca.latent_q, cca.latent_kv) == (
                8, 2, 128, 2, 2, 64, 5e6, 1024, 256)
    moe = decoder.moe
    assert (moe.n_experts, moe.held, moe.first_held, moe.top_k, moe.skip,
            moe.router_outputs, moe.router_hidden, moe.n_shared) == (
                16, 16, 0, 1, True, 17, 256, 0)
    assert dict(model.span_attrs) == {
        "experts_held": 16, "experts_total": 16, "routed_rows_bound": 1024,
        "router_outputs": 17, "skip_expert": 16, "latent_q": 1024,
        "latent_kv": 256, "conv_taps": "2+2"}
    tiny = jax.eval_shape(manifest.build_model(config, tiny=True).init,
                          jax.random.key(0))
    assert len(tiny["base"]["blocks"]) == 3
    assert tiny["base"]["blocks"][1]["mlp"]["w_up"].shape == (4, 64, 32)
    assert tiny["base"]["blocks"][1]["mlp"]["router"]["w3"].shape == (8, 5)
    assert tiny["base"]["blocks"][1]["cca"]["linear_k"].shape == (64, 32)
    assert {a.dtype for a in jax.tree_util.tree_leaves(tiny)} == {
        jnp.dtype(jnp.float32)}


def test_required_work_follows_the_shapes(config):
    flops = manifest.load_module(ROOT, "flops", CONFIG)
    need = flops.required(config, JOB)
    macs = need["forward_macs_per_token"]
    h = 2048
    mixer = h * 1024 + h * 256 + 2 * h * 128 + 1024 * h
    conv = 10 * 2 * 128 * 128
    router = h * 256 + 2 * 256 * 256 + 256 * 17
    assert macs["frozen"] == 10 * (mixer + conv + router)
    assert macs["experts"] == 10 * (16 / 17) * 3 * h * 2048
    assert macs["head"] == h * 65568
    assert macs["attention"] == 10 * 8 * 256 * 8193 / 2
    assert macs["adapters"] == 10 * 204_800
    assert need["skip_share"] == pytest.approx(1 / 17)
    per_token = 4 * (macs["frozen"] + macs["experts"] + macs["head"]) \
        + 6 * (macs["adapters"] + macs["attention"])
    assert need["flops_per_token"] == per_token
    assert need["flops_per_sample"] == per_token * 8192
    assert need["flops_per_round"] == per_token * 4 * 8192
    assert 58e12 < need["flops_per_round"] < 59e12
    assert need["cca_core_flops_per_round"] == 6 * macs["attention"] * 32768
    # keys and values once a key-value head: 6 passes over 8 + 2 heads
    assert need["cca_core_bytes_per_round"] == \
        2 * 10 * 6 * (8 + 2) * 128 * 32768
    assert need["expert_flops_per_round"] == 4 * macs["experts"] * 32768
    assert need["kernel"] == "matmul"
    assert "mla_core_flops_per_round" not in need
    assert "sparse_core_flops_per_round" not in need
    # the issue's shares of a token's forward matrix work at 8,192
    layer = 2 * (mixer + conv + router + macs["experts"] / 10
                 + macs["attention"] / 10 + macs["head"] / 10)
    assert 79e6 < layer < 82e6  # 81.3e6 were no token to skip
    assert 2 * macs["attention"] / 10 == pytest.approx(16.8e6, rel=0.01)
    double = flops.required(config, dict(JOB, n_samples=[2, 2, 2, 2]))
    assert double["flops_per_round"] == 2 * need["flops_per_round"]
    from fedbench.roofline import least_seconds

    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    for kernel in ("expert", "cca_core", "kernel"):
        assert least_seconds(need[f"{kernel}_flops_per_round"],
                             need[f"{kernel}_bytes_per_round"],
                             peaks)[1] == "compute"


def test_the_readers_divide_least_time_by_scope_time(config):
    need = manifest.load_module(ROOT, "flops", CONFIG).required(config, JOB)
    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    cell = {"required": need, "peaks": peaks, "chips": 1}
    wave = {"runs": 2, "phase_part_s": {
        "forward": {"compressed_attention": 0.2, "cca_mix": 0.1,
                    "cca_core": 0.3, "router": 0.02, "moe": 0.5},
        "backward": {"compressed_attention": 0.2, "cca_mix": 0.1,
                     "cca_core": 0.5, "router": 0.04}}}
    reduced = {"devices": {"/device:TPU:0": {"wave": wave}}, "n_rounds": 2}

    def read(name, seen=reduced, cell=cell):
        return manifest.load_module(ROOT, "layer_metrics", name).read(
            seen, {"n_waves": 1}, cell)

    assert read("cca_ms") == pytest.approx(700.0)
    assert read("cca_mix_ms") == pytest.approx(100.0)
    assert read("cca_core_ms") == pytest.approx(400.0)
    assert read("zaya_router_ms") == pytest.approx(30.0)
    least = need["cca_core_flops_per_round"] / peaks["flops_per_s_bf16"]
    assert read("cca_core_roofline") == pytest.approx(100 * least / 0.4)
    assert 0 < read("cca_core_roofline") < 100
    # a program without the scopes (the parent's), or a configuration
    # without the counts: nothing, and no error
    bare = {"devices": {"d": {"wave": {"runs": 1, "phase_part_s": {
        "forward": {"mlp": 0.1, "moe": 0.2}}}}}}
    for name in NEW_METRICS:
        assert read(name, bare) is None
        assert read(name, None) is None
    assert read("cca_core_roofline",
                cell=dict(cell, required={"kernel": "matmul"})) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_runs_through_the_harness_and_is_correct(trace, capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--trace", str(trace), "--rehearse-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    # 3 layers of 10 leaves of the mixer, 8 of two merges, 2 norms and
    # 13 of the expert layer (9 of the router, its bias, three stacks),
    # the table and a norm
    assert any("frozen leaves unchanged: 101 of 101: ok" in l for l in lines)
    if not trace:
        assert set(result["metrics"]) == {"samples_per_s_per_chip", "round_s",
                                          "setup_s"}
        return
    assert result["attempted"] == 2
    wanted = {m["name"] for m in manifest.metrics_for(BENCH["per_layer"],
                                                      CELL)}
    assert set(result["metrics"]) == wanted
    assert set(NEW_METRICS) <= wanted
    assert not {"conv_roofline", "matmul_roofline", "norm_ms", "mla_ms",
                "moe_ms", "lm_loss_ms", "sparse_core_ms"} & wanted
    for name, m in result["metrics"].items():
        assert m["value"] == (0 if m["unit"] == "count" else None), name
    names = manifest.load_trace_names(
        ROOT, manifest.load_config(ROOT, BENCH, CONFIG))
    assert {"compressed_attention", "cca_mix", "cca_core", "moe", "router",
            "expert_matmul", "lm_loss", "norm"} <= set(names["parts"])
    from fedbench import trace_reduce

    scope = "jit(f)/local_train/jvp(block3)/moe/router/dot_general"
    assert trace_reduce.part_of(scope, names) == "router"
    scope = "jit(f)/block0/compressed_attention/cca_mix/mul"
    assert trace_reduce.part_of(scope, names) == "cca_mix"


@pytest.mark.parametrize("seed", [5, 4294967311])
def test_a_round_of_the_program_is_the_reference_round(config, seed):
    """``FedSim.run_round`` on the probe cohort against
    ``reference_round`` with the loss of ``references/zaya1_8b.py``,
    through the files the harness loads, at ``tiny`` sizes in float32:
    the adapters agree and every frozen leaf is the array that went
    in."""
    import jax

    job = run.job_of(manifest.load_workload(ROOT, CELL), True)
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
    assert "lm_head" not in want["base"]


def _reference_on_a_probe(config, seed=3):
    import jax

    module = manifest.load_module(ROOT, "references", CONFIG)
    sized = manifest.sized(config, True)
    job = run.job_of(manifest.load_workload(ROOT, CELL), True)
    _, params, _, _, _, _, _ = run.build_cell(ROOT, config, job, 1, seed, True)
    pdata, _ = run.probe_cohort(ROOT, config, job, True, seed)
    x, y = pdata["x"][0], pdata["y"][0]
    mask = jax.numpy.ones((x.shape[0],))
    return module, sized, params, (x, y, mask)


def test_the_references_parts_move_its_loss(config, monkeypatch):
    """Nothing in the reference is decoration: in blocks of 4 queries it
    gives the loss it gives whole; without the routers' state, without
    the skip's place in the softmax, without the merges' shifts, with
    the table's norm at 1 or without the convolutions' biases it gives
    another."""
    import jax
    import jax.numpy as jnp

    module, sized, params, batch = _reference_on_a_probe(config)
    whole = float(module.make_loss(sized)(params, *batch))
    monkeypatch.setattr(module, "QUERY_BLOCK", 4)
    monkeypatch.setattr(module, "LOSS_BLOCK", 8)
    assert float(module.make_loss(sized)(params, *batch)) == pytest.approx(
        whole, rel=1e-6)

    def changed(edit):
        base = jax.tree_util.tree_map(lambda a: a, params["base"])
        for blk in base["blocks"]:
            edit(blk)
        return float(module.make_loss(sized)(
            {"base": base, "lora": params["lora"]}, *batch))

    def no_state(blk):
        router = blk["mlp"]["router"]
        router["state_scale"] = jnp.zeros_like(router["state_scale"])

    def no_skip(blk):  # the 17th output can never be chosen
        blk["mlp"]["router_bias"] = blk["mlp"]["router_bias"].at[-1].set(-9.0)

    def no_shift(blk):
        blk["merge_mlp"]["b_y"] = jnp.zeros_like(blk["merge_mlp"]["b_y"])

    def no_bias(blk):
        blk["cca"]["conv0_b"] = jnp.zeros_like(blk["cca"]["conv0_b"])

    def no_temperature(blk):
        blk["cca"]["temp"] = jnp.ones_like(blk["cca"]["temp"])

    for edit in (no_state, no_skip, no_shift, no_bias, no_temperature):
        assert abs(changed(edit) - whole) > 1e-5 * abs(whole), edit.__name__


def test_the_reference_is_plain_and_convolves():
    """No ``vmap``, no grouped product and no sort; its convolutions are
    ``lax.conv_general_dilated`` (the program's are slices and
    products), its skip an explicit ``where``, its blocks of queries
    checkpointed."""
    path = os.path.join(ROOT, "fedbench", "references", f"{CONFIG}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    called = {n.func.attr for n in ast.walk(tree)
              if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)}
    assert not called & {"vmap", "ragged_dot", "ragged_dot_general",
                         "argsort", "sort", "top_k", "custom_vjp",
                         "custom_jvp", "stop_gradient", "gelu", "softmax"}
    assert {"conv_general_dilated", "where", "argmax"} <= called
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
