"""The ``command_a_plus`` configuration and its cell at ``tiny`` sizes on
the CPU: the configuration file against what it promises (every catalog
key, the five cuts each with its published count, the deployment and
every assumption beside it), the model its builder makes and its bytes
against the file's arithmetic, the FLOP and pair counts against the
shapes and a brute-force count, the whole configuration's
``FedSim.run_round`` against ``reference_round`` through the files the
harness loads, the reference's parts told apart from their absence, and
the float8 control over the limits. The cell is built once a module
(``on_the_probe``); the untraced rehearsal through ``fedbench/run.py``
runs for every configuration in ``test_fedbench_rehearsal.py``. Every
check of ``BENCHMARK.json`` is by membership, never by position, so that
the next PR's appended entries fail nothing."""

import ast
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest, reference, run  # noqa: E402
from test_fedbench_manifest import widths_named  # noqa: E402

BENCH = manifest.load_manifest(ROOT)
CELL, CONFIG = "command_a_plus_c4_l8192", "command_a_plus"
NEW_METRICS = ["shared_expert_ms", "shared_expert_roofline",
               "group16_core_ms", "group16_core_roofline",
               "routed_experts_ms"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
REDUCED = {"num_hidden_layers": (4, 32), "num_attention_heads": (32, 128),
           "num_key_value_heads": (2, 8), "num_experts": (8, 128),
           "vocab_size": (32768, 262144)}
# CohereLabs/command-a-plus-05-2026's config.json as the model-configs
# catalog holds it, but for the five keys the cut changes
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_experts_per_tok": 8,
    "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False,
}
JOB = {"n_samples": [1, 1, 1, 1], "batch": 1, "local_epochs": 1,
       "seq_len": 8192}


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(ROOT, BENCH, CONFIG)


def test_the_configuration_keeps_every_published_width(config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == list(REDUCED)
    for key, (held, published) in REDUCED.items():
        assert (config[key], config[f"{key}_published"]) == (held, published)
    assert widths_named(config["reduced"]) == []
    for width in ("head_dim", "hidden_size", "intermediate_size",
                  "num_experts_per_tok"):
        assert widths_named([width]), width
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026"][0]
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert config[REDUCED.get(key) and f"{key}_published" or key] \
                == value, key
    # 16 query heads a key-value head, as published; a whole period
    assert config["num_attention_heads"] // config["num_key_value_heads"] \
        == 128 // 8 == 16
    assert config["layer_types"][:config["num_hidden_layers"]] == PERIOD
    assert config["first_held_expert"] == 0
    # the builder's words for three published keys
    assert (config["norm"], config["full_layer_rope"],
            config["rope_pairs"]) == ("layer", False, "adjacent")
    for said in ("layer_norm_eps", "global NoPE", "rope_gptj"):
        assert said in config["builder_keys_why"], said
    for said in ("eight pipeline stages", "four ways", "16 ways",
                 "eight ways", "a quarter of a deployment's rows",
                 "512 a client", "four times theirs"):
        assert said in config["deployment"], said
    why = config["reduced_why"]
    assert set(why) == set(REDUCED) | {"arithmetic"}
    for said in ("640,159,744", "2,694,860,800", "5,393,956,864 bytes",
                 "5.02 GiB", "31.9 %", "1,384,448 a layer", "218.3 B",
                 "25.0 B"):
        assert said in why["arithmetic"], said
    for said in ("held_unchanged", "compiled for a v5e", "5.044 GiB",
                 "8.574 GiB", "16 + 1"):
        assert said in why["num_attention_heads"], said
    for key in ("nope", "rotation", "window_edge", "layer_norm", "block",
                "router", "shared_average", "prefix_dense", "tower",
                "router_init", "qk_aligned", "embed_std", "lora",
                "lora_b_std", "param_dtype", "unused_keys"):
        assert len(config["assumed"][key]) > 40, key
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/"
        "main/config.json")
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == f"fedbench/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert config["scopes"]["parts"] == [
        "sliding_attention", "window_core", "full_core", "moe", "router",
        "expert_matmul", "shared_expert", "lm_loss"]
    tiny = config["tiny"]["sizes"]
    assert tiny["num_hidden_layers"] == 4  # one period
    assert tiny["sliding_window"] < manifest.load_workload(
        ROOT, CELL)["tiny"]["seq_len"]
    assert (tiny["num_experts_published"], tiny["num_experts"],
            tiny["num_shared_experts"], tiny["num_attention_heads"],
            tiny["num_key_value_heads"]) == (16, 2, 2, 4, 2)


def test_the_builder_hands_the_program_the_published_sizes(config):
    decoder = manifest.resolve(config["builder"]["kwargs"]["config"], config)
    assert (decoder.d_model, decoder.n_heads, decoder.n_kv_heads,
            decoder.head_dim, decoder.vocab_size, decoder.window,
            decoder.rope_theta, decoder.rope_yarn, decoder.norm_eps,
            decoder.embed_std, decoder.tie_embeddings,
            decoder.first_dense_layers, decoder.qk_aligned,
            decoder.parallel_block, decoder.norm, decoder.full_layer_rope,
            decoder.rope_pairs, decoder.residual_merge, decoder.qk_norm) == (
        4096, 32, 2, 128, 32768, 4096, 50000, None, 1e-5, 0.02, True, 0, 0.5,
        True, "layer", False, "adjacent", False, False)
    assert [decoder.kind_of(i) for i in range(decoder.n_layers)] == PERIOD
    experts = decoder.moe
    assert (experts.n_experts, experts.held, experts.first_held,
            experts.top_k, experts.d_ff, experts.router_scores,
            experts.n_shared, experts.shared_combine,
            experts.router_bias_range, experts.routed_scale,
            experts.router_hidden) == (
        128, 8, 0, 8, 4096, "sigmoid", 4, "average", None, 1.0, None)
    assert decoder.multipliers.lm_head == config["logit_scale"] == 1
    assert decoder.mla is None and decoder.cca is None and decoder.ssm is None
    assert hash(decoder) == hash(manifest.resolve(
        config["builder"]["kwargs"]["config"], config))


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
    for name in NEW_METRICS:  # by membership, at no fixed position
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
        module = manifest.load_module(ROOT, "layer_metrics", name)
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            by_name[name]["layer"], by_name[name]["unit"],
            by_name[name]["moves"], by_name[name]["source"])
    for name in NEW_METRICS:
        roofline = name.endswith("_roofline")
        assert (by_name[name]["unit"], by_name[name]["moves"],
                by_name[name]["layer"], by_name[name]["better"]) == (
            ("%", "samples_per_s_per_chip", "kernels", "higher") if roofline
            else ("ms", "round_s", "local training + model", "lower"))
    # no list the benchmark had is joined, and no other cell reports
    # these five
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            assert not {m["name"] for m in manifest.metrics_for(
                BENCH["per_layer"], other["name"])} & set(NEW_METRICS)
    assert CELL not in [w for m in BENCH["per_layer"]
                        if m["name"] not in NEW_METRICS
                        for w in m.get("workloads", [])]
    wanted = {m["name"] for m in manifest.metrics_for(BENCH["per_layer"],
                                                      CELL)}
    assert set(NEW_METRICS) <= wanted
    assert not {"conv_roofline", "matmul_roofline", "norm_ms", "mla_ms",
                "moe_ms", "lm_loss_ms", "window_core_ms"} & wanted


def test_the_model_built_from_the_file_is_the_rank_it_states(config):
    """640,159,744 parameters a layer, 5,393,956,864 bytes at 4 layers,
    the router, the norms and the adapters float32, adapters on the four
    attention projections and the shared experts' three wide matrices,
    from shapes: the file's arithmetic reckoned again."""
    import jax
    import jax.numpy as jnp

    model = manifest.build_model(config, tiny=False)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    base = jax.tree_util.tree_leaves(shapes["base"])
    h, fe, v, e, held = 4096, 4096, 32768, 128, 8
    attention = 2 * h * 4096 + 2 * h * 256
    shared = 3 * h * 4 * fe
    layer = attention + shared + h * e + held * 3 * h * fe + h
    assert (attention, shared, h * e, held * 3 * h * fe) == (
        35_651_584, 201_326_592, 524_288, 402_653_184)
    assert layer == 640_159_744
    assert sum(a.size for a in base) == 4 * layer + v * h + h \
        == 2_694_860_800
    weighs = sum(a.size * a.dtype.itemsize for a in base)
    # every matrix and stack in bfloat16, the router and the norms float32
    assert weighs == 2 * (4 * (attention + shared + held * 3 * h * fe)
                          + v * h) + 4 * (4 * (h * e + h) + h) \
        == 5_393_956_864
    assert 5.02 < weighs / 2**30 < 5.03
    assert 0.318 < weighs / (15.75 * 2**30) < 0.320
    assert set(shapes["base"]) == {"tok_emb", "blocks", "norm_f"}  # tied
    assert shapes["base"]["tok_emb"].shape == (v, h)
    assert shapes["base"]["tok_emb"].dtype == jnp.bfloat16
    for kind, b in zip(PERIOD, shapes["base"]["blocks"]):
        key = {"sliding_attention": "sliding_attn",
               "full_attention": "attn"}[kind]
        assert set(b) == {"norm", key, "mlp"}  # one norm a block
        assert set(b["norm"]) == {"scale"}
        assert sum(a.size for a in jax.tree_util.tree_leaves(b)) == layer
        assert {k: a.shape for k, a in b[key].items()} == {
            "wq": (h, 4096), "wk": (h, 256), "wv": (h, 256), "wo": (4096, h)}
        mlp = dict(b["mlp"])
        assert {k: (a.shape, a.dtype) for k, a in mlp.pop("shared").items()
                } == {"w_gate": ((h, 4 * fe), jnp.bfloat16),
                      "w_up": ((h, 4 * fe), jnp.bfloat16),
                      "w_down": ((4 * fe, h), jnp.bfloat16)}
        assert {k: (a.shape, a.dtype) for k, a in mlp.items()} == {
            "router": ((h, e), jnp.float32),
            "w_gate": ((held, h, fe), jnp.bfloat16),
            "w_up": ((held, h, fe), jnp.bfloat16),
            "w_down": ((held, fe, h), jnp.bfloat16)}
    lora = shapes["lora"]
    assert {k.rsplit("/", 1)[1] for k in lora} == {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert len(lora) == 4 * 7 and all(
        "/shared/" in k for k in lora if k.rsplit("/", 1)[1].startswith("w_"))
    assert sum(a.size for a in jax.tree_util.tree_leaves(lora)) \
        == 4 * 1_384_448 == 5_537_792
    engine = manifest.engine_args(config, {})
    assert engine["trainable"]("lora/blocks/1/mlp/shared/w_up/a", None)
    assert not engine["trainable"]("base/blocks/1/mlp/router", None)
    assert dict(model.span_attrs) == {
        "experts_held": 8, "experts_total": 128, "routed_rows_bound": 1024,
        "shared_experts": 4, "shared_combine": "average", "window": 4096,
        "window_layers": 3, "full_layers": 1, "parallel_block": True,
        "norm": "layer", "full_layer_rope": "none",
        "rope_pairs": "adjacent", "heads_held": "32+2"}


@pytest.mark.parametrize("length,window", [(16, 5), (24, 1), (9, 9), (7, 30)])
def test_the_pair_counts_are_a_brute_force_count(length, window):
    flops = manifest.load_module(ROOT, "flops", CONFIG)
    seen = sum(1 for t in range(length) for s in range(length)
               if 0 <= t - s < window)
    assert flops.pairs_seen(length, window) == seen
    assert flops.pairs_seen(length) == length * (length + 1) // 2 \
        == sum(1 for t in range(length) for s in range(length) if s <= t)


def test_required_work_follows_the_shapes(config):
    flops = manifest.load_module(ROOT, "flops", CONFIG)
    need = flops.required(config, JOB)
    macs = need["forward_macs_per_token"]
    h, fe = 4096, 4096
    assert flops.pairs_seen(8192, 4096) == 25_167_872
    assert flops.pairs_seen(8192) == 33_558_528
    assert macs["frozen"] == 4 * (35_651_584 + h * 128)
    assert macs["shared"] == 4 * 3 * h * 4 * fe
    assert macs["experts"] == 4 * 8 * 8 / 128 * 3 * h * fe  # half a row
    assert macs["head"] == h * 32768
    assert macs["adapters"] == 4 * 16 * (2 * 8192 + 2 * 4352)
    assert macs["shared_adapters"] == 4 * 16 * 3 * 20480
    assert macs["adapters"] + macs["shared_adapters"] == 5_537_792
    assert macs["window_core"] == 3 * 32 * 256 * 25_167_872 / 8192
    assert macs["full_core"] == 1 * 32 * 256 * 33_558_528 / 8192
    per_token = 4 * (macs["frozen"] + macs["shared"] + macs["experts"]
                     + macs["head"]) \
        + 6 * (macs["adapters"] + macs["shared_adapters"]) \
        + 7 * (macs["window_core"] + macs["full_core"])
    assert need["flops_per_token"] == per_token
    assert need["flops_per_round"] == per_token * 4 * 8192
    tokens = 4 * 8192
    # 3.5 x 512 FLOPs a pair, a head and a sequence; 75.0 % of the pairs
    assert need["window_core_flops_per_round"] == pytest.approx(
        3 * 32 * 4 * 25_167_872 * 1792)
    assert need["full_core_flops_per_round"] == pytest.approx(
        32 * 4 * 33_558_528 * 1792)
    assert need["window_core_flops_per_round"] \
        / need["full_core_flops_per_round"] / 3 \
        == pytest.approx(0.7500, abs=1e-4)
    assert need["window_core_bytes_per_round"] \
        == 3 * tokens * 2 * 4 * (32 + 2) * 128
    assert need["full_core_bytes_per_round"] == tokens * 2 * 4 * 34 * 128
    assert need["expert_flops_per_round"] == 4 * macs["experts"] * tokens
    assert need["expert_bytes_per_round"] == 2 * 2 * (
        4 * 8 * 3 * h * fe + 4 * 0.5 * (3 * h + 3 * fe) * tokens)
    assert need["shared_expert_flops_per_round"] == (
        4 * macs["shared"] + 6 * macs["shared_adapters"]) * tokens
    assert need["shared_expert_bytes_per_round"] == 2 * 2 * (
        macs["shared"] + 4 * (3 * h + 3 * 4 * fe) * tokens)
    assert need["kernel"] == "matmul"
    # 181 TFLOP a round of required work (the issue's 249 counts a
    # checkpointed block's second forward, 6 FLOPs a frozen parameter;
    # the benchmark's files count 4): the shared experts 59 %, the
    # cores 14 % (the issue's own 17.3 and 7.7 TFLOP), the routed 7 %
    assert 181e12 < need["flops_per_round"] < 182e12
    assert need["shared_expert_flops_per_round"] / need["flops_per_round"] \
        == pytest.approx(0.586, abs=0.002)
    assert need["expert_flops_per_round"] / need["flops_per_round"] \
        == pytest.approx(0.0727, abs=0.001)
    assert need["window_core_flops_per_round"] == pytest.approx(
        17.32e12, rel=2e-3)
    assert need["full_core_flops_per_round"] == pytest.approx(
        7.70e12, rel=2e-3)
    for absent in ("mla_core_flops_per_round", "cca_core_flops_per_round",
                   "ssd_scan_flops_per_round", "scan_flops_per_round"):
        assert absent not in need
    double = flops.required(config, dict(JOB, n_samples=[2, 2, 2, 2]))
    assert double["flops_per_round"] == 2 * need["flops_per_round"]
    from fedbench.roofline import least_seconds

    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    for part in ("window_core", "full_core", "shared_expert", "expert"):
        assert least_seconds(need[f"{part}_flops_per_round"],
                             need[f"{part}_bytes_per_round"],
                             peaks)[1] == "compute"


def test_the_readers_divide_least_time_by_scope_time(config):
    from fedbench.roofline import least_seconds

    need = manifest.load_module(ROOT, "flops", CONFIG).required(config, JOB)
    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    cell = {"required": need, "peaks": peaks, "chips": 1}
    wave = {"runs": 2, "phase_part_s": {
        "forward": {"sliding_attention": 0.06, "window_core": 0.1,
                    "full_core": 0.05, "attention": 0.02, "moe": 0.1,
                    "router": 0.02, "expert_matmul": 0.08,
                    "shared_expert": 0.6},
        "recompute": {"shared_expert": 0.6, "window_core": 0.1},
        "backward": {"sliding_attention": 0.1, "window_core": 0.3,
                     "full_core": 0.15, "attention": 0.04, "moe": 0.2,
                     "expert_matmul": 0.16, "shared_expert": 1.2}}}
    reduced = {"devices": {"/device:TPU:0": {"wave": wave}}, "n_rounds": 2}

    def read(name, seen=reduced, cell=cell):
        return manifest.load_module(ROOT, "layer_metrics", name).read(
            seen, {"n_waves": 1}, cell)

    assert read("shared_expert_ms") == pytest.approx(1200.0)
    assert read("group16_core_ms") == pytest.approx(350.0)
    assert read("routed_experts_ms") == pytest.approx(280.0)
    least, _ = least_seconds(need["shared_expert_flops_per_round"],
                             need["shared_expert_bytes_per_round"], peaks)
    assert read("shared_expert_roofline") == pytest.approx(100 * least / 1.2)
    least, _ = least_seconds(
        need["window_core_flops_per_round"]
        + need["full_core_flops_per_round"],
        need["window_core_bytes_per_round"]
        + need["full_core_bytes_per_round"], peaks)
    assert read("group16_core_roofline") == pytest.approx(100 * least / 0.35)
    for name in ("shared_expert_roofline", "group16_core_roofline"):
        assert 0 < read(name) < 100
    # a program without the scopes (the parent's, or another model's), or
    # a configuration without the counts: nothing, and no error
    bare = {"devices": {"d": {"wave": {"runs": 1, "phase_part_s": {
        "forward": {"mlp": 0.1, "attention": 0.2}}}}}}
    for name in NEW_METRICS:
        assert read(name, bare) is None
        assert read(name, None) is None
    for name in ("shared_expert_roofline", "group16_core_roofline"):
        assert read(name, cell=dict(cell, required={"kernel": "matmul"})) \
            is None
    names = manifest.load_trace_names(ROOT, config)
    from fedbench import trace_reduce

    inside = "jit(f)/local_train/jvp(block1)/checkpoint/"
    for scope, part in (
            (inside + "sliding_attention/window_core/pallas_call",
             "window_core"),
            (inside + "sliding_attention/dot_general", "sliding_attention"),
            ("jit(f)/block3/attention/full_core/pallas_call", "full_core"),
            (inside + "moe/shared_expert/dot_general", "shared_expert"),
            (inside + "moe/routed_block/expert_matmul/gmm", "expert_matmul"),
            (inside + "moe/router/top_k", "router"),
            (inside + "moe/routed_block/gather", "moe"),
            (inside + "norm/rsqrt", "norm")):
        assert trace_reduce.part_of(scope, names) == part


def test_the_expert_shares_add_up_to_the_uncut_layer(config):
    """The guide's test of the cut through the file's own builder at
    ``tiny`` sizes: the eight ranks of 2 of 16 experts (each built as
    the file builds its own, from its first held expert on) compute
    parts whose sum, with the averaged shared experts counted once, is
    the layer that holds every expert. (``test_fedbench_glm5.py``'s
    test of the same name counts a *summed* shared expert, so this
    file names its first held expert ``first_held_expert`` and is not
    among that test's cases.)"""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from baton_tpu.models import moe

    assert "first_expert_held" not in config
    sized = manifest.sized(config, True)
    decoder = manifest.resolve(config["builder"]["kwargs"]["config"], sized)
    cfg = decoder.moe
    assert (cfg.first_held, cfg.held, cfg.n_experts) == (4, 2, 16)
    whole_cfg = dataclasses.replace(cfg, experts_held=None, first_held=0)
    key = jax.random.key(4)
    d, f = decoder.d_model, decoder.d_ff
    whole = moe.moe_init(key, d, f, whole_cfg)
    x = jax.random.normal(jax.random.key(5), (2, 12, d), jnp.float32)
    want = moe.moe_apply(whole, x, whole_cfg)
    shared = cfg.shared_weight * moe.swiglu(whole["shared"], x)
    total = shared
    for first in range(0, cfg.n_experts, cfg.held):
        rank_cfg = dataclasses.replace(cfg, first_held=first)
        rank = moe.moe_init(key, d, f, rank_cfg)
        np.testing.assert_array_equal(
            np.asarray(rank["w_up"]),
            np.asarray(whole["w_up"][first:first + cfg.held]))
        total = total + moe.moe_apply(rank, x, rank_cfg) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(moe.moe_dense_oracle(whole, x, whole_cfg)),
        rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def on_the_probe(config):
    """The cell at ``tiny`` sizes, built once: ``(sized, job, params,
    sim, (pdata, sizes), (ok, compared), want)``: the harness's own
    probe of seed 4294967311, and the reference's round over the same
    cohort (``fedbench/reference.py::reference_round``)."""
    seed = 4294967311
    job = run.job_of(manifest.load_workload(ROOT, CELL), True)
    _, params, _, _, _, mesh, sim = run.build_cell(
        ROOT, config, job, 1, seed, True)
    probed = run.probe(ROOT, config, job, True, seed, sim, params, mesh)
    sized = manifest.sized(config, True)
    pdata, sizes = run.probe_cohort(ROOT, config, job, True, seed)
    want, _ = reference.reference_round(
        manifest.load_module(ROOT, "references", CONFIG).make_loss(sized),
        params, pdata, sizes, job["learning_rate"],
        manifest.engine_args(config, job)["trainable"])
    return sized, job, params, sim, (pdata, sizes), probed, want


def test_a_round_of_the_program_is_the_reference_round(config, on_the_probe):
    """``FedSim.run_round`` on the probe cohort against
    ``reference_round`` with the loss of ``references/command_a_plus.py``,
    through the files the harness loads, at ``tiny`` sizes in float32 (a
    window of 5 over 16 tokens, one period, experts 4 and 5 of 16 held,
    2 shared experts averaged): the adapters agree and every frozen
    leaf is the array that went in."""
    import jax

    sized, _, params, _, (pdata, _), (ok, compared), want = on_the_probe
    assert ok
    assert compared["reference"][0] < 1e-4
    assert compared["reference_l2"][0] < 1e-4
    assert compared["loss_gap"][0] < 1e-5
    # 4 layers of 4 leaves of attention, 7 of the expert layer and a
    # norm, the table and a norm
    assert compared["frozen_leaves_changed"] == (0, 0)
    assert len(jax.tree_util.tree_leaves(params["base"])) == 4 * 12 + 2
    for a, b in zip(jax.tree_util.tree_leaves(want["base"]),
                    jax.tree_util.tree_leaves(params["base"])):
        assert a is b
    assert int(pdata["x"].max()) < sized["vocab_size"]
    # every adapter moved, the shared experts' among them
    moved = {k: float(abs(want["lora"][k]["b"] - params["lora"][k]["b"]).max())
             for k in params["lora"]}
    assert min(moved.values()) > 0 and any("/shared/" in k for k in moved)


def test_the_float8_control_comes_out_not_correct(config, on_the_probe):
    """The same reference with every product's operands rounded to
    float8, put in the program's place, fails the cell's limits; the
    program passes them (``fedbench/control.py``'s comparison, on the
    module's one cell)."""
    import jax.numpy as jnp

    sized, job, params, _, (pdata, sizes), (_, compared), plain = \
        on_the_probe
    limits = {"max": config["probe_tolerance"],
              "l2": config["probe_l2_tolerance"]}
    assert compared["reference"][0] <= limits["max"]
    assert compared["reference_l2"][0] <= limits["l2"]
    make_loss = manifest.load_module(ROOT, "references", CONFIG).make_loss
    trainable = manifest.engine_args(config, job)["trainable"]
    rounded, _ = reference.reference_round(
        make_loss(sized, reference.rounded_to(jnp.float8_e4m3fn)), params,
        pdata, sizes, job["learning_rate"], trainable)
    control = {norm: reference.update_disagreement(
        params, rounded, plain, norm, trainable) for norm in ("max", "l2")}
    assert control["l2"] > limits["l2"] or control["max"] > limits["max"], \
        control


def test_the_programs_scopes_are_the_ones_the_metrics_read(on_the_probe):
    """The wave program at ``tiny`` sizes as JAX lowers it: a block's
    norm once, the windowed blocks' ops under ``sliding_attention`` with
    the core under ``window_core``, the full block's core under
    ``full_core`` inside ``attention``, the expert layers' under ``moe``
    with ``router``, ``expert_matmul`` and ``shared_expert``."""
    import jax

    _, job, params, sim, (pdata, sizes), _, _ = on_the_probe
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True  # the instructions' op_name scopes
    text = sim.lower_wave(
        params, pdata, sizes, jax.random.key(0), job["local_epochs"],
        job["wave_size"]).compiler_ir(dialect="hlo").as_hlo_module(
            ).to_string(options)
    for scope in ("(block0)/sliding_attention/window_core/",
                  "(block2)/sliding_attention/dot_general",
                  "(block3)/attention/full_core/",
                  "(block3)/attention/dot_general",
                  "/moe/router/", "/moe/routed_block/expert_matmul/",
                  "/moe/shared_expert/", "(lm_loss)/"):
        assert scope in text, scope
    assert "(block3)/sliding_attention" not in text
    assert "(block0)/attention" not in text


def test_the_references_parts_move_its_loss(config, on_the_probe,
                                            monkeypatch):
    """Nothing in the reference is decoration. In blocks of 4 queries
    and 4 tokens of the loss it gives the loss it gives whole, and the
    program's; with the window one key wider, with another rotary base,
    with one expert more a token, with one shared expert fewer in the
    mean, or with a larger eps, it gives another; and it refuses a
    rotation or a combination it does not compute."""
    import jax.numpy as jnp

    import jax

    sized, _, params, _, (pdata, _), _, _ = on_the_probe
    module = manifest.load_module(ROOT, "references", CONFIG)
    batch = (pdata["x"][0], pdata["y"][0], jnp.ones((1,)))

    def changed(**sizes):
        return float(jax.jit(module.make_loss(dict(sized, **sizes)))(
            params, *batch))

    whole = changed()
    model = manifest.build_model(config, tiny=True)
    program = float(model.masked_loss(
        params, dict(zip(("x", "y", "mask"), batch)), None))
    assert whole == pytest.approx(program, rel=2e-6)
    monkeypatch.setattr(module, "QUERY_BLOCK", 4)
    monkeypatch.setattr(module, "LOSS_BLOCK", 4)
    assert changed() == pytest.approx(whole, rel=1e-6)
    others = {
        "a key wider": changed(sliding_window=sized["sliding_window"] + 1),
        "another base": changed(rope_theta=10000),
        "one expert more": changed(num_experts_per_tok=3),
        "another rank": changed(first_held_expert=0),
    }
    for name, value in others.items():
        assert abs(value - whole) > 1e-6 * abs(whole), name
    with pytest.raises(ValueError, match="rope_gptj"):
        changed(position_embedding_type="rope_neox")
    with pytest.raises(ValueError, match="averages"):
        changed(shared_expert_combination_strategy="sum")


def test_the_reference_is_plain():
    """No ``vmap``, no ``custom_vjp``, no sort and no ``top_k``, nothing
    of ``baton_tpu``; SiLU, the sigmoid and the softmax written out; the
    routed experts a ``scan`` over the held stacks, the shared experts a
    loop over slices, the blocks of queries a ``map``, a layer, an
    expert of either kind, a block of queries and a block of the loss
    under ``checkpoint``."""
    path = os.path.join(ROOT, "fedbench", "references", f"{CONFIG}.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    called = [n.func.attr for n in ast.walk(tree)
              if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)]
    assert not set(called) & {"vmap", "custom_vjp", "custom_jvp", "top_k",
                              "sort", "argsort", "stop_gradient", "silu",
                              "softmax", "sigmoid", "ragged_dot", "roll",
                              "pallas_call"}
    assert {"where", "exp", "scan", "map", "matmul", "einsum"} <= set(called)
    imported = [n.names[0].name if isinstance(n, ast.Import) else n.module
                for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert sorted(imported) == ["jax", "jax.numpy"]
    assert source.count("jax.checkpoint") >= 5
    assert "for j in range(n_shared)" in source and "/ n_shared" in source
