"""The seam between a decoder block and its mixer: ``llama.MIXERS``.

A block and its model ask a mixer's record and nothing else, so a
further architecture is one entry of the table. Held here: each entry
answers what the accepted configurations run, a mixer from outside the
package works through the table alone, and ``llama.py`` names a layer
type or a mixer's parameter key nowhere but in the table."""

import ast
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import llama
from baton_tpu.models.llama import (
    MIXERS,
    LlamaConfig,
    Mixer,
    core_outputs_kept,
    decoder_lora_model,
    projection_lora_target,
)
from baton_tpu.models.transformer import dense_init

REPO = pathlib.Path(__file__).resolve().parent.parent
LAYER_TYPES = ("full_attention", "linear_attention", "latent_attention",
               "compressed_attention", "parallel_ssm_attention",
               "sliding_attention")
PARAMETER_KEYS = ("attn", "linear_attn", "mla", "cca", "parallel",
                  "sliding_attn")


def _accepted(name: str, tiny: bool) -> LlamaConfig:
    """The ``LlamaConfig`` of an accepted configuration's file, as the
    benchmark's builder resolves it."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from fedbench import manifest

    config = json.loads(
        (REPO / "fedbench" / "configs" / f"{name}.json").read_text())
    return manifest.resolve(config["builder"]["kwargs"]["config"],
                            manifest.sized(config, tiny))


# kind, the configuration that runs it, its cell's sequence length, the
# blocks that keep a kernel's outputs there on a TPU, and the record's
# two answers at 8,192 tokens on a TPU
@pytest.mark.parametrize(
    "kind,config,cell_length,cell_kept,keeps_at_8192,kernel_at_8192", [
        ("full_attention", "olmo_hybrid_7b", 1024, 0, False, True),
        ("linear_attention", "olmo_hybrid_7b", 1024, 0, False, False),
        ("latent_attention", "sarvam_105b", 2048, 5, False, True),
        ("latent_attention", "glm_5", 8192, 0, True, False),
        ("compressed_attention", "zaya1_8b", 8192, 10, False, True),
        ("parallel_ssm_attention", "falcon_h1_34b", 4096, 6, False, True),
        ("sliding_attention", "mellum2_12b", 8192, 8, False, True),
        ("full_attention", "mellum2_12b", 8192, 8, False, True),
        ("sliding_attention", "command_a_plus", 8192, 4, False, True),
        ("full_attention", "command_a_plus", 8192, 4, False, True),
    ])
def test_an_entry_answers_what_the_accepted_configuration_runs(
        kind, config, cell_length, cell_kept, keeps_at_8192, kernel_at_8192):
    mixer = MIXERS[kind]
    published, tiny = _accepted(config, False), _accepted(config, True)
    assert kind in {published.kind_of(i) for i in range(published.n_layers)}

    # its parameters live under its key and under no other mixer's
    block = llama._block_init(jax.random.key(0), tiny, kind)
    assert {m.key for m in MIXERS.values()} & set(block) == {mixer.key}
    # LoRA adapts the 2-D leaves the record lists, directly under the
    # key, and nothing inside a tree of the mixer's own (the indexer)
    flat = {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(block[mixer.key])[0]}
    adapted = {p for p, leaf in flat.items()
               if projection_lora_target(f"blocks/0/{mixer.key}/{p}", leaf)}
    assert adapted == {p for p, leaf in flat.items()
                       if p in mixer.projections and leaf.ndim == 2}
    assert adapted == set(mixer.projections) & set(flat)
    if config == "glm_5":
        assert any(p.startswith("indexer/") for p in flat)
    # another mixer's key does not lend its names
    for other in MIXERS.values():
        if other is not mixer:
            stolen = set(mixer.projections) - set(other.projections)
            assert not any(projection_lora_target(
                f"blocks/0/{other.key}/{name}", None) for name in stolen)

    assert not mixer.keeps_its_inputs(published, 64)
    assert not mixer.core_is_kernel(published, "cpu", 1, 64,
                                    llama.default_attention)
    assert mixer.keeps_its_inputs(published, 8192) is keeps_at_8192
    assert mixer.core_is_kernel(published, "tpu", 1, 8192,
                                llama.default_attention) is kernel_at_8192
    assert core_outputs_kept(published, "tpu", 1, cell_length) == cell_kept
    assert core_outputs_kept(published, "cpu", 1, cell_length) == 0


def _running_mean_mixer(calls):
    """A mixer from outside the package: a token's output is the mean of
    the stream up to it, through one projection."""
    def init(rng, cfg, out_std):
        return {"w_mean": dense_init(rng, cfg.d_model, cfg.d_model,
                                     stddev=out_std),
                "shift": jnp.zeros((cfg.d_model,), jnp.float32)}

    def apply(p, h, cfg, rope, attention_fn):
        calls.append(rope)
        with jax.named_scope("running_mean"):
            steps = jnp.arange(1, h.shape[1] + 1, dtype=jnp.float32)
            mean = jnp.cumsum(h.astype(jnp.float32), axis=1) \
                / steps[None, :, None]
            return (mean + p["shift"]).astype(h.dtype) \
                @ p["w_mean"].astype(h.dtype)

    return Mixer(key="mean", init=init, apply=apply,
                 projections=("w_mean",),
                 facts=lambda cfg: (("mean_over", "prefix"),))


def test_a_mixer_from_outside_is_one_entry_of_the_table(monkeypatch):
    calls = []
    monkeypatch.setitem(MIXERS, "running_mean", _running_mean_mixer(calls))
    cfg = LlamaConfig.tiny(layer_types=("running_mean", "full_attention"))
    model = decoder_lora_model(cfg, compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=2, b_std=0.02,
                               remat=True)
    params = model.init(jax.random.key(0))
    first, second = params["base"]["blocks"]
    assert "mean" in first and "attn" not in first
    assert "attn" in second and "mean" not in second
    assert "blocks/0/mean/w_mean" in params["lora"]
    assert "blocks/0/mean/shift" not in params["lora"]
    assert dict(model.span_attrs)["mean_over"] == "prefix"

    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 9))
    batch = {"x": jnp.asarray(toks[:, :-1], jnp.int32),
             "y": jnp.asarray(toks[:, 1:], jnp.int32)}

    def loss(lora):
        return jnp.mean(model.per_example_loss(
            {"base": params["base"], "lora": lora}, batch, None))

    before, grads = jax.value_and_grad(loss)(params["lora"])
    assert calls and all(rope is None for rope in calls)  # it asked for none
    for leaf in jax.tree_util.tree_leaves(grads["blocks/0/mean/w_mean"]):
        assert float(jnp.max(jnp.abs(leaf))) > 0
    stepped = jax.tree_util.tree_map(lambda a, g: a - 0.5 * g,
                                     params["lora"], grads)
    assert float(loss(stepped)) < float(before)


def test_a_layers_angles_are_its_kinds_made_once_a_kind_a_trace(monkeypatch):
    """Four layers of two kinds whose rotations differ in width: each
    kind's angles are made once a trace, and a layer is handed its own
    kind's (by whichever configuration field is set, the full-attention
    layers would be handed latent attention's)."""
    from baton_tpu.models.transformer import MLAConfig

    made = []

    def counted(kind):
        rope = MIXERS[kind].rope

        def counting(cfg, length):
            made.append(kind)
            return rope(cfg, length)

        return dataclasses.replace(MIXERS[kind], rope=counting)

    for kind in ("latent_attention", "full_attention"):
        monkeypatch.setitem(MIXERS, kind, counted(kind))
    cfg = LlamaConfig.tiny(
        n_layers=4, mla=MLAConfig(kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
                                  block=8),
        layer_types=("latent_attention", "full_attention") * 2)
    assert cfg.head_dim != cfg.mla.rope_dim
    model = llama.llama_lm_model(cfg, remat=True)
    params = model.init(jax.random.key(0))
    batch = {"x": jnp.zeros((2, 8), jnp.int32),
             "y": jnp.zeros((2, 8), jnp.int32)}
    jax.make_jaxpr(lambda p: model.per_example_loss(p, batch, None))(params)
    assert sorted(made) == ["full_attention", "latent_attention"]
    assert np.isfinite(np.asarray(
        model.per_example_loss(params, batch, None))).all()


def test_the_two_kinds_of_a_windowed_model_take_their_own_rotary_tables():
    """Three windowed layers to one full one: each kind's angles are made
    once a trace; the windowed layers' are plain, the full layers' are
    ``yarn``'s (other frequencies, ``cos`` and ``sin`` times the
    ``attention_factor``); a block is told by its parameters' key, and
    the model says what its window is and what its kernels would visit."""
    from baton_tpu.models.transformer import rope_angles

    handed = {}

    def recording(kind):
        apply = MIXERS[kind].apply

        def recorded(p, h, cfg, rope, attention_fn):
            handed.setdefault(kind, []).append(rope)
            return apply(p, h, cfg, rope, attention_fn)

        return recorded

    cfg = _accepted("mellum2_12b", True)
    assert cfg.window == 5 and dict(cfg.rope_yarn)["factor"] == 16
    kinds = [cfg.kind_of(i) for i in range(cfg.n_layers)]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"]
    model = llama.llama_lm_model(cfg, remat=True)
    assert dict(model.span_attrs) == {
        "experts_held": 8, "experts_total": 8, "routed_rows_bound": 2048,
        "router_scores": "softmax_chosen", "window": 5, "window_layers": 3,
        "full_layers": 1, "rope_yarn_factor": 16}
    params = model.init(jax.random.key(0))
    assert ["sliding_attn" in b for b in params["blocks"]] == [
        True, True, True, False]
    assert ["attn" in b for b in params["blocks"]] == [
        False, False, False, True]
    batch = {"x": jnp.zeros((2, 16), jnp.int32),
             "y": jnp.zeros((2, 16), jnp.int32)}
    with pytest.MonkeyPatch.context() as patch:
        for kind in ("sliding_attention", "full_attention"):
            patch.setitem(MIXERS, kind, dataclasses.replace(
                MIXERS[kind], apply=recording(kind)))
        recorded = llama.llama_lm_model(cfg)  # no jit: the arrays as made
        assert np.isfinite(np.asarray(
            recorded.per_example_loss(params, batch, None))).all()
    assert len(handed["sliding_attention"]) == 3
    assert len(handed["full_attention"]) == 1
    plain = rope_angles(16, cfg.head_dim, cfg.rope_theta)
    for cos, sin in handed["sliding_attention"]:
        np.testing.assert_array_equal(cos, plain[0])
        np.testing.assert_array_equal(sin, plain[1])
        assert cos is handed["sliding_attention"][0][0]  # made once
    cos, sin = handed["full_attention"][0]
    factor = dict(cfg.rope_yarn)["attention_factor"]
    np.testing.assert_allclose(cos[0], factor, rtol=1e-6)  # position 0
    np.testing.assert_allclose(cos ** 2 + sin ** 2, factor ** 2, rtol=1e-5)
    assert not np.allclose(cos / factor, plain[0])  # other frequencies
    # once traced, what the kernels' rule visits at the traced length
    assert dict(recorded.span_attrs)["window_tiles"] == 1
    published = _accepted("mellum2_12b", False)
    seen = MIXERS["sliding_attention"].seen(published, 8192)
    assert seen == {"window_tiles": 15, "causal_tiles": 72,
                    "window_grid_steps": 16, "causal_grid_steps": 128}
    assert MIXERS["full_attention"].seen(published, 8192) == {}


def test_yarn_angles_against_a_hand_written_case():
    """``rope_angles`` with a ``yarn`` group at a head of 8 channels,
    theta 10,000, factor 4 over 64 original positions, 4 fast and 1 slow
    turns: the correction dims are ``8 ln(64 / (4 . 2 pi)) / (2 ln 1e4)
    = 0.406 -> 0`` and ``8 ln(64 / (2 pi)) / (2 ln 1e4) = 1.008 -> 2``,
    so channel 0 keeps its frequency, channel 1 is half interpolated and
    channels 2 and 3 turn at a quarter of theirs; ``cos`` and ``sin``
    carry the ``attention_factor``."""
    from baton_tpu.models.transformer import rope_angles

    yarn = {"factor": 4, "original_max_position_embeddings": 64,
            "beta_fast": 4, "beta_slow": 1, "attention_factor": 1.5}
    cos, sin = rope_angles(6, 8, 10000.0, yarn)
    plain = np.asarray([1.0, 0.1, 0.01, 0.001])
    wanted = plain * np.asarray([1.0, 0.5 + 0.5 / 4, 0.25, 0.25])
    angle = np.arange(6)[:, None] * wanted[None, :]
    np.testing.assert_allclose(cos, 1.5 * np.cos(angle), rtol=1e-5)
    np.testing.assert_allclose(sin, 1.5 * np.sin(angle), rtol=1e-5, atol=1e-7)
    # no group: the plain table it always was
    cos, sin = rope_angles(6, 8, 10000.0)
    np.testing.assert_allclose(cos, np.cos(np.arange(6)[:, None] * plain),
                               rtol=1e-5)


def test_a_layer_type_outside_the_table_is_refused():
    cfg = LlamaConfig.tiny(layer_types=("full_attention", "sliding_window"))
    with pytest.raises(ValueError, match="sliding_window"):
        llama.llama_lm_model(cfg)
    with pytest.raises(ValueError, match="sliding_window"):
        llama._block_init(jax.random.key(0), cfg, cfg.kind_of(1))
    with pytest.raises(ValueError, match="sliding_window"):
        core_outputs_kept(cfg, "tpu", 1, 8192)


def test_llama_names_a_mixer_only_in_its_table():
    """Read from the source, without importing it: each layer type and
    each parameter key of a mixer is a string literal once, in
    ``MIXERS``; ``LlamaConfig.kind_of`` names the two kinds a
    configuration without ``layer_types`` falls back to; the windowed
    mixer's scope in a trace carries its layer type's name (the
    benchmark's metrics read it); nothing else in the module spells one
    out."""
    tree = ast.parse((REPO / "baton_tpu" / "models" / "llama.py").read_text(
        encoding="utf-8"))
    names = set(LAYER_TYPES + PARAMETER_KEYS)

    def literals(node):
        return [n.value for n in ast.walk(node)
                if isinstance(n, ast.Constant) and n.value in names]

    (table,) = [n for n in tree.body if isinstance(n, ast.Assign)
                and [t.id for t in n.targets if isinstance(t, ast.Name)]
                == ["MIXERS"]]
    (kind_of,) = [n for n in ast.walk(tree)
                  if isinstance(n, ast.FunctionDef) and n.name == "kind_of"]
    assert sorted(literals(table)) == sorted(names)
    assert [k.value for k in table.value.keys] == list(LAYER_TYPES)
    assert sorted(literals(kind_of)) == ["full_attention", "latent_attention"]
    (sliding,) = [n for n in ast.walk(tree)
                  if isinstance(n, ast.FunctionDef)
                  and n.name == "_sliding_apply"]
    assert [literals(d) for d in sliding.decorator_list] == [
        ["sliding_attention"]]
    assert len(literals(tree)) == len(names) + 3, (
        "baton_tpu/models/llama.py spells a layer type or a mixer's "
        "parameter key outside MIXERS and LlamaConfig.kind_of: ask the "
        "table instead")


def test_an_aligned_draw_scores_a_tokens_own_key_high():
    """``qk_aligned``: a query head's projection is that share of its
    key head's and the rest its own, the deviation kept, so a token's
    score of its own key has the mean ``a sqrt(head_dim)`` among scores
    of deviation 1; at 0 the draws are the independent ones they were,
    in both kinds of attention."""
    from baton_tpu.models.transformer import mha_init

    d, heads, kv, dh = 64, 8, 2, 32
    plain = mha_init(jax.random.key(0), d, heads, kv, dh)
    again = mha_init(jax.random.key(0), d, heads, kv, dh, qk_aligned=0.0)
    np.testing.assert_array_equal(plain["wq"], again["wq"])
    drawn = mha_init(jax.random.key(0), d, heads, kv, dh, qk_aligned=0.5)
    np.testing.assert_array_equal(plain["wk"], drawn["wk"])
    assert float(jnp.std(drawn["wq"])) == pytest.approx(
        float(jnp.std(plain["wq"])), rel=0.05)
    h = jax.random.normal(jax.random.key(1), (512, d))
    q = (h @ drawn["wq"]).reshape(512, kv, heads // kv, dh)
    k = (h @ drawn["wk"]).reshape(512, kv, 1, dh)
    own = jnp.sum(q * k, -1) / dh ** 0.5
    other = jnp.sum(q * jnp.roll(k, 1, axis=0), -1) / dh ** 0.5
    assert float(jnp.mean(own)) == pytest.approx(0.5 * dh ** 0.5, rel=0.1)
    assert abs(float(jnp.mean(other))) < 0.1
    assert float(jnp.std(other)) == pytest.approx(1.0, rel=0.15)
    cfg = LlamaConfig.tiny(layer_types=("sliding_attention",
                                        "full_attention"),
                           window=4, qk_aligned=0.5)
    for kind in cfg.layer_types:
        block = llama._block_init(jax.random.key(2), cfg, kind)
        p = block[MIXERS[kind].key]
        groups = p["wq"].reshape(cfg.d_model, cfg.n_kv_heads, -1,
                                 cfg.head_dim)
        shared = p["wk"].reshape(cfg.d_model, cfg.n_kv_heads, 1,
                                 cfg.head_dim)
        corr = float(jnp.sum(groups * shared) / (
            jnp.linalg.norm(groups) * jnp.linalg.norm(
                jnp.broadcast_to(shared, groups.shape))))
        assert corr == pytest.approx(0.5, abs=0.05), kind
