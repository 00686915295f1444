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
               "compressed_attention", "parallel_ssm_attention")
PARAMETER_KEYS = ("attn", "linear_attn", "mla", "cca", "parallel")


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
    configuration without ``layer_types`` falls back to; nothing else
    in the module spells one out."""
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
    assert len(literals(tree)) == len(names) + 2, (
        "baton_tpu/models/llama.py spells a layer type or a mixer's "
        "parameter key outside MIXERS and LlamaConfig.kind_of: ask the "
        "table instead")
