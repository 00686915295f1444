"""The decoder block's second order and what came with it: a parallel
block (one norm, the mixer and the feed-forward side by side, one add)
against the same written by hand, the LayerNorm with a scale and no
bias, full layers that take no rotation beside windowed layers that keep
theirs, and the rotation of adjacent pairs against the rotation by
halves on de-interleaved weights. One decoder is made a module; every
test reads it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import llama
from baton_tpu.models.llama import MIXERS, LlamaConfig, llama_lm_model
from baton_tpu.models.moe import MoEConfig, moe_apply
from baton_tpu.models.transformer import (
    apply_rope, default_attention, layer_norm, layer_normalize,
    multi_head_attention, rope_angles)

PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
CFG = LlamaConfig(
    vocab_size=96, max_len=32, d_model=64, n_layers=4, n_heads=4,
    n_kv_heads=2, head_dim=8, d_ff=32, rope_theta=50000.0, window=5,
    layer_types=PERIOD, norm_eps=1e-5, tie_embeddings=True, embed_std=1.0,
    parallel_block=True, norm="layer", full_layer_rope=False,
    rope_pairs="adjacent",
    moe=MoEConfig(n_experts=16, top_k=2, d_ff=32, experts_held=2,
                  first_held=4, n_shared=2, shared_combine="average"))
LENGTH = 12


@pytest.fixture(scope="module")
def decoder():
    model = llama_lm_model(CFG)
    params = model.init(jax.random.key(5))
    # norms that a test can tell from their absence
    for i, b in enumerate(params["blocks"]):
        b["norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
            jax.random.key(100 + i), (CFG.d_model,))
    x = jax.random.normal(jax.random.key(6), (2, LENGTH, CFG.d_model))
    return model, params, x


def _by_hand(p, x, kind):
    """``x + a + m`` of one block, each part from the package's own
    pieces, the norm written out."""
    xf = x - jnp.mean(x, axis=-1, keepdims=True)
    h = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      + CFG.norm_eps) * p["norm"]["scale"]
    m = MIXERS[kind]
    a = m.apply(p[m.key], h, CFG, m.rope(CFG, LENGTH), default_attention)
    return x + a + moe_apply(p["mlp"], h, CFG.moe)


@pytest.mark.parametrize("layer", [0, 3], ids=["sliding", "full"])
def test_a_parallel_block_is_x_plus_a_plus_m_by_hand(decoder, layer):
    _, params, x = decoder
    p, kind = params["blocks"][layer], PERIOD[layer]
    rope = MIXERS[kind].rope(CFG, LENGTH)

    def block(p, x):
        return llama._block_apply(p, x, None, CFG, rope, default_attention)[0]

    got, want = block(p, x), _by_hand(p, x, kind)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the sequential order is another function of the same numbers
    serial = dataclasses.replace(CFG, parallel_block=False)
    sequential = llama._block_apply(
        dict(p, norm_attn=p["norm"], norm_mlp=p["norm"]), x, None, serial,
        rope, default_attention)[0]
    assert float(jnp.max(jnp.abs(sequential - want))) > 1e-2
    w = jax.random.normal(jax.random.key(7), x.shape)
    g_got = jax.grad(lambda p, x: jnp.sum(block(p, x) * w), (0, 1))(p, x)
    g_want = jax.grad(lambda p, x: jnp.sum(_by_hand(p, x, kind) * w),
                      (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_a_parallel_block_holds_one_norm(decoder):
    _, params, _ = decoder
    for kind, p in zip(PERIOD, params["blocks"]):
        assert set(p) == {"norm", MIXERS[kind].key, "mlp"}
        assert set(p["norm"]) == {"scale"}
    assert set(params["norm_f"]) == {"scale"}
    sequential = llama._block_init(
        jax.random.key(0), dataclasses.replace(CFG, parallel_block=False),
        "full_attention", True)
    assert set(sequential) == {"norm_attn", "attn", "norm_mlp", "mlp"}
    with pytest.raises(NotImplementedError, match="one plain add"):
        llama_lm_model(dataclasses.replace(CFG, residual_merge=True))
    with pytest.raises(ValueError, match="norm"):
        dataclasses.replace(CFG, norm="batch")
    with pytest.raises(ValueError, match="rope_pairs"):
        dataclasses.replace(CFG, rope_pairs="triples")


def test_the_scale_only_layer_norm_is_layer_normalize_with_a_zero_bias(
        decoder):
    _, params, x = decoder
    scale = params["blocks"][0]["norm"]["scale"]
    got = layer_norm(x, {"scale": scale}, CFG.norm_eps)
    want = layer_normalize(
        x, {"scale": scale, "bias": jnp.zeros_like(scale)}, CFG.norm_eps)
    np.testing.assert_array_equal(got, want)
    # the mean is taken off: an RMSNorm of a shifted stream is another
    shifted = x + 3.0
    np.testing.assert_allclose(
        layer_norm(shifted, {"scale": scale}, CFG.norm_eps), got,
        rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(llama._NORMS["rms"](
        shifted, {"scale": scale}, CFG.norm_eps) - got))) > 0.1
    # the decoder's last norm is the same one
    assert llama._normed(x, {"scale": scale}, CFG).tolist() == got.tolist()


def test_full_layers_take_no_rotation_and_sliding_layers_theirs(decoder,
                                                                monkeypatch):
    """In one decoder: the windowed mixer is handed ``rope_theta``'s
    angles and the full mixer none, and the model's output moves with
    the first alone."""
    model, params, _ = decoder
    cos, sin = rope_angles(LENGTH, CFG.head_dim, CFG.rope_theta)
    assert MIXERS["full_attention"].rope(CFG, LENGTH) is None
    for got, want in zip(MIXERS["sliding_attention"].rope(CFG, LENGTH),
                         (cos, sin)):
        np.testing.assert_array_equal(got, want)
    with_rope = dataclasses.replace(CFG, full_layer_rope=True)
    assert MIXERS["full_attention"].rope(with_rope, LENGTH) is not None
    handed = {}
    real = multi_head_attention

    def seen(p, x, *args, rope=None, **kwargs):
        handed[kwargs.get("core_scope")] = (rope, kwargs.get("rope_pairs"))
        return real(p, x, *args, rope=rope, **kwargs)

    monkeypatch.setattr(llama, "multi_head_attention", seen)
    monkeypatch.setattr(llama, "mha_apply", seen)
    batch = {"x": jnp.arange(2 * LENGTH).reshape(2, LENGTH) % CFG.vocab_size}
    logits = model.apply(params, batch, None)
    assert handed["full_core"] == (None, "adjacent")
    assert handed["window_core"][0] is not None
    assert handed["window_core"][1] == "adjacent"
    monkeypatch.undo()
    rotated = llama_lm_model(with_rope).apply(params, batch, None)
    assert float(jnp.max(jnp.abs(rotated - logits))) > 1e-3
    assert dict(model.span_attrs) == {
        "experts_held": 2, "experts_total": 16, "routed_rows_bound": 512,
        "shared_experts": 2, "shared_combine": "average", "window": 5,
        "window_layers": 3, "full_layers": 1, "parallel_block": True,
        "norm": "layer", "full_layer_rope": "none",
        "rope_pairs": "adjacent", "heads_held": "4+2",
        "core_outputs_kept": 0, "window_tiles": 1, "causal_tiles": 1,
        "window_grid_steps": 1, "causal_grid_steps": 1}
    # a decoder without the four says none of them
    plain = llama_lm_model(LlamaConfig.tiny())
    assert not set(dict(plain.span_attrs)) & {
        "parallel_block", "norm", "full_layer_rope", "rope_pairs",
        "heads_held", "shared_combine"}


def test_adjacent_pairs_are_the_halves_of_de_interleaved_weights(decoder):
    """Turning channels ``(2i, 2i + 1)`` of ``x W`` is turning channels
    ``(i, i + Dh / 2)`` of ``x W'``, ``W'`` the columns of ``W`` with
    each head's even channels first and its odd ones after, and the
    scores of a query and a key are the same either way."""
    _, params, x = decoder
    p = params["blocks"][0]["sliding_attn"]
    dh, cos_sin = CFG.head_dim, rope_angles(LENGTH, CFG.head_dim,
                                            CFG.rope_theta)

    def heads(w, n):
        y = (x @ w).reshape(2, LENGTH, n, dh)
        return y.transpose(0, 2, 1, 3)

    def de_interleaved(w, n):
        w = w.reshape(CFG.d_model, n, dh // 2, 2)
        return jnp.concatenate([w[..., 0], w[..., 1]], axis=-1).reshape(
            CFG.d_model, n * dh)

    q = apply_rope(heads(p["wq"], 4), *cos_sin, "adjacent")
    k = apply_rope(heads(p["wk"], 2), *cos_sin, "adjacent")
    q_half = apply_rope(heads(de_interleaved(p["wq"], 4), 4), *cos_sin)
    k_half = apply_rope(heads(de_interleaved(p["wk"], 2), 2), *cos_sin)
    # the same numbers on other channels ...
    np.testing.assert_allclose(
        jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1), q_half,
        rtol=1e-5, atol=1e-6)
    # ... and the same scores
    np.testing.assert_allclose(
        jnp.einsum("bhqd,bhkd->bhqk", q[:, ::2], k),
        jnp.einsum("bhqd,bhkd->bhqk", q_half[:, ::2], k_half),
        rtol=1e-4, atol=1e-5)
    # by halves on the weights as they are it is another rotation
    assert float(jnp.max(jnp.abs(
        apply_rope(heads(p["wq"], 4), *cos_sin) - q))) > 0.1
    # written out a pair at a time
    raw = heads(p["wq"], 4)
    cos, sin = cos_sin
    even, odd = raw[..., 0::2], raw[..., 1::2]
    want = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(raw.shape)
    np.testing.assert_allclose(q, want, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="rope pairs"):
        apply_rope(raw, cos, sin, "triples")
