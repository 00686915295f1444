"""Compressed convolutional attention (models/transformer.py::cca_apply)
and what the decoder needs around it: the mixer against a plain float32
implementation written here (token by token where that is plainer),
causality through both convolutions and the value shift, the
convolutions' slice-and-multiply form against ``lax.conv``, grouped
heads on both branches of the core, the partial rotation, the client
``vmap``, the affine merges, the tied head and the two streams of the
decoder block.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import llama, transformer
from baton_tpu.models.llama import (
    LlamaConfig,
    _joined,
    _merge_init,
    core_outputs_kept,
    decoder_lora_model,
    llama_lm_model,
)
from baton_tpu.models.moe import MoEConfig
from baton_tpu.models.transformer import (
    CCAConfig,
    blocked_causal_core,
    cca_apply,
    cca_convolve,
    cca_core,
    cca_init,
    next_token_loss,
    per_token_cross_entropy,
    rope_angles,
    tied_logits,
)
from conftest import flash_kernels

D = 24
CFG = CCAConfig(n_heads=4, n_kv_heads=2, head_dim=8, block=4, rope_theta=100.0)


def _mixer(cfg=CFG, seed=0, length=10, batch=2):
    p = cca_init(jax.random.key(seed), D, cfg)
    x = jax.random.normal(jax.random.key(seed + 1), (batch, length, D))
    return p, x, rope_angles(length, cfg.rope_dim, cfg.rope_theta)


def plain_cca(p, x, cfg: CCAConfig):
    """The issue's equations in numpy float64, a token, a head and a tap
    at a time."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    b, l, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g, rot = hq // hkv, cfg.rope_dim
    q_lat, k_lat = x @ p["linear_q"], x @ p["linear_k"]
    u = np.concatenate([q_lat, k_lat], -1)               # [b, l, C]
    c = u.shape[-1]

    def at(a, t):  # zeros before the sequence
        return a[:, t] if t >= 0 else np.zeros_like(a[:, 0])

    # y at positions -1 .. l - 1, then z at 0 .. l - 1
    y = {t: p["conv0_w"][0] * at(u, t - 1) + p["conv0_w"][1] * at(u, t)
         + p["conv0_b"] for t in range(-1, l)}
    z = np.zeros((b, l, c))
    for t in range(l):
        for h in range(hq + hkv):
            ch = slice(h * d, (h + 1) * d)
            z[:, t, ch] = (y[t - 1][:, ch] @ p["conv1_w"][0, h]
                           + y[t][:, ch] @ p["conv1_w"][1, h]
                           + p["conv1_b"][ch])
    q_lat = q_lat.reshape(b, l, hq, d)
    k_lat = k_lat.reshape(b, l, hkv, d)
    q = z[..., :hq * d].reshape(b, l, hq, d) \
        + 0.5 * (q_lat + np.repeat(k_lat, g, axis=2))
    k = z[..., hq * d:].reshape(b, l, hkv, d) + 0.5 * (
        q_lat.reshape(b, l, hkv, g, d).mean(3) + k_lat)
    v = np.stack([x @ p["val_proj1"],
                  np.concatenate([np.zeros((b, 1, D)), x[:, :-1]], 1)
                  @ p["val_proj2"]], axis=2)             # [b, l, 2, d]
    q = d ** 0.5 * q / np.linalg.norm(q, axis=-1, keepdims=True)
    k = d ** 0.5 * k / np.linalg.norm(k, axis=-1, keepdims=True) \
        * p["temp"][:, None]

    def turned(a):  # channel i of the first rot with i + rot / 2
        out = a.copy()
        for t in range(l):
            for i in range(rot // 2):
                angle = t * cfg.rope_theta ** (-2.0 * i / rot)
                c_, s_ = np.cos(angle), np.sin(angle)
                a1, a2 = a[:, t, :, i], a[:, t, :, i + rot // 2]
                out[:, t, :, i] = a1 * c_ - a2 * s_
                out[:, t, :, i + rot // 2] = a2 * c_ + a1 * s_
        return out

    q, k = turned(q), turned(k)
    out = np.zeros((b, l, hq, d))
    for h in range(hq):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // g]) / d ** 0.5
        s = np.where(np.tril(np.ones((l, l), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[:, :, h] = np.einsum("bqk,bkd->bqd", w, v[:, :, h // g])
    return out.reshape(b, l, hq * d) @ p["o_proj"]


@pytest.mark.parametrize("cfg", [
    CFG, dataclasses.replace(CFG, rotary_factor=1.0),
    dataclasses.replace(CFG, n_heads=2, block=16),
], ids=["half_rotary", "whole_rotary", "one_head_a_group"])
def test_the_mixer_is_the_plain_one(cfg):
    p, x, rope = _mixer(cfg)
    np.testing.assert_allclose(np.asarray(cca_apply(p, x, cfg, rope)),
                               plain_cca(p, x, cfg), rtol=2e-4, atol=2e-5)


def test_every_gradient_is_the_plain_mixers():
    """The plain mixer again in ``jax.numpy`` with a ``lax.conv``,
    repeated key heads and a whole ``[L, L]`` softmax: the gradients of
    the input and of every leaf."""
    p, x, rope = _mixer()
    cfg = CFG
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g, rot = hq // hkv, cfg.rope_dim
    weight = jax.random.normal(jax.random.key(9), x.shape)

    def plain(p, x):
        b, l, _ = x.shape
        q_lat, k_lat = x @ p["linear_q"], x @ p["linear_k"]
        z = _lax_convolutions(p, jnp.concatenate([q_lat, k_lat], -1), cfg)
        q_lat = q_lat.reshape(b, l, hq, d)
        k_lat = k_lat.reshape(b, l, hkv, d)
        q = z[..., :hq * d].reshape(b, l, hq, d) \
            + 0.5 * (q_lat + jnp.repeat(k_lat, g, axis=2))
        k = z[..., hq * d:].reshape(b, l, hkv, d) + 0.5 * (
            q_lat.reshape(b, l, hkv, g, d).mean(3) + k_lat)
        v = jnp.stack([x @ p["val_proj1"],
                       jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :l]
                       @ p["val_proj2"]], axis=2)
        q = d ** 0.5 * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        k = d ** 0.5 * k / jnp.linalg.norm(k, axis=-1, keepdims=True) \
            * p["temp"][:, None]
        cos, sin = (jnp.concatenate([a, a], -1)[:, None] for a in rope)

        def turned(a):
            half = jnp.concatenate([-a[..., rot // 2:rot], a[..., :rot // 2]],
                                   -1)
            return jnp.concatenate(
                [a[..., :rot] * cos + half * sin, a[..., rot:]], -1)

        q, k = turned(q), jnp.repeat(turned(k), g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                         jnp.repeat(v, g, axis=2))
        return out.reshape(b, l, hq * d) @ p["o_proj"]

    def grads(fn):
        return jax.value_and_grad(
            lambda p, x: jnp.sum(fn(p, x) * weight), argnums=(0, 1))(p, x)

    (want, want_g), (got, got_g) = grads(plain), grads(
        lambda p, x: cca_apply(p, x, cfg, rope))
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for name, (g_, w_) in {**{k: (got_g[0][k], want_g[0][k]) for k in p},
                           "x": (got_g[1], want_g[1])}.items():
        scale = float(jnp.max(jnp.abs(w_)))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(g_ - w_))) <= 2e-4 * scale, name


def _lax_convolutions(p, u, cfg):
    """The two convolutions as ``lax.conv_general_dilated``: zeros
    before the sequence, then both unpadded."""
    d, c = cfg.head_dim, u.shape[-1]
    u = jnp.pad(u, ((0, 0), (cfg.time0 + cfg.time1 - 2, 0), (0, 0)))
    y = jax.lax.conv_general_dilated(
        u, p["conv0_w"][:, None, :], (1,), "VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c,
        precision="highest") + p["conv0_b"]
    kernel = jnp.transpose(p["conv1_w"], (0, 2, 1, 3)).reshape(
        cfg.time1, d, c)
    return jax.lax.conv_general_dilated(
        y, kernel, (1,), "VALID", dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c // d, precision="highest") + p["conv1_b"]


@pytest.mark.parametrize("taps", [(2, 2), (3, 2), (2, 4)])
def test_the_slices_and_products_are_the_convolutions(taps):
    cfg = dataclasses.replace(CFG, time0=taps[0], time1=taps[1])
    p = cca_init(jax.random.key(3), D, cfg)
    u = jax.random.normal(jax.random.key(4), (2, 9, 6 * cfg.head_dim))
    np.testing.assert_allclose(np.asarray(cca_convolve(p, u, cfg)),
                               np.asarray(_lax_convolutions(p, u, cfg)),
                               rtol=1e-5, atol=1e-5)
    # and no convolution is in the program: under a client vmap one
    # would be a grouped convolution over the clients
    text = str(jax.make_jaxpr(jax.vmap(
        lambda u: cca_convolve(p, u, cfg)))(u[None]))
    assert "conv_general_dilated" not in text


def test_an_output_does_not_see_the_tokens_after_it():
    """Through both convolutions, the value shift and the core: change
    the tokens from ``t`` on and every output before ``t`` stays; the
    output at ``t`` does see token ``t - 1`` through three ways (the
    two taps and the shifted values), so changing it moves ``t``."""
    p, x, rope = _mixer(length=12)
    base = cca_apply(p, x, CFG, rope)
    for t in (1, 5, 11):
        later = x.at[:, t:].set(jax.random.normal(jax.random.key(t),
                                                  x[:, t:].shape))
        moved = cca_apply(p, later, CFG, rope)
        np.testing.assert_array_equal(np.asarray(moved[:, :t]),
                                      np.asarray(base[:, :t]))
        assert float(jnp.max(jnp.abs(moved[:, t:] - base[:, t:]))) > 1e-3
    # the shifted values alone: with every other way cut, token t - 1
    # still reaches output t and token t + 1 does not
    cut = dict(p, linear_q=jnp.zeros_like(p["linear_q"]),
               linear_k=jnp.zeros_like(p["linear_k"]),
               val_proj1=jnp.zeros_like(p["val_proj1"]))
    base = cca_apply(cut, x, CFG, rope)
    moved = cca_apply(cut, x.at[:, 4].add(1.0), CFG, rope)
    changed = np.asarray(jnp.max(jnp.abs(moved - base), axis=(0, 2))) > 1e-6
    assert not changed[:5].any() and changed[5]


def test_the_first_token_sees_the_first_convolutions_bias():
    """Two zeros stand before the sequence and neither convolution pads
    again: the second one's older tap reads ``b0`` at the first token,
    not a zero."""
    p = cca_init(jax.random.key(1), D, CFG)
    u = jnp.zeros((1, 3, 6 * CFG.head_dim))
    z = cca_convolve(p, u, CFG).reshape(3, 6, CFG.head_dim)
    b0 = p["conv0_b"].reshape(6, CFG.head_dim)
    want = jnp.einsum("hd,hde->he", b0, p["conv1_w"][0] + p["conv1_w"][1]) \
        + p["conv1_b"].reshape(6, CFG.head_dim)
    np.testing.assert_allclose(np.asarray(z[0]), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(z[2]), np.asarray(z[0]), rtol=1e-6)


def _grouped_operands(lead=()):
    keys = jax.random.split(jax.random.key(2), 4)
    q = jax.random.normal(keys[0], lead + (1, 4, 32, 16))
    k, v = (jax.random.normal(kk, lead + (1, 2, 32, 16)) for kk in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape)


def _repeated(core):
    """``core`` with every key-value head written out once a query head
    of its group: no grouping left."""
    return lambda q, k, v: core(
        q, jnp.repeat(k, q.shape[-3] // k.shape[-3], axis=-3),
        jnp.repeat(v, q.shape[-3] // v.shape[-3], axis=-3), 0.25, 8)


def _through(core, q, k, v, weight):
    return jax.value_and_grad(lambda q, k, v: jnp.sum(core(q, k, v) * weight),
                              argnums=(0, 1, 2))(q, k, v)


def test_grouped_heads_are_the_repeated_heads():
    """The blocked plain core with 4 query heads on 2 key-value heads:
    what repeating each key-value head twice gives, values and all three
    gradients (a key head's is the sum over its group)."""
    q, k, v, weight = _grouped_operands()
    grouped = lambda q, k, v: blocked_causal_core(q, k, v, 0.25, 8)  # noqa: E731
    (want, want_g), (got, got_g) = (
        _through(_repeated(blocked_causal_core), q, k, v, weight),
        _through(grouped, q, k, v, weight))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g_, w_ in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_), rtol=1e-4,
                                   atol=1e-5)


@pytest.fixture
def kernel_core(monkeypatch):
    """``cca_core`` on its kernel branch, as ``tests/test_mla.py`` takes
    ``causal_core`` there: the selector says yes, the kernel's blocks
    are 8 queries by 16 keys, ``flash_attention`` interprets itself."""
    monkeypatch.setattr(transformer, "core_runs_the_kernel",
                        lambda backend, length, block: True)
    monkeypatch.setattr(transformer, "_CORE_KERNEL_BLOCKS", (8, 16))
    return lambda q, k, v: cca_core(q, k, v, 0.25, 8)


def test_the_kernel_branch_takes_grouped_heads(kernel_core):
    """The flash kernels at 4 query heads on 2 key-value heads (the
    cell runs 8 on 2): the blocked plain core's values and gradients,
    ``dk`` and ``dv`` summed over a group of 2."""
    q, k, v, weight = _grouped_operands()
    assert "pallas_call" in str(jax.make_jaxpr(kernel_core)(q, k, v))
    (want, want_g), (got, got_g) = (
        _through(_repeated(blocked_causal_core), q, k, v, weight),
        _through(kernel_core, q, k, v, weight))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g_, w_ in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_), rtol=1e-4,
                                   atol=1e-5)


def test_the_kernel_branch_under_the_wave_programs_nesting(kernel_core):
    """A client ``vmap`` around ``jax.checkpoint`` around the grouped
    core, as the decoder block has it."""
    q, k, v, weight = _grouped_operands(lead=(2,))

    def nested(core):
        def client(q, k, v, weight):
            return _through(jax.checkpoint(core), q, k, v, weight)
        return jax.vmap(client)(q, k, v, weight)

    (want, want_g), (got, got_g) = (
        nested(_repeated(blocked_causal_core)), nested(kernel_core))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    for g_, w_ in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_), rtol=1e-4,
                                   atol=1e-5)


def test_the_core_carries_the_scope_of_its_mixer():
    q, k, v, _ = _grouped_operands()
    text = jax.jit(lambda q, k, v: cca_core(q, k, v, 0.25, 8)).lower(
        q, k, v).as_text(debug_info=True)
    assert "cca_core" in text and "mla_core" not in text
    p, x, rope = _mixer()
    text = jax.jit(lambda x: cca_apply(p, x, CFG, rope)).lower(x).as_text(
        debug_info=True)
    for scope in ("compressed_attention", "compressed_attention/cca_mix",
                  "compressed_attention/cca_core"):
        assert scope in text, scope


def test_under_a_client_vmap_the_mixer_is_each_clients():
    """Frozen leaves carry no client axis, the adapters' stand-ins (here
    ``linear_q`` itself) do."""
    p, x, rope = _mixer(batch=1)
    xs = jnp.stack([x, 2.0 * x, x[:, ::-1]])
    wq = jnp.stack([p["linear_q"], 0.5 * p["linear_q"], p["linear_q"]])
    got = jax.vmap(lambda wq, x: cca_apply(dict(p, linear_q=wq), x, CFG, rope))(
        wq, xs)
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(got[i]),
            np.asarray(cca_apply(dict(p, linear_q=wq[i]), xs[i], CFG, rope)),
            rtol=1e-5, atol=1e-6)


def test_the_values_need_two_key_value_heads():
    with pytest.raises(NotImplementedError):
        CCAConfig(n_heads=8, n_kv_heads=4)
    assert (CCAConfig().latent_q, CCAConfig().latent_kv,
            CCAConfig().rope_dim) == (1024, 256, 64)


# ------------------------------------------------------------ the decoder
def test_a_merge_is_not_an_add():
    m = _merge_init(jax.random.key(0), D)
    x, y = jax.random.normal(jax.random.key(1), (2, 3, D))
    want = m["a_x"] * (x + m["b_x"]) + m["a_y"] * (y + m["b_y"])
    got = _joined({"merge_attn": m}, "merge_attn", x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert float(jnp.max(jnp.abs(got - (x + y)))) > 0.1
    np.testing.assert_array_equal(
        np.asarray(_joined({}, "merge_attn", x, y)), np.asarray(x + y))
    # in float32 whatever the stream's dtype, and back in it
    half = _joined({"merge_attn": m}, "merge_attn", x.astype(jnp.bfloat16),
                   y.astype(jnp.bfloat16))
    assert half.dtype == jnp.bfloat16


def test_the_tied_head_is_the_table_transposed(monkeypatch):
    x = jax.random.normal(jax.random.key(0), (2, 6, D))
    table = jax.random.normal(jax.random.key(1), (40, D))
    y = jax.random.randint(jax.random.key(2), (2, 6), 0, 40)
    np.testing.assert_allclose(np.asarray(tied_logits(x, table)),
                               np.asarray(x @ table.T), rtol=1e-5, atol=1e-5)
    want = per_token_cross_entropy(x @ table.T, y)
    np.testing.assert_allclose(
        np.asarray(next_token_loss(x, table, y, tied=True)), np.asarray(want),
        rtol=1e-5)
    # in blocks too, and no [D, V] copy of the table is made
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 2 ** 22)
    big = jax.random.normal(jax.random.key(3), (4096, D))
    xs = jax.random.normal(jax.random.key(4), (1, 4096, D))
    ys = jax.random.randint(jax.random.key(5), (1, 4096), 0, 4096)
    jaxpr = jax.make_jaxpr(lambda x, w: next_token_loss(x, w, ys, tied=True))(
        xs, big)
    assert "scan" in str(jaxpr)
    assert f"[{D},4096]" not in str(jaxpr)
    np.testing.assert_allclose(
        np.asarray(next_token_loss(xs, big, ys, tied=True)),
        np.asarray(per_token_cross_entropy(xs @ big.T, ys)), rtol=1e-4,
        atol=1e-4)


DECODER = LlamaConfig(
    vocab_size=48, max_len=32, d_model=D, n_layers=3, n_heads=4, n_kv_heads=2,
    d_ff=16, rope_theta=100.0, layer_types=("compressed_attention",) * 3,
    cca=CFG, moe=MoEConfig(n_experts=4, top_k=1, d_ff=16, router_hidden=8,
                           skip=True, router_bias_range=0.1),
    residual_merge=True, tie_embeddings=True, embed_std=1.0, norm_eps=1e-5)


def _batch(seed=0, length=12):
    ids = jax.random.randint(jax.random.key(seed), (2, length + 1), 0, 48)
    return {"x": ids[:, :-1], "y": ids[:, 1:]}


def test_the_decoders_tree_is_the_kind_it_names():
    shapes = jax.eval_shape(llama_lm_model(DECODER).init, jax.random.key(0))
    assert "lm_head" not in shapes and shapes["tok_emb"].shape == (48, D)
    for blk in shapes["blocks"]:
        assert set(blk) == {"norm_attn", "cca", "merge_attn", "merge_mlp",
                            "norm_mlp", "mlp"}
        assert blk["cca"]["o_proj"].shape == (32, D)
        assert blk["cca"]["conv1_w"].shape == (2, 6, 8, 8)
        assert blk["mlp"]["router"]["w3"].shape == (8, 5)
        assert blk["mlp"]["router_bias"].shape == (5,)
        assert blk["mlp"]["w_up"].shape == (4, D, 16)
    lora = jax.eval_shape(decoder_lora_model(DECODER, rank=2).init,
                          jax.random.key(0))
    assert {k.rsplit("/", 1)[-1] for k in lora["lora"]} == {
        "linear_q", "linear_k", "val_proj1", "val_proj2", "o_proj"}
    assert len(lora["lora"]) == 15
    half = jax.eval_shape(
        llama_lm_model(DECODER, param_dtype=jnp.bfloat16).init,
        jax.random.key(0))["blocks"][0]
    assert half["cca"]["linear_q"].dtype == half["cca"]["conv1_w"].dtype \
        == half["mlp"]["w_up"].dtype == jnp.bfloat16
    assert {a.dtype for a in jax.tree_util.tree_leaves(
        (half["mlp"]["router"], half["mlp"]["router_bias"],
         half["merge_attn"], half["cca"]["temp"], half["cca"]["conv0_b"]))
    } == {jnp.dtype(jnp.float32)}


def test_the_routers_state_runs_down_the_depth():
    """Layer 0 is handed zeros and is a block like the others; cut the
    state between the layers (``state_scale`` 0) and the loss moves;
    under ``remat`` the two streams go through the checkpoint and the
    gradients are the unrematted model's."""
    model = llama_lm_model(DECODER)
    params = model.init(jax.random.key(0))
    batch = _batch()
    loss = float(jnp.sum(model.per_example_loss(params, batch, None)))

    def without_state(p):
        router = dict(p["mlp"]["router"],
                      state_scale=jnp.zeros_like(
                          p["mlp"]["router"]["state_scale"]))
        return dict(p, mlp=dict(p["mlp"], router=router))

    cut = dict(params, blocks=[without_state(b) for b in params["blocks"]])
    assert abs(float(jnp.sum(model.per_example_loss(cut, batch, None)))
               - loss) > 1e-4
    first = dict(params, blocks=[without_state(params["blocks"][0])]
                 + params["blocks"][1:])
    assert float(jnp.sum(model.per_example_loss(first, batch, None))) \
        == pytest.approx(loss, rel=1e-6)  # zeros times any scale

    def grads(m):
        return jax.grad(lambda p: jnp.sum(m.per_example_loss(p, batch, None)))(
            params)

    for g_, w_ in zip(jax.tree_util.tree_leaves(
            grads(llama_lm_model(DECODER, remat=True))),
            jax.tree_util.tree_leaves(grads(model))):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_), rtol=1e-4,
                                   atol=1e-6)
    traced = jax.make_jaxpr(
        lambda p: llama_lm_model(DECODER, remat=True).per_example_loss(
            p, batch, None))(params)
    # one trace for the three layers: three checkpoints of one jaxpr
    assert str(traced).count("checkpoint") + str(traced).count("remat") >= 3


def test_a_dense_layer_among_stateful_routers_is_refused():
    with pytest.raises(NotImplementedError):
        llama_lm_model(dataclasses.replace(DECODER, first_dense_layers=1))


def test_a_decoder_of_compressed_attention_trains():
    model = decoder_lora_model(DECODER, compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=2, b_std=0.02)
    params = model.init(jax.random.key(0))
    batch = _batch(1)
    assert dict(model.span_attrs) == {
        "experts_held": 4, "experts_total": 4, "routed_rows_bound": 1024,
        "router_outputs": 5, "skip_expert": 4, "latent_q": 32,
        "latent_kv": 16, "conv_taps": "2+2"}

    def loss(lora):
        return jnp.mean(model.per_example_loss(
            {"base": params["base"], "lora": lora}, batch, None))

    lora, first = params["lora"], float(loss(params["lora"]))
    step = jax.jit(lambda lo: jax.tree_util.tree_map(
        lambda a, g: a - 0.05 * g, lo, jax.grad(loss)(lo)))
    for _ in range(20):
        lora = step(lora)
    assert float(loss(lora)) < first
    logits = model.apply(params, batch, None)
    assert logits.shape == (2, 12, 48) and logits.dtype == jnp.float32


# ---------------------------------------------------------------------------
# the block's checkpoint keeps the kernel's output and log-sum-exp


def test_a_block_of_two_streams_runs_the_forward_kernel_once(kernel_core):
    """A block of ``DECODER`` (the tokens' stream and the routers')
    whose core is the kernel, two clients under ``vmap``: one forward
    kernel under the model's checkpoint, two under a bare one, and the
    same gradients bit for bit."""
    length = 32
    p = llama._block_init(jax.random.key(3), DECODER, DECODER.kind_of(0), True)
    x = jax.random.normal(jax.random.key(4), (2, 1, length, D))
    rope = rope_angles(length, CFG.rope_dim, CFG.rope_theta)

    def grad(block):
        def client(p, x):
            r = jnp.zeros(x.shape[:2] + (DECODER.moe.router_hidden,))
            y, r = block(p, x, r, DECODER, rope, None)
            return jnp.sum(y ** 2) + jnp.sum(r ** 2)

        return jax.grad(lambda p, x: jnp.sum(
            jax.vmap(client, in_axes=(None, 0))(p, x)), argnums=(0, 1))

    kept = grad(llama._checkpointed_block())
    bare = grad(jax.checkpoint(llama._block_apply, static_argnums=(3, 5)))
    assert flash_kernels(kept, p, x) == (1, 1)
    assert flash_kernels(bare, p, x) == (2, 1)
    for g, w in zip(*(jax.tree_util.tree_leaves(fn(p, x))
                      for fn in (kept, bare))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("backend,length,kept", [
    ("tpu", 8192, 3),    # zaya1_c4_l8192's sequences: every block
    ("cpu", 8192, 0),
    ("tpu", 1024, 0),    # one of the kernel's blocks
    ("tpu", 8200, 0),    # a length the blocks do not divide
])
def test_the_blocks_that_keep_a_cores_outputs(backend, length, kept):
    assert core_outputs_kept(DECODER, backend, 1, length) == kept
    assert (kept > 0) is transformer.core_is_the_kernel(backend, length)


@pytest.mark.parametrize("remat", [True, False])
def test_the_model_says_what_its_last_trace_keeps(remat, kernel_core):
    """``span_attrs`` gains ``core_outputs_kept`` where the model is
    traced and meets a length: with the selector forced, every block of
    a ``remat`` model, none of one that holds no checkpoint; and a
    ``remat`` model says how many blocks keep their products, none on a
    device without a plan budget (``llama.blocks_kept``)."""
    model = llama_lm_model(DECODER, remat=remat)
    static = dict(model.span_attrs)
    assert "core_outputs_kept" not in static
    params = jax.eval_shape(model.init, jax.random.key(0))
    jax.eval_shape(model.per_example_loss, params, _batch(1, length=32), None)
    assert dict(model.span_attrs) == {
        **static, "core_outputs_kept": 3 if remat else 0,
        **({"blocks_kept": 0} if remat else {})}
    assert model.span_attrs == tuple(static.items())
    assert hash(model.span_attrs) == hash(tuple(static.items()))
