"""Latent attention whose queries choose their keys
(``models/transformer.py``: ``MLAConfig.q_rank`` and ``.indexer``,
``index_scores``, ``select_keys``, ``chosen_keys``, the choice in
``causal_core`` and in the three kernels of ``ops/flash_attention.py``,
the mixer a client at a time).

Oracles: the layer written the plain way in this file (numpy float64,
whole heads, ``[L, L]`` scores, a stable sort for the choice); the
blocked plain core for the kernels; the mixer PR 34 left, copied here,
for a configuration that names neither a query latent nor an indexer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import transformer
from baton_tpu.models.llama import (
    LlamaConfig,
    decoder_lora_model,
    llama_lm_model,
    projection_lora_target,
)
from baton_tpu.models.lora import lora_trainable
from baton_tpu.models.transformer import (
    IndexerConfig,
    MLAConfig,
    apply_rope,
    blocked_causal_core,
    causal_core,
    chosen_keys,
    index_scores,
    mla_apply,
    mla_init,
    mla_rope_angles,
    rms_normalize,
    select_keys,
)
from baton_tpu.ops.flash_attention import flash_attention

HEADS, D = 4, 64


def _cfg(topk=6, block=8, **kw):
    return MLAConfig(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=24, q_rank=24,
                     rope_theta=1e6, norm_eps=1e-5, block=block,
                     indexer=IndexerConfig(heads=2, dim=16, topk=topk,
                                           rope_dim=8), **kw)


def _params(cfg, seed=0):
    p = mla_init(jax.random.key(seed), D, HEADS, cfg)
    # scales and a bias that differ, so that their place matters
    p["q_a_norm"]["scale"] = 1 + 0.05 * jnp.arange(24.0)
    p["kv_norm"]["scale"] = 1 - 0.01 * jnp.arange(32.0)
    if "indexer" in p:
        p["indexer"]["k_norm"] = {"scale": 1 + 0.1 * jnp.arange(16.0),
                                  "bias": 0.05 * jnp.arange(16.0) - 0.3}
    return p


def _plain_layer(p, x, cfg):
    """numpy float64, whole heads, the choice by a stable sort.
    ``(y, chosen [B, L, L])``."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    b, l, _ = x.shape
    ix = cfg.indexer

    def rms(y, scale):
        return y / np.sqrt(np.mean(y * y, -1, keepdims=True) + cfg.norm_eps) \
            * scale

    def heads(y, n):
        return y.reshape(b, l, n, -1).transpose(0, 2, 1, 3)

    angle = np.arange(l)[:, None] * cfg.rope_theta ** (
        -np.arange(0, cfg.rope_dim, 2) / cfg.rope_dim)
    cos, sin = np.cos(angle), np.sin(angle)

    def turn(y, start):
        half = start + cfg.rope_dim // 2
        y1, y2 = y[..., start:half], y[..., half:start + cfg.rope_dim]
        return np.concatenate(
            [y[..., :start], y1 * cos - y2 * sin, y2 * cos + y1 * sin,
             y[..., start + cfg.rope_dim:]], -1)

    c_q = rms(x @ p["wq_a"], p["q_a_norm"]["scale"])
    q = turn(heads(c_q @ p["wq_b"], HEADS), cfg.nope_dim)
    c = x @ p["wkv_a"]
    kv = heads(rms(c[..., :cfg.kv_rank], p["kv_norm"]["scale"]) @ p["wkv_b"],
               HEADS)
    shared = np.broadcast_to(c[:, None, :, cfg.kv_rank:],
                             (b, HEADS, l, cfg.rope_dim))
    k = turn(np.concatenate([kv[..., :cfg.nope_dim], shared], -1),
             cfg.nope_dim)
    v = kv[..., cfg.nope_dim:]
    q_i = turn(heads(c_q @ p["indexer"]["wq"], ix.heads), 0)
    k_i = x @ p["indexer"]["wk"]
    mean = k_i.mean(-1, keepdims=True)
    k_i = (k_i - mean) / np.sqrt(k_i.var(-1, keepdims=True) + cfg.norm_eps) \
        * p["indexer"]["k_norm"]["scale"] + p["indexer"]["k_norm"]["bias"]
    k_i = turn(k_i, 0)
    w = (x @ p["indexer"]["w_heads"]).transpose(0, 2, 1) \
        * (ix.heads ** -0.5 * ix.dim ** -0.5)
    index = np.einsum("bhq,bhqk->bqk", w, np.maximum(
        np.einsum("bhqd,bkd->bhqk", q_i, k_i), 0))
    chosen = np.zeros((b, l, l), bool)
    for n in range(b):
        for t in range(l):
            best = np.argsort(-index[n, t, :t + 1], kind="stable")[:ix.topk]
            chosen[n, t, best] = True
    s = q @ k.transpose(0, 1, 3, 2) * cfg.qk_dim ** -0.5
    s = np.where(chosen[:, None], s, -np.inf)
    weights = np.exp(s - s.max(-1, keepdims=True))
    out = (weights / weights.sum(-1, keepdims=True)) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, l, -1) @ p["wo"], chosen


def _causal_scores(nprng, shape, steps=None):
    """Random scores, ``-inf`` above the diagonal; with ``steps`` rounded
    to so few values that many are equal."""
    s = nprng.normal(size=shape)
    if steps:
        s = np.round(s * steps) / steps
    l = shape[-1]
    return jnp.asarray(np.where(np.tril(np.ones((l, l), bool)), s, -np.inf),
                       jnp.float32)


# ------------------------------------------------------------ the choice
@pytest.mark.parametrize("topk,steps", [(6, None), (6, 2), (1, 2), (40, None)],
                         ids=["distinct", "ties", "one-key", "topk-past-L"])
def test_a_query_chooses_exactly_its_topk_best_keys(topk, steps, nprng):
    """``min(t + 1, topk)`` keys a query, the ones a stable descending
    sort puts first: equal scores go to the lower index (with scores in
    steps of a half nearly every query has equal ones at its
    threshold, -0.0 and 0.0 among them)."""
    l = 32
    scores = _causal_scores(nprng, (2, l, l), steps)
    tau, cut = select_keys(scores, topk)
    chosen = np.asarray(chosen_keys(scores, tau, cut))
    assert chosen.dtype == np.int8
    assert (chosen.sum(-1) == np.minimum(np.arange(l) + 1, topk)).all()
    raw = np.asarray(scores, np.float64)
    for n in range(2):
        for t in range(l):
            best = np.argsort(-raw[n, t, :t + 1], kind="stable")[:topk]
            assert set(np.flatnonzero(chosen[n, t])) == set(best), (n, t)
    # a query whose prefix holds no more than topk keys has no threshold
    assert (np.asarray(tau)[:, :min(topk, l)] == -np.inf).all()


def test_the_choice_is_what_top_k_gives(nprng):
    """``jax.lax.top_k`` over a row (what the benchmark's reference
    uses) chooses the same keys where no score is a zero."""
    scores = _causal_scores(nprng, (1, 48, 48), steps=None)
    chosen = np.asarray(chosen_keys(scores, *select_keys(scores, 10)))[0]
    value, at = jax.lax.top_k(scores, 10)
    for t in range(48):
        want = {int(a) for a, v in zip(at[0, t], value[0, t]) if v > -np.inf}
        assert set(np.flatnonzero(chosen[t])) == want


# ---------------------------------------------------- the layer, forward
@pytest.mark.parametrize("block", [4, 16], ids=["blocked", "whole"])
def test_the_layer_is_the_plain_layer(block, nprng):
    """12 tokens, 5 keys a query: index scores in blocks of 4 queries
    and whole, the choice, the core over the chosen keys."""
    cfg = _cfg(topk=5, block=block)
    p = _params(cfg)
    assert p["wq_a"].shape == (D, 24) and p["wq_b"].shape == (24, HEADS * 24)
    assert "wq" not in p
    assert p["indexer"]["wq"].shape == (24, 2 * 16)
    assert p["indexer"]["wk"].shape == (D, 16)
    assert p["indexer"]["w_heads"].shape == (D, 2)
    x = jnp.asarray(nprng.normal(size=(2, 12, D)), jnp.float32)
    rope = mla_rope_angles(12, cfg)
    want, want_chosen = _plain_layer(p, x, cfg)
    got = mla_apply(p, x, HEADS, cfg, rope)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    q_in = rms_normalize(x @ p["wq_a"], p["q_a_norm"]["scale"], cfg.norm_eps)
    scores = index_scores(p["indexer"], x, q_in, cfg, rope)
    assert scores.shape == (2, 12, 12) and scores.dtype == jnp.float32
    assert (np.asarray(scores)[:, np.triu_indices(12, 1)[0],
                               np.triu_indices(12, 1)[1]] == -np.inf).all()
    chosen = chosen_keys(scores, *select_keys(scores, 5))
    np.testing.assert_array_equal(np.asarray(chosen) != 0, want_chosen)


def test_the_norm_before_the_mixer_may_be_applied_inside(nprng):
    cfg = _cfg(topk=5)
    p = _params(cfg)
    x = jnp.asarray(nprng.normal(size=(1, 12, D)), jnp.float32)
    rope = mla_rope_angles(12, cfg)
    norm = {"scale": 1 + 0.02 * jnp.arange(float(D))}
    inside = mla_apply(p, x, HEADS, cfg, rope, pre_norm=norm)
    outside = mla_apply(p, rms_normalize(x, norm["scale"], cfg.norm_eps),
                        HEADS, cfg, rope)
    np.testing.assert_allclose(np.asarray(inside), np.asarray(outside),
                               rtol=1e-6, atol=1e-6)


def test_with_topk_past_the_length_the_layer_is_the_one_without_an_indexer(
        nprng):
    """Bit for bit, forward and gradient: no query chooses, so no index
    score is computed and the mixer is the one it was."""
    with_ix, without = _cfg(topk=12), MLAConfig(
        kv_rank=32, nope_dim=16, rope_dim=8, v_dim=24, q_rank=24,
        rope_theta=1e6, norm_eps=1e-5, block=8)
    assert not with_ix.selects(12) and with_ix.selects(13)
    assert not without.selects(10 ** 6)
    p = _params(with_ix)
    bare = {k: v for k, v in p.items() if k != "indexer"}
    assert jax.tree_util.tree_structure(bare) == jax.tree_util.tree_structure(
        mla_init(jax.random.key(0), D, HEADS, without))
    x = jnp.asarray(nprng.normal(size=(2, 12, D)), jnp.float32)
    rope = mla_rope_angles(12, with_ix)

    def through(params, cfg):
        return jax.value_and_grad(lambda pp, xx: jnp.sum(
            mla_apply(pp, xx, HEADS, cfg, rope) ** 2), argnums=(0, 1))(
                params, x)

    (got, (got_p, got_x)), (want, (want_p, want_x)) = \
        through(p, with_ix), through(bare, without)
    assert float(got) == float(want)
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want_x))
    for name in bare:
        jax.tree_util.tree_map(np.testing.assert_array_equal, got_p[name],
                               want_p[name])
    assert "indexer" not in str(jax.make_jaxpr(
        lambda pp: mla_apply(pp, x, HEADS, with_ix, rope))(p).jaxpr.eqns[-1])


# ------------------------------------- a configuration that names neither
def _mla_of_pr_34(p, x, n_heads, cfg, rope):
    """``mla_apply`` as PR 34 left it, word for word."""
    b, l, _ = x.shape
    cos, sin = rope

    def heads(y, width):
        return y.reshape(b, l, n_heads, width).transpose(0, 2, 1, 3)

    q = heads(x @ p["wq"].astype(x.dtype), cfg.qk_dim)
    c = x @ p["wkv_a"].astype(x.dtype)
    latent = rms_normalize(c[..., :cfg.kv_rank], p["kv_norm"]["scale"])
    kv = heads(latent @ p["wkv_b"].astype(x.dtype), cfg.nope_dim + cfg.v_dim)
    k_rope = jnp.broadcast_to(c[:, None, :, cfg.kv_rank:],
                              (b, n_heads, l, cfg.rope_dim))
    k = jnp.concatenate([kv[..., :cfg.nope_dim], k_rope], axis=-1)
    v = kv[..., cfg.nope_dim:]
    if cfg.qk_norm:
        q = rms_normalize(q, p["q_norm"]["scale"])
        k = rms_normalize(k, p["k_norm"]["scale"])

    def rotated(y):
        return jnp.concatenate(
            [y[..., :cfg.nope_dim],
             apply_rope(y[..., cfg.nope_dim:], cos, sin)], axis=-1)

    out = causal_core(rotated(q), rotated(k), v, cfg.softmax_scale, cfg.block)
    out = out.transpose(0, 2, 1, 3).reshape(b, l, n_heads * cfg.v_dim)
    return out @ p["wo"].astype(x.dtype)


@pytest.mark.parametrize("qk_norm", [True, False])
def test_without_a_query_latent_or_an_indexer_the_mixer_is_todays(qk_norm):
    """The parameter tree (names, shapes and the draws themselves) of
    ``sarvam_105b``'s mixer, and its values, forward and gradient,
    against the mixer PR 34 left: since PR 47 the program is another
    (the weights are cut and not the activations, the rotary parts
    turned alone, the shared key once), the function is the same to a
    float32 rounding."""
    yarn = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "deepseek_yarn"}
    cfg = MLAConfig(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=12,
                    qk_norm=qk_norm, rope_scaling=yarn, block=4)
    assert cfg.q_rank is None and cfg.indexer is None
    p = mla_init(jax.random.key(3), D, HEADS, cfg)
    kq, ka, kb, ko = jax.random.split(jax.random.key(3), 4)
    assert set(p) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"} | (
        {"q_norm", "k_norm"} if qk_norm else set())
    np.testing.assert_array_equal(
        np.asarray(p["wq"]),
        np.asarray(transformer.dense_init(kq, D, HEADS * 24)))
    np.testing.assert_array_equal(
        np.asarray(p["wo"]),
        np.asarray(transformer.dense_init(ko, HEADS * 12, D)))
    if qk_norm:  # scales that differ, so that their place matters
        p["q_norm"]["scale"] = 1 + 0.1 * jnp.arange(24.0)
        p["k_norm"]["scale"] = 1 - 0.02 * jnp.arange(24.0)
    x, weight = jax.random.normal(jax.random.key(5), (2, 2, 12, D))
    rope = mla_rope_angles(12, cfg)

    def through(fn):
        return jax.value_and_grad(
            lambda pp, xx: jnp.sum(fn(pp, xx, HEADS, cfg, rope) * weight),
            argnums=(0, 1))(p, x)

    (got, got_g), (want, want_g) = through(mla_apply), through(_mla_of_pr_34)
    # (a sum of 1,536 terms of either sign)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    assert jax.tree_util.tree_structure(got_g) == \
        jax.tree_util.tree_structure(want_g)
    for g, w in zip(*map(jax.tree_util.tree_leaves, (got_g, want_g))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


# ------------------------------------------------------------ gradients
def test_no_cotangent_reaches_the_indexer_and_none_of_it_trains(nprng):
    cfg = _cfg(topk=5)
    p = _params(cfg)
    x = jnp.asarray(nprng.normal(size=(2, 12, D)), jnp.float32)
    rope = mla_rope_angles(12, cfg)
    weight = jnp.asarray(nprng.normal(size=(2, 12, D)), jnp.float32)
    grads = jax.grad(lambda pp: jnp.sum(
        mla_apply(pp, x, HEADS, cfg, rope) * weight))(p)
    for leaf in jax.tree_util.tree_leaves(grads["indexer"]):
        assert not np.asarray(leaf).any()
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"):
        assert np.asarray(grads[name]).any(), name
    # no adapter on an indexer leaf, and the partition trains adapters
    for name in ("wq", "wk", "w_heads"):
        assert not projection_lora_target(f"blocks/0/mla/indexer/{name}",
                                          p["indexer"][name])
    assert projection_lora_target("blocks/0/mla/wq_a", p["wq_a"])
    assert projection_lora_target("blocks/0/mla/wq_b", p["wq_b"])
    model = decoder_lora_model(
        LlamaConfig.tiny(mla=cfg, embed_std=1.0), compute_dtype=jnp.float32,
        param_dtype=jnp.float32, rank=2)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert not [k for k in shapes["lora"] if "indexer" in k]
    assert {k.rsplit("/", 1)[-1] for k in shapes["lora"] if "/mla/" in k} \
        == {"wq_a", "wq_b", "wkv_a", "wkv_b", "wo"}
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    from baton_tpu.core.partition import path_str

    assert not [path_str(k) for k, leaf in paths
                if "indexer" in path_str(k)
                and lora_trainable(path_str(k), leaf)]


def test_the_adapters_gradient_is_the_plain_layers(nprng):
    """The gradient through the chosen keys alone: against finite
    differences of the plain layer with its choice held (a step small
    enough moves no choice; where it would, the plain layer's loss
    jumps and the check fails)."""
    cfg = _cfg(topk=5)
    p = _params(cfg)
    x = jnp.asarray(nprng.normal(size=(1, 12, D)), jnp.float32)
    rope = mla_rope_angles(12, cfg)
    weight = nprng.normal(size=(1, 12, D))
    grads = jax.grad(lambda pp: jnp.sum(
        mla_apply(pp, x, HEADS, cfg, rope) * weight))(p)

    def plain_loss(params):
        return float(np.sum(_plain_layer(params, x, cfg)[0] * weight))

    for name, at in (("wq_b", (3, 5)), ("wkv_b", (7, 2)), ("wo", (11, 9)),
                     ("wkv_a", (20, 33))):
        step = 1e-4
        up = dict(p, **{name: p[name].at[at].add(step)})
        down = dict(p, **{name: p[name].at[at].add(-step)})
        want = (plain_loss(up) - plain_loss(down)) / (2 * step)
        assert float(grads[name][at]) == pytest.approx(want, rel=2e-2,
                                                       abs=2e-4), name


# ------------------------------------------------- the core and the kernels
def _core_operands(nprng, dk, dv, l=32, lead=()):
    q, k = (jnp.asarray(nprng.normal(size=lead + (1, 3, l, dk)), jnp.float32)
            for _ in range(2))
    v, weight = (jnp.asarray(nprng.normal(size=lead + (1, 3, l, dv)),
                             jnp.float32) for _ in range(2))
    scores = _causal_scores(nprng, lead + (1, l, l))
    flat = scores.reshape((-1, l, l))
    chosen = chosen_keys(flat, *select_keys(flat, 7)).reshape(
        lead + (1, l, l))
    return q, k, v, weight, chosen


def test_the_blocked_core_over_chosen_keys_is_the_masked_softmax(nprng):
    q, k, v, _, chosen = _core_operands(nprng, 24, 24)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * 0.3
    s = np.where(np.asarray(chosen)[:, None] != 0, s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = (w / w.sum(-1, keepdims=True)) @ np.asarray(v, np.float64)
    for block in (8, 64):
        got = blocked_causal_core(q, k, v, 0.3, block, chosen)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("dk,dv", [(32, 32), (24, 16)],
                         ids=["256/256", "192/128"])
@pytest.mark.parametrize("backward_form", ["one kernel", "two passes"])
def test_the_kernels_with_a_choice_are_the_blocked_core(dk, dv, backward_form,
                                                        nprng, monkeypatch):
    """Forward, dq, dk and dv of the three Pallas kernels (interpreted)
    with values as wide as the keys and narrower, in both forms of the
    backward, against the plain core over the same chosen keys."""
    from baton_tpu.ops import flash_attention as fa

    if backward_form == "two passes":
        monkeypatch.setattr(fa, "_DQ_RESIDENT_BYTES", 0)
    q, k, v, weight, chosen = _core_operands(nprng, dk, dv)

    def through(core):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v) * weight),
            argnums=(0, 1, 2))(q, k, v)

    (want, want_g) = through(
        lambda q, k, v: blocked_causal_core(q, k, v, 0.3, 8, chosen))
    (got, got_g) = through(lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.3, block_q=8, block_k=16,
        chosen=chosen, interpret=True))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    # and the choice matters: the dense causal core differs
    dense = flash_attention(q, k, v, causal=True, scale=0.3, block_q=8,
                            block_k=16, interpret=True)
    picked = flash_attention(q, k, v, causal=True, scale=0.3, block_q=8,
                             block_k=16, chosen=chosen, interpret=True)
    assert float(jnp.max(jnp.abs(dense - picked))) > 1e-2


def test_without_a_choice_the_kernels_take_the_operands_they_took(nprng):
    q, k, v, _, chosen = _core_operands(nprng, 24, 16)

    def calls(chosen):
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=8, block_k=16, chosen=chosen,
            interpret=True))))(q))
        return text.count("pallas_call"), text.count("i8[")

    n_calls, int8 = calls(None)
    assert n_calls == 2 and int8 == 0       # forward, one backward kernel
    assert calls(chosen)[0] == 2 and calls(chosen)[1] > 0


def test_the_kernel_branch_of_the_core_takes_the_choice(nprng, monkeypatch):
    monkeypatch.setattr(transformer, "core_runs_the_kernel",
                        lambda backend, length, block: True)
    monkeypatch.setattr(transformer, "_CORE_KERNEL_BLOCKS", (8, 16))
    q, k, v, _, chosen = _core_operands(nprng, 32, 32)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda q: causal_core(q, k, v, 0.3, 8, chosen))(q))
    np.testing.assert_allclose(
        np.asarray(causal_core(q, k, v, 0.3, 8, chosen)),
        np.asarray(blocked_causal_core(q, k, v, 0.3, 8, chosen)),
        rtol=1e-5, atol=1e-5)


# --------------------------------------------------- a client at a time
def _decoder(cfg_mla, **kw):
    return LlamaConfig.tiny(mla=cfg_mla, embed_std=1.0, n_layers=2,
                            norm_eps=1e-5, **kw)


def test_the_mixer_a_client_at_a_time_is_the_vmap(nprng):
    """Under a client ``vmap`` the choosing mixer is a ``lax.map`` over
    the clients (a ``while`` in the program, one client's arrays in
    it); its values and the adapters' gradients are those of each
    client alone."""
    cfg = _decoder(_cfg(topk=6))
    model = decoder_lora_model(cfg, compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=4, alpha=8,
                               b_std=0.02)
    params = model.init(jax.random.key(0))
    clients = 3
    toks = nprng.integers(0, cfg.vocab_size, size=(clients, 1, 17))
    batch = {"x": jnp.asarray(toks[..., :-1], jnp.int32),
             "y": jnp.asarray(toks[..., 1:], jnp.int32)}
    lora = jax.tree_util.tree_map(
        lambda a: jnp.stack([a * (1 + 0.1 * i) for i in range(clients)]),
        params["lora"])

    def loss(lo, b):
        return jnp.mean(model.per_example_loss(
            {"base": params["base"], "lora": lo}, b, None))

    step = jax.vmap(jax.value_and_grad(loss))
    text = str(jax.make_jaxpr(step)(lora, batch))
    assert "while" in text or "scan" in text
    # no array of the mixer holds clients x heads x tokens
    assert f"f32[{clients},4,16,24]" not in text
    got_loss, got = jax.jit(step)(lora, batch)
    for i in range(clients):
        one = jax.tree_util.tree_map(lambda a: a[i], (lora, batch))
        want_loss, want = jax.value_and_grad(loss)(*one)
        assert float(got_loss[i]) == pytest.approx(float(want_loss), rel=1e-6)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g[i]), np.asarray(w),
                                       rtol=1e-4, atol=1e-6)


def test_a_sequence_no_longer_than_topk_keeps_the_blocks_checkpoint():
    """Where no query chooses, the decoder block is under ``remat``
    whole, as it was; where one does, the mixer stands outside it (it
    keeps its own inputs and the choice) and the feed-forward is under
    its own."""
    cfg = _decoder(_cfg(topk=8))
    model = llama_lm_model(cfg, remat=True)
    params = jax.eval_shape(model.init, jax.random.key(0))

    def program(length):
        batch = {"x": jnp.zeros((1, length), jnp.int32),
                 "y": jnp.zeros((1, length), jnp.int32)}
        return str(jax.make_jaxpr(jax.grad(lambda p: jnp.mean(
            model.per_example_loss(p, batch, None))))(params))

    short, long = program(8), program(16)
    assert "custom_vmap_call" not in short and "custom_vmap_call" in long
    assert "i8[1,16,16]" in long and "i8[" not in short


@pytest.mark.parametrize("length,kept", [(2048, 2), (4096, 0)])
def test_a_mixer_outside_the_blocks_checkpoint_keeps_nothing_there(
        length, kept):
    """Where queries choose (2,048 of more keys) the mixer stands
    outside the block's checkpoint and keeps its own residuals; where
    none does the block is whole and keeps its core's two outputs."""
    from baton_tpu.models.llama import core_outputs_kept

    assert core_outputs_kept(_decoder(_cfg(topk=2048)), "tpu", 1,
                             length) == kept


def test_a_decoder_whose_queries_choose_trains(nprng):
    from baton_tpu.core.training import make_local_trainer

    cfg = _decoder(_cfg(topk=6), max_len=16)
    model = llama_lm_model(cfg, remat=True)
    params = model.init(jax.random.key(0))
    assert all("indexer" in b["mla"] for b in params["blocks"])
    trainer = make_local_trainer(model, batch_size=2, learning_rate=5e-2)
    toks = nprng.integers(0, cfg.vocab_size, size=(2, 17))
    data = {"x": jnp.asarray(toks[:, :-1], jnp.int32),
            "y": jnp.asarray(toks[:, 1:], jnp.int32)}
    trained, _, hist = trainer.train(params, data, jnp.asarray(2),
                                     jax.random.key(1), 4)
    assert float(hist[-1]) < float(hist[0])
    for before, after in zip(params["blocks"], trained["blocks"]):
        for a, b in zip(jax.tree_util.tree_leaves(before["mla"]["indexer"]),
                        jax.tree_util.tree_leaves(after["mla"]["indexer"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_indexers_rotary_width_is_the_keys():
    with pytest.raises(NotImplementedError):
        MLAConfig(rope_dim=64, indexer=IndexerConfig(rope_dim=32))
    assert hash(_cfg()) == hash(_cfg())
