"""Latent attention (models/transformer.py::mla_apply): keys and values
from a normalised low-rank latent, one rotary key shared by all heads,
queries and keys wider than values, ``deepseek_yarn`` frequencies, and
a causal core computed in blocks of queries.

Oracle: the mixer written the plain way in this file, whole heads, the
``[L, L]`` scores at once, in float32."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import llama, transformer
from baton_tpu.models.llama import (
    LlamaConfig,
    core_outputs_kept,
    llama_lm_model,
)
from baton_tpu.models.transformer import (
    MLAConfig,
    blocked_causal_core,
    causal_core,
    core_runs_the_kernel,
    mla_apply,
    mla_init,
    mla_rope_angles,
    yarn_inv_freq,
)
from conftest import flash_kernels

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
HEADS, D = 4, 64


def _cfg(block, **kw):
    return MLAConfig(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=12,
                     qk_norm=True, rope_scaling=YARN, block=block, **kw)


def _plain_rms(x, scale):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def _plain_mla(p, x, cfg, inv_freq):
    """numpy float64, whole heads."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    b, l, _ = x.shape

    def heads(y):
        return y.reshape(b, l, HEADS, -1).transpose(0, 2, 1, 3)

    q = heads(x @ p["wq"])
    c = x @ p["wkv_a"]
    kv = heads(_plain_rms(c[..., :cfg.kv_rank], p["kv_norm"]["scale"])
               @ p["wkv_b"])
    shared = np.broadcast_to(c[:, None, :, cfg.kv_rank:],
                             (b, HEADS, l, cfg.rope_dim))
    k = np.concatenate([kv[..., :cfg.nope_dim], shared], -1)
    v = kv[..., cfg.nope_dim:]
    if cfg.qk_norm:
        q = _plain_rms(q, p["q_norm"]["scale"])
        k = _plain_rms(k, p["k_norm"]["scale"])
    angle = np.arange(l)[:, None] * np.asarray(inv_freq, np.float64)
    cos, sin = np.cos(angle), np.sin(angle)

    def rotate(y):
        half = cfg.rope_dim // 2
        kept, y1, y2 = (y[..., :cfg.nope_dim],
                        y[..., cfg.nope_dim:cfg.nope_dim + half],
                        y[..., cfg.nope_dim + half:])
        return np.concatenate(
            [kept, y1 * cos - y2 * sin, y2 * cos + y1 * sin], -1)

    m = 0.1 * math.log(40) + 1
    s = rotate(q) @ rotate(k).transpose(0, 1, 3, 2) \
        * (cfg.qk_dim ** -0.5 * m * m)
    s = np.where(np.tril(np.ones((l, l), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    out = (w / w.sum(-1, keepdims=True)) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, l, -1) @ p["wo"]


def _formula(dim, theta, factor, original, fast, slow):
    """The frequencies as arXiv:2309.00071 and DeepSeek-V2's code state
    them, channel by channel."""
    def channel(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = max(math.floor(channel(fast)), 0), \
        min(math.ceil(channel(slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0), 1)
        out.append(plain / factor * ramp + plain * (1 - ramp))
    return np.asarray(out)


@pytest.mark.parametrize("dim,theta,factor", [(64, 10000.0, 40.0),
                                              (8, 10000.0, 40.0),
                                              (32, 500000.0, 8.0)])
def test_yarn_frequencies_are_the_formulas(dim, theta, factor):
    got = np.asarray(yarn_inv_freq(dim, theta, factor, 4096, 32, 1))
    np.testing.assert_allclose(got, _formula(dim, theta, factor, 4096, 32, 1),
                               rtol=2e-6)
    # fast channels keep their frequency, the slowest is slowed by factor
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(theta ** (-(dim - 2) / dim) / factor,
                                    rel=2e-6)


def test_the_published_scaling_gives_the_published_scale():
    cfg = MLAConfig(rope_scaling=YARN)
    assert cfg.qk_dim == 192
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert MLAConfig().softmax_scale == pytest.approx(192 ** -0.5)
    assert hash(cfg) == hash(MLAConfig(rope_scaling=dict(YARN)))
    with pytest.raises(ValueError):
        MLAConfig(rope_scaling={"type": "linear", "factor": 2})
    cos, sin = mla_rope_angles(16, cfg)
    assert cos.shape == sin.shape == (16, 32)


@pytest.mark.parametrize("block", [4, 16], ids=["blocked", "whole"])
@pytest.mark.parametrize("qk_norm", [True, False])
def test_latent_attention_is_the_whole_head_form(block, qk_norm, nprng):
    """12 tokens in blocks of 4 queries (three blocks, each against its
    causal prefix) and in one block."""
    cfg = _cfg(block) if qk_norm else MLAConfig(
        kv_rank=32, nope_dim=16, rope_dim=8, v_dim=12, rope_scaling=YARN,
        block=block)
    p = mla_init(jax.random.key(0), D, HEADS, cfg)
    assert p["wq"].shape == (D, HEADS * 24)
    assert p["wkv_a"].shape == (D, 40)
    assert p["wkv_b"].shape == (32, HEADS * 28)
    assert p["wo"].shape == (HEADS * 12, D)
    assert ("q_norm" in p) == qk_norm
    if qk_norm:  # scales that differ, so that their place matters
        p["q_norm"]["scale"] = 1 + 0.1 * jnp.arange(24.0)
        p["k_norm"]["scale"] = 1 - 0.02 * jnp.arange(24.0)
    p["kv_norm"]["scale"] = 1 + 0.05 * jnp.arange(32.0)
    x = jnp.asarray(nprng.normal(size=(2, 12, D)), jnp.float32)
    got = mla_apply(p, x, HEADS, cfg, mla_rope_angles(12, cfg))
    want = _plain_mla(p, x, cfg, yarn_inv_freq(8, 10000.0, 40, 4096, 32, 1))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_the_blocked_core_has_the_unblocked_cores_gradients(nprng):
    q, k = (jnp.asarray(nprng.normal(size=(2, 3, 16, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(nprng.normal(size=(2, 3, 16, 10)), jnp.float32)
    weight = jnp.asarray(nprng.normal(size=(2, 3, 16, 10)), jnp.float32)

    def through(block):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(causal_core(q, k, v, 0.3, block) * weight),
            argnums=(0, 1, 2))(q, k, v)

    (want, want_g), (got, got_g) = through(16), through(4)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    # the first query sees its own key alone
    out = causal_core(q, k, v, 0.3, 4)
    np.testing.assert_allclose(np.asarray(out[:, :, 0]),
                               np.asarray(v[:, :, 0]), rtol=1e-6)


@pytest.mark.parametrize("backend,length,block,kernel", [
    ("tpu", 2048, 512, True),     # the cell's sequences
    ("tpu", 1024, 512, True),
    ("cpu", 2048, 512, False),    # tier-1's backend
    ("gpu", 2048, 512, False),
    ("tpu", 2000, 512, False),    # a length the blocks do not divide
    ("tpu", 512, 512, False),     # one block: the plain computation
    ("tpu", 12, 512, False),
])
def test_the_core_is_the_kernel_on_a_tpu_over_whole_blocks(
        backend, length, block, kernel):
    assert core_runs_the_kernel(backend, length, block) is kernel


def _core_operands(nprng, lead=()):
    q, k = (jnp.asarray(nprng.normal(size=lead + (1, 3, 32, 24)), jnp.float32)
            for _ in range(2))
    v, weight = (jnp.asarray(nprng.normal(size=lead + (1, 3, 32, 16)),
                             jnp.float32) for _ in range(2))
    return q, k, v, weight


@pytest.fixture
def kernel_core(monkeypatch):
    """``causal_core`` on its kernel branch: the selector says yes, the
    kernel's blocks are 8 queries by 16 keys, and off a TPU
    ``flash_attention`` interprets itself."""
    monkeypatch.setattr(transformer, "core_runs_the_kernel",
                        lambda backend, length, block: True)
    monkeypatch.setattr(transformer, "_CORE_KERNEL_BLOCKS", (8, 16))
    return causal_core


def _shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield getattr(var.aval, "shape", ())
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


def _assert_kernel_ran(fn, *args):
    assert "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_the_kernel_branch_is_the_blocked_core(kernel_core, nprng):
    q, k, v, weight = _core_operands(nprng)

    def through(core):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(core(q, k, v, 0.3, 8) * weight),
            argnums=(0, 1, 2))(q, k, v)

    _assert_kernel_ran(lambda q, k, v: kernel_core(q, k, v, 0.3, 8), q, k, v)
    # the gradient's program holds q, k, v, the output and the
    # log-sum-exp, and no array of scores outside the kernels
    held = set(_shapes(jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(kernel_core(q, k, v, 0.3, 8))))(q).jaxpr))
    assert (1, 3, 32) in held and (1, 3, 32, 32) not in held
    (want, want_g), (got, got_g) = through(blocked_causal_core), \
        through(kernel_core)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(kernel_core(q, k, v, 0.3, 8)),
        np.asarray(blocked_causal_core(q, k, v, 0.3, 8)), rtol=1e-5,
        atol=1e-5)


def test_the_kernel_branch_under_the_wave_programs_nesting(kernel_core,
                                                           nprng):
    """``jax.vmap`` over a client axis of 2 around ``jax.checkpoint``
    (the decoder block's ``remat``) around the core: the kernel's grid
    takes the client axis, its forward is recomputed for the backward."""
    q, k, v, weight = _core_operands(nprng, lead=(2,))

    def through(core):
        def client(q, k, v, weight):
            block = jax.checkpoint(lambda q, k, v: core(q, k, v, 0.3, 8))
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(block(q, k, v) * weight),
                argnums=(0, 1, 2))(q, k, v)
        return jax.vmap(client)(q, k, v, weight)

    _assert_kernel_ran(
        jax.vmap(lambda q, k, v: kernel_core(q, k, v, 0.3, 8)), q, k, v)
    (want, want_g), (got, got_g) = through(blocked_causal_core), \
        through(kernel_core)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def test_no_score_tensor_is_held_whole_past_a_block():
    """In the gradient's program the largest float32 array of scores is
    a block of queries against its prefix, never ``[L, L]``."""
    l, block = 32, 8
    q = jnp.zeros((1, 2, l, 24))
    v = jnp.zeros((1, 2, l, 10))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(causal_core(q, q, v, 1.0, block))))(q)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield getattr(var.aval, "shape", ())
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert (1, 2, block, l) in seen and (1, 2, block, block) in seen
    assert (1, 2, l, l) not in seen


def test_a_decoder_of_latent_attention_trains(nprng):
    from baton_tpu.core.training import make_local_trainer

    cfg = LlamaConfig.tiny(mla=_cfg(8), embed_std=1.0)
    model = llama_lm_model(cfg, remat=True)
    params = model.init(jax.random.key(0))
    assert all("mla" in b and "attn" not in b for b in params["blocks"])
    trainer = make_local_trainer(model, batch_size=2, learning_rate=5e-2)
    toks = nprng.integers(0, cfg.vocab_size, size=(2, cfg.max_len + 1))
    data = {"x": jnp.asarray(toks[:, :-1], jnp.int32),
            "y": jnp.asarray(toks[:, 1:], jnp.int32)}
    _, _, hist = trainer.train(params, data, jnp.asarray(2),
                               jax.random.key(1), 4)
    assert float(hist[-1]) < float(hist[0])


# ---------------------------------------------------------------------------
# the block's checkpoint keeps the kernel's output and log-sum-exp


def _block_grads(block, cfg, length, clients=2):
    """``(gradient function, operands)`` of one decoder block of ``cfg``
    under ``block``'s checkpoint, ``clients`` under ``vmap``, with
    something after the block that needs its output."""
    p = llama._block_init(jax.random.key(3), cfg, cfg.kind_of(0))
    x = jax.random.normal(jax.random.key(4), (clients, 1, length, cfg.d_model))
    rope = mla_rope_angles(length, cfg.mla)

    def loss(p, x):
        def client(x):
            y, _ = block(p, x, None, cfg, rope, transformer.default_attention)
            return jnp.sum(y ** 2)
        return jnp.sum(jax.vmap(client)(x))

    return jax.grad(loss, argnums=(0, 1)), (p, x)


def _bare_block():
    return jax.checkpoint(llama._block_apply, static_argnums=(3, 5))


def test_a_block_runs_the_cores_forward_kernel_once(kernel_core):
    """A block of latent attention whose core is the kernel, clients
    under ``vmap`` as in the wave program: under the model's checkpoint
    one forward kernel where a bare checkpoint has two, and the bare
    checkpoint's gradients bit for bit."""
    cfg = LlamaConfig.tiny(mla=_cfg(8))
    kept, operands = _block_grads(llama._checkpointed_block(), cfg, 32)
    bare, _ = _block_grads(_bare_block(), cfg, 32)
    assert flash_kernels(kept, *operands) == (1, 1)
    assert flash_kernels(bare, *operands) == (2, 1)
    got, want = kept(*operands), bare(*operands)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(*map(jax.tree_util.tree_leaves, (got, want))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_a_block_without_a_kernel_is_the_block_it_was():
    """On the CPU the core is the blocked plain computation: nothing
    carries the kernel's names, and the gradient's program is the bare
    checkpoint's to the letter."""
    import re

    # (another length than the test above: ``jax.checkpoint`` keeps its
    # trace of ``_block_apply`` by the arguments' shapes, selector and all)
    cfg = LlamaConfig.tiny(mla=_cfg(8))
    kept, operands = _block_grads(llama._checkpointed_block(), cfg, 24)
    bare, _ = _block_grads(_bare_block(), cfg, 24)
    assert flash_kernels(kept, *operands) == (0, 0)
    texts = [re.sub(r"policy=\S.*", "policy", str(jax.make_jaxpr(fn)(*operands)))
             for fn in (kept, bare)]
    assert "policy" in texts[0] and texts[0] == texts[1]


@pytest.mark.parametrize("backend,length,kept", [
    ("tpu", 2048, 2),    # sarvam_105b_c4_l2048's sequences: every block
    ("tpu", 8192, 2),
    ("cpu", 2048, 0),    # tier-1's backend: no kernel, nothing to keep
    ("tpu", 1024, 0),    # one of the kernel's blocks: the plain core
    ("tpu", 2000, 0),    # a length the blocks do not divide
])
def test_the_blocks_that_keep_a_cores_outputs_are_those_of_the_kernel(
        backend, length, kept):
    cfg = LlamaConfig.tiny(mla=_cfg(8))
    assert core_outputs_kept(cfg, backend, 1, length) == kept
    assert (kept == cfg.n_layers) is transformer.core_is_the_kernel(
        backend, length)
