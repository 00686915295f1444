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


# ---------------------------------------------------------------------------
# each operand of the core made once, where the core reads it (PR 47)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("widths", [(16, 8), (16, 12), (8, 4, 4)],
                         ids=["nope_rope", "nope_v", "three"])
def test_a_column_cut_weight_gives_the_whole_products_columns(
        widths, dtype, nprng):
    """``x @ part`` of a column-cut ``Adapted`` is the same columns of
    ``x @ whole``, forward and for the gradients to ``a``, ``b`` and
    ``x``. Dropping output columns reorders no contraction, so forward
    and for ``b`` (contracted over the tokens) the two are one number;
    the CPU's library picks its kernel by the product's shape, so the
    test holds them to a rounding of the last place. The gradients to
    ``a`` and ``x`` are contracted over the columns, which the whole
    product sums with zeros between them."""
    from baton_tpu.models.lora import Adapted

    heads, d, r = 3, 32, 4
    n = heads * sum(widths)
    w, a, b, x = (jnp.asarray(nprng.normal(size=shape), jnp.float32)
                  for shape in ((d, n), (d, r), (r, n), (2, 5, d)))
    w, x = w.astype(dtype), x.astype(dtype)
    edges = np.cumsum((0,) + widths)

    def columns(y, i):  # part i of [..., heads * sum(widths)]
        y = y.reshape(y.shape[:-1] + (heads, sum(widths)))
        return y[..., edges[i]:edges[i + 1]].reshape(y.shape[:-2] + (-1,))

    parts = Adapted(w, a, b, 2.0).columns(heads, widths)
    assert [part.shape for part in parts] == [(d, heads * k) for k in widths]
    for i, width in enumerate(widths):
        weight = jnp.asarray(nprng.normal(size=(2, 5, heads * width)),
                             jnp.float32)

        def part(a, b, x):
            y = x @ Adapted(w, a, b, 2.0).columns(heads, widths)[i]
            return jnp.sum(y * weight), y

        def whole(a, b, x):
            y = columns(x @ Adapted(w, a, b, 2.0), i)
            return jnp.sum(y * weight), y

        (_, got), got_g = jax.jit(jax.value_and_grad(
            part, argnums=(0, 1, 2), has_aux=True))(a, b, x)
        (_, want), want_g = jax.jit(jax.value_and_grad(
            whole, argnums=(0, 1, 2), has_aux=True))(a, b, x)
        assert got.dtype == dtype
        last_place = 2e-6 if dtype == jnp.float32 else 2 ** -7
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=last_place, atol=last_place)
        np.testing.assert_allclose(np.asarray(got_g[1]),
                                   np.asarray(want_g[1]), rtol=last_place,
                                   atol=last_place)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        for g, want_one in zip(got_g[::2], want_g[::2]):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(want_one, np.float32),
                rtol=tol, atol=tol)


def test_the_parts_of_one_projection_share_its_low_rank_product(nprng):
    """``x A`` is made once a projection, not once a part."""
    from baton_tpu.models.lora import Adapted

    w = Adapted(jnp.zeros((32, 3 * 24)), jnp.zeros((32, 4)),
                jnp.zeros((4, 3 * 24)), 2.0)
    jaxpr = jax.make_jaxpr(lambda x: transformer._projected_parts(
        x, w, 3, (16, 8)))(jnp.zeros((2, 5, 32)))
    products = [eqn for eqn in jaxpr.jaxpr.eqns
                if eqn.primitive.name == "dot_general"]
    # the low-rank product, then W's and B's a part
    assert len(products) == 5
    assert sum(eqn.outvars[0].aval.shape[-1] == 4 for eqn in products) == 1


def _whole_head_mla(p, x, n_heads, cfg, rope):
    """The mixer the plain way in JAX, for its gradients: whole heads,
    the wide activations cut and joined, each head's key turned for
    itself, the ``[L, L]`` scores at once."""
    b, l, _ = x.shape
    cos, sin = rope

    def heads(y):
        return y.reshape(b, l, n_heads, -1).transpose(0, 2, 1, 3)

    def rms(y, scale):
        return y * jax.lax.rsqrt(
            jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps) * scale

    def rotated(y):
        half = cfg.rope_dim // 2
        kept, y1, y2 = (y[..., :cfg.nope_dim],
                        y[..., cfg.nope_dim:cfg.nope_dim + half],
                        y[..., cfg.nope_dim + half:])
        return jnp.concatenate(
            [kept, y1 * cos - y2 * sin, y2 * cos + y1 * sin], -1)

    if cfg.q_rank is None:
        q = heads(x @ p["wq"])
    else:
        q = heads(rms(x @ p["wq_a"], p["q_a_norm"]["scale"]) @ p["wq_b"])
    c = x @ p["wkv_a"]
    kv = heads(rms(c[..., :cfg.kv_rank], p["kv_norm"]["scale"]) @ p["wkv_b"])
    shared = jnp.broadcast_to(c[:, None, :, cfg.kv_rank:],
                              (b, n_heads, l, cfg.rope_dim))
    k = jnp.concatenate([kv[..., :cfg.nope_dim], shared], -1)
    v = kv[..., cfg.nope_dim:]
    if cfg.qk_norm:
        q, k = rms(q, p["q_norm"]["scale"]), rms(k, p["k_norm"]["scale"])
    s = jnp.einsum("bhqd,bhkd->bhqk", rotated(q), rotated(k),
                   precision="highest") * cfg.softmax_scale
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                     precision="highest")
    return out.transpose(0, 2, 1, 3).reshape(b, l, -1) @ p["wo"]


# the two shapes of configuration the cells run, narrow and at the
# widths that decide the layout (``mla_qk_layout``: 192 rides the
# sublanes of the kernel, 256 is whole lane tiles)
_SHAPES = {
    "sarvam": dict(kv_rank=32, qk_norm=True, rope_scaling=YARN),
    "glm": dict(kv_rank=32, q_rank=24, rope_theta=1e6, norm_eps=1e-5),
}
_WIDTHS = {
    ("sarvam", False): dict(nope_dim=16, rope_dim=8, v_dim=12),
    ("sarvam", True): dict(nope_dim=128, rope_dim=64, v_dim=128),
    ("glm", False): dict(nope_dim=24, rope_dim=8, v_dim=32),
    ("glm", True): dict(nope_dim=192, rope_dim=64, v_dim=256),
}


def _shaped(shape, kernel, n_heads, length, seed=0):
    """``(cfg, parameters, x, weight, rope)`` of one such mixer with
    norm scales that differ, so that their place matters."""
    cfg = MLAConfig(block=8, **_SHAPES[shape], **_WIDTHS[shape, kernel])
    p = mla_init(jax.random.key(seed), D, n_heads, cfg)
    for name in ("q_norm", "k_norm", "kv_norm", "q_a_norm"):
        if name in p:
            n = p[name]["scale"].shape[0]
            p[name]["scale"] = 1 + 0.5 * jnp.sin(jnp.arange(n) + len(name))
    x, weight = jax.random.normal(jax.random.key(seed + 1),
                                  (2, 2, length, D))
    return cfg, p, x, 0.1 * weight, mla_rope_angles(length, cfg)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["the_plain_core", "the_kernel"])
@pytest.mark.parametrize("shape", ["sarvam", "glm"])
def test_the_mixer_is_the_whole_head_form_with_its_gradients(
        shape, kernel, request):
    """Both shapes of configuration, forward and every gradient,
    against the plain mixer of this file: on the plain blocked core at
    narrow widths (row-major operands), and with the core the
    interpreted kernel at the cells' widths, two heads: 128 + 64 / 128
    makes q and k channel-major (``[B, H, 192, L]``), 192 + 64 / 256
    row-major with the rotary 64 in lanes 192 to 255."""
    n_heads, length = (2, 32) if kernel else (HEADS, 12)
    if kernel:  # blocks of 8 queries by 16 keys
        request.getfixturevalue("kernel_core")
    cfg, p, x, weight, rope = _shaped(shape, kernel, n_heads, length)
    layout = transformer.mla_qk_layout(cfg, "tpu", length)
    assert layout == ("channel_major" if kernel and shape == "sarvam"
                      else "row_major")

    def through(fn):
        def loss(pp, xx):
            y = fn(pp, xx, n_heads, cfg, rope)
            return jnp.sum(y * weight), y

        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))
        if kernel and fn is mla_apply:
            _assert_kernel_ran(both, p, x)
        return both(p, x)

    ((_, got), got_g), ((_, want), want_g) = \
        through(mla_apply), through(_whole_head_mla)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert jax.tree_util.tree_structure(got_g) == \
        jax.tree_util.tree_structure(want_g)
    for g, w in zip(*map(jax.tree_util.tree_leaves, (got_g, want_g))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("shape", ["sarvam", "glm"])
def test_the_shared_key_turned_once_is_the_key_turned_a_head_at_a_time(
        shape, monkeypatch):
    """In bfloat16: the keys the mixer hands its core, the shared rotary
    key turned once at ``[B, 1, L, rope]`` and then given to the heads
    (under ``qk_norm`` times each head's scalar), against the keys of
    the mixer that broadcast it to the heads first and normed and
    turned each head's copy: the two differ by the order of two
    roundings under ``qk_norm`` (a bfloat16 rounding of the normed key
    before the rotation then, none now), and in nothing without it."""
    cfg, p, x, _, rope = _shaped(shape, False, HEADS, 12)
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim == 2 else a, p)
    x = x.astype(jnp.bfloat16)
    seen = {}

    def core(q, k, v, scale, block, chosen=None):
        seen.update(q=q, k=k, v=v)
        return blocked_causal_core(q, k, v, scale, block, chosen)

    monkeypatch.setattr(transformer, "causal_core", core)
    _, handed = jax.jit(
        lambda p, x: (mla_apply(p, x, HEADS, cfg, rope), dict(seen)))(p, x)
    b, l = x.shape[:2]
    c = x @ p["wkv_a"]
    latent = transformer.rms_normalize(
        c[..., :cfg.kv_rank], p["kv_norm"]["scale"], cfg.norm_eps)
    kv = (latent @ p["wkv_b"]).reshape(b, l, HEADS, -1).transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :cfg.nope_dim],
         jnp.broadcast_to(c[:, None, :, cfg.kv_rank:],
                          (b, HEADS, l, cfg.rope_dim))], -1)
    if cfg.qk_norm:
        k = transformer.rms_normalize(k, p["k_norm"]["scale"], cfg.norm_eps)
    k = jnp.concatenate(
        [k[..., :cfg.nope_dim],
         transformer.apply_rope(k[..., cfg.nope_dim:], *rope)], -1)
    got, want = (np.asarray(y, np.float32) for y in (handed["k"], k))
    assert handed["k"].dtype == jnp.bfloat16 and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(handed["v"], np.float32),
                                  np.asarray(kv[..., cfg.nope_dim:],
                                             np.float32))
    if not cfg.qk_norm:
        np.testing.assert_array_equal(got, want)
    else:
        # the nope part to the statistic's float32 rounding, which may
        # tip a bfloat16; the rotary part to two roundings of 2 ** -8
        assert np.abs(got - want).max() <= 2 ** -7 * np.abs(want).max()
        assert np.mean(got != want) < 0.5


@pytest.mark.parametrize("shape,backend,length,layout", [
    ("sarvam", "tpu", 2048, "channel_major"),  # sarvam_105b_c4_l2048
    ("glm", "tpu", 8192, "row_major"),         # glm5_c4_l8192: 256 wide
    ("sarvam", "cpu", 2048, "row_major"),      # tier-1: the plain core
    ("sarvam", "tpu", 2000, "row_major"),      # no whole blocks: the same
    ("sarvam", "tpu", 1024, "row_major"),      # one block
])
def test_queries_and_keys_are_laid_out_as_the_core_takes_them(
        shape, backend, length, layout):
    cfg = MLAConfig(**_SHAPES[shape], **_WIDTHS[shape, True])
    assert transformer.mla_qk_layout(cfg, backend, length) == layout


def test_a_decoder_of_latent_attention_says_how_it_laid_them_out():
    """``baton.round`` carries ``mla_qk_layout`` beside
    ``core_outputs_kept`` once the model has been traced."""
    cfg = LlamaConfig.tiny(mla=_cfg(8))
    model = llama_lm_model(cfg)
    assert "mla_qk_layout" not in dict(model.span_attrs)
    ids = jnp.zeros((1, cfg.max_len), jnp.int32)
    jax.eval_shape(lambda p: model.apply(p, {"x": ids}, None),
                   jax.eval_shape(model.init, jax.random.key(0)))
    assert dict(model.span_attrs)["mla_qk_layout"] == "row_major"  # the CPU
