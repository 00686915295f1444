"""Flash-attention kernel vs the dense oracle.

``dot_product_attention`` (transformer.py:105-133) is the reference
semantics; the Pallas kernel must match it in forward values AND in
gradients (custom VJP with blockwise recompute) across causal, biased,
GQA, padded-length, and bf16 configurations. Runs in interpret mode on
the CPU test backend — same kernel code as TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models.transformer import dot_product_attention, padding_bias
from baton_tpu.ops.flash_attention import (
    KEPT_OUTPUTS,
    flash_attention,
    make_flash_attention_fn,
)
from conftest import flash_kernels


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


def _qkv(seed, b, hq, hkv, l, d, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return (
        _rand(k1, b, hq, l, d, dtype=dtype),
        _rand(k2, b, hkv, l, d, dtype=dtype),
        _rand(k3, b, hkv, l, d, dtype=dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv(0, 2, 4, 4, 32, 16)
    want = dot_product_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_with_key_bias():
    q, k, v = _qkv(1, 2, 2, 2, 16, 8)
    mask = jnp.concatenate(
        [jnp.ones((2, 12)), jnp.zeros((2, 4))], axis=1
    )
    bias = padding_bias(mask)
    want = dot_product_attention(q, k, v, bias=bias)
    got = flash_attention(q, k, v, bias=bias, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_gqa():
    q, k, v = _qkv(2, 1, 8, 2, 16, 8)  # 4 query heads per kv head
    want = dot_product_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_unpadded_length():
    # L=20 is not a multiple of the block: exercises internal padding
    q, k, v = _qkv(3, 1, 2, 2, 20, 8)
    want = dot_product_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.fixture(params=["one_kernel", "two_pass"])
def backward_form(request, monkeypatch):
    """Both forms of the backward: where a head's dq fits in VMEM one
    kernel computes the three gradients, past that the two passes do
    (forced here by a budget of nothing)."""
    from baton_tpu.ops import flash_attention as fa

    if request.param == "two_pass":
        monkeypatch.setattr(fa, "_DQ_RESIDENT_BYTES", 0)
    assert fa._dq_fits_vmem(32, 16) is (request.param == "one_kernel")
    return request.param


def test_the_backward_is_one_kernel_while_dq_fits_in_vmem():
    from baton_tpu.ops.flash_attention import _dq_fits_vmem

    assert _dq_fits_vmem(2048, 192)      # latent attention's core
    assert _dq_fits_vmem(8192, 256)      # and where its queries choose
    assert _dq_fits_vmem(8192, 128)
    assert not _dq_fits_vmem(16384, 256)  # long contexts: two passes
    assert not _dq_fits_vmem(32768, 128)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal, backward_form):
    q, k, v = _qkv(4, 2, 4, 2, 16, 8)
    mask = jnp.concatenate([jnp.ones((2, 13)), jnp.zeros((2, 3))], axis=1)
    bias = padding_bias(mask)

    def dense_loss(q, k, v, bias):
        out = dot_product_attention(q, k, v, bias=bias, causal=causal)
        return (out * jnp.cos(out)).sum()

    def flash_loss(q, k, v, bias):
        out = flash_attention(q, k, v, bias=bias, causal=causal,
                              block_q=8, block_k=8)
        return (out * jnp.cos(out)).sum()

    want = jax.grad(dense_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    got = jax.grad(flash_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_gradients_gqa_fold(backward_form):
    # kv grads must fold the query-head group correctly (sum over group)
    q, k, v = _qkv(5, 1, 4, 1, 8, 8)

    def dense_loss(k):
        return dot_product_attention(q, k, v, causal=True).sum()

    def flash_loss(k):
        return flash_attention(q, k, v, causal=True,
                               block_q=8, block_k=8).sum()

    want = jax.grad(dense_loss)(k)
    got = jax.grad(flash_loss)(k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_bfloat16_io():
    q, k, v = _qkv(6, 1, 2, 2, 16, 8, dtype=jnp.bfloat16)
    want = dot_product_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_seam_in_model():
    """The kernel drops into the zoo through the attention_fn seam and a
    full LM training step stays finite and matches the dense-path loss."""
    from baton_tpu.core.training import make_local_trainer
    from baton_tpu.models.llama import LlamaConfig, llama_lm_model

    cfg = LlamaConfig.tiny(max_len=16, n_heads=4, n_kv_heads=2)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, cfg.max_len)
    ).astype(np.int32)
    data = {"x": jnp.asarray(toks), "y": jnp.asarray(toks)}

    losses = {}
    for name, attn in [
        ("dense", None),
        ("flash", make_flash_attention_fn(block_q=8, block_k=8)),
    ]:
        kw = {} if attn is None else {"attention_fn": attn}
        model = llama_lm_model(cfg, **kw)
        trainer = make_local_trainer(model, batch_size=2, learning_rate=1e-2)
        params = model.init(jax.random.key(0))
        _, _, hist = trainer.train(
            params, data, jnp.asarray(2), jax.random.key(1), 1
        )
        losses[name] = float(hist[0])
    assert np.isfinite(losses["flash"])
    np.testing.assert_allclose(losses["flash"], losses["dense"],
                               rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------------
# values narrower than keys (latent attention: 192 / 128), an explicit
# scale. Oracle: the whole [L, L] scores at once in float32, written here.


def _plain(q, k, v, causal, scale):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    if causal:
        l = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _qkv_widths(seed, b, hq, hkv, l, dk, dv):
    q, k, _ = _qkv(seed, b, hq, hkv, l, dk)
    return q, k, _rand(jax.random.key(seed + 100), b, hkv, l, dv)


WIDTHS = [(24, 16), (192, 128)]


@pytest.mark.parametrize("dk,dv", WIDTHS)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)], ids=["mha", "gqa"])
def test_narrower_values_forward(dk, dv, causal, hq, hkv):
    q, k, v = _qkv_widths(7, 2, hq, hkv, 32, dk, dv)
    got = flash_attention(q, k, v, causal=causal, scale=0.11,
                          block_q=8, block_k=16)
    assert got.shape == (2, hq, 32, dv)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_plain(q, k, v, causal, 0.11)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dk,dv", WIDTHS)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)], ids=["mha", "gqa"])
def test_narrower_values_gradients(dk, dv, causal, hq, hkv, backward_form):
    """dq and dk as wide as the keys, dv as wide as the values; blocks
    of 8 queries and 16 keys, so a causal backward skips tiles."""
    q, k, v = _qkv_widths(8, 1, hq, hkv, 32, dk, dv)
    weight = _rand(jax.random.key(9), 1, hq, 32, dv)

    def grads(attend):
        return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * weight),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, scale=0.11, block_q=8, block_k=16))
    want = grads(lambda q, k, v: _plain(q, k, v, causal, 0.11))
    for g, w, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_the_default_scale_is_the_keys_width():
    q, k, v = _qkv_widths(10, 1, 2, 2, 16, 24, 16)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, block_q=8, block_k=8)),
        np.asarray(flash_attention(q, k, v, scale=24 ** -0.5,
                                   block_q=8, block_k=8)), rtol=0, atol=0)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, block_q=8, block_k=8)),
        np.asarray(_plain(q, k, v, False, 24 ** -0.5)), rtol=1e-5, atol=1e-5)


def test_narrower_values_with_a_key_bias_and_a_padded_length():
    # L = 20 pads to the blocks; the bias masks the last four real keys
    q, k, v = _qkv_widths(11, 2, 2, 2, 20, 24, 16)
    bias = padding_bias(jnp.concatenate(
        [jnp.ones((2, 16)), jnp.zeros((2, 4))], axis=1))
    got = flash_attention(q, k, v, bias=bias, causal=True, scale=0.2,
                          block_q=8, block_k=8)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * 0.2
    seen = (jnp.arange(20)[:, None] >= jnp.arange(20)[None, :]) \
        & (jnp.arange(20) < 16)[None, :]
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v,
                      precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_equal_widths_are_what_they_were(causal, backward_form):
    """``Dv == Dk``: the public kernel and the ring's block entry points
    against the dense oracle the file has always been held to, outputs,
    log-sum-exp and the four gradients."""
    from baton_tpu.ops.flash_attention import flash_block_bwd, flash_block_fwd

    q, k, v = _qkv(12, 2, 4, 2, 32, 16)
    bias2d = 0.5 * _rand(jax.random.key(13), 2, 32)
    bias = bias2d[:, None, None, :]
    weight = _rand(jax.random.key(14), 2, 4, 32, 16)

    def dense(q, k, v, bias):
        return dot_product_attention(q, k, v, bias=bias, causal=causal)

    want, vjp = jax.vjp(dense, q, k, v, bias)
    want_g = vjp(weight)
    out, lse = flash_block_fwd(q, k, v, bias2d, causal, block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1),
                   precision="highest") * 16 ** -0.5 + bias
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(s, axis=-1)),
        rtol=1e-5, atol=1e-5)
    got_g = flash_block_bwd(q, k, v, bias2d, out, weight, lse, causal,
                            block_q=8, block_k=16)
    public = flash_attention(q, k, v, bias=bias, causal=causal,
                             block_q=8, block_k=16)
    np.testing.assert_array_equal(np.asarray(public), np.asarray(out))
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g).reshape(w.shape),
                                   np.asarray(w), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# under a checkpoint: the forward kernel's two outputs carry names


def _checkpointed_grad(policy, hkv=2):
    """The gradient of a checkpointed call of the kernel whose output
    something after it needs, and its operands."""
    q, k, v = _qkv(11, 1, 4, hkv, 32, 16)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=8, block_k=16)

    if policy != "no_checkpoint":
        attend = jax.checkpoint(attend, policy=policy)
    return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) ** 2),
                    argnums=(0, 1, 2)), (q, k, v)


@pytest.mark.parametrize("kept,forwards", [(True, 1), (False, 2)],
                         ids=["the_two_names_saved", "a_bare_checkpoint"])
def test_a_checkpoint_that_keeps_the_outputs_runs_the_forward_once(
        kept, forwards, backward_form):
    """A bare ``jax.checkpoint`` makes the output and the log-sum-exp
    again for the backward kernel, a second run of the forward kernel;
    one whose policy saves ``KEPT_OUTPUTS`` hands it the first run's.
    The same kernels on the same operands: the same gradients, bit for
    bit."""
    policy = (jax.checkpoint_policies.save_only_these_names(*KEPT_OUTPUTS)
              if kept else None)
    grad, operands = _checkpointed_grad(policy)
    backwards = 1 if backward_form == "one_kernel" else 2
    assert flash_kernels(grad, *operands) == (forwards, backwards)
    plain, _ = _checkpointed_grad("no_checkpoint")
    assert flash_kernels(plain, *operands) == (1, backwards)
    for got, want in zip(grad(*operands), plain(*operands)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_policy_of_other_names_keeps_nothing_of_the_kernel():
    grad, operands = _checkpointed_grad(
        jax.checkpoint_policies.save_only_these_names("context"))
    assert flash_kernels(grad, *operands) == (2, 1)


# ---------------------------------------------------------------------
# a window beside the causal rule


def _window_losses(window, block_q, block_k):
    def dense(q, k, v):
        out = dot_product_attention(q, k, v, causal=True, window=window)
        return (out * jnp.cos(out)).sum()

    def flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=block_q, block_k=block_k)
        return (out * jnp.cos(out)).sum()

    return dense, flash


# window, blocks of queries and of keys over 48 tokens (padded to 64 by
# the larger block): a multiple of both tiles, of neither, one tile, one
# key, and one longer than the sequence (the causal kernel's result)
WINDOWS = [(16, 8, 16), (20, 16, 8), (7, 16, 16), (1, 8, 8), (100, 16, 16)]
# query heads on key-value heads: the cells' small groups, and 16 query
# heads on one key-value head, whose dk and dv the backward sums over
# the group outside the kernel
HEADS = [(w, (4, 2)) for w in WINDOWS] + [((20, 16, 8), (16, 1))]


@pytest.mark.parametrize(
    "window,block_q,block_k,heads", [w + (h,) for w, h in HEADS],
    ids=[f"{w[0]}-{w[1]}-{w[2]}" + ("" if h == (4, 2) else f"-{h[0]}on{h[1]}")
         for w, h in HEADS])
def test_a_window_forward_and_all_three_gradients(window, block_q, block_k,
                                                  heads, backward_form):
    """The windowed kernels against the dense masked core with grouped
    heads (4 query heads on 2, 16 on 1): the output and the gradients
    of q, k and v, in both forms of the backward."""
    q, k, v = _qkv(11, 2, *heads, 48, 8)
    dense, flash = _window_losses(window, block_q, block_k)
    want = jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    if window >= 48:  # it never binds: the causal core itself
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True, window=window,
                                       block_q=block_q, block_k=block_k)),
            np.asarray(flash_attention(q, k, v, causal=True,
                                       block_q=block_q, block_k=block_k)),
            rtol=1e-6, atol=1e-6)


# the band's grid with what the other tests leave out: a gradient of the
# keys' bias, keys that ride the sublanes (192 wide) and keys that do
# not, and 40 tokens that the two blocks pad apart (to 48 queries and 40
# keys, to 40 and 48), so that a band runs past its side's last block.
# Windows: one key, less than a block, a block, several, and more than
# the sequence
BANDS = [(window, width, blocks)
         for window in (1, 5, 8, 20, 100)
         for width, blocks in ((8, (16, 8)), (192, (8, 16)))]


@pytest.mark.parametrize("window,width,blocks", BANDS)
def test_a_windows_band_against_the_dense_core(window, width, blocks,
                                               backward_form):
    """The windowed kernels, whose grids run over the band, against
    ``dot_product_attention`` with grouped heads (4 query heads on 2):
    the output and the gradients of q, k, v and the keys' bias, in both
    forms of the backward."""
    from baton_tpu.ops.flash_attention import _keys_ride_sublanes

    assert _keys_ride_sublanes(width) is (width == 192)
    length = 40
    q, k, v = _qkv(21, 2, 4, 2, length, width)
    bias = 0.5 * _rand(jax.random.key(22), 2, 1, 1, length)
    weight = _rand(jax.random.key(23), 2, 4, length, width)

    def dense(q, k, v, bias):
        return dot_product_attention(q, k, v, bias=bias, causal=True,
                                     window=window)

    def flash(q, k, v, bias):
        return flash_attention(q, k, v, bias=bias, causal=True,
                               window=window, block_q=blocks[0],
                               block_k=blocks[1])

    want, back = jax.vjp(dense, q, k, v, bias)
    got, kernel_back = jax.vjp(flash, q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    for g, w in zip(kernel_back(weight), back(weight)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_a_windowed_call_that_names_no_blocks_gets_the_kernels_own():
    """Under a window of 1,024 a call that names no blocks runs at 1,024
    x 1,024 (``_own_blocks``): bit for bit the call that names them,
    and the dense masked core's output and gradients, over 2,304 tokens
    (three blocks of queries, the last one padded)."""
    length, window = 2304, 1024
    q, k, v = _qkv(31, 1, 2, 1, length, 8)
    weight = _rand(jax.random.key(32), 1, 2, length, 8)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, causal=True, window=window)

    def own(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window)

    def named(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=1024, block_k=1024)

    want, back = jax.vjp(dense, q, k, v)
    got, own_back = jax.vjp(own, q, k, v)
    same, named_back = jax.vjp(named, q, k, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    for g, n, w in zip(own_back(weight), named_back(weight), back(weight)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(n))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_a_window_needs_the_causal_rule():
    q, k, v = _qkv(12, 1, 2, 2, 16, 8)
    with pytest.raises(AssertionError, match="window"):
        flash_attention(q, k, v, causal=False, window=4)


@pytest.mark.parametrize("window,block_q,block_k",
                         [(16, 8, 16), (20, 16, 8), (7, 8, 8)])
def test_a_skipped_tile_is_not_read(window, block_q, block_k, backward_form):
    """NaN wherever the rule says a tile is skipped. A block of keys is
    needed by some block of queries, so the poison is laid a row and a
    column of the grid at a time: for a block of queries, the keys and
    values of every block it skips are NaN, and its rows of the output
    and of dq are finite and the clean call's; for a block of keys, the
    queries of every block that skips it are NaN, and its rows of dk
    and dv are finite and the clean call's."""
    from baton_tpu.ops.flash_attention import _tile_is_needed

    length = 64
    q, k, v = _qkv(13, 1, 4, 2, length, 8)

    def call(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=block_q, block_k=block_k)
        return out * jnp.cos(out)

    @jax.jit  # one trace for every pattern of poison
    def run(q, k, v):
        out, back = jax.vjp(call, q, k, v)
        return (out,) + back(jnp.ones_like(out))

    clean = run(q, k, v)
    n_q, n_k = length // block_q, length // block_k
    needed = np.asarray([[bool(_tile_is_needed(True, window, i, j, block_q,
                                               block_k))
                          for j in range(n_k)] for i in range(n_q)])
    assert 0 < needed.sum() < needed.size

    def poisoned(x, blocks, block):
        at = np.repeat(np.asarray(blocks), block)
        return jnp.where(at[None, None, :, None], jnp.nan, x)

    for i in range(n_q):
        if needed[i].all():
            continue
        got = run(q, poisoned(k, ~needed[i], block_k),
                  poisoned(v, ~needed[i], block_k))
        rows = slice(i * block_q, (i + 1) * block_q)
        for g, c in zip(got[:2], clean[:2]):  # the output and dq
            assert np.isfinite(np.asarray(g[:, :, rows])).all(), i
            np.testing.assert_allclose(np.asarray(g[:, :, rows]),
                                       np.asarray(c[:, :, rows]),
                                       rtol=1e-5, atol=1e-5)
    for j in range(n_k):
        if needed[:, j].all():
            continue
        got = run(poisoned(q, ~needed[:, j], block_q), k, v)
        rows = slice(j * block_k, (j + 1) * block_k)
        for g, c in zip(got[2:], clean[2:]):  # dk and dv
            assert np.isfinite(np.asarray(g[:, :, rows])).all(), j
            np.testing.assert_allclose(np.asarray(g[:, :, rows]),
                                       np.asarray(c[:, :, rows]),
                                       rtol=1e-5, atol=1e-5)


# blocks of queries and of keys, and how many of each: lengths that both
# blocks divide, and 40 tokens padded to each block on its own (the
# queries further than the keys, and the keys further than the queries)
GRIDS = [(8, 16, 8, 4), (16, 8, 4, 8), (8, 8, 6, 6), (32, 8, 2, 8),
         (16, 8, 3, 5), (8, 16, 5, 3)]


@pytest.mark.parametrize("block_q,block_k,n_q,n_k", GRIDS)
def test_the_rule_the_skip_and_the_index_maps_are_one(block_q, block_k, n_q,
                                                      n_k):
    """By brute force over blocks and windows that are and are not
    multiples of one another: a tile is needed exactly where the rule
    holds for some pair of its positions; the block ranges the index
    maps hold a step to are exactly the needed tiles of a row or a
    column of the grid; and the grids as the kernels walk them, the
    sequence without a window and the band under one: the steps of
    every row and of every column compute each needed tile once and
    nothing else, a step that computes asks for its own tile and one
    that does not for a needed tile of its row or column (a band's
    spare step: the last one again), and under a window no row and no
    column has more than the band's spare steps to skip."""
    from baton_tpu.ops import flash_attention as fa

    for window in (None, 1, 5, 8, 16, 20, 33, 200):
        needed = np.zeros((n_q, n_k), bool)
        for i in range(n_q):
            for j in range(n_k):
                q_pos = i * block_q + np.arange(block_q)[:, None]
                k_pos = j * block_k + np.arange(block_k)[None, :]
                pairs = fa._sees(q_pos, k_pos, True, window)
                needed[i, j] = pairs.any()
                assert bool(fa._tile_is_needed(
                    True, window, i, j, block_q, block_k)) == needed[i, j]
        at = (True, window, block_q, block_k)
        sides = (
            # a row of the forward's and of dq's grid, then a column of
            # dk's and dv's: the band's rule, the other side's blocks,
            # the index map of (outer, step), tile (i, j) of the two
            (fa._key_blocks, n_q, n_k, lambda i, t: fa._needed_k(
                True, window, i, t, block_q, block_k, n_k),
             lambda i, j: (i, j), needed),
            (fa._query_blocks, n_k, n_q, lambda j, t: fa._needed_q(
                True, window, t, j, block_q, block_k, n_q),
             lambda j, i: (i, j), needed.T))
        for blocks, n_outer, n_inner, asked, tile, seen in sides:
            steps = fa._inner_steps(blocks, True, window, n_outer, n_inner,
                                    block_q, block_k)
            longest = 0
            for outer in range(n_outer):
                first, last = blocks(True, window, outer, block_q, block_k)
                first = 0 if first is None else int(first)
                last = n_inner - 1 if last is None else min(int(last),
                                                            n_inner - 1)
                band = np.flatnonzero(seen[outer])
                assert (band[0], band[-1]) == (first, last)
                assert len(band) == last - first + 1  # a band
                longest = max(longest, len(band))
                computed = []
                for t in range(steps):
                    inner = int(fa._stepped(blocks, True, window, outer, t,
                                            block_q, block_k))
                    held = int(asked(outer, t))
                    assert seen[outer, held]
                    if fa._step_computes(True, window, *tile(outer, inner),
                                         block_q, block_k, n_q, n_k):
                        computed.append(inner)
                        assert held == inner
                    elif window is not None:  # a spare step: the last again
                        assert (held, inner > last) == (last, True)
                assert computed == list(band)
            assert steps == (n_inner if window is None else longest)


def test_the_grids_steps_are_counted_as_the_kernels_walk_them():
    """The benchmark's shape, a window of 1,024 over 8,192 tokens: at
    512 x 1,024 blocks 30 tiles in 32 steps forward (16 blocks of
    queries by 2 of keys) and backward (8 blocks of keys by 4 of
    queries) where the grid over the sequence takes 128 for the causal
    kernel's 72; at the blocks a windowed call gets where it names none
    (1,024 x 1,024 under a window of 1,024 or more) 15 tiles in 16."""
    from baton_tpu.ops import flash_attention as fa

    assert (fa.tiles_visited(8192, 1024, 512, 1024),
            fa.tiles_visited(8192)) == (30, 72)
    assert (fa.tiles_visited(8192, 1024, 1024, 1024),
            fa.tiles_visited(8192, None, 1024, 1024)) == (15, 36)
    assert fa.tiles_visited(1024, 1024, 512, 1024) == fa.tiles_visited(1024)
    assert [fa.grid_steps(8192, 1024, 512, 1024, backward=b)
            for b in (False, True)] == [32, 32]
    assert [fa.grid_steps(8192, backward=b) for b in (False, True)] \
        == [128, 128]
    # a call that names no blocks: the windowless 512 x 1,024 whatever
    # the length, and under a window the window decides
    assert fa._own_blocks(None, None, None) == (512, 1024)
    assert fa._own_blocks(1023, None, None) == (512, 1024)
    assert fa._own_blocks(1024, None, None) == (1024, 1024)
    assert fa._own_blocks(4096, None, 512) == (1024, 512)
    assert fa._own_blocks(4096, 256, None) == (256, 1024)
    assert (fa.tiles_visited(8192, 1024), fa.grid_steps(8192, 1024),
            fa.grid_steps(8192, 1024, backward=True)) == (15, 16, 16)
    assert (fa.tiles_visited(8192, 512), fa.grid_steps(8192, 512)) \
        == (fa.tiles_visited(8192, 512, 512, 1024),
            fa.grid_steps(8192, 512, 512, 1024))
    # blocks of 512 x 512 (3 a row and a column), 1,024 x 1,024 (2), and
    # 1,024 x 512 (4 blocks of keys a row, 2 of queries a column)
    assert [fa.grid_steps(8192, 1024, bq, bk, backward=b)
            for bq, bk in ((512, 512), (1024, 1024), (1024, 512))
            for b in (False, True)] == [48, 48, 16, 16, 32, 32]
    # a window that never binds walks a row's causal prefix, the longest
    # of which is the sequence; one key a query, one block or two
    assert fa.grid_steps(1024, 4096, 128, 128) == 8 * 8
    assert fa.grid_steps(1024, 1, 128, 128) == 8
    assert fa.grid_steps(1024, 1, 256, 128) == 4 * 2
    # a sequence shorter than a block is one step
    assert fa.grid_steps(100, 10) == fa.grid_steps(100) == 1


# jaxpr digests of calls without a window, taken on the commit before
# the window came (PR 47's tree: the same four calls, sha256 of
# ``str(jax.make_jaxpr(grad))``): grouped heads in one backward kernel,
# the two passes, keys on the sublanes with a choice of keys, no mask
WINDOWLESS = {
    "one_kernel_gqa": ((1, 4, 2, 512, 128, 128, 128, 256, False, True),
                       "9c9b0e796563fc98"),
    "two_pass": ((1, 2, 1, 32768, 128, 128, 512, 1024, False, True),
                 "4bc0e3d395083a3a"),
    "d_major_chosen": ((1, 2, 2, 256, 192, 128, 128, 128, True, True),
                       "c890733467d1cead"),
    "not_causal": ((1, 2, 2, 256, 128, 128, 128, 128, False, False),
                   "bba0c5c2c5bad279"),
}


@pytest.mark.parametrize("name", sorted(WINDOWLESS))
def test_a_call_without_a_window_is_the_program_it_was(name):
    import hashlib

    (b, hq, hkv, l, dk, dv, bq, bk, chosen, causal), digest = WINDOWLESS[name]
    q = jnp.zeros((b, hq, l, dk), jnp.bfloat16)
    k = jnp.zeros((b, hkv, l, dk), jnp.bfloat16)
    v = jnp.zeros((b, hkv, l, dv), jnp.bfloat16)
    c = jnp.ones((b, l, l), jnp.int8) if chosen else None

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk, chosen=c,
            interpret=True).astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    # and a window is another program
    if causal and not chosen and l <= 512:
        def windowed(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk, window=128,
                interpret=True).astype(jnp.float32))
        other = str(jax.make_jaxpr(jax.grad(windowed, argnums=(0, 1, 2)))(
            q, k, v))
        assert other != text
