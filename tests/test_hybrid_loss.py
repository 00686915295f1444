"""The decoders' next-token loss at test sizes on the CPU: in blocks
against the unblocked one, a token's own logit by comparison, the
gradient on either side of a frozen or a differentiated head and what
its trace holds, what a decoder says of the side it was traced on.
(Split from ``test_hybrid_decoder.py``, PR 52, the functions as they
were: the shared inputs are ``_hybrid_decoder_shared.py``'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import transformer
from baton_tpu.models.llama import (
    LlamaConfig,
    decoder_lora_model,
    llama_lm_model,
)
from baton_tpu.models.transformer import (
    next_token_loss,
    per_token_cross_entropy,
)

from _hybrid_decoder_shared import _hybrid, _close, _equations


# ----------------------------------------------------- the loss in blocks
@pytest.mark.parametrize("length", [12, 13])
@pytest.mark.parametrize("masked", [False, True])
def test_the_loss_in_blocks_is_the_unblocked_loss(length, masked, monkeypatch):
    """Values and gradients (of the hidden states and of the head), with
    blocks that divide the length and with a padded tail block; through
    the decoder with and without a ``loss_mask``."""
    b, d, v = 3, 16, 50
    kx, kw, ky = jax.random.split(jax.random.key(length), 3)
    x = jax.random.normal(kx, (b, length, d))
    w = jax.random.normal(kw, (d, v)) * d ** -0.5
    y = jax.random.randint(ky, (b, length), 0, v)
    weight = jnp.arange(1.0, b * length + 1).reshape(b, length)

    def unblocked(x, w):
        return per_token_cross_entropy(x @ w, y)

    def through(fn):
        return jax.value_and_grad(lambda x, w: jnp.sum(fn(x, w) * weight),
                                  argnums=(0, 1))(x, w)

    with jax.default_matmul_precision("highest"):
        want_tok, (want, want_g) = unblocked(x, w), through(unblocked)
        # one block's budget: 4 tokens of 3 rows -> 3 blocks (12), 4 (13)
        monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                            4 * b * 4 * v)
        blocked = lambda x, w: next_token_loss(x, w, y)  # noqa: E731
        text = str(jax.make_jaxpr(blocked)(x, w))
        assert "scan" in text and f"f32[{b},{length},{v}]" not in text
        got_tok, (got, got_g) = blocked(x, w), through(blocked)
    assert got_tok.shape == (b, length)
    _close(got_tok, want_tok, rtol=1e-6)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, wg in zip(got_g, want_g):
        _close(g, wg, rtol=1e-5)

    # the decoder's per-example loss, blocked and not
    model = llama_lm_model(LlamaConfig.tiny(vocab_size=v))
    params = model.init(jax.random.key(1))
    batch = {"x": y, "y": jnp.roll(y, -1, axis=1)}
    if masked:
        batch["loss_mask"] = (jnp.arange(length) < length // 2).astype(
            jnp.float32)[None].repeat(b, 0)
    got_loss = model.per_example_loss(params, batch, None)
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 2 ** 40)
    want_loss = model.per_example_loss(params, batch, None)
    np.testing.assert_allclose(np.asarray(got_loss), np.asarray(want_loss),
                               rtol=1e-5)


def test_block_count_follows_the_shapes():
    """The published head (100,352 ids) at 1,024 tokens a row is four
    blocks of 256; a small vocabulary is one block and no scan."""
    small = str(jax.make_jaxpr(lambda x, w: next_token_loss(
        x, w, jnp.zeros((2, 16), jnp.int32)))(
            jnp.zeros((2, 16, 8)), jnp.zeros((8, 96))))
    assert "scan" not in small
    big = jax.make_jaxpr(lambda x, w: next_token_loss(
        x, w, jnp.zeros((1, 1024), jnp.int32)))(
            jax.ShapeDtypeStruct((1, 1024, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((64, 100352), jnp.bfloat16))
    scans = [e for e in _equations(big.jaxpr) if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 4


@pytest.mark.parametrize("under_vmap", [False, True])
@pytest.mark.parametrize("shape", [(2, 16, 128), (4, 61, 203)])
def test_a_tokens_own_logit_by_comparison_is_the_gathers(shape, under_vmap):
    """Values and the gradient with respect to the logits against
    ``logz - take_along_axis``, at an aligned and a misaligned shape;
    a label outside ``[0, V)`` reads a logit of 0."""
    v = shape[-1]
    kl, ky, kg = jax.random.split(jax.random.key(v), 3)
    logits = 3.0 * jax.random.normal(kl, shape)
    labels = jax.random.randint(ky, shape[:-1], 0, v)
    weight = jax.random.normal(kg, shape[:-1])

    def gathered(logits, labels):
        own = jnp.take_along_axis(logits, labels[..., None], axis=-1)
        return jax.nn.logsumexp(logits, axis=-1) - own[..., 0]

    wrap = jax.vmap if under_vmap else (lambda fn: fn)
    ours, gathers = wrap(per_token_cross_entropy), wrap(gathered)

    def gradient(fn):
        return jax.grad(lambda z: jnp.sum(fn(z, labels) * weight))(logits)

    np.testing.assert_allclose(
        np.asarray(ours(logits, labels)),
        np.asarray(gathers(logits, labels)), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(gradient(ours)), np.asarray(gradient(gathers)),
        rtol=0, atol=1e-6)

    outside = labels.at[0, 0].set(v).at[-1, -1].set(-1)
    tok = np.asarray(ours(logits, outside))
    logz = np.asarray(jax.nn.logsumexp(logits, axis=-1))
    assert tok[0, 0] == logz[0, 0] and tok[-1, -1] == logz[-1, -1]
    np.testing.assert_array_equal(
        tok.ravel()[1:-1], np.asarray(ours(logits, labels)).ravel()[1:-1])


@pytest.mark.parametrize("scanned", [False, True])
@pytest.mark.parametrize("tied", [False, True])
def test_the_losss_gradient_lowers_without_a_scatter(tied, scanned,
                                                     monkeypatch):
    """A gather's transpose is a scatter-add into the whole block of
    logits; the comparison's is a ``where``."""
    b, l, d, v = 2, 12, 16, 50
    if scanned:  # blocks of 4 tokens
        monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                            4 * b * 4 * v)
    y = jnp.zeros((b, l), jnp.int32)

    def loss(x, w):
        return jnp.sum(next_token_loss(x, w, y, tied=tied))

    args = jnp.zeros((b, l, d)), jnp.zeros((v, d) if tied else (d, v))
    assert ("scan" in str(jax.make_jaxpr(loss)(*args))) == scanned
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).as_text()
    assert "dot_general" in lowered and "scatter" not in lowered


def _eqns(jaxpr, name):
    return [e for e in _equations(jaxpr) if e.primitive.name == name]


@pytest.mark.parametrize("under_vmap", [False, True],
                         ids=["plain", "clients_under_vmap"])
@pytest.mark.parametrize("length,blocks", [(12, 1), (13, 4)],
                         ids=["one_block", "blocks_with_a_padded_tail"])
@pytest.mark.parametrize("head", ["frozen", "differentiated"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_losss_gradient_on_either_side_is_the_unblocked_losss(
        tied, head, length, blocks, under_vmap, monkeypatch):
    """Values, ``dx`` and (where the head takes a gradient) ``dW``
    against ``per_token_cross_entropy(x @ w, y)`` at ``highest``, under
    a per-token weight as a ``loss_mask`` gives and a multiplier on the
    logits; a label outside ``[0, V)`` reads a logit of 0 on both
    sides of the gradient too."""
    c, b, d, v, multiplier = 3, 2, 16, 50, 1.5
    kx, kw, ky, kg = jax.random.split(jax.random.key(length), 4)
    x = jax.random.normal(kx, (c, b, length, d))
    w = jax.random.normal(kw, (v, d) if tied else (d, v)) * d ** -0.5
    y = jax.random.randint(ky, (c, b, length), 0, v)
    y = y.at[0, 0, 0].set(v).at[-1, -1, -1].set(-1)
    weight = jax.random.uniform(kg, (c, b, length)) * (
        jnp.arange(length) % 3 != 1)
    if not under_vmap:
        x, y, weight = x[0], y[0], weight[0]
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                        4 * b * 4 * v if blocks > 1 else 2 ** 40)

    def unblocked(x, w, y):
        return per_token_cross_entropy(
            multiplier * (x @ (w.T if tied else w)), y)

    def blocked(x, w, y):
        return next_token_loss(x, w, y, tied=tied, multiplier=multiplier)

    def through(fn):
        over = (jax.vmap(fn, in_axes=(0, None, 0)) if under_vmap else fn)
        return jax.value_and_grad(
            lambda x, w: jnp.sum(over(x, w, y) * weight),
            argnums=(0, 1) if head == "differentiated" else 0)(x, w)

    x1, y1 = (x[0], y[0]) if under_vmap else (x, y)  # one client's
    with jax.default_matmul_precision("highest"):
        assert ("scan" in str(jax.make_jaxpr(blocked)(x1, w, y1))) == (
            blocks > 1)
        want, want_g = through(unblocked)
        got, got_g = through(blocked)
        tok = blocked(x1, w, y1)
        logz = jax.nn.logsumexp(
            multiplier * (x1 @ (w.T if tied else w)), axis=-1)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, wg in zip(jax.tree_util.tree_leaves(got_g),
                     jax.tree_util.tree_leaves(want_g)):
        assert g.shape == wg.shape and g.dtype == wg.dtype
        _close(g, wg, rtol=1e-5)
    assert float(tok[0, 0]) == pytest.approx(float(logz[0, 0]), rel=1e-6)


@pytest.mark.parametrize("under_vmap", [False, True],
                         ids=["plain", "clients_under_vmap"])
@pytest.mark.parametrize("head,dots,said", [("frozen", 2, 2),
                                            ("differentiated", 4, 3)])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_a_frozen_heads_gradient_is_made_in_the_forward(tied, head, dots, said,
                                                        under_vmap,
                                                        monkeypatch):
    """With the head closed over, the gradient of the blocked loss is
    one scan of two products a block, the logits and ``(softmax -
    onehot)`` back through the head, and a multiply by the cotangent;
    with the head differentiated the blocks are checkpointed as they
    were: the logits, the logits again and ``dx``, and ``dW`` the
    fourth. ``head_products_a_block`` says which was traced, and
    nothing after a call that traced no gradient of a blocked loss."""
    c, b, l, d, v = 3, 2, 12, 16, 50
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 4 * b * 4 * v)
    y = jnp.zeros((c, b, l), jnp.int32)
    x, w = jnp.zeros((c, b, l, d)), jnp.zeros((v, d) if tied else (d, v))

    def client(x, w, y):
        return jnp.sum(next_token_loss(x, w, y, tied=tied))

    def loss(x, w):
        if under_vmap:
            return jnp.sum(jax.vmap(client, in_axes=(0, None, 0))(x, w, y))
        return client(x[0], w, y[0])

    if head == "frozen":
        grad = jax.grad(lambda x: loss(x, w))
        jaxpr = jax.make_jaxpr(grad)(x).jaxpr
    else:
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr
    assert transformer.head_products_a_block() == said
    assert len(_eqns(jaxpr, "dot_general")) == dots
    assert len(_eqns(jaxpr, "scan")) == (1 if head == "frozen" else 2)
    assert bool(_eqns(jaxpr, "remat2")) == (head != "frozen")
    if head == "frozen":  # the scan keeps one array of the stream's shape
        scan, = _eqns(jaxpr, "scan")
        shapes = [a.aval.shape for a in scan.outvars]
        lead = (3, c) if under_vmap else (3,)
        assert sorted(shapes) == sorted([lead + (b, 4), lead + (b, 4, d)])
    jax.make_jaxpr(lambda x: loss(x, w))(x)  # values alone
    assert transformer.head_products_a_block() is None
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 2 ** 40)
    jax.make_jaxpr(jax.grad(lambda x: loss(x, w)))(x)  # one block
    assert transformer.head_products_a_block() is None


def test_a_loss_that_takes_no_cotangent_hands_none_back(monkeypatch):
    """The blocked loss as an output nothing differentiates, beside one
    that is: the stream's gradient is the other output's alone."""
    b, l, d, v = 2, 12, 16, 50
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 4 * b * 4 * v)
    x = jax.random.normal(jax.random.key(0), (b, l, d))
    w = jax.random.normal(jax.random.key(1), (d, v))
    y = jnp.zeros((b, l), jnp.int32)
    g, tok = jax.grad(lambda x: (jnp.sum(x), next_token_loss(x, w, y)),
                      has_aux=True)(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones((b, l, d)))
    _close(tok, per_token_cross_entropy(x @ w, y), rtol=1e-5)


@pytest.mark.parametrize("head,said", [("frozen", 2), ("trained", 3)])
def test_a_decoder_says_which_side_its_loss_was_traced_on(head, said,
                                                          monkeypatch):
    """``span_attrs`` gains ``head_products_a_block`` where a gradient
    of the blocked loss is traced: 2 under adapters, whose base holds
    the head, 3 where the whole model trains; a model whose loss is one
    block says nothing."""
    cfg = _hybrid(n_layers=2)
    model = (decoder_lora_model(cfg, rank=2, b_std=0.02) if head == "frozen"
             else llama_lm_model(cfg))
    params = model.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 13), 0, cfg.vocab_size)
    batch = {"x": ids[:, :-1], "y": ids[:, 1:]}

    def loss(trained, held):
        p = {**held, **trained} if held else trained
        return jnp.sum(model.per_example_loss(p, batch, None))

    trained, held = (({"lora": params["lora"]}, {"base": params["base"]})
                     if head == "frozen" else (params, None))
    jax.make_jaxpr(jax.grad(loss))(trained, held)
    assert "head_products_a_block" not in dict(model.span_attrs)
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                        4 * 2 * 4 * cfg.vocab_size)
    jax.make_jaxpr(jax.grad(loss))(trained, held)
    assert dict(model.span_attrs)["head_products_a_block"] == said
