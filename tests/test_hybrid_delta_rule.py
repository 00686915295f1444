"""The gated delta rule of the hybrid decoder's linear-attention layers
at test sizes on the CPU: the chunked form against the recurrence
written token by token (values and gradients, lengths that are and are
not a multiple of the chunk, keys that correlate), the chunk's inverse
against float64 and what its trace holds, the gates' draw. (Split from
``test_hybrid_decoder.py`` by mixer, PR 52, the functions as they were:
the shared inputs are ``_hybrid_decoder_shared.py``'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import delta_rule
from baton_tpu.models.delta_rule import chunked_delta_rule, gated_delta_init

from _hybrid_decoder_shared import (
    _token_by_token,
    _scan_inputs,
    _keyed_inputs,
    _chunk_matrix,
    _close,
    _equations,
    _value_and_grads,
)


@pytest.mark.parametrize("length,chunk", [(16, 4), (10, 4), (7, 64),
                                          (130, 64), (64, 64)])
def test_chunked_delta_rule_is_the_recurrence_token_by_token(length, chunk):
    """Values and all five gradients; 10 and 130 tokens leave a tail
    chunk that is padded, 7 tokens are one chunk shorter than 64."""
    args = _scan_inputs(length, (2,), length)
    with jax.default_matmul_precision("highest"):
        want_o = _token_by_token(*args)
        got_o = chunked_delta_rule(*args, chunk)
        want, want_g = _value_and_grads(_token_by_token)(*args)
        got, got_g = _value_and_grads(
            lambda *a: chunked_delta_rule(*a, chunk))(*args)
    assert got_o.shape == want_o.shape == (2, length, 3, 16)
    _close(got_o, want_o)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        _close(g, w)


@pytest.mark.parametrize("keys,rtol", [("correlated", 2e-5),
                                       ("identical", 2e-4)])
def test_chunked_delta_rule_on_keys_that_correlate(keys, rtol):
    """Two chunks of 64 whose ``I + A`` is far from the identity, values
    and all five gradients. With identical keys the recurrence itself is
    ill-conditioned, the float32 token-by-token scan no less: that limit
    says no worse than forward substitution, not exact."""
    args = _keyed_inputs(keys, 5, (2,), 128)
    with jax.default_matmul_precision("highest"):
        want_o = _token_by_token(*args)
        got_o = chunked_delta_rule(*args, 64)
        _, want_g = _value_and_grads(_token_by_token)(*args)
        _, got_g = _value_and_grads(
            lambda *a: chunked_delta_rule(*a, 64))(*args)
    _close(got_o, want_o, rtol)
    for g, w in zip(got_g, want_g):
        _close(g, w, rtol)


@pytest.mark.parametrize("size", [1, 4, 7, 24, 50, 64])
@pytest.mark.parametrize("keys", ["random", "correlated", "identical"])
def test_the_chunk_inverse_against_float64(keys, size):
    """``_unit_lower_inverse`` of one chunk's ``I + A`` against
    ``numpy.linalg.inv`` in float64, to 1e-5 of the inverse's largest
    entry: a single row, the tests' chunk of 4, three sizes that are no
    power of two (7, 24 and 50 are padded to 8, 32 and 64) and the
    cell's 64."""
    _, k, _, g, beta = _keyed_inputs(keys, size, (2,), size)
    a = _chunk_matrix(k, g, beta).astype(np.float32)
    want = np.linalg.inv(a.astype(np.float64))
    got = np.asarray(delta_rule._unit_lower_inverse(jnp.asarray(a)))
    assert got.shape == a.shape and got.dtype == np.float32
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert not np.triu(got, 1).any()


def test_the_gradient_inverts_a_chunk_once_by_products_at_highest(monkeypatch):
    """What no CPU run shows by value. The trace of value and gradients
    (activations in bfloat16, as the cell runs them) holds no
    ``triangular_solve``; its products of two float32 operands are ``T
    rhs`` and the two of the hand-written backward (every other product
    has an operand in bfloat16) and each says ``Precision.HIGHEST``
    itself, because a TPU's default rounds float32 operands to bfloat16;
    the inverse holds no ``dot_general`` at all (its levels are sums of
    elementwise products, float32 on any backend); and the backward
    reuses the forward's inverse."""
    calls = []
    inverse = delta_rule._unit_lower_inverse
    monkeypatch.setattr(delta_rule, "_unit_lower_inverse",
                        lambda a: calls.append(a.shape) or inverse(a))
    q, k, v, g, beta = _keyed_inputs("correlated", 1, (2,), 128)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    traced = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(chunked_delta_rule(*a, 64).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert calls == [(2, 3, 2, 64, 64)]
    eqns = list(_equations(traced.jaxpr))
    assert "triangular_solve" not in {e.primitive.name for e in eqns}
    in_float32 = [
        e for e in eqns if e.primitive.name == "dot_general"
        and all(x.aval.dtype == jnp.float32 for x in e.invars)]
    assert len(in_float32) == 3
    for e in in_float32:
        precision = e.params["precision"]
        pair = precision if isinstance(precision, tuple) else (precision,) * 2
        assert all(p == jax.lax.Precision.HIGHEST for p in pair), e
    alone = jax.make_jaxpr(inverse)(jnp.zeros((2, 64, 64)))
    assert "dot_general" not in {
        e.primitive.name for e in _equations(alone.jaxpr)}


def test_gated_delta_init_draws_the_gates_as_the_papers_code_does():
    p = gated_delta_init(jax.random.key(0), 64, 4, 8, 16)
    a = np.exp(np.asarray(p["a_log"]))
    assert ((a > 0) & (a < 16)).all()
    dt = np.log1p(np.exp(np.asarray(p["dt_bias"])))  # softplus
    assert ((dt > 9e-4) & (dt < 0.11)).all()
