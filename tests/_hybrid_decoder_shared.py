"""What the hybrid decoder's test files share (``test_hybrid_decoder.py``,
``test_hybrid_delta_rule.py``, ``test_hybrid_delta_rule_clients.py``,
``test_hybrid_loss.py``): the tiny hybrid configuration, the delta
rule's recurrence token by token, its seeded inputs, a chunk's matrix
in float64, and the comparisons."""

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.models.llama import LlamaConfig

PERIOD = ("linear_attention",) * 3 + ("full_attention",)


def _hybrid(n_layers=4, chunk=4, **kw):
    return LlamaConfig.tiny(
        vocab_size=96, max_len=32, d_model=64, n_layers=n_layers, n_heads=4,
        n_kv_heads=4, d_ff=128, rope_theta=None, qk_norm=True,
        layer_types=PERIOD * 2, linear_n_heads=4, linear_key_dim=8,
        linear_value_dim=16, linear_chunk=chunk, **kw)


def _token_by_token(q, k, v, g, beta):
    """``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``: one ``lax.scan`` over the positions."""
    b, l, h, d_k = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhd,bhde->bhe", k_t, state)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t * b_t[..., None],
                                   v_t - seen)
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state)

    by_position = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d_k, v.shape[-1])),
                        by_position)
    return jnp.moveaxis(o, 0, 1)


def _scan_inputs(seed, lead, l, h=3, d_k=8, d_v=16):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    return (unit(jax.random.normal(ks[0], lead + (l, h, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(ks[1], lead + (l, h, d_k))),
            jax.random.normal(ks[2], lead + (l, h, d_v)),
            -1.5 * jax.random.uniform(ks[3], lead + (l, h)),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], lead + (l, h))))


def _keyed_inputs(keys, seed, lead, l, h=3, d_k=8, d_v=16):
    """``_scan_inputs`` with keys, gates and decays a trained mixer can
    produce and uncorrelated draws never do: ``correlated`` keys lie
    within 0.3 of one direction a head, ``beta = 1.9``, a decay within
    1 % of 1; ``identical`` keys are all the first unit vector, ``beta =
    2``, no decay (the chunk's ``A`` is exactly 2 everywhere below the
    diagonal, the largest the mixer can make)."""
    q, k, v, g, beta = _scan_inputs(seed, lead, l, h, d_k, d_v)
    if keys == "random":
        return q, k, v, g, beta
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    k0, k1 = jax.random.split(jax.random.key(seed + 100))
    direction = unit(jax.random.normal(k0, lead + (1, h, d_k)))
    if keys == "correlated":
        k = unit(direction + 0.3 * k)
        return (q, k, v, jnp.log1p(-0.01 * jax.random.uniform(k1, g.shape)),
                jnp.full_like(beta, 1.9))
    assert keys == "identical"
    return (q, jnp.zeros_like(k).at[..., 0].set(1.0), v, jnp.zeros_like(g),
            jnp.full_like(beta, 2.0))


def _chunk_matrix(k, g, beta):
    """``I + A`` of one chunk a batch element and head, in float64 on the
    host: ``A[t, s] = beta_t (k_t . k_s) alpha_(s+1) ... alpha_t`` below
    the diagonal. ``[B, H, L, L]`` from ``k [B, L, H, d_k]``."""
    k, g, beta = (np.moveaxis(np.asarray(a, np.float64), 1, 2)
                  for a in (k, g, beta))
    since = np.cumsum(g, axis=-1)
    decay = np.exp(since[..., :, None] - since[..., None, :])
    a = np.einsum("bhtd,bhsd->bhts", k * beta[..., None], k) * decay
    return np.tril(a, -1) + np.eye(a.shape[-1])


def _close(got, want, rtol=2e-5):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-12)
    assert float(jnp.max(jnp.abs(got - want))) <= rtol * scale


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _value_and_grads(fn):
    return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                              argnums=(0, 1, 2, 3, 4))
