"""Gated delta-rule mixer: linear attention with a ``[d_k, d_v]`` state a
head (Yang, Kautz, Hatamizadeh, arXiv:2412.06464; gates in ``(0, 2)``
for negative eigenvalues: Grazzi et al., arXiv:2411.12537).

Per head, with ``x`` the block's normalised input and ``t`` the position::

    q, k, v = silu(conv4(x Wq)), silu(conv4(x Wk)), silu(conv4(x Wv))
    q, k    = q / |q| / sqrt(d_k),  k / |k|
    beta_t  = 2 sigmoid(x Wb)                 (sigmoid alone without
                                               ``allow_neg_eigval``)
    alpha_t = exp(-exp(A_log) softplus(x Wa + dt_bias))
    S_t     = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t
    y       = [rms_norm_head(o_t) * silu(x Wg)] Wo

The recurrence is computed in chunks (the WY form of arXiv:2412.06464
section 3): inside a chunk matrix products and one unit-triangular
solve, between chunks the state carried by a ``lax.scan`` that does the
sequential part alone (the state at each chunk's start and the chunk's
corrected values); every chunk's output is then one batched product.
State, gates and the solve are float32, the matrix operands are in the
activations' dtype. The backward is JAX's through that scan. Nothing
here knows a client axis: the mixer vmaps like any other block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from baton_tpu.models.transformer import dense_init, matmul

CONV_TAPS = 4


def gated_delta_init(key, d_model, n_heads, d_k, d_v, out_std=None):
    """``A_log`` and ``dt_bias`` as the paper's code draws them (Mamba
    2's): ``A ~ U(0, 16)``, a step ``dt`` log-uniform in ``[1e-3, 1e-1]``
    stored through the inverse of softplus. The four-tap convolutions
    are depthwise ``[taps, channels]``, no bias."""
    kq, kk, kv, kg, ko, ka, kb, kc, kA, kdt = jax.random.split(key, 10)
    kcq, kck, kcv = jax.random.split(kc, 3)

    def conv(k, ch):
        bound = CONV_TAPS ** -0.5
        return jax.random.uniform(k, (CONV_TAPS, ch), jnp.float32, -bound,
                                  bound)

    dt = jnp.exp(jax.random.uniform(kdt, (n_heads,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "wq": dense_init(kq, d_model, n_heads * d_k),
        "wk": dense_init(kk, d_model, n_heads * d_k),
        "wv": dense_init(kv, d_model, n_heads * d_v),
        "wg": dense_init(kg, d_model, n_heads * d_v),
        "wo": dense_init(ko, n_heads * d_v, d_model, stddev=out_std),
        "wa": dense_init(ka, d_model, n_heads),
        "wb": dense_init(kb, d_model, n_heads),
        "conv_q": conv(kcq, n_heads * d_k),
        "conv_k": conv(kck, n_heads * d_k),
        "conv_v": conv(kcv, n_heads * d_v),
        "a_log": jnp.log(jax.random.uniform(kA, (n_heads,), jnp.float32,
                                            1e-3, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm_o": jnp.ones((d_v,), jnp.float32),
    }


def _causal_conv_silu(x, taps):
    """Depthwise causal convolution over ``x [B, L, ch]`` with ``taps
    [T, ch]`` (tap ``T-1`` meets the current token), then SiLU; float32."""
    x = x.astype(jnp.float32)
    n_taps, l = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n_taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + l] * taps[j].astype(jnp.float32)
            for j in range(n_taps))
    return jax.nn.silu(y)


def _l2_normalised(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@jax.named_scope("delta_scan")
def chunked_delta_rule(q, k, v, g, beta, chunk: int):
    """``o [B, L, H, d_v]`` of the gated delta rule from a zero state.

    ``q, k [B, L, H, d_k]`` and ``v [B, L, H, d_v]`` in the dtype the
    products run in; ``g = log alpha`` and ``beta`` ``[B, L, H]``
    float32. Any ``L``: the chunk is the shorter of ``chunk`` and ``L``,
    and the tail is padded with tokens that leave the state alone
    (``beta = 0``, ``alpha = 1``), which follow every real token."""
    b, l, h, d_k = q.shape
    d_v = v.shape[-1]
    dtype = v.dtype
    c = min(chunk, l)
    n = -(-l // c)
    if n * c != l:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, n * c - l)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))

    def chunks(a):  # [B, L, H, ...] -> [B, H, N, C, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    f32 = jnp.float32
    decay_to = jnp.cumsum(g, axis=-1)  # log of the decay since chunk start
    lower = jnp.tril(jnp.ones((c, c), bool))
    gap = decay_to[..., :, None] - decay_to[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gap, 0.0)), 0.0)
    k_beta = k.astype(f32) * beta[..., None]
    # (I + A) [u | w] = [beta v | beta k alpha^(0..t)], A strictly lower
    a = jnp.einsum("bhncd,bhnsd->bhncs", k_beta.astype(dtype), k,
                   preferred_element_type=f32) * decay
    a = jnp.where(jnp.tril(lower, -1), a, 0.0) + jnp.eye(c, dtype=f32)
    rhs = jnp.concatenate(
        [v.astype(f32) * beta[..., None],
         k_beta * jnp.exp(decay_to)[..., None]], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., :d_v].astype(dtype), solved[..., d_v:].astype(dtype)
    to_end = decay_to[..., -1:]
    k_end = (k.astype(f32) * jnp.exp(to_end - decay_to)[..., None]
             ).astype(dtype)

    def step(state, xs):
        """The sequential part: the chunk's values corrected by what the
        state already predicts, and the state carried to its end."""
        w_i, u_i, k_i, decay_i = xs
        v_new = u_i.astype(f32) - jnp.einsum(
            "bhcd,bhde->bhce", w_i, state.astype(dtype),
            preferred_element_type=f32)
        v_new = v_new.astype(dtype)
        carried = state * decay_i[..., None, None] + jnp.einsum(
            "bhcd,bhce->bhde", k_i, v_new, preferred_element_type=f32)
        return carried, (state.astype(dtype), v_new)

    by_chunk = tuple(jnp.moveaxis(a, 2, 0)
                     for a in (w, u, k_end, jnp.exp(to_end[..., 0])))
    _, (states, v_new) = jax.lax.scan(
        step, jnp.zeros((b, h, d_k, d_v), f32), by_chunk)
    states, v_new = jnp.moveaxis(states, 0, 2), jnp.moveaxis(v_new, 0, 2)
    within = jnp.einsum("bhncd,bhnsd->bhncs", q, k,
                        preferred_element_type=f32) * decay
    q_decayed = (q.astype(f32) * jnp.exp(decay_to)[..., None]).astype(dtype)
    o = jnp.einsum("bhncd,bhnde->bhnce", q_decayed, states,
                   preferred_element_type=f32)
    o = o + jnp.einsum("bhncs,bhnse->bhnce", within.astype(dtype), v_new,
                       preferred_element_type=f32)
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, h, d_v)
    return o[:, :l].astype(dtype)


@jax.named_scope("linear_attention")
def gated_delta_apply(p, x, n_heads: int, chunk: int = 64,
                      allow_neg_eigval: bool = True, eps: float = 1e-6):
    """The mixer over ``x [B, L, D]`` (already normalised) -> ``[B, L, D]``."""
    b, l, _ = x.shape
    d_k = p["wq"].shape[1] // n_heads
    d_v = p["wv"].shape[1] // n_heads

    def proj(w):
        return x @ w.astype(x.dtype)

    def gate(w):  # a gate's 30 outputs are not rounded to x's dtype
        return matmul(x, w, jnp.float32)

    q = _causal_conv_silu(proj(p["wq"]), p["conv_q"]).reshape(b, l, n_heads, d_k)
    k = _causal_conv_silu(proj(p["wk"]), p["conv_k"]).reshape(b, l, n_heads, d_k)
    v = _causal_conv_silu(proj(p["wv"]), p["conv_v"]).reshape(b, l, n_heads, d_v)
    q = _l2_normalised(q) * d_k ** -0.5
    k = _l2_normalised(k)
    beta = jax.nn.sigmoid(gate(p["wb"]))
    if allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        gate(p["wa"]) + p["dt_bias"].astype(jnp.float32))
    o = chunked_delta_rule(q.astype(x.dtype), k.astype(x.dtype),
                           v.astype(x.dtype), g, beta, chunk)
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * p["norm_o"].astype(jnp.float32)
    gate = jax.nn.silu(proj(p["wg"]).astype(jnp.float32))
    y = (o.reshape(b, l, n_heads * d_v) * gate).astype(x.dtype)
    return y @ p["wo"].astype(x.dtype)
