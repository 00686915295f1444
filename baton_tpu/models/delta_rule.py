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
section 3): inside a chunk matrix products and the inverse of one
unit-lower matrix, ``T = (I + A)^-1``, by block recursion, once a chunk
(``_unit_lower_inverse``: six levels of small products, no row-by-row
solve); between chunks the state carried by a ``lax.scan`` that does the
sequential part alone (the state at each chunk's start and the chunk's
corrected values); every chunk's output is then one batched product.
State, gates, the inverse and ``T [beta v | beta k alpha]`` are float32:
the inverse's products are elementwise and that ``dot`` and its
backward's say ``precision=HIGHEST`` themselves, because a TPU rounds
the operands of a float32 ``dot`` to bfloat16 by default. The other
matrix operands are in the activations' dtype. The backward of
``T rhs`` is written by hand and reuses ``T`` (``_solve_unit_lower``);
the rest is JAX's through that scan. Nothing here knows a client axis:
the mixer vmaps like any other block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from baton_tpu.models.transformer import dense_init, matmul

CONV_TAPS = 4
_HIGHEST = jax.lax.Precision.HIGHEST


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


def _causal_conv_silu(x, taps, bias=None):
    """Depthwise causal convolution over ``x [B, L, ch]`` with ``taps
    [T, ch]`` (tap ``T-1`` meets the current token) and a ``bias [ch]``
    where one is given, then SiLU; float32. Slices of the padded
    sequence and products, not ``lax.conv``: under a client ``vmap``
    that would be a grouped convolution over the clients."""
    x = x.astype(jnp.float32)
    n_taps, l = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n_taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + l] * taps[j].astype(jnp.float32)
            for j in range(n_taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y)


def _l2_normalised(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _unit_lower_inverse(a):
    """``a^-1`` for ``a [..., C, C]`` float32 with a unit diagonal and
    nothing above it, any ``C``, by block recursion: a block of one row
    is its own inverse, and sizes double, ``[[L11, 0], [A21, L22]]^-1 =
    [[T11, 0], [-T22 A21 T11, T22]]``, two products a level over every
    pair of diagonal blocks of every matrix at once. Every factor is
    bounded like the inverse itself, so this is as stable as forward
    substitution (the product form ``(I - A)(I + A^2)(I + A^4)...`` is
    exact on paper and loses every digit in float32 once keys
    correlate). The blocks have 1 to ``C / 2`` rows, a corner of the
    MXU's 128 x 128 tile and of a vector register's 128 lanes, so the
    leading axes go on the lanes and a level's products are sums of
    elementwise products there: float32 as written, whatever the
    backend's matmul precision."""
    lead, c = a.shape[:-2], a.shape[-1]
    m = 1 << (c - 1).bit_length()
    # rows of zeros below: the padded blocks invert to the identity, and
    # the result's first C rows and columns never read them
    a = jnp.pad(a, ((0, 0),) * len(lead) + ((0, m - c),) * 2)
    a = jnp.moveaxis(a.reshape((-1, m, m)), 0, -1)  # [m, m, B]
    # A21 of every pair of diagonal blocks of m / 2, m / 4, ... 1 rows,
    # each [pairs, s, s, B], from the diagonal blocks of twice the rows
    below, d = [], a[None]
    while d.shape[1] > 1:
        half = d.shape[1] // 2
        below.append(d[:, half:, :half])
        d = jnp.stack([d[:, :half, :half], d[:, half:, half:]], axis=1
                      ).reshape((-1, half, half, d.shape[-1]))

    def product(x, y):  # [P, s, s, B] each, over the two middle axes
        return jnp.sum(x[:, :, :, None] * y[:, None, :, :], axis=2)

    t = jnp.ones_like(d)  # m blocks of one row
    for a21 in reversed(below):
        pairs = t.reshape((-1, 2) + a21.shape[1:])
        t11, t22 = pairs[:, 0], pairs[:, 1]
        t21 = -product(product(t22, a21), t11)
        t = jnp.concatenate(
            [jnp.concatenate([t11, jnp.zeros_like(t11)], axis=2),
             jnp.concatenate([t21, t22], axis=2)], axis=1)
    return jnp.moveaxis(t[0, :c, :c], -1, 0).reshape(lead + (c, c))


@jax.custom_vjp
def _solve_unit_lower(a, rhs):
    """``a^-1 rhs`` for a unit-lower ``a [..., C, C]`` and ``rhs [..., C,
    R]``, float32. The backward reuses the forward's inverse: with ``x =
    a^-1 rhs``, ``d rhs = a^-T d x`` and ``d a = -(d rhs) x^T`` below the
    diagonal."""
    return _solve_unit_lower_fwd(a, rhs)[0]


def _solve_unit_lower_fwd(a, rhs):
    t = _unit_lower_inverse(a)
    solved = jnp.matmul(t, rhs, precision=_HIGHEST)
    return solved, (t, solved)


def _solve_unit_lower_bwd(residuals, d_solved):
    t, solved = residuals
    d_rhs = jnp.einsum("...sc,...sr->...cr", t, d_solved,
                       precision=_HIGHEST)
    d_a = -jnp.einsum("...cr,...sr->...cs", d_rhs, solved,
                      precision=_HIGHEST)
    return jnp.tril(d_a, -1), d_rhs


_solve_unit_lower.defvjp(_solve_unit_lower_fwd, _solve_unit_lower_bwd)


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
    solved = _solve_unit_lower(a, rhs)
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
