"""Mamba-2 state-space mixer: a ``[d_state, head_dim]`` state a head
under a scalar decay a head and token (Dao & Gu, arXiv:2405.21060, the
state-space duality form), as Falcon-H1 (arXiv:2507.22448) runs it
beside attention with its own multipliers.

With ``u`` the block's normalised input, ``m`` the five multipliers of
:class:`~baton_tpu.models.transformer.Multipliers` laid over the parts
of the input projection, head ``h`` reading group ``h // (H / G)`` of
``B`` and ``C``, and ``t`` the position::

    z, xBC, dt  = split(((ssm_in u) W_in) * m)
    xs, B, C    = split(silu(conv4(xBC) + bias))
    delta_t     = softplus(dt_t + dt_bias),  a_t = exp(-exp(A_log) delta_t)
    S_t         = a_t S_{t-1} + delta_t B_t xs_t^T            S_0 = 0
    y_t         = C_t^T S_t + D xs_t
    out         = ssm_out (rms_norm_group(y * silu(z)) * norm) W_out

The recurrence is computed in chunks (:func:`chunked_ssd`): inside a
chunk ``C B^T`` a group, times the lower-triangular decay ``exp(segsum
(delta A))`` a head, which is formed from differences of the running sum
*masked before* the exponential (above the diagonal the difference is
positive and unbounded), one product with ``delta xs``; a chunk's end
state as one batched product; between chunks a ``lax.scan`` that carries
the ``[H, d_state, head_dim]`` state and does the sequential part alone;
the read-out of the carried state one more batched product. Decays,
``delta``, the running sums and the carried state are float32; the
matrix operands are in the activations' dtype with float32 sums, as
:mod:`baton_tpu.models.delta_rule`'s are. The convolution is that
module's slices and products. Nothing here knows a client axis: the
mixer vmaps like any other block.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.models.delta_rule import _causal_conv_silu
from baton_tpu.models.transformer import Multipliers, dense_init, scaled


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    n_heads: int = 32
    head_dim: int = 128
    d_state: int = 256
    # B and C are shared by the n_heads / n_groups heads of a group
    n_groups: int = 2
    conv_taps: int = 4
    chunk: int = 128
    # the gated norm's, over each group's channels
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(f"{self.n_heads} heads do not divide into "
                             f"{self.n_groups} groups")

    @property
    def d_ssm(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def parts(self) -> tuple:
        """The widths of z, x, B, C and dt in the input projection."""
        bc = self.n_groups * self.d_state
        return (self.d_ssm, self.d_ssm, bc, bc, self.n_heads)

    def spread(self, five) -> np.ndarray:
        """One number a part laid over the projection's columns."""
        return np.repeat(np.asarray(five, np.float32), self.parts)


def mamba2_init(key, d_model: int, cfg: SSMConfig,
                multipliers: Multipliers = Multipliers(), out_std=None):
    """``A_log`` and ``dt_bias`` as :func:`~baton_tpu.models.delta_rule.
    gated_delta_init` draws them (Mamba 2's). ``D`` and the gated
    norm's weight are drawn uniform in [0.5, 1.5] and the convolution's
    bias like its taps, not ones and zeros: a test has to tell a term
    that is applied from one left out. The two projections are drawn
    against their multipliers (:class:`Multipliers`), the input
    projection a part at a time."""
    k_in, k_out, k_w, k_b, k_a, k_dt, k_d, k_n = jax.random.split(key, 8)
    channels = cfg.d_ssm + 2 * cfg.n_groups * cfg.d_state
    bound = cfg.conv_taps ** -0.5
    dt = jnp.exp(jax.random.uniform(k_dt, (cfg.n_heads,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": dense_init(k_in, d_model, sum(cfg.parts)) / (
            multipliers.ssm_in * cfg.spread(multipliers.ssm)),
        "conv_w": jax.random.uniform(k_w, (cfg.conv_taps, channels),
                                     jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(k_b, (channels,), jnp.float32, -bound,
                                     bound),
        "a_log": jnp.log(jax.random.uniform(k_a, (cfg.n_heads,), jnp.float32,
                                            1e-3, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d": jax.random.uniform(k_d, (cfg.n_heads,), jnp.float32, 0.5, 1.5),
        "norm": jax.random.uniform(k_n, (cfg.d_ssm,), jnp.float32, 0.5, 1.5),
        "out_proj": dense_init(
            k_out, cfg.d_ssm, d_model,
            (out_std or cfg.d_ssm ** -0.5) / multipliers.ssm_out),
    }


@jax.named_scope("ssd_scan")
def chunked_ssd(x, delta, a, b_mat, c_mat, d_skip, chunk: int):
    """``y [B, L, H, P]`` of the recurrence above from a zero state.

    ``x [B, L, H, P]`` and ``b_mat, c_mat [B, L, G, N]`` in the dtype the
    products run in; ``delta [B, L, H]`` (not negative), ``a [H]``
    (negative) and ``d_skip [H]`` float32. Any ``L``: the chunk is the
    shorter of ``chunk`` and ``L``, and the tail is padded with tokens
    that leave the state alone (``delta = 0``: no decay, no update),
    which follow every real token."""
    b, l, h, p = x.shape
    g, n = b_mat.shape[2:]
    k = h // g
    dtype, f32 = x.dtype, jnp.float32
    c = min(chunk, l)
    nc = -(-l // c)
    if nc * c != l:
        x, delta, b_mat, c_mat = (
            jnp.pad(m, ((0, 0), (0, nc * c - l)) + ((0, 0),) * (m.ndim - 2))
            for m in (x, delta, b_mat, c_mat))
    # [B, L, ...] -> [B, Z, C, ...], the heads as [G, K]
    x = x.reshape(b, nc, c, g, k, p)
    delta = delta.reshape(b, nc, c, g, k)
    b_mat, c_mat = (arr.reshape(b, nc, c, g, n) for arr in (b_mat, c_mat))
    # log of the decay since the chunk's start, the token's own included
    since = jnp.cumsum(delta * a.reshape(g, k), axis=2)
    x_delta = x.astype(f32) * delta[..., None]

    # inside a chunk: token i reads token j <= i through exp(since_i -
    # since_j); above the diagonal that difference is positive without
    # bound, so it is masked before the exponential, and again after it
    rows = jnp.moveaxis(since, 2, -1)  # [B, Z, G, K, C]
    lower = jnp.tril(jnp.ones((c, c), bool))
    gap = rows[..., :, None] - rows[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gap, 0.0)), 0.0)
    cb = jnp.einsum("bzign,bzjgn->bzgij", c_mat, b_mat,
                    preferred_element_type=f32)
    weights = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bzgkij,bzjgkp->bzigkp", weights, x_delta.astype(dtype),
                   preferred_element_type=f32)

    # what a chunk adds to the state by its end, and how far the state it
    # met has decayed by then
    to_end = jnp.exp(since[:, :, -1:] - since)
    made = jnp.einsum("bzjgn,bzjgkp->zbgknp", b_mat,
                      (x_delta * to_end[..., None]).astype(dtype),
                      preferred_element_type=f32)
    kept = jnp.moveaxis(jnp.exp(since[:, :, -1]), 1, 0)  # [Z, B, G, K]

    def step(state, chunk_of):
        """The sequential part: the state a chunk meets, and the state
        carried to its end."""
        made_z, kept_z = chunk_of
        return state * kept_z[..., None, None] + made_z, state.astype(dtype)

    _, met = jax.lax.scan(step, jnp.zeros((b, g, k, n, p), f32),
                          (made, kept))
    y = y + jnp.exp(since)[..., None] * jnp.einsum(
        "bzign,zbgknp->bzigkp", c_mat, met, preferred_element_type=f32)
    y = y + d_skip.astype(f32).reshape(g, k, 1) * x.astype(f32)
    return y.reshape(b, nc * c, h, p)[:, :l].astype(dtype)


def _between_projections(proj, p, cfg: SSMConfig, on_parts: tuple):
    """From the input projection's ``proj [B, L, sum(cfg.parts)]`` to
    the gated, normalised ``y [B, L, d_ssm]`` the output projection
    takes, both in the activations' dtype; float32 in between (``dt``'s
    one number a head, the convolution, the gates, the norm), the
    recurrence's operands in the activations' dtype again."""
    b, l, _ = proj.shape
    dtype, f32 = proj.dtype, jnp.float32
    h, g, n = cfg.n_heads, cfg.n_groups, cfg.d_state
    proj = proj.astype(f32)
    if any(m != 1 for m in on_parts):
        proj = proj * cfg.spread(on_parts)
    z, xbc, dt = jnp.split(proj, (cfg.d_ssm, sum(cfg.parts[:4])), axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc = _causal_conv_silu(xbc, p["conv_w"], p["conv_b"])
    xs, b_mat, c_mat = jnp.split(xbc, (cfg.d_ssm, cfg.d_ssm + g * n), axis=-1)
    y = chunked_ssd(
        xs.reshape(b, l, h, cfg.head_dim).astype(dtype),
        jax.nn.softplus(dt + p["dt_bias"].astype(f32)),
        -jnp.exp(p["a_log"].astype(f32)),
        b_mat.reshape(b, l, g, n).astype(dtype),
        c_mat.reshape(b, l, g, n).astype(dtype), p["d"], cfg.chunk)
    # gated, then normalised a group of channels at a time
    y = y.astype(f32).reshape(b, l, g, -1) * jax.nn.silu(z).reshape(
        b, l, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    return (y.reshape(b, l, cfg.d_ssm) * p["norm"].astype(f32)).astype(dtype)


@jax.named_scope("ssm")
def mamba2_apply(p, u, cfg: SSMConfig,
                 multipliers: Multipliers = Multipliers()):
    """The branch over ``u [B, L, D]`` (already normalised) -> ``[B, L,
    D]``. What lies between the two projections is under a checkpoint
    of its own: its float32 intermediates and the recurrence's (a
    chunk's decays, every chunk's state twice) are ten times the two
    arrays it keeps instead, and a fiftieth of a block's work to make
    again."""
    between = {name: p[name] for name in ("conv_w", "conv_b", "dt_bias",
                                          "a_log", "d", "norm")}
    y = jax.checkpoint(_between_projections, static_argnums=(2, 3))(
        scaled(u, multipliers.ssm_in) @ p["in_proj"].astype(u.dtype),
        between, cfg, multipliers.ssm)
    return scaled(y @ p["out_proj"].astype(u.dtype), multipliers.ssm_out)
