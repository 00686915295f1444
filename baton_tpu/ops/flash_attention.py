"""Fused flash attention — the transformer zoo's hot op as a Pallas
TPU kernel.

The reference has no attention at all (its demo model is a 10→1 linear
layer, reference demo.py:15-49); this kernel exists for the model
families the new framework adds (BERT/Llama/ViT — BASELINE configs 3-5),
replacing the dense ``dot_product_attention`` einsum path
(baton_tpu/models/transformer.py) on the hot path:

* **never materializes the L×L score matrix in HBM** — scores live as
  one [block_q, block_k] VMEM tile at a time, with the online softmax
  (running max/sum rescaling) recurrence, so attention memory is
  O(L·Dh) instead of O(L²);
* **MXU-shaped**: every contraction is a ``jnp.dot`` with
  ``preferred_element_type=float32`` over 128-aligned tiles; softmax
  algebra rides the VPU in fp32 regardless of input dtype;
* **trains**: a custom VJP with a Pallas backward kernel recomputes
  p = exp(s − lse) blockwise from the saved logsumexp — the standard
  flash-attention backward — so the O(L²) probs are never stored for
  the backward pass either;
* **GQA for free**: the kv-head block index map sends query head ``h``
  to kv head ``h // (Hq//Hkv)`` — no ``jnp.repeat`` materialization;
* matches the seam contract ``attention_fn(q, k, v, bias, causal)``
  (transformer.py:31-32): additive per-key bias [B, 1, 1, L], static
  causal masking from global positions.

Interpret mode exists for the CPU tests (same code path, same math)
and is refused on a TPU backend — :func:`_resolve_interpret` is the one
place that decides.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _spec(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place that decides between the Mosaic-compiled kernel and
    the Pallas interpreter. ``None`` means the backend's only sound
    choice: compiled on a TPU, interpreted on the CPU (the test
    backend). Interpreting on a TPU would report a kernel that never
    ran, so it is an error there, as is any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError(
                "flash attention: interpret=True on a TPU backend — the "
                "Pallas interpreter is for CPU tests only")
        return False
    if backend != "cpu":
        raise NotImplementedError(
            f"flash attention has a TPU kernel and a CPU interpreter; "
            f"backend {backend!r} has neither")
    return True if interpret is None else bool(interpret)


# ======================================================================
# forward kernel: grid (B, Hq, Lq/block_q)


def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, nk):
    # Grid (B, Hq, Lq/bq, Lk/bk) with the kv axis INNERMOST ('arbitrary'):
    # the online-softmax state (acc/m/l) lives in VMEM scratch across the
    # j loop while Mosaic double-buffers the k/v block DMAs — the r2
    # whole-K/V-per-program version re-fetched all of K/V from HBM for
    # every q block (nq× traffic) and could not overlap DMA with compute.
    # m/l are (bq, 128) lane-broadcast: TPU vector layout wants the minor
    # dim lane-aligned, so the scalar-per-row state rides 128 lanes.
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: a kv block strictly in every query's future contributes
    # nothing — skip its matmuls (≈half the FLOPs on average)
    needed = True
    if causal:
        needed = j * block_k <= i * block_q + (block_q - 1)

    @pl.when(needed)
    def _accumulate():
        # operands stay in the input dtype (bf16 on the bf16 path): the
        # MXU multiplies bf16 natively with fp32 accumulation via
        # preferred_element_type — upcasting first would force 4-8x
        # slower fp32 MXU passes. Softmax statistics are fp32 throughout.
        q = q_ref[...]                                   # [bq, D]
        kj = k_ref[...]                                  # [bk, D]
        vj = v_ref[...]
        # contract D via dot_general — an explicit kj.T would force a
        # Mosaic relayout before the MXU op
        s = lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = s + b_ref[...]                               # [1, bk] bias
        if causal:
            q_pos = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, :1]                            # [bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(vj.dtype), vj, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        m = m_ref[:, :1]
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, :] = (m + jnp.log(l))[:, 0]


def _compiler_params(n_parallel: int):
    """Mark the leading grid axes parallel, the innermost sequential."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _fwd(q, k, v, bias2d, causal, scale, block_q, block_k, interpret):
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    group = hq // hkv
    nk = lk // block_k
    grid = (b, hq, lq // block_q, nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _spec((None, None, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            _spec((None, None, block_k, d),
                  lambda b_, h, i, j: (b_, h // group, j, 0)),
            _spec((None, None, block_k, d),
                  lambda b_, h, i, j: (b_, h // group, j, 0)),
            _spec((None, 1, block_k), lambda b_, h, i, j: (b_, 0, j)),
        ],
        out_specs=[
            _spec((None, None, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            _spec((None, None, 1, block_q), lambda b_, h, i, j: (b_, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, lq), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, d)),
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
        ],
        compiler_params=None if interpret else _compiler_params(3),
        interpret=interpret,
    )(q, k, v, bias2d.reshape(b, 1, lk))
    return out, lse.reshape(b, hq, lq)


# ======================================================================
# backward: the standard two-pass flash-attention backward, blockwise
# recompute of p from the saved lse (no O(L²) residuals). Pass 1 grids
# (B, Hq, Lk/block_k, Lq/block_q) and accumulates dk/dv/db over the
# innermost q axis; pass 2 grids (B, Hq, Lq/block_q, Lk/block_k) and
# accumulates dq over the innermost kv axis. Only block-sized tiles are
# ever VMEM-resident, so VMEM is O(block²), independent of L (the r1
# single-program-per-head version held ~7 full [L, d] buffers).
# delta = rowsum(do·o) is precomputed outside pallas.


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, b_ref,
                    dk_ref, dv_ref, db_ref, *, scale, causal,
                    block_q, block_k):
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref[...])
        dv_ref[...] = jnp.zeros_like(dv_ref[...])
        db_ref[...] = jnp.zeros_like(db_ref[...])

    qi = q_ref[...]                                            # [bq, D]
    doi = do_ref[...]                                          # [bq, D]
    lsei = lse_ref[0][:, None]                                 # [bq, 1]
    delta = delta_ref[0][:, None]                              # [bq, 1]
    kj = k_ref[...]                                            # [bk, D]
    vj = v_ref[...]
    bj = b_ref[...]                                            # [1, bk]

    s = (lax.dot_general(
        qi, kj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bj)
    if causal:
        q_pos = i * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lsei)                                      # [bq, bk]
    dp = lax.dot_general(
        doi, vj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)                                      # [bq, bk]
    # contract the bq axis directly (p^T·do, ds^T·q without transposes)
    dv_ref[...] += lax.dot_general(
        p.astype(doi.dtype), doi, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_ref[...] += scale * lax.dot_general(
        ds.astype(qi.dtype), qi, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    db_ref[...] += ds.sum(axis=0)[None, :]


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, b_ref,
                   dq_ref, *, scale, causal, block_q, block_k):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref[...])

    qi = q_ref[...]
    doi = do_ref[...]
    lsei = lse_ref[0][:, None]
    delta = delta_ref[0][:, None]
    kj = k_ref[...]
    vj = v_ref[...]
    bj = b_ref[...]

    s = (lax.dot_general(
        qi, kj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bj)
    if causal:
        q_pos = i * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lsei)
    dp = lax.dot_general(
        doi, vj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    dq_ref[...] += scale * jnp.dot(
        ds.astype(kj.dtype), kj, preferred_element_type=jnp.float32
    )


def _bwd_call(q, k, v, bias2d, out, dout, lse,
              causal, scale, block_q, block_k, interpret):
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    group = hq // hkv
    nq, nk = lq // block_q, lk // block_k

    # delta [B, Hq, Lq] in fp32 — cheap elementwise reduce, let XLA fuse it
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    # low-rank operands get an explicit size-1 second-minor dim so their
    # kept last-two block dims satisfy Mosaic's (8, 128) tiling rule
    lse4 = lse.reshape(b, hq, 1, lq)
    delta4 = delta.reshape(b, hq, 1, lq)
    bias3 = bias2d.reshape(b, 1, lk)

    def in_specs(qi, kj):
        """Common input specs; ``qi``/``kj`` pick the q/kv block index out
        of the two trailing grid axes (x, y)."""
        q_spec = _spec((None, None, block_q, d),
                       lambda b_, h, x, y: (b_, h, qi(x, y), 0))
        lse_spec = _spec((None, None, 1, block_q),
                         lambda b_, h, x, y: (b_, h, 0, qi(x, y)))
        kv_spec = _spec((None, None, block_k, d),
                        lambda b_, h, x, y: (b_, h // group, kj(x, y), 0))
        bias_spec = _spec((None, 1, block_k),
                          lambda b_, h, x, y: (b_, 0, kj(x, y)))
        return [q_spec, q_spec, lse_spec, lse_spec,
                kv_spec, kv_spec, bias_spec]

    # pass 1: dk/dv/db — grid (…, kv, q), q innermost (accumulated over)
    dk_h, dv_h, db_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, hq, nk, nq),
        in_specs=in_specs(qi=lambda x, y: y, kj=lambda x, y: x),
        out_specs=[
            _spec((None, None, block_k, d), lambda b_, h, x, y: (b_, h, x, 0)),
            _spec((None, None, block_k, d), lambda b_, h, x, y: (b_, h, x, 0)),
            _spec((None, None, 1, block_k), lambda b_, h, x, y: (b_, h, 0, x)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, lk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, lk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1, lk), jnp.float32),
        ],
        compiler_params=None if interpret else _compiler_params(3),
        interpret=interpret,
    )(q, dout, lse4, delta4, k, v, bias3)

    # pass 2: dq — grid (…, q, kv), kv innermost (accumulated over)
    (dq,) = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, hq, nq, nk),
        in_specs=in_specs(qi=lambda x, y: x, kj=lambda x, y: y),
        out_specs=[
            _spec((None, None, block_q, d), lambda b_, h, x, y: (b_, h, x, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, lq, d), jnp.float32),
        ],
        compiler_params=None if interpret else _compiler_params(3),
        interpret=interpret,
    )(q, dout, lse4, delta4, k, v, bias3)

    # per-query-head kv grads fold back onto the Hkv axis (GQA)
    dk = dk_h.reshape(b, hkv, group, lk, d).sum(axis=2)
    dv = dv_h.reshape(b, hkv, group, lk, d).sum(axis=2)
    dbias = db_h[:, :, 0].sum(axis=1)                          # [B, Lk]
    return dq, dk, dv, dbias


# ======================================================================
# custom-vjp core (static: causal/scale/blocks/interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, bias2d, causal, scale, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, bias2d, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, bias2d, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd(
        q, k, v, bias2d, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, bias2d, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, dout):
    q, k, v, bias2d, out, lse = res
    dq, dk, dv, dbias = _bwd_call(
        q, k, v, bias2d, out, dout, lse,
        causal, scale, block_q, block_k, interpret,
    )
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        dbias.astype(bias2d.dtype),
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


# ======================================================================
# public API


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array] = None,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention matching ``dot_product_attention`` semantics
    (transformer.py:105-133): q [B, Hq, L, Dh], k/v [B, Hkv, L, Dh],
    optional additive per-key ``bias`` [B, 1, 1, L], fp32 softmax,
    returns [B, Hq, L, Dh] in q's dtype. Differentiable via Pallas
    forward+backward kernels.

    Sequence lengths are padded to the block size internally (padded
    keys get -inf bias; padded query rows are sliced off), so any L
    works; multiples of 128 avoid the padding entirely.
    """
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    assert v.shape == k.shape
    interpret = _resolve_interpret(interpret)
    scale = d ** -0.5

    if bias is None:
        bias2d = jnp.zeros((b, lk), jnp.float32)
    else:
        assert bias.shape == (b, 1, 1, lk), (
            f"bias must be [B,1,1,L], got {bias.shape}"
        )
        bias2d = bias.reshape(b, lk).astype(jnp.float32)

    block_q, block_k, pad_q, pad_k = _prepare_padding(
        lq, lk, block_q, block_k, interpret
    )
    q = _pad_len(q, pad_q)
    k, v = _pad_len(k, pad_k), _pad_len(v, pad_k)
    bias2d = _pad_bias2d(bias2d, pad_k)

    out = _flash(q, k, v, bias2d, causal, scale, block_q, block_k, interpret)
    if pad_q:
        out = out[:, :, :lq, :]
    return out


def _prepare_padding(lq, lk, block_q, block_k, interpret):
    """Clamped blocks + the q/k pad amounts for them (shared by the
    public kernel and the ring block entry points)."""
    block_q, block_k = _pick_blocks(lq, lk, block_q, block_k, interpret)
    return block_q, block_k, (-lq) % block_q, (-lk) % block_k


def _pad_len(x, pad):
    """Zero-pad the sequence axis (2) of a [B, H, L, D] tensor."""
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def _pad_bias2d(bias2d, pad):
    """-inf-pad the key axis of a [B, L] bias: padded keys attend nothing."""
    if not pad:
        return bias2d
    return jnp.pad(bias2d, ((0, 0), (0, pad)), constant_values=NEG_INF)


def _round_pow2(n: int) -> int:
    """Smallest power of two >= n (block size for short sequences)."""
    p = 1
    while p < n:
        p *= 2
    return p


# ======================================================================
# block-level entry points for sequence-parallel composition
# (parallel/ring_attention.py::flash_ring_attention): one K/V block's
# flash forward returning the normalized output AND the logsumexp (for
# cross-block online combination), and the matching backward given the
# GLOBAL out/lse — the standard ring-attention decomposition, where each
# block's backward against full-softmax statistics yields exactly its
# contribution to the global gradients.


def _pick_blocks(lq, lk, block_q, block_k, interpret):
    """Clamp requested block sizes to the sequence. Interpret mode (CPU
    tests) shrinks to the pow2 sequence so tiny shapes don't pay
    128-padding; real TPU lowering keeps blocks >= 128 — they appear as
    the minor dim of the lse/db tiles and the second-minor of the score
    tile, so they must stay (8, 128)-tile aligned (short sequences pad
    up to one block, padded keys carrying -inf bias)."""
    if interpret:
        return (min(block_q, _round_pow2(lq)),
                min(block_k, _round_pow2(lk)))
    return (max(128, min(block_q, _round_pow2(lq))),
            max(128, min(block_k, _round_pow2(lk))))


def flash_block_fwd(q, k, v, bias2d, causal, block_q=512, block_k=1024,
                    interpret=None):
    """One block's flash forward: (out [B,Hq,Lq,D] normalized, lse
    [B,Hq,Lq] fp32). ``bias2d`` is the per-key additive bias [B, Lk].
    NOT differentiable — pair with :func:`flash_block_bwd` inside an
    outer custom VJP."""
    b, hq, lq, d = q.shape
    lk = k.shape[2]
    interpret = _resolve_interpret(interpret)
    scale = d ** -0.5
    block_q, block_k, pad_q, pad_k = _prepare_padding(
        lq, lk, block_q, block_k, interpret
    )
    q = _pad_len(q, pad_q)
    k, v = _pad_len(k, pad_k), _pad_len(v, pad_k)
    bias2d = _pad_bias2d(bias2d, pad_k)
    out, lse = _fwd(q, k, v, bias2d.astype(jnp.float32), causal, scale,
                    block_q, block_k, interpret)
    if pad_q:
        out = out[:, :, :lq, :]
        lse = lse[:, :, :lq]
    return out, lse


def flash_block_bwd(q, k, v, bias2d, out, dout, lse, causal,
                    block_q=512, block_k=1024, interpret=None):
    """One block's flash backward against GLOBAL (out, lse): returns
    (dq, dk, dv, dbias2d) — this block's exact contributions to the
    global gradients."""
    b, hq, lq, d = q.shape
    lk = k.shape[2]
    interpret = _resolve_interpret(interpret)
    scale = d ** -0.5
    block_q, block_k, pad_q, pad_k = _prepare_padding(
        lq, lk, block_q, block_k, interpret
    )
    q = _pad_len(q, pad_q)
    out = _pad_len(out, pad_q)
    dout = _pad_len(dout, pad_q)  # zero dout rows => zero grads
    if pad_q:
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
    k, v = _pad_len(k, pad_k), _pad_len(v, pad_k)
    bias2d = _pad_bias2d(bias2d, pad_k)
    dq, dk, dv, dbias = _bwd_call(
        q, k, v, bias2d.astype(jnp.float32), out, dout, lse,
        causal, scale, block_q, block_k, interpret,
    )
    if pad_q:
        dq = dq[:, :, :lq, :]
    if pad_k:
        dk = dk[:, :, : lk, :]
        dv = dv[:, :, : lk, :]
        dbias = dbias[:, :lk]
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dbias
    )


def make_flash_attention_fn(block_q: int = 512, block_k: int = 1024,
                            interpret: Optional[bool] = None):
    """Seam-compatible ``attention_fn`` (transformer.py:31-32) for any
    model in the zoo: ``model(..., attention_fn=make_flash_attention_fn())``."""

    def attention_fn(q, k, v, bias=None, causal=False):
        return flash_attention(
            q, k, v, bias=bias, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )

    return attention_fn
