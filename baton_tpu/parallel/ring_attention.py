"""Sequence parallelism: ring attention and Ulysses (all-to-all) attention.

The reference has no long-context machinery of any kind (SURVEY §5 —
its demo model is a 10->1 linear layer, reference demo.py:15-49); these
kernels exist so the transformer zoo scales past one chip's HBM on
sequence length, the TPU way:

* **Ring attention** (:func:`ring_attention`): K/V blocks rotate around
  the mesh axis via ``lax.ppermute`` (ICI neighbor exchange — the
  topology ring attention was designed for) while each device's Q stays
  put, accumulating exact softmax attention with the online
  (max/sum-rescaling) recurrence. N steps, each overlapping a block
  matmul with a neighbor push; memory per device is O(L/N · L/N)
  scores, never the full L×L.
* **Ring × flash** (:func:`flash_ring_attention`): the same ring, but
  each shard's block math runs the Pallas flash kernel
  (ops/flash_attention.py) — per-shard memory falls from the dense
  [L/N × L/N] fp32 score block to the kernel's O(block), and the block
  matmuls inherit its measured MXU speed. Differentiable via a
  ring-level custom VJP that re-rotates K/V in the backward and runs
  each block's flash backward against the global softmax statistics.
* **Ulysses attention** (:func:`ulysses_attention`): two
  ``lax.all_to_all``s swap sequence-sharding for head-sharding, run
  dense local attention over the full sequence for H/N heads, and swap
  back. Cheaper collectives for moderate L; requires heads % devices
  == 0 (ring has no such constraint).

Both are exact (not approximations) and drop into any model in the zoo
through the ``attention_fn`` seam (:mod:`baton_tpu.models.transformer`)
via :func:`make_ring_attention_fn` / :func:`make_ulysses_attention_fn`,
which shard_map the [B, H, L, Dh] tensors over a sequence mesh axis at
the attention boundary. Additive per-key padding biases ([B, 1, 1, L],
the transformer seam's masking convention) ARE supported: under ring
the bias is sharded with K/V and rotates around the ring with them;
under Ulysses it is all-gathered to full length alongside the
head-resharded K/V. Causal masking is computed from global positions
and is exact; fully-masked future blocks skip their matmuls entirely.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from baton_tpu.parallel.partition import dim_spec


SEQ_AXIS = "seq"

_NEG = -1e30


def _block_scores(q, k, scale):
    """[B,Hq,Lq,Dh] x [B,Hkv,Lk,Dh] -> fp32 [B,Hq,Lq,Lk] with GQA
    head-grouping (query head h reads kv head h // (Hq//Hkv))."""
    b, hq, lq, dh = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    if hq != hkv:
        qg = q.reshape(b, hkv, hq // hkv, lq, dh)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k).reshape(b, hq, lq, lk)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    return s.astype(jnp.float32) * scale


def _block_pv(p, v, hq):
    """[B,Hq,Lq,Lk] probs x [B,Hkv,Lk,Dh] -> [B,Hq,Lq,Dh], GQA-grouped."""
    b, _, lq, lk = p.shape
    hkv = v.shape[1]
    if hq != hkv:
        pg = p.reshape(b, hkv, hq // hkv, lq, lk)
        return jnp.einsum("bhgqk,bhkd->bhgqd", pg, v).reshape(
            b, hq, lq, v.shape[3]
        )
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = False,
                   bias=None, striped: bool = False):
    """Exact attention with K/V ring-rotated over ``axis_name``.

    Call inside ``shard_map`` with q, k, v sharded on the length axis
    ([B, H, L/N, Dh] per device). The online-softmax carry (running max
    ``m``, normalizer ``l``, accumulator ``o``) is rescaled as each new
    K/V block arrives, so the result is bit-for-bit a softmax over the
    full sequence, never materializing L×L scores.

    ``bias`` is the per-shard additive key bias [B, Lk/N] (fp32; -inf to
    mask padding keys) — it is sharded exactly like K/V and rides the
    same ring rotations, so global key positions keep their bias no
    matter which device currently holds the block.

    ``striped=True`` switches the position mapping to the striped
    (round-robin) layout: device ``d``'s local index ``j`` is global
    token ``j*N + d``. Contiguous causal sharding is load-IMBALANCED —
    device 0's queries see one block, device N-1's see all N, so
    wall-clock is the worst device and the causal skip saves energy but
    not time. Striping gives every (query-shard, key-block) pair ~half
    a block of unmasked work, so all devices finish together (the
    "striped attention" layout). Use
    :func:`make_striped_attention_fn`, which handles the token
    permutation at the seam.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, hq, lc, dh = q.shape
    lk = k.shape[2]
    scale = dh ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    # carries start device-invariant but become device-varying inside the
    # loop (ppermute outputs are varying); mark them varying up front so
    # the fori_loop carry types are stable
    def varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    if bias is None:
        # locally-created zeros are invariant; the real bias arrives as a
        # shard_map input (already varying) — both must match the
        # ppermuted b_cur in the loop carry
        bias = varying(jnp.zeros((b, lk), jnp.float32))
    bias = bias.astype(jnp.float32)

    qf = q.astype(jnp.float32)
    o = varying(jnp.zeros((b, hq, lc, dh), jnp.float32))
    m = varying(jnp.full((b, hq, lc), _NEG, jnp.float32))
    l = varying(jnp.zeros((b, hq, lc), jnp.float32))

    def accum(s, o, m, l, k_cur, v_cur, b_cur):
        # after s forward rotations, this device holds the block that
        # originated on device (my - s) mod n
        src = (my - s) % n

        def attend(carry):
            o, m, l = carry
            scores = _block_scores(qf, k_cur.astype(jnp.float32), scale)
            scores = scores + b_cur[:, None, None, :]
            if causal:
                if striped:
                    # striped layout: local j on shard d = token j*n + d
                    q_pos = my + n * jnp.arange(lc)
                    k_pos = src + n * jnp.arange(lk)
                else:
                    q_pos = my * lc + jnp.arange(lc)
                    k_pos = src * lc + jnp.arange(lk)
                scores = jnp.where(
                    q_pos[:, None] >= k_pos[None, :], scores, _NEG
                )
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            # fully-masked entries: exp(NEG - NEG) == 1 must be zeroed
            p = jnp.where(scores > _NEG / 2, p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + _block_pv(
                p, v_cur.astype(jnp.float32), hq
            )
            return o_new, m_new, l_new

        if causal and not striped:
            # contiguous layout: a block strictly in this shard's future
            # is fully masked — skip its two matmuls (≈halves causal ring
            # FLOPs on average, but the savings land unevenly: device 0
            # skips almost everything, device n-1 nothing). The striped
            # layout has no fully-masked pairs to skip; its win is that
            # every pair carries the SAME ~half-block of work.
            return lax.cond(src <= my, attend, lambda c: c, (o, m, l))
        return attend((o, m, l))

    def step(s, carry):
        o, m, l, k_cur, v_cur, b_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        b_cur = lax.ppermute(b_cur, axis_name, perm)
        o, m, l = accum(s, o, m, l, k_cur, v_cur, b_cur)
        return o, m, l, k_cur, v_cur, b_cur

    # step 0 is peeled (local block needs no rotation) and the rotation
    # happens at the top of each remaining step, so exactly n-1 ppermute
    # pairs are issued — a tail rotation whose result is discarded would
    # otherwise waste one neighbor-exchange of full K/V per layer per step
    o, m, l = accum(0, o, m, l, k, v, bias)
    o, m, l, _, _, _ = lax.fori_loop(1, n, step, (o, m, l, k, v, bias))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ======================================================================
# ring × flash: the per-shard block math runs the Pallas flash kernel
# (ops/flash_attention.py) instead of materializing the dense
# [Lq/N, Lk/N] fp32 score block — per-shard memory drops to the flash
# kernel's O(block) and the MXU block math inherits its measured speed.
# Differentiation is a ring-level custom VJP: the forward saves only
# (out, global lse); the backward re-rotates K/V and runs each block's
# flash backward against the GLOBAL statistics — each such call yields
# exactly that block's contribution to the global gradients, with dk/dv/
# dbias accumulators riding the same ring back to their home shard.


def _ring_combine(o, lse, blk_out, blk_lse):
    """Online combination of two normalized partial softmax results over
    disjoint key sets: (o, lse) ⊕ (blk_out, blk_lse)."""
    lse_new = jnp.logaddexp(lse, blk_lse)
    w_old = jnp.exp(lse - lse_new)[..., None]
    w_new = jnp.exp(blk_lse - lse_new)[..., None]
    return o * w_old + blk_out.astype(jnp.float32) * w_new, lse_new


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_ring(q, k, v, bias2d, axis_name, causal, block_q, block_k,
                interpret):
    out, _ = _flash_ring_fwd(q, k, v, bias2d, axis_name, causal,
                             block_q, block_k, interpret)
    return out


def _flash_ring_fwd(q, k, v, bias2d, axis_name, causal, block_q, block_k,
                    interpret):
    from baton_tpu.ops.flash_attention import flash_block_fwd

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    if bias2d is None:
        bias2d = varying(jnp.zeros((q.shape[0], k.shape[2]), jnp.float32))

    # peeled diagonal block: the only one needing intra-block causal
    o0, lse0 = flash_block_fwd(q, k, v, bias2d, causal,
                               block_q, block_k, interpret)
    o = o0.astype(jnp.float32)
    lse = lse0

    def step(s, carry):
        o, lse, k_cur, v_cur, b_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        b_cur = lax.ppermute(b_cur, axis_name, perm)
        src = (my - s) % n

        def attend(carry):
            o, lse = carry
            blk_out, blk_lse = flash_block_fwd(
                q, k_cur, v_cur, b_cur, False, block_q, block_k, interpret
            )
            return _ring_combine(o, lse, blk_out, blk_lse)

        if causal:
            # blocks from the future are fully masked: skip them
            o, lse = lax.cond(src < my, attend, lambda c: c, (o, lse))
        else:
            o, lse = attend((o, lse))
        return o, lse, k_cur, v_cur, b_cur

    o, lse, _, _, _ = lax.fori_loop(1, n, step, (o, lse, k, v, bias2d))
    return o.astype(q.dtype), lse


def _flash_ring_save(q, k, v, bias2d, axis_name, causal, block_q, block_k,
                     interpret):
    out, lse = _flash_ring_fwd(q, k, v, bias2d, axis_name, causal,
                               block_q, block_k, interpret)
    return out, (q, k, v, bias2d, out, lse)


def _flash_ring_bwd(axis_name, causal, block_q, block_k, interpret,
                    res, dout):
    from baton_tpu.ops.flash_attention import flash_block_bwd

    q, k, v, bias2d, out, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    had_bias = bias2d is not None
    if bias2d is None:
        bias2d = varying(jnp.zeros((q.shape[0], k.shape[2]), jnp.float32))

    # peeled diagonal block at home
    dq, dk_acc, dv_acc, db_acc = flash_block_bwd(
        q, k, v, bias2d, out, dout, lse, causal,
        block_q, block_k, interpret,
    )
    dq = dq.astype(jnp.float32)
    dk_acc = dk_acc.astype(jnp.float32)
    dv_acc = dv_acc.astype(jnp.float32)

    def step(s, carry):
        dq, dk_acc, dv_acc, db_acc, k_cur, v_cur, b_cur = carry
        # grads ride the ring WITH their K/V block, returning home after
        # the final post-loop rotation
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        b_cur = lax.ppermute(b_cur, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        db_acc = lax.ppermute(db_acc, axis_name, perm)
        src = (my - s) % n

        def attend(carry):
            dq, dk_acc, dv_acc, db_acc = carry
            bdq, bdk, bdv, bdb = flash_block_bwd(
                q, k_cur, v_cur, b_cur, out, dout, lse, False,
                block_q, block_k, interpret,
            )
            return (
                dq + bdq.astype(jnp.float32),
                dk_acc + bdk.astype(jnp.float32),
                dv_acc + bdv.astype(jnp.float32),
                db_acc + bdb,
            )

        if causal:
            dq, dk_acc, dv_acc, db_acc = lax.cond(
                src < my, attend, lambda c: c,
                (dq, dk_acc, dv_acc, db_acc),
            )
        else:
            dq, dk_acc, dv_acc, db_acc = attend(
                (dq, dk_acc, dv_acc, db_acc)
            )
        return dq, dk_acc, dv_acc, db_acc, k_cur, v_cur, b_cur

    dq, dk_acc, dv_acc, db_acc, _, _, _ = lax.fori_loop(
        1, n, step, (dq, dk_acc, dv_acc, db_acc, k, v, bias2d)
    )
    # one final rotation brings each block's accumulated grads home
    dk_acc = lax.ppermute(dk_acc, axis_name, perm)
    dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    db_acc = lax.ppermute(db_acc, axis_name, perm)
    return (
        dq.astype(q.dtype),
        dk_acc.astype(k.dtype),
        dv_acc.astype(v.dtype),
        db_acc.astype(res[3].dtype) if had_bias else None,
    )


_flash_ring.defvjp(_flash_ring_save, _flash_ring_bwd)


def flash_ring_attention(q, k, v, axis_name: str = SEQ_AXIS,
                         causal: bool = False, bias=None,
                         block_q: int = 512, block_k: int = 1024,
                         interpret=None):
    """Exact ring attention whose per-shard block math is the Pallas
    flash kernel. Call inside ``shard_map`` with q/k/v length-sharded
    ([B, H, L/N, Dh] per device) and ``bias`` the per-shard [B, L/N]
    additive key bias (or None). Differentiable (ring-level custom VJP).
    """
    return _flash_ring(q, k, v, bias, axis_name, causal,
                       block_q, block_k, interpret)


def ulysses_attention(q, k, v, axis_name: str = SEQ_AXIS,
                      causal: bool = False, bias=None):
    """Exact attention via head<->sequence all-to-all re-sharding.

    Call inside ``shard_map`` with q, k, v sharded on length. Each
    device ends up with the *full* sequence for H/N heads, runs the
    dense kernel, and re-shards back to length. Requires both the query
    and kv head counts to be divisible by the axis size.
    """
    from baton_tpu.models.transformer import dot_product_attention

    n = lax.psum(1, axis_name)

    def to_heads(x):
        # [B, H, L/N, Dh] -> [B, H/N, L, Dh]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    full_bias = None
    if bias is not None:
        # per-shard [B, Lk/N] key bias -> full [B, 1, 1, Lk]: every device
        # attends over the whole sequence after the head re-shard, so it
        # needs every key's bias (cheap — bias is [B, L], not [B, L, Dh])
        full = lax.all_gather(bias.astype(jnp.float32), axis_name,
                              axis=1, tiled=True)
        full_bias = full[:, None, None, :]

    out = dot_product_attention(
        to_heads(q), to_heads(k), to_heads(v), bias=full_bias, causal=causal
    )
    return to_seq(out)


def _seq_sharded_fn(kernel, mesh: Mesh, axis_name: str, with_bias: bool,
                    check_vma: bool = True):
    spec = dim_spec(axis_name, 2, 4)  # [B, H, L, Dh] sharded on L
    bias_spec = dim_spec(axis_name, 1, 2)  # [B, L] key bias, sharded on L

    # check_vma=False only for the flash-ring kernel: its embedded
    # pallas_call out_shape structs carry no varying-manifest
    # annotation; the dense ring/Ulysses kernels keep full VMA checking
    if with_bias:
        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(spec, spec, spec, bias_spec), out_specs=spec,
            check_vma=check_vma,
        )
        def sharded(q, k, v, bias2d):
            return kernel(q, k, v, bias=bias2d)
    else:
        @partial(
            jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=check_vma,
        )
        def sharded(q, k, v):
            return kernel(q, k, v)

    return sharded


def _check_seam_bias(bias, b, lk):
    """The transformer seam passes additive key bias as [B, 1, 1, L]
    (transformer.py contract); flatten to the [B, L] the SP kernels
    shard."""
    if bias.shape != (b, 1, 1, lk):
        raise ValueError(
            f"sequence-parallel attention supports per-key bias "
            f"[B, 1, 1, L] only; got {bias.shape}"
        )
    return bias.reshape(b, lk)


def make_ring_attention_fn(mesh: Mesh, axis_name: str = SEQ_AXIS):
    """An ``attention_fn`` for the model zoo: shards [B, H, L, Dh] over
    ``mesh[axis_name]`` on L and runs :func:`ring_attention`. The
    sequence length must be divisible by the axis size. Padded (BERT/
    ViT-style) batches work: the [B, 1, 1, L] key bias is sharded with
    K/V and rotates around the ring."""

    def attention_fn(q, k, v, bias=None, causal=False):
        n = mesh.shape[axis_name]
        if q.shape[2] % n:
            raise ValueError(
                f"ring attention needs sequence length divisible by mesh "
                f"axis {axis_name!r} size {n}; got L={q.shape[2]}"
            )
        kernel = partial(ring_attention, axis_name=axis_name, causal=causal)
        fn = _seq_sharded_fn(kernel, mesh, axis_name,
                             with_bias=bias is not None)
        if bias is None:
            return fn(q, k, v)
        return fn(q, k, v, _check_seam_bias(bias, q.shape[0], k.shape[2]))

    return attention_fn


def make_striped_attention_fn(mesh: Mesh, axis_name: str = SEQ_AXIS):
    """An ``attention_fn`` running CAUSAL ring attention in the striped
    (round-robin) token layout — the load-balanced form of causal
    sequence parallelism.

    Why: under the contiguous layout, causality makes the ring
    imbalanced — the shard holding the sequence tail attends every
    rotated block while the head shard attends one, so the step time is
    the tail shard's and the causal skip saves no wall-clock. Striping
    assigns token ``t`` to device ``t % N``: every (shard, rotated
    block) pair then carries the same ~half block of unmasked work and
    all devices finish each ring step together.

    The permutation in/out of striped order happens here at the seam
    (one gather each way around the attention stack); positions inside
    the kernel are mapped accordingly, so the result equals dense causal
    attention exactly. Non-causal calls fall back to the plain ring
    (striping buys nothing without a triangular mask).
    """

    plain_ring = make_ring_attention_fn(mesh, axis_name)

    def attention_fn(q, k, v, bias=None, causal=False):
        n = mesh.shape[axis_name]
        l = q.shape[2]
        if l % n:
            raise ValueError(
                f"striped attention needs sequence length divisible by "
                f"mesh axis {axis_name!r} size {n}; got L={l}"
            )
        if not causal:
            # striping buys nothing without a triangular mask — delegate
            # to the one ring seam instead of duplicating it
            return plain_ring(q, k, v, bias=bias, causal=False)

        # stripe: token j*n + d -> contiguous slot (d, j), so the
        # contiguous shard_map spec hands device d exactly its stripe
        perm = jnp.arange(l).reshape(l // n, n).T.reshape(l)
        inv = jnp.argsort(perm)
        qs, ks, vs = (x[:, :, perm, :] for x in (q, k, v))
        kernel = partial(ring_attention, axis_name=axis_name, causal=True,
                         striped=True)
        fn = _seq_sharded_fn(kernel, mesh, axis_name,
                             with_bias=bias is not None)
        if bias is None:
            out = fn(qs, ks, vs)
        else:
            b2 = _check_seam_bias(bias, q.shape[0], k.shape[2])
            out = fn(qs, ks, vs, b2[:, perm])
        return out[:, :, inv, :]

    return attention_fn


def make_flash_ring_attention_fn(mesh: Mesh, axis_name: str = SEQ_AXIS,
                                 block_q: int = 512, block_k: int = 1024,
                                 interpret=None):
    """An ``attention_fn`` for the model zoo backed by
    :func:`flash_ring_attention`: sequence parallelism over
    ``mesh[axis_name]`` with the Pallas flash kernel doing each shard's
    block math — the long-context configuration for TPU (ICI ppermute
    between shards, MXU flash blocks within them)."""

    def attention_fn(q, k, v, bias=None, causal=False):
        n = mesh.shape[axis_name]
        if q.shape[2] % n:
            raise ValueError(
                f"ring attention needs sequence length divisible by mesh "
                f"axis {axis_name!r} size {n}; got L={q.shape[2]}"
            )
        kernel = partial(
            flash_ring_attention, axis_name=axis_name, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        fn = _seq_sharded_fn(kernel, mesh, axis_name,
                             with_bias=bias is not None, check_vma=False)
        if bias is None:
            return fn(q, k, v)
        return fn(q, k, v, _check_seam_bias(bias, q.shape[0], k.shape[2]))

    return attention_fn


def make_ulysses_attention_fn(mesh: Mesh, axis_name: str = SEQ_AXIS):
    """An ``attention_fn`` for the model zoo backed by
    :func:`ulysses_attention`. Head counts must be divisible by the
    axis size. Padded batches work: the per-key bias shard is
    all-gathered next to the head re-shard."""

    def attention_fn(q, k, v, bias=None, causal=False):
        n = mesh.shape[axis_name]
        hq, hkv = q.shape[1], k.shape[1]
        if hq % n or hkv % n:
            raise ValueError(
                f"Ulysses attention needs query AND kv head counts "
                f"divisible by mesh axis {axis_name!r} size {n}; got "
                f"Hq={hq}, Hkv={hkv} (use ring attention for GQA models "
                f"whose kv heads don't divide)"
            )
        if q.shape[2] % n:
            raise ValueError(
                f"Ulysses attention needs sequence length divisible by "
                f"mesh axis {axis_name!r} size {n}; got L={q.shape[2]}"
            )
        kernel = partial(ulysses_attention, axis_name=axis_name,
                         causal=causal)
        fn = _seq_sharded_fn(kernel, mesh, axis_name,
                             with_bias=bias is not None)
        if bias is None:
            return fn(q, k, v)
        return fn(q, k, v, _check_seam_bias(bias, q.shape[0], k.shape[2]))

    return attention_fn
