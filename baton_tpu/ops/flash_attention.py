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
  the backward pass either; the forward kernel's two outputs carry
  names (``KEPT_OUTPUTS``), so that a ``jax.checkpoint`` around the
  call can save them and not run the forward kernel a second time;
* **GQA for free**: the kv-head block index map sends query head ``h``
  to kv head ``h // (Hq//Hkv)`` — no ``jnp.repeat`` materialization;
* **values as wide as they are**: ``Dv`` is read from ``v`` and need not
  be the keys' ``Dk`` (latent attention: 192 / 128 or 256 / 256,
  ``transformer.py::causal_core``); the value-side blocks (``v``, the
  output, its cotangent, ``dv``, the accumulator) are ``Dv`` wide, the
  key-side ones (``q``, ``k``, ``dq``, ``dk``) ``Dk``, and where ``Dk``
  is not whole lane tiles they are handed to the kernels with the
  sequence minor (:func:`_keys_ride_sublanes`); the softmax ``scale``
  is an argument (``Dk ** -0.5`` unless given);
* matches the seam contract ``attention_fn(q, k, v, bias, causal)``
  (transformer.py:31-32): additive per-key bias [B, 1, 1, L], static
  causal masking from global positions;
* **a choice of keys a query** beyond that contract: ``chosen
  [B, Lq, Lk]`` (int8, one for all heads; latent attention whose
  learned index chose each query's keys, ``transformer.py::
  chosen_keys``) masks a tile's scores beside the causal rule in the
  forward and both backward kernels. Every tile the causal rule leaves
  is still visited (a learned choice scatters over the prefix; a tile
  no query chose anything of could be skipped and is not), so the work
  is the dense causal core's. A call without one builds the kernels it
  built before there was any;
* **a window** beside the causal rule: with ``window`` a query sees
  itself and the ``window - 1`` keys before it (``0 <= q - k <
  window``). It is a second static bound of the same kind, and one rule
  (:func:`_sees` of two positions) is the mask, the skip (the rule at a
  tile's corners) and, solved for a block index, the band a row or a
  column of tiles holds (:func:`_key_blocks`, :func:`_query_blocks`).
  Under a window a grid's inner axis runs over that band and not over
  the sequence: it is as long as the longest band (:func:`_inner_steps`,
  counted where the call is traced: 2 blocks of keys a block of queries
  and 2 the other way at 1,024 x 1,024 blocks, 8,192 tokens and a
  window of 1,024; 2 and 4 at 512 x 1,024), step ``t`` is block
  ``first + t`` (:func:`_stepped`), and the only steps that compute
  nothing are a shorter band's spare ones, where the sequence starts
  (rows) or ends (columns): 1 of a head's 16 (2 of 32 at 512 x 1,024,
  where the grid over the sequence skipped 98 of 128). A skipped step
  was measured on a v5e at 0.12 us in the forward kernel and 0.38 in
  the backward's, a twentieth and a tenth of a computed one (2.2 and
  3.8 us; a row of the forward grid costs 1.2 us of its own and a
  column of the backward's 1.8: PERF.md section 6, PR 49). A windowed
  call that names no blocks gets :func:`_own_blocks`. A call without
  a window builds the kernels it built before: its grids run over the
  sequence, and a tile in every query's future is a skipped step.

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
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
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
# what the kernels share: a tile's scores, and which tiles a causal mask
# leaves anything of


def _keys_ride_sublanes(dk: int) -> bool:
    """Whether the kernels take q and k (and hand back dq and dk) with
    the head's width second-minor, ``[B, H, Dk, L]``: a width past one
    lane tile that does not fill its last one (latent attention's 192).
    As the minor dim it is padded to the next 128 lanes in HBM, in VMEM
    and by every XLA op that makes q and k, and the rotary part's 64
    beside it to twice itself: 160 ms a wave of ``sarvam_105b_c4_l2048``
    (PERF.md section 6, PR 34). With the sequence minor nothing is
    padded, and XLA, asked for that layout (:func:`_sequence_minor`),
    makes the ``swapaxes`` around the kernels move nothing. Whole tiles,
    and widths under one tile (not measured), stay ``[B, H, L, Dk]``."""
    return dk > 128 and dk % 128 != 0


_SEQUENCE_MINOR = Layout(major_to_minor=(0, 1, 3, 2))


def _sequence_minor(x):
    """``x [B, H, L, Dk]`` as ``[B, H, Dk, L]``, asking XLA to have laid
    ``x`` out that way already: the ops that made it then write no lane
    padding and the ``swapaxes`` moves nothing."""
    return jnp.swapaxes(with_layout_constraint(x, _SEQUENCE_MINOR), 2, 3)


def _from_sequence_minor(xt):
    """The way back for a gradient ``[B, H, Dk, L]``."""
    return with_layout_constraint(jnp.swapaxes(xt, 2, 3), _SEQUENCE_MINOR)


def _key_side_spec(d_major, block, dk, index_map):
    """The block of q, k, dq or dk that ``index_map`` (grid indices ->
    batch, head, block of the sequence) names, in either layout."""
    def place(*grid):
        b_, h, s = index_map(*grid)
        return (b_, h, 0, s) if d_major else (b_, h, s, 0)

    shape = (None, None, dk, block) if d_major else (None, None, block, dk)
    return _spec(shape, place)


def _sees(q_pos, k_pos, causal, window):
    """The rule: whether the query at ``q_pos`` sees the key at
    ``k_pos`` (positions, or arrays of them; ``None`` where every query
    sees every key): no key after it under ``causal``, and with
    ``window`` none ``window`` or more positions before it."""
    seen = None
    if causal:
        seen = q_pos >= k_pos
    if window is not None:
        near = q_pos - k_pos < window
        seen = near if seen is None else seen & near
    return seen


def _scores(q, k, bias, chosen, *, scale, causal, window, d_major, i, j,
            block_q, block_k):
    """One tile's float32 scores ``[block_q, block_k]`` of ``q [bq, Dk]``
    and ``k [bk, Dk]`` (``[Dk, bq]`` and ``[Dk, bk]`` with ``d_major``)
    plus the keys' ``bias [1, bk]``, masked where the tile of ``chosen
    [bq, bk]`` (int8, or None: every key) is zero: the operands stay in
    their dtype
    (the MXU multiplies bfloat16 natively and accumulates in float32;
    upcasting first would force 4-8x slower float32 passes), contracted
    by ``dot_general`` (an explicit ``k.T`` would force a Mosaic relayout
    before the MXU op). ``causal`` and ``window`` mask by the tile's
    global positions (:func:`_sees`)."""
    over = 0 if d_major else 1
    s = lax.dot_general(
        q, k, (((over,), (over,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bias
    if causal or window is not None:
        q_pos = i * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(_sees(q_pos, k_pos, causal, window), s, NEG_INF)
    if chosen is not None:
        s = jnp.where(chosen.astype(jnp.int32) != 0, s, NEG_INF)
    return s


def _tile_is_needed(causal, window, i, j, block_q, block_k):
    """Whether any query of block ``i`` sees any key of block ``j``
    (``None``: every tile is needed): :func:`_sees` at the tile's two
    corners, its first key against its last query for the causal bound
    and its first query against its last key for the window's."""
    needed = None
    if causal:
        needed = j * block_k <= i * block_q + (block_q - 1)
    if window is not None:
        near = i * block_q - (j * block_k + (block_k - 1)) < window
        needed = near if needed is None else needed & near
    return needed


def _step_computes(causal, window, i, j, block_q, block_k, nq, nk):
    """Whether the grid step that stands for tile ``(i, j)`` computes
    (``None``: every step does): the tile is needed and, where the grid
    runs over a band, is one of the sequence's ``nq`` by ``nk``."""
    needed = _tile_is_needed(causal, window, i, j, block_q, block_k)
    if window is not None:
        needed &= (i < nq) & (j < nk)
    return needed


def _on_needed_tiles(body, *, causal, window, i, j, block_q, block_k,
                     nq, nk):
    """Run ``body()`` on tile ``(i, j)`` of queries and keys, ``nq`` by
    ``nk`` of them. Without a window the grid steps over every tile,
    and under a causal mask one strictly in every query's future
    contributes nothing and is skipped, forward and backward (about
    half the FLOPs): 56 of a head's 128 steps at 8,192 tokens, each
    measured at a twentieth of a computed one forward and a tenth
    backward (PERF.md section 6, PR 49).
    Under a window the grid steps over the band (:func:`_stepped`) and
    what is skipped is a shorter band's spare step: a tile past the
    row's last (the first rows, by the causal bound), past the
    column's last or past the sequence's end (the last columns).
    :func:`_needed_k` / :func:`_needed_q` keep a skipped step's DMAs
    away too."""
    computes = _step_computes(causal, window, i, j, block_q, block_k, nq, nk)
    if computes is None:
        body()
    else:
        pl.when(computes)(body)


def _at_least_zero(x):
    """``max(x, 0)`` of a block index in a kernel or an index map, and
    of a Python int where a grid's extent is counted."""
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _key_blocks(causal, window, i, block_q, block_k):
    """``(first, last)``: the blocks of keys that block ``i`` of queries
    sees anything of, :func:`_tile_is_needed` solved for ``j``; ``None``
    on a side the rule does not bound (``first`` is never negative, and
    ``last`` may lie past the keys' last block where the queries are
    padded further than the keys)."""
    last = (i * block_q + (block_q - 1)) // block_k if causal else None
    if window is None:
        return None, last
    return _at_least_zero(i * block_q - (window - 1)) // block_k, last


def _query_blocks(causal, window, j, block_q, block_k):
    """The same for a block ``j`` of keys: the blocks of queries that
    see anything of it, :func:`_tile_is_needed` solved for ``i``
    (``last`` lies past the sequence's end for the last blocks)."""
    first = (j * block_k) // block_q if causal else None
    if window is None:
        return first, None
    return first, (j * block_k + (block_k - 1) + (window - 1)) // block_q


def _inner_steps(blocks, causal, window, n_outer, n_inner, block_q, block_k):
    """How long a grid's inner axis is beside ``n_outer`` blocks of the
    other side (``blocks``: :func:`_key_blocks` beside blocks of
    queries, :func:`_query_blocks` beside blocks of keys): the
    sequence's ``n_inner`` blocks without a window, under one the most
    blocks any band holds. Python ints throughout."""
    if window is None:
        return n_inner
    bands = (blocks(causal, window, x, block_q, block_k)
             for x in range(n_outer))
    return max(min(last, n_inner - 1) - first + 1 for first, last in bands)


def _stepped(blocks, causal, window, outer, t, block_q, block_k):
    """The block that step ``t`` of a grid's inner axis stands for
    beside block ``outer`` of the other side: block ``t`` without a
    window, under one the band's ``first + t``, which a spare step
    carries past the band's last block (:func:`_on_needed_tiles` skips
    it, the index maps hold it back)."""
    if window is None:
        return t
    return blocks(causal, window, outer, block_q, block_k)[0] + t


def _held_to(x, first, last):
    """``x`` held to ``[first, last]``, either side ``None`` for open."""
    if last is not None:
        x = jnp.minimum(x, last)
    if first is not None:
        x = jnp.maximum(x, first)
    return x


def _needed(blocks, causal, window, outer, t, n, block_q, block_k):
    """The block that step ``t`` of the inner axis asks for, one of
    ``n``. Without a window a skipped step names the nearest needed
    block again. Under one the step is the band's ``first + t``, and a
    spare step names the band's last block again (the sequence's last,
    where the band runs past its end). Pallas fetches nothing for a
    block index that did not change."""
    first, last = blocks(causal, window, outer, block_q, block_k)
    if window is None:
        return _held_to(t, first, last)
    return jnp.minimum(first + t, jnp.minimum(last, n - 1))


def _needed_k(causal, window, i, t, block_q, block_k, nk):
    """The block of keys that step ``t`` beside block ``i`` of queries
    asks for: without a window the steps skipped under the causal rule
    come last and name the last needed block, and so do a band's spare
    steps (the first rows': what a skipped step costs now is their 1 of
    16 steps at Mellum2's shapes)."""
    return _needed(_key_blocks, causal, window, i, t, nk, block_q, block_k)


def _needed_q(causal, window, t, j, block_q, block_k, nq):
    """The same for a grid whose inner axis runs over blocks of queries
    beside block ``j`` of keys: without a window the steps skipped
    under the causal rule come first and name the first needed block; a
    band's spare steps (the last columns') name the last."""
    return _needed(_query_blocks, causal, window, j, t, nq, block_q, block_k)


def _own_blocks(window, block_q, block_k):
    """The blocks of queries and keys of a call, each the caller's where
    it named one: 512 x 1,024, and 1,024 x 1,024 under a window of 1,024
    tokens or more (a rule of the window alone; the lengths only clamp,
    :func:`_pick_blocks`). A band's row is two blocks of keys at either
    size, so twice the queries a row halve the rows, and a row's own
    cost (its first and last step, its write-back) is half a tile's: on
    a v5e the windowed core at Mellum2's shapes took 2.64 ms forward
    and 7.72 forward and backward at 1,024 x 1,024 against 2.93 and
    8.22 at 512 x 1,024, 3.88 and 8.70 at 512 x 512, 4.58 and 10.14 at
    1,024 x 512 (PERF.md section 6, PR 49). Smaller windows were not
    measured and keep the blocks they had; a call without a window is
    behind its digests."""
    wide = window is not None and window >= 1024
    return (block_q or (1024 if wide else 512), block_k or 1024)


def _tpu_grid(length, window, block_q, block_k):
    """The blocks :func:`flash_attention` would pick from these on a TPU
    for ``length`` queries and keys, and how many of each there are."""
    block_q, block_k, pad_q, pad_k = _prepare_padding(
        length, length, *_own_blocks(window, block_q, block_k),
        interpret=False)
    return (block_q, block_k, (length + pad_q) // block_q,
            (length + pad_k) // block_k)


def tiles_visited(length: int, window: Optional[int] = None,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> int:
    """The tiles a head's causal forward runs on (does not skip) over
    ``length`` tokens on a TPU, at the blocks :func:`flash_attention`
    would pick from these (its own where none are named): the kernels'
    own rule, counted."""
    block_q, block_k, nq, nk = _tpu_grid(length, window, block_q, block_k)
    return sum(
        bool(_tile_is_needed(True, window, i, j, block_q, block_k))
        for i in range(nq) for j in range(nk))


def grid_steps(length: int, window: Optional[int] = None,
               block_q: Optional[int] = None, block_k: Optional[int] = None,
               backward: bool = False) -> int:
    """The steps a head's causal forward grid takes to get there,
    computed or skipped: the kernels' own grid, counted (``backward``:
    the grid of dk and dv, blocks of keys by the blocks of queries that
    see them)."""
    block_q, block_k, nq, nk = _tpu_grid(length, window, block_q, block_k)
    if backward:
        return nk * _inner_steps(_query_blocks, True, window, nk, nq,
                                 block_q, block_k)
    return nq * _inner_steps(_key_blocks, True, window, nq, nk,
                             block_q, block_k)


# ======================================================================
# forward kernel: grid (B, Hq, Lq/block_q, Lk/block_k), under a window
# (B, Hq, Lq/block_q, the blocks of keys a band holds)


def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, *rest,
                scale, causal, window, d_major, block_q, block_k, nq, nk,
                steps):
    # rest: the tile of chosen keys where the call has one, then the
    # outputs and the scratch
    *c_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    # Grid (B, Hq, Lq/bq, Lk/bk) with the kv axis INNERMOST ('arbitrary'):
    # the online-softmax state (acc/m/l) lives in VMEM scratch across the
    # j loop while Mosaic double-buffers the k/v block DMAs — the r2
    # whole-K/V-per-program version re-fetched all of K/V from HBM for
    # every q block (nq× traffic) and could not overlap DMA with compute.
    # m/l are (bq, 128) lane-broadcast: TPU vector layout wants the minor
    # dim lane-aligned, so the scalar-per-row state rides 128 lanes.
    # Under a window the innermost axis has ``steps`` steps, the row's
    # band of key blocks from its first on (:func:`_stepped`).
    i = pl.program_id(2)
    t = pl.program_id(3)
    j = _stepped(_key_blocks, causal, window, i, t, block_q, block_k)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        vj = v_ref[...]                                  # [bk, Dv]
        # softmax statistics are fp32 throughout
        s = _scores(q_ref[...], k_ref[...], b_ref[...],
                    c_ref[0][...] if c_ref else None, scale=scale,
                    causal=causal, window=window, d_major=d_major, i=i, j=j,
                    block_q=block_q, block_k=block_k)
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

    _on_needed_tiles(_accumulate, causal=causal, window=window, i=i, j=j,
                     block_q=block_q, block_k=block_k, nq=nq, nk=nk)

    @pl.when(t == steps - 1)
    def _finalize():
        m = m_ref[:, :1]
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, :] = (m + jnp.log(l))[:, 0]


def _vmem_limit(chosen) -> dict:
    """What a kernel with a tile of chosen keys says of VMEM: at blocks
    of 1,024 and heads of 256 / 256 its tiles are 1.8 MB past the
    compiler's default scoped limit (compiled for a v5e, PR 39). A
    kernel without one says what it said."""
    return ({} if chosen is None
            else {"vmem_limit_bytes": _ONE_KERNEL_VMEM_LIMIT_BYTES})


def _compiler_params(n_parallel: int, n_sequential: int = 1, **kwargs):
    """Mark the leading grid axes parallel, the innermost sequential."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_sequential, **kwargs)


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _chosen_spec(block_q, block_k, place):
    """The tile of ``chosen [B, Lq, Lk]`` a grid step reads; ``place``:
    grid indices -> (block of queries, block of keys)."""
    def at(b_, h, x, y):
        qi, kj = place(x, y)
        return (b_, qi, kj)

    return _spec((None, block_q, block_k), at)


def _fwd(q, k, v, bias2d, chosen, causal, scale, block_q, block_k,
         interpret, window=None):
    b, hq, lq, dk = q.shape
    hkv, lk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = lq // block_q, lk // block_k
    steps = _inner_steps(_key_blocks, causal, window, nq, nk, block_q,
                         block_k)
    grid = (b, hq, nq, steps)
    d_major = _keys_ride_sublanes(dk)
    if d_major:
        q, k = _sequence_minor(q), _sequence_minor(k)

    def kj(i, t):
        return _needed_k(causal, window, i, t, block_q, block_k, nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        d_major=d_major,
        block_q=block_q, block_k=block_k, nq=nq, nk=nk, steps=steps,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _key_side_spec(d_major, block_q, dk,
                           lambda b_, h, i, j: (b_, h, i)),
            _key_side_spec(d_major, block_k, dk,
                           lambda b_, h, i, j: (b_, h // group, kj(i, j))),
            _spec((None, None, block_k, dv),
                  lambda b_, h, i, j: (b_, h // group, kj(i, j), 0)),
            _spec((None, 1, block_k), lambda b_, h, i, j: (b_, 0, kj(i, j))),
        ] + ([] if chosen is None else [
            _chosen_spec(block_q, block_k, lambda i, j: (i, kj(i, j)))]),
        out_specs=[
            _spec((None, None, block_q, dv), lambda b_, h, i, j: (b_, h, i, 0)),
            _spec((None, None, 1, block_q), lambda b_, h, i, j: (b_, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, lq), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, dv)),
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
        ],
        compiler_params=None if interpret else _compiler_params(
            3, **_vmem_limit(chosen)),
        interpret=interpret,
    )(q, k, v, bias2d.reshape(b, 1, lk),
      *(() if chosen is None else (chosen,)))
    return out, lse.reshape(b, hq, lq)


# ======================================================================
# backward: blockwise recompute of p from the saved lse (no O(L²)
# residuals); delta = rowsum(do·o) is precomputed outside pallas. dq and
# dk are as wide as the keys, dv and do as wide as the values. Two forms,
# chosen by the shapes (:func:`_dq_fits_vmem`):
#
# * ONE kernel where a head's whole float32 dq [Lq, Dk] fits in VMEM:
#   grid (B, Hq, Lk/block_k, Lq/block_q), q innermost (under a window
#   the blocks of queries a band holds); dk/dv/db
#   accumulate in their output blocks over the q axis, dq's output block
#   is the head's, resident over both inner axes, and every tile adds
#   into its rows. A tile's p and ds are computed once for the three
#   gradients: five products a tile for the two-pass form's seven, and
#   half its vector work (the vector unit, not the MXU, paces these
#   kernels on a v5e);
# * the standard two passes for longer sequences: pass 1 on the same
#   grid accumulates dk/dv/db, pass 2 grids (B, Hq, Lq/block_q,
#   Lk/block_k) as the forward does and accumulates dq over the
#   innermost kv axis. Only
#   block-sized tiles are ever VMEM-resident, so VMEM is O(block²),
#   independent of L (the r1 single-program-per-head version held ~7
#   full [L, d] buffers).

# what the one-kernel form may hold of VMEM for dq, twice (Pallas
# double-buffers an output block): L = 8,192 at Dk = 256, 16,384 at 128.
# Its [bq, bk] float32 temporaries (scores, p, dp, ds) are past the
# compiler's default scoped limit at blocks of 1,024, so the kernel
# states its own; a v5e's VMEM is 128 MiB. 8 MiB (PR 34, sized at
# 192 / 128 and 2,048 keys) put [1, 64, 8192, 256 / 256] on the two
# passes: forward and backward 74.3 ms a call there against 58.6 in one
# kernel (my chip run, PR 39; 72.5 against 57.7 without a choice of
# keys)
_DQ_RESIDENT_BYTES = 16 * 1024 ** 2
_ONE_KERNEL_VMEM_LIMIT_BYTES = 64 * 1024 ** 2


def _dq_fits_vmem(lq: int, dk: int) -> bool:
    return 2 * 4 * lq * dk <= _DQ_RESIDENT_BYTES


def _p_and_ds(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, b_ref, c_ref,
              *, scale, causal, window, d_major, i, j, block_q, block_k):
    """A tile's probabilities from the saved log-sum-exp, and the
    gradient of its scores, both float32 ``[bq, bk]``. ``c_ref``: the
    tile of chosen keys, or None."""
    s = _scores(q_ref[...], k_ref[...], b_ref[...],
                None if c_ref is None else c_ref[...], scale=scale,
                causal=causal, window=window, d_major=d_major, i=i, j=j,
                block_q=block_q, block_k=block_k)
    p = jnp.exp(s - lse_ref[0][:, None])                       # [bq, bk]
    dp = lax.dot_general(
        do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta_ref[0][:, None])


def _grad_of_keys(x, ds, over: int, d_major: bool):
    """``ds [bq, bk]`` contracted over its axis ``over`` with ``x``, the
    block of q (for dk, ``over = 0``) or of k (for dq, ``over = 1``):
    ``[b, Dk]`` of ``x [b', Dk]``, or ``[Dk, b]`` of ``x [Dk, b']`` with
    ``d_major``, float32."""
    if d_major:
        dims = (((1,), (over,)), ((), ()))
        return lax.dot_general(x, ds, dims,
                               preferred_element_type=jnp.float32)
    dims = (((over,), (0,)), ((), ()))
    return lax.dot_general(ds, x, dims, preferred_element_type=jnp.float32)


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, b_ref,
                    *rest, scale, causal, window, d_major, block_q, block_k,
                    nq, nk, has_chosen):
    # rest: the tile of chosen keys where the call has one, then (dk, dv,
    # db) in the two-pass form; the one-kernel form puts the head's whole
    # dq [Lq, Dk] first
    c_ref, out_refs = (rest[0], rest[1:]) if has_chosen else (None, rest)
    *dq_ref, dk_ref, dv_ref, db_ref = out_refs
    j = pl.program_id(2)
    t = pl.program_id(3)
    i = _stepped(_query_blocks, causal, window, j, t, block_q, block_k)

    @pl.when(t == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref[...])
        dv_ref[...] = jnp.zeros_like(dv_ref[...])
        db_ref[...] = jnp.zeros_like(db_ref[...])

    def _accumulate():
        qi = q_ref[...]                              # [bq, Dk] ([Dk, bq])
        doi = do_ref[...]                                      # [bq, Dv]
        p, ds = _p_and_ds(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                          b_ref, c_ref, scale=scale, causal=causal,
                          window=window, d_major=d_major, i=i, j=j,
                          block_q=block_q, block_k=block_k)
        # contract the bq axis directly (p^T·do, ds^T·q without transposes)
        dv_ref[...] += lax.dot_general(
            p.astype(doi.dtype), doi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db_ref[...] += ds.sum(axis=0)[None, :]
        ds = ds.astype(qi.dtype)
        dk_ref[...] += scale * _grad_of_keys(qi, ds, 0, d_major)
        if not dq_ref:
            return
        dq = scale * _grad_of_keys(k_ref[...], ds, 1, d_major)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        at = (slice(None), rows) if d_major else (rows, slice(None))

        # the first block of keys a block of queries sees sets its rows,
        # the later ones add to them: block 0, causal or not, unless a
        # window has left it behind
        first = 0 if window is None else _key_blocks(
            causal, window, i, block_q, block_k)[0]

        @pl.when(j == first)
        def _set():
            dq_ref[0][at] = dq

        @pl.when(j > first)
        def _add():
            dq_ref[0][at] += dq

    _on_needed_tiles(_accumulate, causal=causal, window=window, i=i, j=j,
                     block_q=block_q, block_k=block_k, nq=nq, nk=nk)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, b_ref,
                   *rest, scale, causal, window, d_major, block_q, block_k,
                   nq, nk, has_chosen):
    c_ref, dq_ref = rest if has_chosen else (None, rest[0])
    i = pl.program_id(2)
    t = pl.program_id(3)
    j = _stepped(_key_blocks, causal, window, i, t, block_q, block_k)

    @pl.when(t == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref[...])

    def _accumulate():
        kj = k_ref[...]
        _, ds = _p_and_ds(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                          b_ref, c_ref, scale=scale, causal=causal,
                          window=window, d_major=d_major, i=i, j=j,
                          block_q=block_q, block_k=block_k)
        dq_ref[...] += scale * _grad_of_keys(kj, ds.astype(kj.dtype), 1,
                                             d_major)

    _on_needed_tiles(_accumulate, causal=causal, window=window, i=i, j=j,
                     block_q=block_q, block_k=block_k, nq=nq, nk=nk)


def _bwd_call(q, k, v, bias2d, out, dout, lse,
              causal, scale, block_q, block_k, interpret, chosen=None,
              window=None):
    b, hq, lq, dk_ = q.shape
    hkv, lk, dv_ = k.shape[1], k.shape[2], v.shape[3]
    group = hq // hkv
    nq, nk = lq // block_q, lk // block_k
    one_kernel = _dq_fits_vmem(lq, dk_)
    d_major = _keys_ride_sublanes(dk_)
    if d_major:
        q, k = _sequence_minor(q), _sequence_minor(k)

    # delta [B, Hq, Lq] in fp32 — cheap elementwise reduce, let XLA fuse it
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    # low-rank operands get an explicit size-1 second-minor dim so their
    # kept last-two block dims satisfy Mosaic's (8, 128) tiling rule
    operands = (q, dout, lse.reshape(b, hq, 1, lq),
                delta.reshape(b, hq, 1, lq), k, v, bias2d.reshape(b, 1, lk))
    if chosen is not None:
        operands += (chosen,)

    def in_specs(qi, kj):
        """Common input specs; ``qi``/``kj`` pick the q/kv block index out
        of the two trailing grid axes (x, y)."""
        lse_spec = _spec((None, None, 1, block_q),
                         lambda b_, h, x, y: (b_, h, 0, qi(x, y)))
        return [
            _key_side_spec(d_major, block_q, dk_,
                           lambda b_, h, x, y: (b_, h, qi(x, y))),
            _spec((None, None, block_q, dv_),
                  lambda b_, h, x, y: (b_, h, qi(x, y), 0)),
            lse_spec, lse_spec,
            _key_side_spec(d_major, block_k, dk_,
                           lambda b_, h, x, y: (b_, h // group, kj(x, y))),
            _spec((None, None, block_k, dv_),
                  lambda b_, h, x, y: (b_, h // group, kj(x, y), 0)),
            _spec((None, 1, block_k), lambda b_, h, x, y: (b_, 0, kj(x, y))),
        ] + ([] if chosen is None else [_chosen_spec(
            block_q, block_k, lambda x, y: (qi(x, y), kj(x, y)))])

    def key_side_shape(length):
        return jax.ShapeDtypeStruct(
            (b, hq, dk_, length) if d_major else (b, hq, length, dk_),
            jnp.float32)

    kernel_args = dict(scale=scale, causal=causal, window=window,
                       d_major=d_major,
                       block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                       has_chosen=chosen is not None)

    # dk/dv/db (and dq where it fits) — grid (…, kv, q), q innermost
    # (accumulated over): every block of queries, or a band's
    out_specs = [
        _key_side_spec(d_major, block_k, dk_, lambda b_, h, x, y: (b_, h, x)),
        _spec((None, None, block_k, dv_), lambda b_, h, x, y: (b_, h, x, 0)),
        _spec((None, None, 1, block_k), lambda b_, h, x, y: (b_, h, 0, x)),
    ]
    out_shape = [
        key_side_shape(lk),
        jax.ShapeDtypeStruct((b, hq, lk, dv_), jnp.float32),
        jax.ShapeDtypeStruct((b, hq, 1, lk), jnp.float32),
    ]
    if one_kernel:
        out_specs.insert(0, _key_side_spec(d_major, lq, dk_,
                                           lambda b_, h, x, y: (b_, h, 0)))
        out_shape.insert(0, key_side_shape(lq))
        params = _compiler_params(
            2, 2, vmem_limit_bytes=_ONE_KERNEL_VMEM_LIMIT_BYTES)
    else:
        params = _compiler_params(3, **_vmem_limit(chosen))
    *dq, dk_h, dv_h, db_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kernel_args),
        grid=(b, hq, nk, _inner_steps(_query_blocks, causal, window, nk, nq,
                                      block_q, block_k)),
        in_specs=in_specs(
            qi=lambda x, y: _needed_q(causal, window, y, x, block_q,
                                      block_k, nq),
            kj=lambda x, y: x),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=None if interpret else params,
        interpret=interpret,
    )(*operands)

    if not one_kernel:
        # pass 2: dq — grid (…, q, kv), kv innermost (accumulated over)
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **kernel_args),
            grid=(b, hq, nq, _inner_steps(_key_blocks, causal, window, nq,
                                          nk, block_q, block_k)),
            in_specs=in_specs(
                qi=lambda x, y: x,
                kj=lambda x, y: _needed_k(causal, window, x, y, block_q,
                                          block_k, nk)),
            out_specs=[_key_side_spec(d_major, block_q, dk_,
                                      lambda b_, h, x, y: (b_, h, x))],
            out_shape=[key_side_shape(lq)],
            compiler_params=None if interpret else _compiler_params(
                3, **_vmem_limit(chosen)),
            interpret=interpret,
        )(*operands)
    dq = dq[0]

    # per-query-head kv grads fold back onto the Hkv axis (GQA)
    dk = dk_h.reshape((b, hkv, group) + dk_h.shape[2:]).sum(axis=2)
    dv = dv_h.reshape(b, hkv, group, lk, dv_).sum(axis=2)
    dbias = db_h[:, :, 0].sum(axis=1)                          # [B, Lk]
    if d_major:
        dq, dk = _from_sequence_minor(dq), _from_sequence_minor(dk)
    return dq, dk, dv, dbias


# ======================================================================
# custom-vjp core (static: causal/scale/blocks/interpret/window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, bias2d, chosen, causal, scale, block_q, block_k,
           interpret, window):
    out, _ = _fwd(q, k, v, bias2d, chosen, causal, scale, block_q, block_k,
                  interpret, window)
    return out


# the names the forward kernel's two outputs carry where the backward
# kernel keeps them (``jax.ad_checkpoint.checkpoint_name``): a
# ``jax.checkpoint`` whose policy saves these two runs the forward
# kernel once, and makes q, k and v again as it makes everything else;
# under a bare one, and outside any, a name does nothing
KEPT_OUTPUTS = ("flash_out", "flash_lse")


def _flash_fwd(q, k, v, bias2d, chosen, causal, scale, block_q, block_k,
               interpret, window):
    out, lse = _fwd(
        q, k, v, bias2d, chosen, causal, scale, block_q, block_k, interpret,
        window
    )
    out, lse = map(checkpoint_name, (out, lse), KEPT_OUTPUTS)
    return out, (q, k, v, bias2d, chosen, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res,
               dout):
    q, k, v, bias2d, chosen, out, lse = res
    dq, dk, dv, dbias = _bwd_call(
        q, k, v, bias2d, out, dout, lse,
        causal, scale, block_q, block_k, interpret, chosen, window,
    )
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        dbias.astype(bias2d.dtype),
        None,  # the choice is no function of anything that trains
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,
    chosen: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention matching ``dot_product_attention`` semantics
    (transformer.py:105-133): q [B, Hq, L, Dk], k [B, Hkv, L, Dk],
    v [B, Hkv, L, Dv] (``Dv`` is read from ``v`` and need not be ``Dk``:
    latent attention's values are as wide as its keys or narrower,
    256 / 256 and 192 / 128), optional additive per-key ``bias``
    [B, 1, 1, L], optional ``chosen`` [B, Lq, Lk] (int8; a query
    attends the keys where it is nonzero, one choice for all heads,
    beside the causal rule and the bias; every query has to have
    chosen a key it may see), fp32 softmax of the scores times
    ``scale`` (``Dk ** -0.5`` unless given), returns [B, Hq, L, Dv] in
    q's dtype. Differentiable via Pallas forward+backward kernels.
    The kernels visit every tile the causal rule leaves and mask what
    was not chosen; with no ``chosen`` they take the operands and are
    the kernels they were. With ``window`` (causal attention alone) a
    query sees itself and the ``window - 1`` keys before it, and the
    kernels' grids run over the band of tiles that leaves: a tile no
    query of which sees a key of is neither computed nor fetched nor,
    but for a shorter band's spare steps, stepped over.

    ``block_q`` / ``block_k`` left ``None`` are the kernels' own
    (:func:`_own_blocks`: 512 x 1,024, under a window of 1,024 tokens
    or more 1,024 x 1,024), clamped to the sequences.
    Sequence lengths are padded to the block size internally (padded
    keys get -inf bias; padded query rows are sliced off), so any L
    works; multiples of 128 avoid the padding entirely.
    """
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    assert k.shape == (b, hkv, lk, d) and v.shape[:3] == k.shape[:3], (
        f"q {q.shape}, k {k.shape}, v {v.shape}")
    assert window is None or (causal and window >= 1), (
        f"a window of {window} needs causal attention")
    interpret = _resolve_interpret(interpret)
    if scale is None:
        scale = d ** -0.5

    if bias is None:
        bias2d = jnp.zeros((b, lk), jnp.float32)
    else:
        assert bias.shape == (b, 1, 1, lk), (
            f"bias must be [B,1,1,L], got {bias.shape}"
        )
        bias2d = bias.reshape(b, lk).astype(jnp.float32)

    block_q, block_k, pad_q, pad_k = _prepare_padding(
        lq, lk, *_own_blocks(window, block_q, block_k), interpret
    )
    q = _pad_len(q, pad_q)
    k, v = _pad_len(k, pad_k), _pad_len(v, pad_k)
    bias2d = _pad_bias2d(bias2d, pad_k)
    if chosen is not None:
        assert chosen.shape == (b, lq, lk), (
            f"chosen must be [B, Lq, Lk], got {chosen.shape}")
        chosen = jnp.pad(chosen.astype(jnp.int8),
                         ((0, 0), (0, pad_q), (0, pad_k)))

    out = _flash(q, k, v, bias2d, chosen, causal, scale, block_q, block_k,
                 interpret, window)
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
    out, lse = _fwd(q, k, v, bias2d.astype(jnp.float32), None, causal, scale,
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


def make_flash_attention_fn(block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Seam-compatible ``attention_fn`` (transformer.py:31-32) for any
    model in the zoo: ``model(..., attention_fn=make_flash_attention_fn())``."""

    def attention_fn(q, k, v, bias=None, causal=False, window=None):
        return flash_attention(
            q, k, v, bias=bias, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window,
        )

    return attention_fn
