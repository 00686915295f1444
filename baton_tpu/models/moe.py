"""Expert layer: a routed feed-forward that is told which experts it
holds.

One of two routers, by the configuration, chooses ``top_k`` of a token's
router outputs and weighs them, in float32. :func:`route` scores every
token against all ``n_experts`` (sigmoid scores, DeepSeek-V3's
convention, arXiv:2412.19437 section 2.1): the ``top_k`` largest of ``s
+ router_bias`` are chosen (the bias chooses, it does not weigh), their
weights are ``routed_scale * s_i / sum of the chosen s``; or, with
``router_scores="softmax_chosen"``, the ``top_k`` largest logits are
chosen and weigh as their softmax over the chosen alone (a softmax over
all experts renormalised over the chosen: the same numbers).
:func:`route_mlp` (``router_hidden``; ZAYA1's, arXiv:2511.17127) takes a
token down to ``router_hidden`` channels, adds the state the layer
before's router left, scaled a channel, and hands the sum on as its
own; an RMSNorm and a three-layer GELU MLP of it give a softmax over
the router's outputs, ``n_experts`` and with ``skip`` one more that is
no expert; the ``top_k`` largest of ``p + router_bias`` are chosen and
weigh ``routed_scale * p_i``, not renormalised. The layer holds the
``experts_held`` experts from ``first_held`` on, as one expert-parallel
rank does, and computes its own experts' part of the result::

    y = sum over chosen i that are held of g_i E_i(h)  +  E_shared(h)
    E(h) = (SiLU(h w_gate) * (h w_up)) w_down

``E_shared`` is the ``n_shared`` shared experts as one SwiGLU of their
widths together, which is their sum; with ``shared_combine="average"``
that product times ``1 / n_shared``, their mean.
A choice that falls on an expert held elsewhere, or on the skip, which
no rank holds, adds nothing here; the
exchange between ranks (an all-to-all of tokens) is not written. No
token is dropped and there is no capacity: the ``top_k`` assignments of
every token are sorted by expert, each projection is one grouped matrix
product over the held experts' rows (:func:`grouped_matmul`), and the
rows go back to their tokens weighted by ``g``.

Only the sort runs over every assignment (``N = T top_k`` int32s).
What follows it, the rows gathered from the tokens, the grouped
products, the SiLU and the backward's float32 passes, the return,
handles a block of ``R`` consecutive sorted rows at a time, where ``R``
(:func:`rows_bound`) is the held rows a uniform router would give,
``N held / n_experts``, times 2, rounded up to the grouped product's
row tile, at most ``N``: a quarter of the assignments where 16 of 128
experts are held, a sixteenth where 8 of 256 are. The held rows come
first in the sorted order, so the first block holds them all unless the
routing gives this rank more than twice its share; then further blocks
run while held rows are left (a ``lax.while_loop``; a block's group
sizes are the running sizes clipped to its window). ``R`` bounds the
arrays, never the rows computed. A layer that holds every expert is
one block over every row, with no loop.

Under a ``vmap`` over clients (``FedSim``'s wave) the expert stacks
carry no client axis when they are frozen: the routed part is a
``jax.custom_vjp`` whose forward and backward are ``custom_vmap``
functions, and their rule folds the client axis into the token axis
(``[C, T, D] -> [C T, D]``), so every client's rows go through one
grouped product a projection and each expert's weights are read once a
pass, not once a client. Stacks that do carry a client axis (experts
that train) take a ``lax.map`` over clients instead. The backward
computes the cotangents of the activations and of the gates; the
stacks' own gradients are computed only where something asks for them
(under ``jit`` they are dead code over a frozen base).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from baton_tpu.models.transformer import (
    dense_init,
    normal_init,
    rms_normalize,
    scaled,
    swiglu,
    swiglu_init,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    # the router's width: every expert of the layer, held here or not
    n_experts: int = 8
    top_k: int = 2
    # width of one expert (and of the shared expert); None: the
    # decoder's ``d_ff``
    d_ff: Optional[int] = None
    # the experts this rank holds: ``experts_held`` from ``first_held``
    # on; None: all of them
    experts_held: Optional[int] = None
    first_held: int = 0
    routed_scale: float = 1.0
    # shared experts, computed for every token beside the routed ones,
    # as one SwiGLU of ``n_shared * d_ff``
    n_shared: int = 0
    # what the shared experts' outputs become: their ``"sum"`` (the wide
    # SwiGLU as it is) or their ``"average"`` (it times ``1 / n_shared``)
    shared_combine: str = "sum"
    # a per-expert bias added to the scores for the choice alone, drawn
    # uniform in +-this range (a trained model's balances the load;
    # zeros could not tell choosing from weighing); None: no bias
    router_bias_range: Optional[float] = None
    # how :func:`route` weighs what it chose: ``"sigmoid"`` of each
    # logit over the chosen ones' sum, or ``"softmax_chosen"``, the
    # softmax of the chosen logits
    router_scores: str = "sigmoid"
    # the width of the MLP router and of the state it hands to the next
    # layer's (:func:`route_mlp`); None: the sigmoid router
    # (:func:`route`)
    router_hidden: Optional[int] = None
    router_norm_eps: float = 1e-6
    # one more router output after the experts' that stands for no
    # expert: a token that chooses it skips the layer
    skip: bool = False

    @property
    def held(self) -> int:
        return self.n_experts if self.experts_held is None else self.experts_held

    @property
    def router_outputs(self) -> int:
        return self.n_experts + self.skip

    @property
    def shared_weight(self) -> float:
        """What the wide shared SwiGLU's output is multiplied by."""
        if self.shared_combine not in ("sum", "average"):
            raise ValueError(
                f"unknown shared_combine {self.shared_combine!r}")
        return 1.0 if self.shared_combine == "sum" else 1.0 / self.n_shared


def moe_init(key, d_model: int, d_ff: int, cfg: MoEConfig):
    kr, kb, kg, ku, kd, ks = jax.random.split(key, 6)
    d_ff = cfg.d_ff or d_ff

    def stack(k, d_in, d_out):
        # the held experts' draws are those the whole layer would give
        # them: a rank's stack is a slice of the uncut layer's
        keys = jax.random.split(k, cfg.n_experts)[
            cfg.first_held:cfg.first_held + cfg.held]
        return jax.vmap(lambda kk: dense_init(kk, d_in, d_out))(keys)

    p = {
        "router": dense_init(kr, d_model, cfg.n_experts)
        if cfg.router_hidden is None else _mlp_router_init(kr, d_model, cfg),
        "w_gate": stack(kg, d_model, d_ff),   # [E_held, D, F]
        "w_up": stack(ku, d_model, d_ff),     # [E_held, D, F]
        "w_down": stack(kd, d_ff, d_model),   # [E_held, F, D]
    }
    if cfg.router_bias_range is not None:
        p["router_bias"] = jax.random.uniform(
            kb, (cfg.router_outputs,), jnp.float32, -cfg.router_bias_range,
            cfg.router_bias_range)
    if cfg.n_shared:
        p["shared"] = swiglu_init(ks, d_model, cfg.n_shared * d_ff)
    return p


@jax.named_scope("router")
def route(p, x, cfg: MoEConfig):
    """``(idx, gate)``, each ``[..., top_k]``: the experts every token
    chose among all ``n_experts`` and their weights, float32."""
    if cfg.router_scores == "softmax_chosen":
        z = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"],
                       precision=jax.lax.Precision.HIGHEST)
        _, idx = jax.lax.top_k(z + p.get("router_bias", 0.0), cfg.top_k)
        return idx, cfg.routed_scale * jax.nn.softmax(
            jnp.take_along_axis(z, idx, axis=-1), axis=-1)
    if cfg.router_scores != "sigmoid":
        raise ValueError(f"unknown router_scores {cfg.router_scores!r}")
    s = jax.nn.sigmoid(jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), p["router"],
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + p.get("router_bias", 0.0), cfg.top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, cfg.routed_scale * chosen / jnp.sum(chosen, -1, keepdims=True)


# the MLP router's three matrices are drawn at this many times the
# fan-in deviation: random weights stand for trained ones, and a trained
# router that chooses one expert is sure of its choice (at 1 the softmax
# is all but flat and ``router_bias`` alone chooses)
_MLP_ROUTER_GAIN = 2.0


def _mlp_router_init(key, d_model: int, cfg: MoEConfig):
    """:func:`route_mlp`'s leaves, float32 all (no name of theirs is a
    projection's: none takes an adapter). The biases are drawn at
    deviation 0.02 and the state's scale uniform in [0.5, 1.5], not
    zeros and ones: a test has to tell a term from its absence."""
    h = cfg.router_hidden
    std = _MLP_ROUTER_GAIN * h ** -0.5
    k_in, k1, k2, k3, kb, ks = jax.random.split(key, 6)

    def centred(w):  # every column sums to nothing
        return w - jnp.mean(w, axis=0)

    b_in, b1, b2 = (normal_init(k, (h,), 0.02)
                    for k in jax.random.split(kb, 3))
    return {
        "w_in": dense_init(k_in, d_model, h), "b_in": b_in,
        "state_scale": jax.random.uniform(ks, (h,), jnp.float32, 0.5, 1.5),
        "norm": jnp.ones((h,), jnp.float32),
        "w1": dense_init(k1, h, h, std), "b1": b1,
        "w2": centred(dense_init(k2, h, h, std)), "b2": b2,
        "w3": centred(dense_init(k3, h, cfg.router_outputs, std)),
    }


@jax.named_scope("router")
def route_mlp(p, x, state, cfg: MoEConfig):
    """``(idx, gate, state_out)``: the router outputs every token chose
    (``[..., top_k]``; ``n_experts`` is the skip's) and their weights,
    float32, and the router's state ``[..., router_hidden]`` for the
    next layer's router, which is all it goes to::

        state_out = x W_in + b_in + state_scale * state
        p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(state_out) + b1) + b2))

    ``state`` is the layer before's ``state_out`` (None: none, the
    first layer's)."""
    r = p["router"]
    hi = jax.lax.Precision.HIGHEST

    def dense(y, w):
        return jnp.einsum("...d,dh->...h", y, w, precision=hi)

    s = dense(x.astype(jnp.float32), r["w_in"]) + r["b_in"]
    if state is not None:
        s = s + r["state_scale"] * state
    z = rms_normalize(s, r["norm"], cfg.router_norm_eps)
    z = jax.nn.gelu(dense(z, r["w1"]) + r["b1"], approximate=False)
    z = jax.nn.gelu(dense(z, r["w2"]) + r["b2"], approximate=False)
    prob = jax.nn.softmax(dense(z, r["w3"]), axis=-1)
    _, idx = jax.lax.top_k(prob + p.get("router_bias", 0.0), cfg.top_k)
    gate = cfg.routed_scale * jnp.take_along_axis(prob, idx, axis=-1)
    return idx, gate, s


# ------------------------------------------------------- grouped products
# The Pallas grouped product's grid is (column tiles, row tiles that
# hold rows, contraction tiles), the contraction innermost, and the
# weight block a step reads is (the row tile's expert, contraction tile,
# column tile): with the contraction in one tile that index is the same
# for every row tile of an expert, Pallas copies no block whose index
# did not change, and a step is bound by the matrix unit; tiled, every
# row tile loads its expert's weights again. A tile that does not
# divide its width is masked (contraction) or padded (columns) and
# multiplied all the same. One product in bfloat16 on a v5e at the
# cells' rows and group sizes, ms, either direction within 0.03 of the
# other (my chip run, PR 51; ``(tk, tn)``, the row tile 256; ``*`` the
# tiles that were, ``(1024, 1024)`` clipped to the widths, last the
# rule's):
#
#   K x N, rows in groups      tiles: ms
#   2,304 x 896, 65,536 in 64  (1024, 896)* 3.30  (768, 896) 2.61
#                              (1152, 896) 2.55  (2304, 896) 1.95
#   896 x 2,304, the same      (896, 1024)* 2.78  (896, 768) 2.21
#                              (896, 1152) 2.12  (896, 2304) 2.04
#   2,048 x 2,048, 30,840 in   (1024, 1024)* 2.23  (2048, 512) 1.75
#   16 of 32,768               (2048, 1024) 1.70
#   4,096 x 2,048, 8,192 in    (1024, 1024)* 1.49  (2048, 1024) 1.47
#   16 of 16,384               (4096, 256) 1.33  (4096, 512) 1.26
#   2,048 x 4,096, the same    (1024, 1024)* 1.56  (2048, 512) 1.32
#                              (2048, 1024) 1.27
#   6,144 x 2,048, 8,192 in    (1024, 1024)* 1.80  (2048, 1024) 1.79
#   8 of 16,384                (3072, 512) 2.25  (6144, 256) 1.58
#   2,048 x 6,144, the same    (1024, 1024)* 1.93  (2048, 512) 1.57
#                              (2048, 768) 1.52  (2048, 1024) 1.50
#
# the rows of a tile (PR 33 read 512 slower at groups of 512 +- 300
# rows: 3.13 ms for 3.00; ``rows_bound`` rounds to it)
_GMM_ROW_TILE = 256
# what the kernel's blocks and accumulator may take of the 16 MiB of
# scoped VMEM a v5e kernel gets: its body keeps more beside them, up to
# 0.83 MiB by the compiler's own count (compiled for a described v5e,
# PR 51: blocks of 15.25 MiB were refused at 16.08, 14.6 MiB compiled)
_GMM_VMEM_BUDGET = 14 * 2 ** 20


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What one grid step of the kernel holds in VMEM: the row, weight
    and output blocks, two buffers each, and the float32 accumulator."""
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def gmm_tiles(k: int, n: int, itemsize: int) -> tuple:
    """``(tm, tk, tn)`` of a grouped product that contracts ``k``
    channels into ``n`` columns (multiples of 128 both) over operands of
    ``itemsize`` bytes, from these alone. Both tiles divide their
    widths, so no step multiplies padding. The contraction is one tile
    wherever that fits ``_GMM_VMEM_BUDGET`` beside some column tile,
    and the column tile the widest that fits beside it (an expert's
    weights then stay in VMEM across its row tiles); where it is not,
    the column tile is the widest up to 1,024 (PR 33's, read with the
    contraction tiled) and the contraction tile the largest that fits
    beside that."""
    tm = _GMM_ROW_TILE

    def fits(tk, tn):
        return _gmm_vmem_bytes(tm, tk, tn, itemsize) <= _GMM_VMEM_BUDGET

    def dividing(width):  # widest first
        return [t for t in range(width, 0, -128) if width % t == 0]

    if fits(k, 128):
        return tm, k, next(t for t in dividing(n) if fits(k, t))
    tn = next(t for t in dividing(n) if t <= 1024)
    return tm, next(t for t in dividing(k) if fits(t, tn)), tn


def expert_tiles(d_model: int, d_ff: int, dtype) -> dict:
    """What a trace says of the grouped products of an expert layer of
    these widths over activations of ``dtype``, where they are the
    Pallas kernel (elsewhere nothing): under ``expert_tiles``, for each
    ``K x N`` (a ``t`` after it: against the transposed stack, the
    backward's) the tiles ``tm x tk x tn`` :func:`gmm_tiles` gives it,
    ``whole`` or ``tiled`` for its contraction, and after ``pad`` the
    share of the elements it multiplies that are padding."""
    if jax.default_backend() != "tpu":
        return {}
    said = []
    for k, n in ((d_model, d_ff), (d_ff, d_model)):
        tm, tk, tn = gmm_tiles(k, n, jnp.dtype(dtype).itemsize)
        padding = 1 - k * n / ((-(-k // tk) * tk) * (-(-n // tn) * tn))
        said += [f"{k}x{n}{t}:{tm}x{tk}x{tn}:"
                 f"{'whole' if tk == k else 'tiled'}:pad{padding:g}"
                 for t in ("", "t")]
    return {"expert_tiles": " ".join(said)}


def _gmm(x, w, sizes, transpose_rhs: bool, interpret: bool = False):
    """The Pallas ``megablox`` grouped product that ships with JAX (it
    visits the row tiles that hold rows and no others), at the tiles
    :func:`gmm_tiles` picks for its widths and dtype. ``interpret``:
    the kernel's body as plain JAX, for a test off the chip."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    n, k = x.shape
    m = w.shape[1] if transpose_rhs else w.shape[2]
    if n % _GMM_ROW_TILE or k % 128 or m % 128:
        raise ValueError(
            f"grouped product of {n} rows, {k} x {m}: the rows have to be "
            f"a multiple of {_GMM_ROW_TILE}, both widths of 128")
    return gmm(x, w, sizes, preferred_element_type=x.dtype,
               tiling=gmm_tiles(k, m, x.dtype.itemsize),
               transpose_rhs=transpose_rhs, interpret=interpret)


@jax.named_scope("expert_matmul")
def grouped_matmul(x, w, sizes, transpose_rhs: bool = False):
    """``x [N, K]``, its rows sorted by expert, ``sizes [E]`` rows an
    expert, times that expert's ``w [E, K, M]`` (``[E, M, K]`` with
    ``transpose_rhs``); rows past ``sum(sizes)`` come out zero. The
    result is in ``x``'s dtype, accumulated in float32. On a TPU the
    Pallas kernel at tiles chosen from ``K``, ``M`` and the dtype
    (:func:`_gmm`, :func:`gmm_tiles`; rows that are no multiple of its
    row tile and widths that are none of 128 are refused, not taken
    elsewhere); off it ``jax.lax.ragged_dot``."""
    w = w.astype(x.dtype)
    if jax.default_backend() == "tpu":
        out = _gmm(x, w, sizes, transpose_rhs)
    else:
        if transpose_rhs:
            w = jnp.swapaxes(w, 1, 2)
        out = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)
    return _rows_of_the_groups(out, sizes)


def _rows_of_the_groups(out, sizes):
    """Neither product writes the rows past the groups: what is there
    is whatever the memory held (on the chip, NaN as soon as not)."""
    return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None],
                     out, 0)


@jax.named_scope("expert_matmul")
def _grouped_outer(x, dy, sizes):
    """``[E, K, M]``: for each expert the sum over its rows of ``x
    [N, K]`` outer ``dy [N, M]``, the gradient of a stack."""
    numbers = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
    return jax.lax.ragged_dot_general(
        jnp.where(live, x, 0), dy, sizes, numbers,
        preferred_element_type=jnp.float32)


def _sorted_rows(idx, n_held: int):
    """The assignments ``idx [T, K]`` (``n_held`` where the expert is
    not held) in expert order, the absent ones last: ``(order, place,
    sizes, live)``: the assignment (``token * K + choice``) of each
    sorted row, each assignment's place in the sorted order ``[T, K]``,
    the rows a held expert, and which sorted rows are of a held
    expert."""
    t, k = idx.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    place = jnp.zeros(t * k, jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32)).reshape(t, k)
    sizes = jnp.zeros(n_held + 1, jnp.int32).at[flat].add(1)[:n_held]
    return order, place, sizes, flat[order] < n_held


# ------------------------------------------------- the rows a rank holds
# the room a block of sorted rows has over the held rows a uniform
# router would give, ``N held / n_experts``. The cells' seeds fill 0.97
# to 1.05 of that expectation (chip_smoke.py prints it a layer), so one
# block holds them with room to spare; a routing that does not fit runs
# further blocks, it does not lose rows
_ROWS_HEADROOM = 2


def rows_bound(n: int, held: int, n_experts: int) -> int:
    """``R``: the sorted rows that everything past the sort handles at a
    time, of ``n`` assignments where ``held`` of ``n_experts`` experts
    are held: the expected held rows times ``_ROWS_HEADROOM``, rounded
    up to the grouped product's row tile, at most ``n`` (every expert
    held: ``n``, and the layer is one block over every row)."""
    tile = _GMM_ROW_TILE
    expected = -(-n * held // n_experts)
    return min(n, -(-_ROWS_HEADROOM * expected // tile) * tile)


def _block_sizes(sizes, lo, r: int):
    """The rows of each held expert inside the window of sorted rows
    ``[lo, lo + r)``: the running sizes clipped to it. They sum to the
    held rows in the window."""
    ends = jnp.cumsum(sizes)
    return jnp.clip(ends, lo, lo + r) - jnp.clip(ends - sizes, lo, lo + r)


def _over_blocks(block, r: int, zeros, order, live, sizes):
    """``block(acc, order_j, live_j, sizes_j) -> acc`` over the blocks
    ``j`` of ``r`` consecutive sorted rows that hold a held expert's
    row, from ``acc = zeros()`` on: ``ceil(sum(sizes) / r)`` trips of a
    ``lax.while_loop`` (nothing differentiates it: both directions of
    the layer are written by hand). With ``r`` every row there is one
    block and no loop: ``block(None, order, live, sizes)``."""
    n = order.shape[0]
    with jax.named_scope("routed_block"):
        if r == n:
            return block(None, order, live, sizes)
        pad = -n % r  # a last window past ``n``: rows of no expert
        order, live = jnp.pad(order, (0, pad)), jnp.pad(live, (0, pad))

        def one(carry):
            j, acc = carry
            lo = j * r
            return j + 1, block(
                acc, jax.lax.dynamic_slice_in_dim(order, lo, r),
                jax.lax.dynamic_slice_in_dim(live, lo, r),
                _block_sizes(sizes, lo, r))

        return jax.lax.while_loop(
            lambda carry: carry[0] * r < jnp.sum(sizes), one,
            (jnp.int32(0), zeros()))[1]


def _routed_forward(n_experts, x, idx, gate, w_gate, w_up, w_down):
    """``x [T, D]``, local expert ids ``idx [T, K]``, weights ``gate
    [T, K]``: the held experts' part of the layer's result ``[T, D]``,
    as a tuple of one (``_over_clients`` takes tuples)."""
    t, k = idx.shape
    order, place, sizes, live = _sorted_rows(idx, w_gate.shape[0])

    def block(acc, order, live, sizes):
        rows = x[order // k]
        mid = jax.nn.silu(grouped_matmul(rows, w_gate, sizes)) \
            * grouped_matmul(rows, w_up, sizes)
        out = grouped_matmul(mid, w_down, sizes)
        if acc is None:  # every row is here: a token reads its own K
            return jnp.sum(out[place].astype(jnp.float32) * gate[..., None],
                           axis=1)
        # a block's rows are summed into their tokens. On a v5e at the
        # cells' shapes (my chip run, PR 40) that is 4.27 ms for 16,384
        # rows of 4,096 where the gather of [T, K] rows, most of them
        # zero rows past the block, is 5.97, and the layer's three
        # passes 32.0 ms for 38.7 (108.4 for 154.8 at 6,144 channels)
        return acc.at[order // k].add(out.astype(jnp.float32)
                                      * gate.reshape(-1)[order][:, None])

    y = _over_blocks(
        block, rows_bound(t * k, w_gate.shape[0], n_experts),
        lambda: jnp.zeros(x.shape, jnp.float32), order, live, sizes)
    return (y.astype(x.dtype),)


def _routed_backward(n_experts, x, idx, gate, dy, w_gate, w_up, w_down):
    """The cotangents of ``x``, ``gate`` and the three stacks, the
    forward's products recomputed (the block is under ``remat``
    anyway). A sorted row reads its token's ``x`` and ``dy``."""
    t, k = idx.shape
    order, place, sizes, live = _sorted_rows(idx, w_gate.shape[0])

    def block(acc, order, live, sizes):
        token = order // k
        rows = x[token]
        a = grouped_matmul(rows, w_gate, sizes).astype(jnp.float32)
        b = grouped_matmul(rows, w_up, sizes).astype(jnp.float32)
        sig = jax.nn.sigmoid(a)
        mid = a * sig * b
        dy_rows = dy[token]
        # d out . w_down^T once, unweighted: its product with mid is the
        # gate's cotangent, weighted by the gate it is mid's
        u = grouped_matmul(dy_rows, w_down, sizes, transpose_rhs=True
                           ).astype(jnp.float32)
        g_rows = jnp.where(live, gate.reshape(-1)[order], 0.0)[:, None]
        d_gate = jnp.sum(u * mid, axis=-1)
        if acc is None:  # every row is here: an assignment reads its own
            d_gate = d_gate[place]
        d_mid = u * g_rows
        d_a = (d_mid * b * sig * (1.0 + a * (1.0 - sig))).astype(x.dtype)
        d_b = (d_mid * a * sig).astype(x.dtype)
        d_rows = grouped_matmul(d_a, w_gate, sizes, transpose_rhs=True) \
            + grouped_matmul(d_b, w_up, sizes, transpose_rhs=True)
        if acc is None:
            d_x = jnp.sum(d_rows[place].astype(jnp.float32), axis=1)
        else:
            d_x = acc[0].at[token].add(d_rows.astype(jnp.float32))
            d_gate = acc[1].at[token, order % k].add(d_gate)
        d_out = (dy_rows.astype(jnp.float32) * g_rows).astype(x.dtype)
        d_stacks = (_grouped_outer(rows, d_a, sizes),
                    _grouped_outer(rows, d_b, sizes),
                    _grouped_outer(mid.astype(x.dtype), d_out, sizes))
        if acc is not None:
            d_stacks = tuple(s + d for s, d in zip(acc[2:], d_stacks))
        return (d_x, d_gate) + d_stacks

    d_x, d_gate, *d_stacks = _over_blocks(
        block, rows_bound(t * k, w_gate.shape[0], n_experts),
        lambda: tuple(jnp.zeros(a.shape, jnp.float32)
                      for a in (x, gate, w_gate, w_up, w_down)),
        order, live, sizes)
    return (d_x.astype(x.dtype), d_gate) + tuple(
        d.astype(w.dtype) for d, w in zip(d_stacks, (w_gate, w_up, w_down)))


# what the sorted copy of the folded clients' rows may hold before the
# layer goes a client at a time: ``rows_bound`` rows of the clients'
# ``C T K`` assignments, ``[R, D]``. sarvam_105b_c4_l2048 folds 128 MiB
# (16,384 rows of 4,096), glm5_c4_l8192 192 MiB (16,384 of 6,144);
# before the rows were bounded every assignment was sorted and copied,
# held here or not, and glm5's four clients would have folded 3 GiB,
# twice in the backward (compiled for a v5e, PR 39: 17.0 of 15.75 GiB)
_FOLDED_ROWS_BYTES = 1024 ** 3


def _over_clients(direction, n_out_acts: int, n_experts: int):
    """``direction(n_experts, x, idx, gate, ..., w_gate, w_up, w_down)
    -> tuple`` as a ``custom_vmap`` function of the arrays whose rule
    folds a client axis on the
    activations into their row axis where the stacks carry none and the
    folded block of sorted rows stays under ``_FOLDED_ROWS_BYTES``; the
    first ``n_out_acts`` results are per row and unfold again, the rest
    (a stack's gradient a client) come from a ``lax.map`` over clients,
    as does everything where a stack carries the axis or the fold would
    be too large."""
    fn = partial(direction, n_experts)
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        def along_clients(arrays, batched):
            return [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                    for a, b in zip(arrays, batched)]

        acts = along_clients(args[:-3], in_batched[:-3])
        stacks = args[-3:]
        if any(in_batched[-3:]):
            out = jax.lax.map(lambda a: fn(*a), (
                *acts, *along_clients(stacks, in_batched[-3:])))
            return out, (True,) * len(out)
        x, idx = acts[:2]
        rows = rows_bound(idx.size, stacks[0].shape[0], n_experts)
        if rows * x.shape[-1] * x.dtype.itemsize > _FOLDED_ROWS_BYTES:
            out = jax.lax.map(lambda a: fn(*a, *stacks), tuple(acts))
            return out, (True,) * len(out)
        folded = wrapped(*(a.reshape((-1,) + a.shape[2:]) for a in acts),
                         *stacks)
        out = tuple(o.reshape((axis_size, -1) + o.shape[1:])
                    for o in folded[:n_out_acts])
        if len(folded) > n_out_acts:
            out += tuple(jax.lax.map(
                lambda a: fn(*a, *stacks)[n_out_acts:], tuple(acts)))
        return out, (True,) * len(out)

    return wrapped


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def routed_experts(n_experts, x, idx, gate, w_gate, w_up, w_down):
    """The held experts' part of the layer: ``n_experts`` the router's
    width (with the stacks' leading axis, what bounds the rows handled:
    :func:`rows_bound`), ``x [T, D]`` in the compute dtype, ``idx
    [T, K]`` the chosen experts counted from the first one held
    (``E_held`` or more: not held here), ``gate [T, K]`` float32."""
    return _over_clients(_routed_forward, 1, n_experts)(
        x, idx, gate, w_gate, w_up, w_down)[0]


def _routed_fwd(n_experts, *args):
    return routed_experts(n_experts, *args), args


def _routed_bwd(n_experts, res, dy):
    x, idx, gate, *stacks = res
    d_x, d_gate, *d_stacks = _over_clients(_routed_backward, 2, n_experts)(
        x, idx, gate, dy, *stacks)
    return (d_x, None, d_gate, *d_stacks)


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def _held_part(p, x, idx, gate, cfg: MoEConfig):
    """The held experts' part of what the router chose, plus the shared
    experts' sum or mean (``cfg.shared_combine``)."""
    b, l, d = x.shape
    local = idx - cfg.first_held
    local = jnp.where((local >= 0) & (local < cfg.held), local, cfg.held)
    y = routed_experts(
        cfg.router_outputs, x.reshape(b * l, d),
        local.reshape(b * l, -1).astype(jnp.int32), gate.reshape(b * l, -1),
        p["w_gate"], p["w_up"], p["w_down"]).reshape(b, l, d)
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            y = y + scaled(swiglu(p["shared"], x), cfg.shared_weight)
    return y


@jax.named_scope("moe")
def moe_apply(p, x, cfg: MoEConfig):
    """``x [B, L, D] -> y [B, L, D]`` in ``x``'s dtype: the held
    experts' part of the routed result plus the shared expert."""
    return _held_part(p, x, *route(p, x, cfg), cfg)


@jax.named_scope("moe")
def moe_apply_with_state(p, x, state, cfg: MoEConfig):
    """:func:`moe_apply` under the router that carries a state
    (:func:`route_mlp`): ``(y, state_out)``."""
    idx, gate, state = route_mlp(p, x, state, cfg)
    return _held_part(p, x, idx, gate, cfg), state


def moe_dense_oracle(p, x, cfg: MoEConfig, state=None):
    """The same layer the plain way, for the CPU tests: every held
    expert computes every token in float32, masked by the token's
    weight for it (``state``: the MLP router's). No sort, no grouped
    product."""
    xf = x.astype(jnp.float32)
    idx, gate = (route(p, x, cfg) if cfg.router_hidden is None
                 else route_mlp(p, x, state, cfg)[:2])
    y = jnp.zeros_like(xf)
    for e in range(cfg.held):
        w = jnp.sum(jnp.where(idx == cfg.first_held + e, gate, 0.0), -1)
        g = jax.nn.silu(xf @ p["w_gate"][e].astype(jnp.float32))
        u = xf @ p["w_up"][e].astype(jnp.float32)
        y = y + w[..., None] * ((g * u) @ p["w_down"][e].astype(jnp.float32))
    if "shared" in p:
        s = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                   p["shared"])
        y = y + cfg.shared_weight * swiglu(s, xf)
    return y.astype(x.dtype)
