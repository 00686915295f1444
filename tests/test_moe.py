"""The expert layer (models/moe.py): a router over all the experts, the
held experts' part by grouped products over sorted rows, no token
dropped, beside a shared expert.

Oracle: :func:`moe_dense_oracle`, every held expert computing every
token, masked by the token's weight for it. The layer has to equal it
under any routing, however uneven, forward and in every gradient; the
shares of all ranks have to add up to the uncut layer; under a ``vmap``
over clients the frozen stacks gain no client axis and no gradient.
The second router (an MLP over a state carried from layer to layer, a
softmax with one output more than there are experts, the skip) is held
to the same: its choice and weight against the formulas, the state's
way down three layers, a skipped token's exact zero and absent row, the
shares of two ranks against the uncut layer.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models.llama import (
    LlamaConfig,
    decoder_lora_model,
    llama_lm_model,
    projection_lora_target,
)
from baton_tpu.models import moe
from baton_tpu.models.moe import (
    MoEConfig,
    _block_sizes,
    _gmm,
    _gmm_vmem_bytes,
    _rows_of_the_groups,
    _sorted_rows,
    expert_tiles,
    gmm_tiles,
    grouped_matmul,
    moe_apply,
    moe_apply_with_state,
    moe_dense_oracle,
    moe_init,
    route,
    route_mlp,
    rows_bound,
)

D, F = 16, 32
WHOLE = MoEConfig(n_experts=8, top_k=2, d_ff=F, routed_scale=2.5, n_shared=1,
                  router_bias_range=0.1)
SHARE = MoEConfig(n_experts=8, top_k=2, d_ff=F, experts_held=4, first_held=2,
                  routed_scale=2.5, n_shared=1, router_bias_range=0.1)


def _params(cfg, seed=0):
    return moe_init(jax.random.key(seed), D, 4 * F, cfg)


def _uneven(p, cfg, most: int, none: int):
    """The router turned so that expert ``most`` takes nearly every
    token's first choice and expert ``none`` is never chosen."""
    bias = jnp.zeros(cfg.n_experts).at[most].set(5.0).at[none].set(-5.0)
    return dict(p, router_bias=bias)


def _close(got, want, rtol=2e-5):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    assert float(jnp.max(jnp.abs(got - want))) <= rtol * scale


@pytest.mark.parametrize("cfg", [WHOLE, SHARE], ids=["whole", "share"])
def test_the_layer_is_the_oracle_under_uneven_routing(cfg, nprng):
    """One held expert is given most rows and one none: no capacity, so
    no token is dropped; forward and the gradient of the input."""
    p = _uneven(_params(cfg), cfg, most=cfg.first_held + 1,
                none=cfg.first_held + 2)
    x = jnp.asarray(nprng.normal(size=(2, 24, D)), jnp.float32)
    idx, _ = route(p, x, cfg)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=cfg.n_experts)
    assert counts[cfg.first_held + 1] >= 40 and counts[cfg.first_held + 2] == 0
    _close(moe_apply(p, x, cfg), moe_dense_oracle(p, x, cfg))
    weight = jnp.asarray(nprng.normal(size=x.shape), jnp.float32)

    def grad(fn):
        return jax.grad(lambda x: jnp.sum(fn(p, x, cfg) * weight))(x)

    _close(grad(moe_apply), grad(moe_dense_oracle))


@pytest.mark.parametrize("cfg", [WHOLE, SHARE], ids=["whole", "share"])
def test_every_gradient_leaf_is_the_oracles(cfg, nprng):
    """The stacks' and the router's gradients too, where something asks
    for them (experts that train)."""
    p = _params(cfg)
    x = jnp.asarray(nprng.normal(size=(2, 12, D)), jnp.float32)

    def grads(fn):
        return jax.grad(lambda p: jnp.sum(fn(p, x, cfg) ** 2))(p)

    got, want = grads(moe_apply), grads(moe_dense_oracle)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(g, w, rtol=1e-4)
    assert float(jnp.max(jnp.abs(got["w_down"]))) > 0


def test_the_shares_add_up_to_the_uncut_layer(nprng):
    """The guide's test of the cut: the routed parts that the four
    ranks of two experts each compute, plus the shared expert once,
    are what the uncut layer gives."""
    p = _params(WHOLE)
    x = jnp.asarray(nprng.normal(size=(2, 16, D)), jnp.float32)
    routed = jnp.zeros_like(x)
    for first in range(0, 8, 2):
        cut = MoEConfig(n_experts=8, top_k=2, d_ff=F, experts_held=2,
                        first_held=first, routed_scale=2.5, n_shared=0,
                        router_bias_range=0.1)
        held = {k: (v[first:first + 2] if k.startswith("w_") else v)
                for k, v in p.items() if k != "shared"}
        # a rank's own init draws the very experts the uncut layer has
        drawn = moe_init(jax.random.key(0), D, 4 * F, cut)
        assert jnp.array_equal(drawn["w_up"], held["w_up"])
        routed = routed + moe_apply(held, x, cut)
    shared_only = dict(p, **{k: jnp.zeros_like(p[k])
                             for k in ("w_gate", "w_up", "w_down")})
    _close(routed + moe_apply(shared_only, x, WHOLE), moe_apply(p, x, WHOLE))
    _close(moe_apply(p, x, WHOLE), moe_dense_oracle(p, x, WHOLE))


# the softmax router: no bias, no scale, no shared expert, the weights a
# softmax over the chosen logits alone
SOFTMAX = MoEConfig(n_experts=4, top_k=2, d_ff=F,
                    router_scores="softmax_chosen")


def test_the_softmax_routers_weights_sum_to_one_over_the_chosen(nprng):
    """The ``top_k`` largest logits are chosen and weigh as their
    softmax over the chosen alone, which is the softmax over every
    expert renormalised over the chosen (``norm_topk_prob``); uneven,
    and no function of a sigmoid."""
    p = moe_init(jax.random.key(0), D, 4 * F, SOFTMAX)
    assert set(p) == {"router", "w_gate", "w_up", "w_down"}
    x = jnp.asarray(nprng.normal(size=(2, 64, D)), jnp.float32)
    idx, gate = route(p, x, SOFTMAX)
    z = x @ p["router"]
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx), -1),
        np.sort(np.argsort(-np.asarray(z), -1)[..., :2], -1))
    _close(gate.sum(-1), jnp.ones(gate.shape[:-1]))
    over_all = jnp.take_along_axis(jax.nn.softmax(z, -1), idx, -1)
    _close(gate, over_all / over_all.sum(-1, keepdims=True))
    assert float(jnp.max(gate)) > 0.7 and float(jnp.min(gate)) < 0.3
    sigmoid = route(p, x, dataclasses.replace(SOFTMAX,
                                              router_scores="sigmoid"))[1]
    assert float(jnp.max(jnp.abs(sigmoid - gate))) > 1e-2
    with pytest.raises(ValueError, match="router_scores"):
        route(p, x, dataclasses.replace(SOFTMAX, router_scores="tanh"))


def test_four_ranks_of_one_expert_add_up_under_the_softmax_router(nprng):
    """Ranks holding 16 + 16 + 16 + 16 of 64 experts (here 1 + 1 + 1 + 1
    of 4) each compute their part of what the softmax router chose;
    the parts add up to the oracle's whole layer, which the layer that
    holds every expert equals, in the value and in the gradient of its
    input."""
    p = moe_init(jax.random.key(1), D, 4 * F, SOFTMAX)
    x = jnp.asarray(nprng.normal(size=(2, 16, D)), jnp.float32)
    whole = moe_dense_oracle(p, x, SOFTMAX)
    parts = jnp.zeros_like(x)
    for first in range(4):
        cut = dataclasses.replace(SOFTMAX, experts_held=1, first_held=first)
        held = {k: (v[first:first + 1] if k.startswith("w_") else v)
                for k, v in p.items()}
        drawn = moe_init(jax.random.key(1), D, 4 * F, cut)
        assert jnp.array_equal(drawn["w_down"], held["w_down"])
        part = moe_apply(held, x, cut)
        _close(part, moe_dense_oracle(held, x, cut))
        parts = parts + part
    _close(parts, whole)
    _close(moe_apply(p, x, SOFTMAX), whole)
    got = jax.grad(lambda x: jnp.sum(jnp.sin(moe_apply(p, x, SOFTMAX))))(x)
    want = jax.grad(lambda x: jnp.sum(jnp.sin(
        moe_dense_oracle(p, x, SOFTMAX))))(x)
    _close(got, want, rtol=1e-4)


# the sigmoid router with no bias and no scale beside shared experts
# that are averaged: 8 ranks of 2 of 16 experts, 2 a token, 2 shared
AVERAGED = MoEConfig(n_experts=16, top_k=2, d_ff=F, n_shared=2,
                     shared_combine="average")


def _swiglu_of_a_slice(shared, x, j):
    at = slice(j * F, (j + 1) * F)
    return (jax.nn.silu(x @ shared["w_gate"][:, at])
            * (x @ shared["w_up"][:, at])) @ shared["w_down"][at]


def test_averaged_shared_experts_are_the_mean_of_their_slices(nprng):
    """``shared_combine="average"``: the wide SwiGLU times ``1 /
    n_shared`` is the mean of the ``n_shared`` SwiGLUs made from slices
    of its matrices (columns of ``w_gate`` and ``w_up``, rows of
    ``w_down``); summed, as every other configuration has them, it is
    ``n_shared`` times that."""
    p = moe_init(jax.random.key(2), D, 4 * F, AVERAGED)
    assert p["shared"]["w_gate"].shape == (D, 2 * F)
    x = jnp.asarray(nprng.normal(size=(2, 16, D)), jnp.float32)
    no_routed = dict(p, w_down=jnp.zeros_like(p["w_down"]))
    mean = sum(_swiglu_of_a_slice(p["shared"], x, j) for j in range(2)) / 2
    _close(moe_apply(no_routed, x, AVERAGED), mean)
    summed = dataclasses.replace(AVERAGED, shared_combine="sum")
    _close(moe_apply(no_routed, x, summed), 2 * mean)
    _close(moe_apply(p, x, AVERAGED), moe_dense_oracle(p, x, AVERAGED))
    assert (AVERAGED.shared_weight, summed.shared_weight) == (0.5, 1.0)
    with pytest.raises(ValueError, match="shared_combine"):
        moe_apply(p, x, dataclasses.replace(AVERAGED, shared_combine="max"))


def test_the_ranks_parts_and_the_shared_mean_once_add_up(nprng):
    """16 ranks holding 8 of 128 experts (here 8 ranks of 2 of 16) each
    compute their part of what the sigmoid router chose among all of
    them; the parts, with the averaged shared experts counted once,
    add up to the oracle's whole layer."""
    p = moe_init(jax.random.key(3), D, 4 * F, AVERAGED)
    x = jnp.asarray(nprng.normal(size=(2, 16, D)), jnp.float32)
    whole = moe_dense_oracle(p, x, AVERAGED)
    parts = jnp.zeros_like(x)
    for first in range(0, 16, 2):
        cut = dataclasses.replace(AVERAGED, experts_held=2, first_held=first,
                                  n_shared=0)
        held = {k: (v[first:first + 2] if k.startswith("w_") else v)
                for k, v in p.items() if k != "shared"}
        drawn = moe_init(jax.random.key(3), D, 4 * F, cut)
        assert jnp.array_equal(drawn["w_gate"], held["w_gate"])
        parts = parts + moe_apply(held, x, cut)
    shared_once = sum(_swiglu_of_a_slice(p["shared"], x, j)
                      for j in range(2)) / 2
    _close(parts + shared_once, whole)
    _close(moe_apply(p, x, AVERAGED), whole)
    # every rank adding the shared mean would count it eight times
    assert float(jnp.max(jnp.abs(parts + 8 * shared_once - whole))) > 1e-2


def test_the_bias_chooses_and_does_not_weigh(nprng):
    p = _params(WHOLE)
    x = jnp.asarray(nprng.normal(size=(1, 64, D)), jnp.float32)
    idx, gate = route(p, x, WHOLE)
    plain_idx, _ = route(dict(p, router_bias=jnp.zeros(8)), x, WHOLE)
    assert not jnp.array_equal(jnp.sort(idx, -1), jnp.sort(plain_idx, -1))
    s = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(s, idx, -1)
    _close(gate, 2.5 * chosen / chosen.sum(-1, keepdims=True))
    _close(gate.sum(-1), jnp.full(gate.shape[:-1], 2.5))
    # weighing by the biased score is another layer
    biased = jnp.take_along_axis(s + p["router_bias"], idx, -1)
    wrong = 2.5 * biased / biased.sum(-1, keepdims=True)
    assert float(jnp.max(jnp.abs(wrong - gate))) > 1e-3


@pytest.mark.parametrize("cfg", [WHOLE, SHARE], ids=["whole", "share"])
def test_under_a_client_vmap_in_a_gradient_in_a_checkpoint(cfg, nprng):
    """``jit(vmap(value_and_grad(checkpoint(loss))))`` over clients, the
    stacks shared, equals a Python loop over the clients."""
    p = _params(cfg)
    xs = jnp.asarray(nprng.normal(size=(3, 2, 12, D)), jnp.float32)

    def loss(x):
        return jnp.sum(jax.checkpoint(lambda x: moe_apply(p, x, cfg))(x) ** 2)

    def plain(x):
        return jnp.sum(moe_dense_oracle(p, x, cfg) ** 2)

    got = jax.jit(jax.vmap(jax.value_and_grad(loss)))(xs)
    for c in range(3):
        want = jax.value_and_grad(plain)(xs[c])
        assert float(got[0][c]) == pytest.approx(float(want[0]), rel=1e-5)
        _close(got[1][c], want[1], rtol=1e-4)


def test_a_fold_too_large_goes_a_client_at_a_time(nprng, monkeypatch):
    """Past ``_FOLDED_ROWS_BYTES`` in a block of the folded clients'
    sorted rows (here every expert is held and the block is every
    assignment) the shared stacks are read a client at a time: a ``while`` over the clients whose grouped
    products see one client's rows, with the values and gradients the
    fold gives."""
    from baton_tpu.models import moe

    p = _params(SHARE)
    xs = jnp.asarray(nprng.normal(size=(3, 2, 12, D)), jnp.float32)

    def step(xs):
        return jax.vmap(jax.value_and_grad(
            lambda x: jnp.sum(moe_apply(p, x, SHARE) ** 2)))(xs)

    folded = jax.jit(step)(xs)
    text = str(jax.make_jaxpr(step)(xs))
    # 3 clients x 24 tokens x 2 choices, folded into one product's rows
    assert "f32[144,16]" in text
    # 3 x 24 x 2 assignments of 16 float32 channels are 9,216 bytes
    monkeypatch.setattr(moe, "_FOLDED_ROWS_BYTES", 9215)
    jax.clear_caches()  # the limit is read when the rule is traced
    text = str(jax.make_jaxpr(step)(xs))
    assert "f32[144,16]" not in text and "f32[48,16]" in text
    mapped = jax.jit(lambda xs: step(xs))(xs)
    _close(mapped[0], folded[0], rtol=1e-5)
    _close(mapped[1], folded[1], rtol=1e-4)
    monkeypatch.setattr(moe, "_FOLDED_ROWS_BYTES", 9216)
    jax.clear_caches()
    assert "f32[144,16]" in str(jax.make_jaxpr(step)(xs))
    jax.clear_caches()
    # the cells, now that the sorted copy holds a block of rows and not
    # every assignment: both fold, sarvam_105b_c4_l2048 128 MiB and
    # glm5_c4_l8192 192 MiB; unbounded, glm5's four clients were 3 GiB
    limit = 1024 ** 3
    assert rows_bound(4 * 2048 * 8, 16, 128) * 4096 * 2 == 128 * 1024 ** 2
    assert rows_bound(4 * 8192 * 8, 8, 256) * 6144 * 2 == 192 * 1024 ** 2
    assert 4 * 2048 * 8 * 4096 * 2 <= limit < 4 * 8192 * 8 * 6144 * 2


# --------------------------------------------- the rows a rank holds
# 2 of 16 experts held, 512 tokens of 2 choices: 1,024 assignments, 128
# of them expected here, so a block holds 256 sorted rows
FEW = MoEConfig(n_experts=16, top_k=2, d_ff=F, experts_held=2, first_held=2,
                routed_scale=2.5, n_shared=1, router_bias_range=0.1)


def _crowded(blocks: int):
    """``FEW``'s parameters with the router turned so that the held
    experts take ``blocks`` blocks of rows: every token's first choice
    falls on the first held expert (two full blocks a sequence of 512),
    and the second held expert is never chosen or keeps its own share
    (a third block)."""
    bias = jnp.zeros(FEW.n_experts).at[FEW.first_held].set(5.0)
    if blocks == 2:
        bias = bias.at[FEW.first_held + 1].set(-5.0)
    return dict(_params(FEW), router_bias=bias)


def _blocks_run(p, x, cfg):
    """The blocks of sorted rows that hold a held expert's row, with
    every leading axis of ``x`` folded into the tokens."""
    idx = np.asarray(route(p, x, cfg)[0])
    rows = int(((idx >= cfg.first_held)
                & (idx < cfg.first_held + cfg.held)).sum())
    return -(-rows // rows_bound(idx.size, cfg.held, cfg.n_experts))


@pytest.mark.parametrize("blocks", [2, 3])
def test_more_held_rows_than_a_block_are_the_oracles(blocks, nprng):
    """No capacity: a routing that gives the held experts more rows
    than the bound runs further blocks and drops nothing. Forward, the
    input's gradient and every leaf's."""
    p = _crowded(blocks)
    x = jnp.asarray(nprng.normal(size=(1, 512, D)), jnp.float32)
    assert rows_bound(1024, FEW.held, FEW.n_experts) == 256
    assert _blocks_run(p, x, FEW) == blocks
    _close(moe_apply(p, x, FEW), moe_dense_oracle(p, x, FEW))

    def grads(fn):
        return jax.grad(lambda p, x: jnp.sum(fn(p, x, FEW) ** 2),
                        argnums=(0, 1))(p, x)

    got, want = jax.jit(lambda: grads(moe_apply))(), grads(moe_dense_oracle)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(g, w, rtol=1e-4)
    assert float(jnp.max(jnp.abs(got[0]["w_down"]))) > 0


@pytest.mark.parametrize("blocks", [2, 3])
def test_more_held_rows_than_a_block_under_the_client_vmap(blocks, nprng):
    """The same under ``jit(vmap(value_and_grad(checkpoint(..))))``:
    three clients folded into one sort of 3,072 assignments whose
    blocks hold 768 rows."""
    p = _crowded(blocks)
    xs = jnp.asarray(nprng.normal(size=(3, 1, 512, D)), jnp.float32)
    assert _blocks_run(p, xs, FEW) == blocks

    def loss(x):
        return jnp.sum(jax.checkpoint(lambda x: moe_apply(p, x, FEW))(x) ** 2)

    def plain(x):
        return jnp.sum(moe_dense_oracle(p, x, FEW) ** 2)

    step = jax.vmap(jax.value_and_grad(loss))
    assert "f32[768,16]" in str(jax.make_jaxpr(step)(xs))
    got = jax.jit(step)(xs)
    for c in range(3):
        want = jax.value_and_grad(plain)(xs[c])
        assert float(got[0][c]) == pytest.approx(float(want[0]), rel=1e-5)
        _close(got[1][c], want[1], rtol=1e-4)


def _made(jaxpr):
    """``(primitive, shape)`` of everything a jaxpr computes, its inner
    jaxprs (a loop's body, a custom rule's) with it."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _made(sub)


@pytest.mark.parametrize("cfg", [FEW, WHOLE], ids=["few", "whole"])
def test_past_the_sort_only_a_block_of_rows_is_held(cfg, nprng):
    """Where a share of the experts is held, the layer and its gradient
    make no array of every assignment's rows (``[N, D]``, ``[N, F]``,
    ``[T, K, D]``), only of a block's; where every expert is held the
    one block is every row and nothing loops or slices: the program is
    the one the layer had before the rows were bounded."""
    p = _params(cfg)
    x = jnp.asarray(nprng.normal(size=(1, 512, D)), jnp.float32)
    n = 512 * cfg.top_k
    r = rows_bound(n, cfg.held, cfg.n_experts)

    def step(p, x):
        return jax.value_and_grad(
            lambda p, x: jnp.sum(moe_apply(p, x, cfg) ** 2),
            argnums=(0, 1))(p, x)

    made = set(_made(jax.make_jaxpr(step)(p, x).jaxpr))
    shapes = {shape for _, shape in made}
    loops = {name for name, _ in made} & {"while", "dynamic_slice", "cond"}
    every = {(n, D), (n, F), (512, cfg.top_k, D)}
    if cfg is FEW:
        assert r == 256 and not shapes & every
        assert {(r, D), (r, F)} <= shapes and "while" in loops
    else:
        assert r == n and every <= shapes and not loops


@pytest.mark.parametrize("tokens, top_k, held, n_experts, want", [
    # sarvam_105b_c4_l2048: four clients' sequences of 2,048 folded
    (4 * 2048, 8, 16, 128, 16384),
    # glm5_c4_l8192: a client's sequence of 8,192, and the four folded
    (8192, 8, 8, 256, 4096),
    (4 * 8192, 8, 8, 256, 16384),
    # every expert held, and a share too large to bound: every row
    (2048, 8, 128, 128, 16384),
    (24, 2, 4, 8, 48),
    # the tile rounds up; a block is never more than the rows there are
    (300, 2, 2, 16, 256),
    (100, 2, 2, 16, 200),
], ids=["sarvam", "glm5-client", "glm5-folded", "all-held", "half-held",
        "rounded-up", "capped"])
def test_the_bound_on_the_rows_a_block_holds(tokens, top_k, held, n_experts,
                                             want):
    n = tokens * top_k
    r = rows_bound(n, held, n_experts)
    assert r == want and r <= n and (r == n or r % 256 == 0)
    assert r >= min(n, 2 * n * held // n_experts)


@pytest.mark.parametrize("block", [0, 1, 2, 3])
def test_a_blocks_group_sizes_sum_to_the_rows_in_its_window(block):
    """Sizes that straddle a block's edges: 200 + 0 + 120 + 300 held
    rows in windows of 256."""
    sizes = jnp.asarray([200, 0, 120, 300], jnp.int32)
    got = np.asarray(_block_sizes(sizes, block * 256, 256))
    want = [[200, 0, 56, 0], [0, 0, 64, 192], [0, 0, 0, 108], [0, 0, 0, 0]]
    assert got.tolist() == want[block]
    assert got.sum() == np.clip(620 - block * 256, 0, 256)


def test_stacks_that_carry_the_client_axis_take_the_map(nprng):
    """Experts that train are a client's own: their gradients under the
    ``vmap`` are each client's, as the loop gives them; and a client's
    gradient of stacks that are shared is its own too."""
    p = _params(SHARE)
    xs = jnp.asarray(nprng.normal(size=(3, 1, 12, D)), jnp.float32)
    ps = jax.tree_util.tree_map(
        lambda a: jnp.stack([a, 1.1 * a, 0.9 * a]), p)

    def grad(fn):
        return jax.grad(lambda p, x: jnp.sum(fn(p, x, SHARE) ** 2))

    got = jax.jit(jax.vmap(grad(moe_apply)))(ps, xs)
    shared = jax.jit(jax.vmap(grad(moe_apply), in_axes=(None, 0)))(p, xs)
    for c in range(3):
        own = jax.tree_util.tree_map(lambda a: a[c], ps)
        want = grad(moe_dense_oracle)(own, xs[c])
        for name in ("w_gate", "w_up", "w_down", "router"):
            _close(got[name][c], want[name], rtol=1e-4)
        _close(shared["w_down"][c],
               grad(moe_dense_oracle)(p, xs[c])["w_down"], rtol=1e-4)


def test_rows_past_the_groups_come_out_zero(nprng):
    x = jnp.asarray(nprng.normal(size=(10, 4)), jnp.float32)
    w = jnp.asarray(nprng.normal(size=(3, 4, 5)), jnp.float32)
    sizes = jnp.asarray([2, 0, 4], jnp.int32)
    got = grouped_matmul(x, w, sizes)
    want = np.zeros((10, 5), np.float32)
    want[:2] = np.asarray(x[:2] @ w[0])
    want[2:6] = np.asarray(x[2:6] @ w[2])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    back = grouped_matmul(got, w, sizes, transpose_rhs=True)
    assert back.shape == x.shape and not np.any(np.asarray(back[6:]))


def _recorder(opened: list):
    """Stands in for ``engine.annotate``: the spans opened, with their
    attributes."""
    import contextlib

    def annotate(name, **attrs):
        class Span(contextlib.AbstractContextManager):
            def __enter__(self):
                opened.append((name, attrs))
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **more):
                attrs.update(more)

        return Span()

    return annotate


def _experts_after_a_dense_layer(**kw):
    return LlamaConfig.tiny(
        n_layers=3, first_dense_layers=1, embed_std=1.0,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff=32, experts_held=4,
                      routed_scale=2.5, n_shared=1, router_bias_range=0.1),
        **kw)


def test_a_leading_dense_layer_then_expert_layers():
    """Block 0 holds a SwiGLU of the dense width, the others a router,
    the held stacks and a shared expert of the expert width; a base in
    bfloat16 keeps its router, the bias and every vector float32; the
    adapters go on 2-D projections alone."""
    cfg = _experts_after_a_dense_layer()
    model = decoder_lora_model(cfg, rank=2)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    blocks = shapes["base"]["blocks"]
    assert "router" not in blocks[0]["mlp"]
    assert blocks[0]["mlp"]["w_up"].shape == (64, 128)
    for b in blocks[1:]:
        assert b["mlp"]["router"].shape == (64, 8)
        assert b["mlp"]["w_up"].shape == (4, 64, 32)
        assert b["mlp"]["shared"]["w_up"].shape == (64, 32)
        assert b["mlp"]["router"].dtype == b["mlp"]["router_bias"].dtype \
            == jnp.float32
        assert b["mlp"]["w_down"].dtype == jnp.bfloat16
    assert {a.dtype for a in jax.tree_util.tree_leaves(shapes["base"])
            if a.ndim == 1} == {jnp.dtype(jnp.float32)}
    assert "blocks/1/mlp/shared/w_up" in shapes["lora"]
    assert not [k for k in shapes["lora"]
                if re.search(r"mlp/(w_|router)", k) and "blocks/0" not in k]
    assert projection_lora_target("blocks/1/mlp/shared/w_up",
                                  blocks[1]["mlp"]["shared"]["w_up"])
    assert not projection_lora_target("blocks/1/mlp/w_up",
                                      blocks[1]["mlp"]["w_up"])


def test_llama_moe_trains(nprng):
    from baton_tpu.core.training import make_local_trainer

    cfg = LlamaConfig.tiny(moe=MoEConfig(n_experts=4, top_k=2))
    model = llama_lm_model(cfg)
    trainer = make_local_trainer(model, batch_size=2, learning_rate=5e-2)
    toks = nprng.integers(0, cfg.vocab_size, size=(2, cfg.max_len))
    data = {"x": jnp.asarray(toks, jnp.int32), "y": jnp.asarray(toks, jnp.int32)}
    params = model.init(jax.random.key(0))
    _, _, hist = trainer.train(
        params, data, jnp.asarray(2), jax.random.key(1), 4
    )
    assert float(hist[-1]) < float(hist[0])


def test_llama_moe_remat_grads(nprng):
    cfg = _experts_after_a_dense_layer()
    plain = llama_lm_model(cfg)
    remat = llama_lm_model(cfg, remat=True, name="llama_moe_remat")
    params = plain.init(jax.random.key(0))
    toks = jnp.asarray(
        nprng.integers(0, cfg.vocab_size, size=(2, cfg.max_len)), jnp.int32
    )
    batch = {"x": toks, "y": toks}

    def loss(model):
        return lambda p: jnp.mean(model.per_example_loss(p, batch, jax.random.key(1)))

    g1 = jax.grad(loss(plain))(params)
    g2 = jax.grad(loss(remat))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_experts_sharded_over_a_mesh_axis_equal_the_replicated_layer(nprng):
    """GSPMD expert parallelism, as ``__graft_entry__`` runs tp + ep:
    the rules put the stacks' expert axis on the ``model`` axis, and
    the layer over stacks so sharded is the replicated one, forward and
    in every gradient."""
    from baton_tpu.parallel.mesh import make_mesh
    from baton_tpu.parallel.tensor_parallel import (
        shard_params_tp,
        transformer_tp_spec,
    )
    from jax.sharding import PartitionSpec as P

    cfg = MoEConfig(n_experts=4, top_k=2, routed_scale=2.5, n_shared=1,
                    router_bias_range=0.1)
    p = moe_init(jax.random.key(0), 16, 32, cfg)
    assert transformer_tp_spec("blocks/0/mlp/w_gate", p["w_gate"]) == P(
        "model", None, None)
    assert transformer_tp_spec("blocks/0/mlp/router", p["router"]) == P()

    sharded = shard_params_tp(p, make_mesh(4, axis_names=("model",)),
                              axis="model")
    assert sharded["w_down"].sharding.spec == P("model", None, None)
    x = jnp.asarray(nprng.normal(size=(2, 12, 16)), jnp.float32)
    apply = jax.jit(lambda p, x: moe_apply(p, x, cfg))
    np.testing.assert_allclose(np.asarray(apply(sharded, x)),
                               np.asarray(apply(p, x)), rtol=1e-5, atol=1e-6)
    grad = jax.jit(jax.grad(lambda p, x: jnp.sum(moe_apply(p, x, cfg) ** 2)))
    for got, want in zip(jax.tree_util.tree_leaves(grad(sharded, x)),
                         jax.tree_util.tree_leaves(grad(p, x))):
        _close(got, want, rtol=1e-5)


@pytest.mark.parametrize("k,budget,tiles", [
    (128, None, (256, 128, 256)), (384, None, (256, 384, 256)),
    (384, 1_400_000, (256, 128, 256))],
    ids=["one_tile_of_128", "384_whole", "384_in_three_tiles"])
@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["stack", "transposed"])
def test_the_chips_grouped_product_is_ragged_dot(transpose_rhs, k, budget,
                                                 tiles, nprng, monkeypatch):
    """The Pallas kernel a TPU runs, its body interpreted here: the
    groups' rows are ``ragged_dot``'s (one group empty, one straddling
    a tile of 256 rows), the rows past them are not written and come
    out zero through the same mask; sizes its tiles do not divide are
    refused. A contraction of 384, 2,304's shape in miniature: in the
    one tile it gets, and in three of 128 where the budget is cut so
    that it does not fit whole."""
    if budget is not None:
        monkeypatch.setattr(moe, "_GMM_VMEM_BUDGET", budget)
    assert gmm_tiles(k, 256, 4) == tiles
    x = jnp.asarray(nprng.normal(size=(512, k)), jnp.float32)
    w = jnp.asarray(nprng.normal(size=(3, k, 256)), jnp.float32)
    sizes = jnp.asarray([200, 0, 120], jnp.int32)
    want = _rows_of_the_groups(jax.lax.ragged_dot(x, w, sizes), sizes)
    if transpose_rhs:
        w = jnp.swapaxes(w, 1, 2)
    got = _rows_of_the_groups(_gmm(x, w, sizes, transpose_rhs, interpret=True),
                              sizes)
    _close(got, want, rtol=1e-5)
    assert not np.any(np.asarray(got[320:]))
    with pytest.raises(ValueError, match="multiple of 256"):
        _gmm(x[:500], w, sizes, transpose_rhs, interpret=True)


# the grouped products of the five configurations with experts, ``K x
# N``, and the ``(tk, tn)`` each was read fastest at on the chip
# (moe.py's table): the contraction whole, the widest column tile that
# fits beside it
_CELLS_PRODUCTS = {
    "mellum2_12b_up": (2304, 896, (2304, 896)),
    "mellum2_12b_down": (896, 2304, (896, 2304)),
    "zaya1_8b_up": (2048, 2048, (2048, 1024)),
    "zaya1_8b_down": (2048, 2048, (2048, 1024)),
    "sarvam_105b_up": (4096, 2048, (4096, 512)),
    "sarvam_105b_down": (2048, 4096, (2048, 1024)),
    "glm_5_up": (6144, 2048, (6144, 256)),
    "glm_5_down": (2048, 6144, (2048, 1024)),
    "command_a_plus_both": (4096, 4096, (4096, 512)),
    # 1,408 = 11 x 128: only 128 and itself divide it
    "only_128_divides_the_columns": (2048, 1408, (2048, 128)),
    "only_128_divides_the_contraction": (1408, 2048, (1408, 1024)),
    # a row tile of 16,384 channels alone is the whole of VMEM
    "a_contraction_that_does_not_fit": (16384, 2048, (2048, 1024)),
    "nor_beside_columns_only_128_divides": (16384, 1408, (8192, 128)),
}


@pytest.mark.parametrize("k,n,want", _CELLS_PRODUCTS.values(),
                         ids=_CELLS_PRODUCTS.keys())
def test_a_products_tiles_come_from_its_widths(k, n, want):
    """Both tiles divide their widths and are multiples of 128 (no
    masked remainder, no padded column tile), the blocks fit the
    budget, the row tile is ``rows_bound``'s, the choice is blind to
    anything but the widths and the item size, and in bfloat16 the
    contraction is one tile in every product a configuration makes."""
    tm, tk, tn = gmm_tiles(k, n, 2)
    assert (tk, tn) == want
    assert tm == 256 == rows_bound(10 ** 6, 1, 10 ** 6)
    assert moe._GMM_VMEM_BUDGET < 16 * 2 ** 20
    for itemsize in (2, 4):
        tm, tk, tn = gmm_tiles(k, n, itemsize)
        assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 == tn % 128
        assert _gmm_vmem_bytes(tm, tk, tn, itemsize) <= moe._GMM_VMEM_BUDGET
        # a wider dividing column tile beside a whole contraction
        # would not fit
        wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
        assert tk < k or not wider or _gmm_vmem_bytes(
            tm, tk, wider[0], itemsize) > moe._GMM_VMEM_BUDGET


@pytest.mark.parametrize("d_model,d_ff", [
    (2304, 896), (2048, 2048), (4096, 2048), (6144, 2048)],
    ids=["mellum2_12b", "zaya1_8b", "sarvam_105b", "glm_5"])
def test_a_trace_says_the_tiles_where_the_kernel_runs(d_model, d_ff,
                                                      monkeypatch):
    """Off a TPU the grouped product is ``ragged_dot`` and the model's
    span says nothing of tiles; on one, both widths' products in either
    direction, every contraction whole, no element padding."""
    assert expert_tiles(d_model, d_ff, jnp.bfloat16) == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    said = expert_tiles(d_model, d_ff, jnp.bfloat16)["expert_tiles"].split()
    assert [s.split(":")[0] for s in said] == [
        f"{d_model}x{d_ff}", f"{d_model}x{d_ff}t",
        f"{d_ff}x{d_model}", f"{d_ff}x{d_model}t"]
    for entry in said:
        shape, tiles, contraction, padding = entry.split(":")
        k, n = map(int, shape.rstrip("t").split("x"))
        assert tiles == "x".join(map(str, gmm_tiles(k, n, 2)))
        assert (contraction, padding) == ("whole", "pad0")


def test_the_wave_program_holds_the_stacks_once_and_takes_no_gradient(
        monkeypatch):
    """Over a frozen bfloat16 base, in the compiled wave program of
    ``FedSim``: no array of an expert stack's shape with a client axis
    before it and no gradient of that shape (nothing of a stack's shape
    is computed at all)."""
    from baton_tpu.models.lora import lora_trainable
    from baton_tpu.parallel import engine
    from baton_tpu.parallel.engine import FedSim

    cfg = _experts_after_a_dense_layer(d_model=48, d_ff=96)
    model = decoder_lora_model(cfg, rank=2, b_std=0.02)
    params = model.init(jax.random.key(0))
    clients = 3
    x = jax.random.randint(jax.random.key(1), (clients, 2, 13), 0, 256)
    data = {"x": x[..., :-1], "y": x[..., 1:]}
    sim = FedSim(model, batch_size=1, learning_rate=0.05,
                 trainable=lora_trainable)
    text = sim.lower_wave(params, data, np.asarray([2, 2, 1], np.int32),
                          jax.random.key(2), 1, None).compile().as_text()
    made = re.findall(r"= (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(", text)
    stacks = {"4,48,32", "4,32,48"}
    assert len(made) > 1000  # the pattern finds the program's instructions
    assert not [m for m in made
                if any(m[1].endswith("," + k) for k in stacks)]
    # what is of a stack's shape is a parameter or a view of one: no
    # dot, no fusion, no reduce, no loop writes such an array, so no
    # gradient of a stack is formed. (The CPU's compiler has no
    # bfloat16 product and converts each operand where it is used, so
    # here a float32 array of that shape is a ``convert`` of a
    # parameter; on the chip there is none: chip_smoke.py's
    # ``moe_mla_lora`` phase holds the TPU's program to that.)
    written = {(dtype, op) for dtype, shape, op in made if shape in stacks}
    assert {d for d, _ in written} <= {"bf16", "f32"}
    assert {op for d, op in written if d == "bf16"} <= {
        "parameter", "copy", "bitcast", "get-tuple-element", "transpose"}
    assert {op for d, op in written if d == "f32"} <= {"convert"}
    spans = []
    monkeypatch.setattr(engine, "annotate", _recorder(spans))
    res = sim.run_round(params, data, np.asarray([2, 2, 1], np.int32),
                        jax.random.key(2), n_epochs=1,
                        collect_client_losses=False)
    assert np.isfinite(float(res.loss_history[-1]))
    # the round's span says what the layer holds, beside the bytes held
    # once: the bfloat16 base and nothing more
    name, attrs = spans[0]
    assert name == "baton.round"
    assert (attrs["experts_held"], attrs["experts_total"]) == (4, 8)
    # half the experts held: a block is every row, 2 a token
    assert attrs["routed_rows_bound"] == 2048
    assert attrs["frozen_bytes"] == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(params["base"]))
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(params["base"]),
        jax.tree_util.tree_leaves(res.params["base"])))


# --------------------------------------- the MLP router, its state, the skip
STATEFUL = MoEConfig(n_experts=4, top_k=1, d_ff=F, router_hidden=8, skip=True,
                     router_bias_range=0.1, router_norm_eps=1e-5)


def _erf_gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def _plain_router(p, x, state):
    r = p["router"]
    s = x @ r["w_in"] + r["b_in"] + r["state_scale"] * state
    z = s / jnp.sqrt(jnp.mean(s * s, -1, keepdims=True) + 1e-5) * r["norm"]
    z = _erf_gelu(z @ r["w1"] + r["b1"])
    z = _erf_gelu(z @ r["w2"] + r["b2"])
    return jax.nn.softmax(z @ r["w3"], axis=-1), s


def test_the_mlp_router_chooses_by_p_plus_bias_and_weighs_by_p(nprng):
    p = _params(STATEFUL)
    assert p["router"]["w3"].shape == (8, 5) and p["router_bias"].shape == (5,)
    assert STATEFUL.router_outputs == 5
    # the second and third matrices' columns sum to nothing
    for name in ("w2", "w3"):
        assert float(jnp.max(jnp.abs(p["router"][name].sum(0)))) < 1e-5
    x = jnp.asarray(nprng.normal(size=(2, 64, D)), jnp.float32)
    state = jnp.asarray(nprng.normal(size=(2, 64, 8)), jnp.float32)
    idx, gate, out = route_mlp(p, x, state, STATEFUL)
    prob, want_state = _plain_router(p, x, state)
    _close(out, want_state)
    assert idx.shape == gate.shape == (2, 64, 1)
    assert jnp.array_equal(idx[..., 0],
                           jnp.argmax(prob + p["router_bias"], -1))
    # the weight is the probability itself: not renormalised (that
    # would be 1), not the biased score
    _close(gate, jnp.take_along_axis(prob, idx, -1))
    assert float(jnp.min(gate)) < 0.9
    unbiased, _, _ = route_mlp(dict(p, router_bias=jnp.zeros(5)), x, state,
                               STATEFUL)
    assert not jnp.array_equal(idx, unbiased)
    # every output is chosen by someone, the skip among them
    assert set(np.asarray(idx).ravel()) == set(range(5))
    # no state is zeros of state
    none = route_mlp(p, x, None, STATEFUL)
    zeros = route_mlp(p, x, jnp.zeros_like(state), STATEFUL)
    for a, b in zip(none, zeros):
        assert jnp.array_equal(a, b)


def test_the_state_carried_down_three_layers_changes_the_third_choice(nprng):
    """Three layers' routers fed the same tokens: with the state handed
    on, layer 2's router sees what layers 0 and 1 left; cut the way
    (zeros in) and some of its tokens choose another expert."""
    layers = [_params(STATEFUL, seed) for seed in range(3)]
    x = jnp.asarray(nprng.normal(size=(1, 96, D)), jnp.float32)
    state = None
    for p in layers:
        idx, _, state = route_mlp(p, x, state, STATEFUL)
    alone, _, own = route_mlp(layers[2], x, None, STATEFUL)
    assert int(jnp.sum(idx != alone)) > 5
    # the state out is this layer's projection plus the scaled state in
    _, _, before = route_mlp(layers[1], x, route_mlp(
        layers[0], x, None, STATEFUL)[2], STATEFUL)
    _close(state, own + layers[2]["router"]["state_scale"] * before)
    # and it carries a gradient to the layer before's input
    def through(x0):
        s = route_mlp(layers[0], x0, None, STATEFUL)[2]
        return jnp.sum(route_mlp(layers[1], x, s, STATEFUL)[1])
    assert float(jnp.max(jnp.abs(jax.grad(through)(x)))) > 0


def test_a_skipped_token_adds_exactly_zero_and_gets_no_row(nprng):
    p = _params(STATEFUL)
    x = jnp.asarray(nprng.normal(size=(2, 48, D)), jnp.float32)
    idx, gate, _ = route_mlp(p, x, None, STATEFUL)
    skipped = np.asarray(idx[..., 0] == STATEFUL.n_experts)
    assert 0 < skipped.sum() < skipped.size
    y, state = moe_apply_with_state(p, x, None, STATEFUL)
    assert (np.asarray(y)[skipped] == 0).all()
    assert (np.abs(np.asarray(y)[~skipped]).max(-1) > 0).all()
    _close(y, moe_dense_oracle(p, x, STATEFUL))
    # no row in the grouped products: the held experts' sizes sum to the
    # tokens that did not skip, and the skipped assignments sort last
    local = jnp.asarray(idx.reshape(-1, 1), jnp.int32)
    order, _, sizes, live = _sorted_rows(local, STATEFUL.held)
    assert int(sizes.sum()) == int((~skipped).sum()) == int(live.sum())
    assert not np.asarray(live)[int(sizes.sum()):].any()
    assert skipped.reshape(-1)[np.asarray(order)[int(sizes.sum()):]].all()
    # one block over every row, no loop: the router is 5 wide over 4 held
    assert rows_bound(96, 4, 5) == 96
    # the gradient of a skipped token's input through the layer is the
    # router's alone (its state goes on), and the oracle's
    weight = jnp.asarray(nprng.normal(size=x.shape), jnp.float32)

    def grad(fn):
        return jax.grad(lambda x: jnp.sum(fn(x) * weight))(x)

    _close(grad(lambda x: moe_apply_with_state(p, x, None, STATEFUL)[0]),
           grad(lambda x: moe_dense_oracle(p, x, STATEFUL)), rtol=1e-4)


def test_two_ranks_of_two_experts_and_the_skip_add_up(nprng):
    """The guide's test of a share, kept although the cell holds all 16:
    ranks holding experts 0-1 and 2-3 route over all five outputs; their
    parts add up to the uncut layer's result, and the skip is nobody's."""
    p = _params(STATEFUL)
    x = jnp.asarray(nprng.normal(size=(2, 40, D)), jnp.float32)
    state = jnp.asarray(nprng.normal(size=(2, 40, 8)), jnp.float32)
    want, want_state = moe_apply_with_state(p, x, state, STATEFUL)
    total = jnp.zeros_like(x)
    for first in (0, 2):
        cut = dataclasses.replace(STATEFUL, experts_held=2, first_held=first)
        drawn = moe_init(jax.random.key(0), D, 4 * F, cut)
        assert jnp.array_equal(drawn["w_up"], p["w_up"][first:first + 2])
        assert jnp.array_equal(drawn["router"]["w3"], p["router"]["w3"])
        part, got_state = moe_apply_with_state(drawn, x, state, cut)
        assert jnp.array_equal(got_state, want_state)
        _close(part, moe_dense_oracle(drawn, x, cut, state))
        total = total + part
    _close(total, want)
    assert float(jnp.max(jnp.abs(total))) > 0


def test_the_stateful_layer_under_the_wave_programs_nesting(nprng):
    """``jit(vmap(value_and_grad(checkpoint)))`` over clients with the
    state an input: the oracle's loss and both gradients a client."""
    p = _params(STATEFUL)
    xs = jnp.asarray(nprng.normal(size=(3, 1, 24, D)), jnp.float32)
    states = jnp.asarray(nprng.normal(size=(3, 1, 24, 8)), jnp.float32)

    def loss(x, r):
        y, r = jax.checkpoint(
            lambda x, r: moe_apply_with_state(p, x, r, STATEFUL))(x, r)
        return jnp.sum(y ** 2) + jnp.sum(r ** 2)

    def plain(x, r):
        return jnp.sum(moe_dense_oracle(p, x, STATEFUL, r) ** 2) \
            + jnp.sum(route_mlp(p, x, r, STATEFUL)[2] ** 2)

    got = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1))))(
        xs, states)
    for c in range(3):
        want = jax.value_and_grad(plain, argnums=(0, 1))(xs[c], states[c])
        assert float(got[0][c]) == pytest.approx(float(want[0]), rel=1e-5)
        for g, w in zip(got[1], want[1]):
            _close(g[c], w, rtol=1e-4)
