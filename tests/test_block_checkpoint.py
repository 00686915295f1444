"""A decoder's checkpoint ends where the device's memory does
(``llama.blocks_kept``): the trailing blocks whose products fit the
plan budget keep them and make no product again; the others are
checkpointed as ever. Held here: the chooser as a pure function of
bytes, what it reads off a model's shapes, and that a model with any
number of kept blocks computes what the model without a checkpoint
computes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.core.model import WAVE_AXIS, clients_in_wave
from baton_tpu.models import llama
from baton_tpu.models.llama import (
    LlamaConfig, blocks_kept, llama_lm_model, plan_bytes)
from baton_tpu.models.moe import MoEConfig
from baton_tpu.models.transformer import IndexerConfig, MLAConfig
from baton_tpu.utils import profiling
from tests._hybrid_decoder_shared import _hybrid

# a step's bytes, in units that keep the arithmetic readable: what is
# held throughout, the loss, and eight blocks of two kinds
FIXED, LOSS = 50, 10
CHECKPOINTED = [1] * 8
KEPT = [12, 12, 12, 7] * 2
WHOLE = [30, 30, 30, 15] * 2


def _plan(k, loss=LOSS):
    return plan_bytes(k, FIXED, loss, CHECKPOINTED, KEPT, WHOLE)


def _kept(budget, scale=1):
    return blocks_kept(
        None if budget is None else budget * scale, FIXED * scale,
        LOSS * scale, *([b * scale for b in blocks]
                        for blocks in (CHECKPOINTED, KEPT, WHOLE)))


def test_no_budget_keeps_nothing():
    assert _kept(None) == 0
    # nor does a budget under what the step holds checkpointed whole
    assert _kept(1) == 0
    assert blocks_kept(10 ** 9, FIXED, LOSS, [], [], []) == 0


def test_the_plan_counts_each_moment_once():
    """Checkpointed whole, the peak is a block made again beside every
    block's input; with the last block kept, that block beside the
    loss (or beside the rest of itself made again, where that is
    more), or the largest of the others made again, whichever is
    larger; with every block kept, all of them beside the loss."""
    assert _plan(0) == 50 + 8 + 30
    assert _plan(1) == 50 + 7 + 30
    assert _plan(3) == 50 + 5 + (7 + 12 + 12) + 10
    assert _plan(8) == 50 + sum(KEPT) + 10
    # a loss smaller than the rest of the last block: its backward is
    # the moment
    assert _plan(3, loss=3) == 50 + 5 + (7 + 12 + 12) + (15 - 7)
    assert _plan(8, loss=3) == 50 + sum(KEPT) + (15 - 7)


@pytest.mark.parametrize("scale", [1, 4], ids=["one_client", "four_scaled"])
def test_the_choice_grows_with_the_budget_and_ends_at_the_depth(scale):
    """Monotone in the budget, never above the depth, the depth where
    everything fits, and the same for a wave of four clients on four
    times the budget as for one client."""
    chosen = [_kept(budget, scale) for budget in range(0, 200)]
    assert chosen == sorted(chosen)
    # not 1: with these bytes a second kept block costs less than what
    # it spares the backward of the blocks before it
    assert set(chosen) == {0, 2, 3, 4, 5, 6, 7, 8}
    assert chosen[-1] == len(KEPT) == _kept(10 ** 6, scale)
    assert chosen == [_kept(budget) for budget in range(0, 200)]
    for budget, k in enumerate(chosen):
        if k:
            assert _plan(k) <= budget
        assert all(_plan(more) > budget for more in range(k + 1, 9))


def test_the_clients_of_a_wave_are_read_off_the_named_axis():
    assert clients_in_wave() == 1
    sizes = []
    jax.vmap(lambda x: sizes.append(clients_in_wave()) or x,
             axis_name=WAVE_AXIS)(jnp.zeros(3))
    jax.vmap(lambda x: jax.lax.scan(
        lambda c, _: (sizes.append(clients_in_wave()) or c, None), x, None,
        length=2)[0], axis_name=WAVE_AXIS)(jnp.zeros(5))
    assert sizes == [3, 5]


# ------------------------------------------------------------ the models
LENGTH = 20
DECODERS = {
    "hybrid": _hybrid(n_layers=4, embed_std=1.0),
    "expert": LlamaConfig.tiny(
        n_layers=3, first_dense_layers=1, embed_std=1.0,
        moe=MoEConfig(n_experts=8, top_k=2, d_ff=32, experts_held=4,
                      routed_scale=2.5, n_shared=1, router_bias_range=0.1)),
    # queries past the sixth choose their keys: the mixer keeps its own
    # inputs and the choice applies to the feed-forward alone
    "latent_attention": LlamaConfig.tiny(
        n_layers=2, embed_std=1.0, norm_eps=1e-5,
        mla=MLAConfig(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=24,
                      q_rank=24, rope_theta=1e6, norm_eps=1e-5, block=8,
                      indexer=IndexerConfig(heads=2, dim=16, topk=6,
                                            rope_dim=8))),
    "parallel_block": LlamaConfig(
        vocab_size=96, max_len=32, d_model=64, n_layers=4, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=32, rope_theta=50000.0, window=5,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        norm_eps=1e-5, tie_embeddings=True, embed_std=1.0,
        parallel_block=True, norm="layer", full_layer_rope=False,
        rope_pairs="adjacent",
        moe=MoEConfig(n_experts=16, top_k=2, d_ff=32, experts_held=2,
                      first_held=4, n_shared=2, shared_combine="average")),
}


def _batch(cfg, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(2, LENGTH + 1))
    return {"x": jnp.asarray(ids[:, :-1], jnp.int32),
            "y": jnp.asarray(ids[:, 1:], jnp.int32)}


def _policies(model, params, batch):
    """The policy of every checkpoint the loss's jaxpr holds, in order,
    those inside another checkpoint apart: one a block."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "remat2":  # jax.checkpoint
                yield e.params["policy"]
                continue
            for value in e.params.values():  # a jaxpr, closed or not
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)

    return list(walk(jax.make_jaxpr(model.per_example_loss)(
        params, batch, None).jaxpr))


@pytest.mark.parametrize("kept", ["none", "one", "all"])
@pytest.mark.parametrize("name", sorted(DECODERS))
def test_kept_blocks_compute_what_no_checkpoint_computes(
        monkeypatch, name, kept):
    """Value and gradients by every parameter with no block kept, the
    last one and all of them against the model without ``remat``, to
    float32 rounding; the jaxpr holds as many checkpoints that keep
    products as the chooser said and the others as they were."""
    cfg = DECODERS[name]
    k = {"none": 0, "one": 1, "all": cfg.n_layers}[kept]
    monkeypatch.setattr(llama, "_plan_budget_bytes", lambda: 1)
    monkeypatch.setattr(llama, "blocks_kept", lambda *plan: k)
    plain = llama_lm_model(cfg)
    model = llama_lm_model(cfg, remat=True)
    params = plain.init(jax.random.key(3))
    batch = _batch(cfg)

    def value_and_grads(m):
        return jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(m.per_example_loss(p, batch, None))))(params)

    want, want_grads = value_and_grads(plain)
    got, got_grads = value_and_grads(model)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_want = jax.tree_util.tree_leaves_with_path(want_grads)
    for (path, w), g in zip(flat_want, jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    seen = dict(model.span_attrs)
    assert seen["blocks_kept"] == k
    assert seen["kept_block_bytes"] > 0
    assert seen["plan_estimate_bytes"] > seen["kept_block_bytes"]
    # a mixer that stands outside the block's checkpoint holds its own
    keeps = [p is llama._products_saveable
             for p in _policies(model, params, batch)]
    mixers_own = len(_policies(plain, params, batch))
    assert len(keeps) == cfg.n_layers + mixers_own
    assert sum(keeps) == k
    if not mixers_own:
        assert keeps == [False] * (cfg.n_layers - k) + [True] * k


def test_a_device_without_a_budget_runs_the_program_it_ran():
    """The CPU is in no table: no block is kept, no estimate is made
    (no abstract trace of a block), and the jaxpr is the one a model
    whose checkpoints know nothing of a budget gives."""
    cfg = DECODERS["hybrid"]
    model = llama_lm_model(cfg, remat=True)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)
    policies = _policies(model, params, batch)
    assert len(policies) == cfg.n_layers
    assert llama._products_saveable not in policies
    seen = dict(model.span_attrs)
    assert seen["blocks_kept"] == 0
    assert "plan_estimate_bytes" not in seen and "kept_block_bytes" not in seen
    assert "blocks_kept" not in dict(llama_lm_model(cfg).span_attrs)


@pytest.mark.parametrize("budget_gb,kept", [(1e-4, 0), (64.0, 4)])
def test_the_budget_is_the_devices_plan_budget(monkeypatch, budget_gb, kept):
    """``profiling.hbm_budget_gb`` of the device the trace is for, the
    one ``FedSim.auto_wave_size`` holds a wave's plan to."""
    monkeypatch.setitem(profiling.HBM_BUDGET_GB, "cpu", budget_gb)
    cfg = DECODERS["hybrid"]
    model = llama_lm_model(cfg, remat=True)
    jax.eval_shape(model.per_example_loss,
                   jax.eval_shape(model.init, jax.random.key(0)),
                   _batch(cfg, 1), None)
    assert dict(model.span_attrs)["blocks_kept"] == kept


def test_a_wave_of_four_holds_four_times_a_clients_blocks(monkeypatch):
    """Under the engine's client ``vmap`` a model sees one client's
    shapes; the estimate is of the wave's."""
    monkeypatch.setitem(profiling.HBM_BUDGET_GB, "cpu", 64.0)
    cfg = DECODERS["hybrid"]
    model = llama_lm_model(cfg, remat=True)
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = _batch(cfg, 2)
    jax.eval_shape(model.per_example_loss, params, batch, None)
    one = dict(model.span_attrs)["kept_block_bytes"]
    jax.eval_shape(
        lambda p, wave: jax.vmap(
            lambda b: model.per_example_loss(p, b, None),
            axis_name=WAVE_AXIS)(wave),
        params, jax.tree_util.tree_map(lambda a: jnp.stack([a] * 4), batch))
    assert dict(model.span_attrs)["kept_block_bytes"] == 4 * one


def test_a_blocks_part_is_its_residuals_less_its_parameters():
    """``residual_bytes`` of ``x -> tanh(x @ w)``: the product's input,
    the ``tanh`` and its derivative, padded to the chip's tiles, and
    not ``w``; under a bare checkpoint the input alone; under the kept
    blocks' policy the input and the product, from which the backward
    makes the other two again."""
    def fn(p, x):
        return jnp.tanh(x @ p["w"])

    p = {"w": jnp.zeros((48, 200), jnp.bfloat16)}
    x = jnp.zeros((1, 30, 48), jnp.bfloat16)
    tile = lambda rows, cols: 2 * rows * cols  # bfloat16, padded
    assert llama.residual_bytes(fn, p, x) == tile(32, 128) + 2 * tile(32, 256)
    assert llama.residual_bytes(jax.checkpoint(fn), p, x) == tile(32, 128)
    assert llama.residual_bytes(
        jax.checkpoint(fn, policy=llama._products_saveable), p, x) \
        == tile(32, 128) + tile(32, 256)
