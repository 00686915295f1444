"""The plain reference round over a frozen base: with a ``trainable``
predicate ``fedbench/reference.py`` differentiates, steps and averages
the accepted leaves alone and hands every rejected leaf back as the
array it was given, so that a base of some gigabytes in bfloat16 costs
the round nothing beyond itself; and the probe cohort has no empty
client at the batches such a model trains at. A small mixed tree on the
CPU; PERF.md section 6 (PR 27) has the same round at 7 B's widths on
the chip."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest, reference, run  # noqa: E402

BENCH = manifest.load_manifest(ROOT)
LR = 0.1
SIZES = np.asarray([1, 2, 3, 4], np.int32)


def _tree(seed=0):
    """A frozen base in bfloat16 with float32 adapters on it; no frozen
    leaf has the shape of a trainable one."""
    k = jax.random.split(jax.random.key(seed), 6)
    return {
        "base": {"w_in": jax.random.normal(k[0], (6, 16), jnp.bfloat16),
                 "w_out": jax.random.normal(k[1], (16, 5), jnp.bfloat16),
                 "bias": jnp.zeros((5,), jnp.bfloat16)},
        "adapter": {"a": 0.1 * jax.random.normal(k[2], (6, 2), jnp.float32),
                    "b": 0.1 * jax.random.normal(k[3], (2, 16), jnp.float32)},
    }


def _adapters_only(path, leaf):
    return path.startswith("adapter/")


def _everything(path, leaf):
    return True


def _loss(params, x, y, mask):
    """Casts each frozen weight where it is used, as a reference over a
    frozen base must."""
    base, adapter = params["base"], params["adapter"]
    w_in = base["w_in"].astype(jnp.float32) + adapter["a"] @ adapter["b"]
    hidden = jnp.tanh(x @ w_in)
    logits = (hidden @ base["w_out"].astype(jnp.float32)
              + base["bias"].astype(jnp.float32))
    return reference.masked_mean_cross_entropy(logits, y, mask)


def _cohort(seed=1):
    kx, ky = jax.random.split(jax.random.key(seed))
    return {"x": jax.random.normal(kx, (4, 4, 6), jnp.float32),
            "y": jax.random.randint(ky, (4, 4), 0, 5, jnp.int32)}


def _round(params, trainable):
    return reference.reference_round(_loss, params, _cohort(), SIZES, LR,
                                     trainable)


def test_rejected_leaves_come_back_as_the_objects_that_went_in():
    params = _tree()
    new, loss = _round(params, _adapters_only)
    assert jax.tree_util.tree_structure(new) == \
        jax.tree_util.tree_structure(params)
    for name, leaf in params["base"].items():
        assert new["base"][name] is leaf, name  # no cast, no copy
    for name, leaf in params["adapter"].items():
        assert new["adapter"][name] is not leaf
        assert new["adapter"][name].dtype == jnp.float32
        assert float(jnp.max(jnp.abs(new["adapter"][name] - leaf))) > 0
    assert loss == loss and loss > 0


def test_no_gradient_of_a_rejected_leaf_is_taken():
    params = _tree()
    grad, moving, held, _ = reference.trainable_grad(_loss, params,
                                                     _adapters_only)
    assert [a.shape for a in moving] == [(6, 2), (2, 16)]
    assert [a.dtype for a in held] == [jnp.bfloat16] * 3
    data = _cohort()
    jaxpr = jax.make_jaxpr(grad)(moving, held, data["x"][0], data["y"][0],
                                 jnp.ones((4,), jnp.float32))
    out = [v.aval.shape for v in jaxpr.jaxpr.outvars]
    assert out == [(), (6, 2), (2, 16)]
    assert not {a.shape for a in held} & set(out)
    # and without a predicate it is the gradient of every leaf, as before
    grad, moving, held, _ = reference.trainable_grad(_loss, params)
    assert len(moving) == 5 and held == []


def test_what_the_round_allocates_is_float32_of_the_trainable_part():
    params = _tree()
    new, _ = _round(params, _adapters_only)
    given = {id(a) for a in jax.tree_util.tree_leaves(params)}
    made = [a for a in jax.tree_util.tree_leaves(new) if id(a) not in given]
    trainable = sum(a.size for a in
                    jax.tree_util.tree_leaves(params["adapter"]))
    assert len(made) == 2
    assert sum(a.nbytes for a in made) <= 4 * trainable
    assert all(a.nbytes <= 4 * a.size for a in made)


@pytest.mark.parametrize("seed", [0, 1])
def test_a_predicate_that_accepts_everything_changes_no_bit(seed):
    params = _tree(seed)
    plain, plain_loss = _round(params, None)
    every, every_loss = _round(params, _everything)
    assert plain_loss == every_loss
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(every)):
        assert a.dtype == b.dtype == jnp.float32
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_accepted_leaves_step_as_they_do_in_the_round_of_all():
    """The frozen base enters the loss undifferentiated and the adapters'
    step is the one they take when every leaf trains."""
    params = _tree()
    new, loss = _round(params, _adapters_only)
    every, every_loss = _round(params, None)
    assert loss == every_loss
    for name in params["adapter"]:
        assert np.array_equal(np.asarray(new["adapter"][name]),
                              np.asarray(every["adapter"][name]))


@pytest.mark.parametrize("norm", ["max", "l2"])
def test_the_disagreement_is_over_the_accepted_leaves(norm):
    before = _tree()
    want, _ = _round(before, _adapters_only)
    got = jax.tree_util.tree_map(lambda a: a, want)
    got["adapter"]["a"] = want["adapter"]["a"] + 1e-3
    got["base"]["w_out"] = before["base"]["w_out"] + 1.0
    over_adapters = reference.update_disagreement(before, got, want, norm,
                                                  _adapters_only)
    assert 0 < over_adapters < 1
    # the moved frozen leaf counts only where every leaf does
    assert reference.update_disagreement(before, got, want, norm) > 10
    assert reference.held_unchanged(before, got, _adapters_only) == (2, 3)
    assert reference.held_unchanged(before, want, _adapters_only) == (3, 3)


def test_a_frozen_leaf_of_another_dtype_is_a_changed_leaf():
    before = _tree()
    after = jax.tree_util.tree_map(lambda a: a, before)
    after["base"]["bias"] = before["base"]["bias"].astype(jnp.float32)
    assert reference.held_unchanged(before, after, _adapters_only) == (2, 3)


@pytest.mark.parametrize("batch,sizes", [(1, [1, 1, 1, 1]), (2, [1, 1, 1, 2]),
                                         (3, [1, 1, 2, 3]), (4, [1, 2, 3, 4]),
                                         (32, [8, 16, 24, 32])])
def test_the_probe_cohort_has_no_empty_client(batch, sizes):
    config = manifest.load_config(ROOT, BENCH, "bert_base")
    data, got = run.probe_cohort(ROOT, config, {"batch": batch, "seq_len": 8},
                                 True, 11)
    assert list(got) == sizes and got.dtype == np.int32
    assert data["x"].shape[:2] == (4, batch)
