"""FedAvg aggregation vs the closed-form oracle (SURVEY §4c).

Oracle: the reference manager's update rule
``value = Σ(client_value · n_samples) / Σ n_samples`` (manager.py:119-126)
evaluated in numpy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from baton_tpu.ops import aggregation as agg


def _oracle_mean(stacked_np, weights_np):
    w = weights_np.astype(np.float64)
    return {
        k: np.tensordot(w, v.astype(np.float64), axes=(0, 0)) / w.sum()
        for k, v in stacked_np.items()
    }


@pytest.fixture
def stacked(nprng):
    c = 8
    return (
        {
            "w": nprng.standard_normal((c, 4, 3)).astype(np.float32),
            "b": nprng.standard_normal((c, 3)).astype(np.float32),
        },
        nprng.integers(1, 100, size=c).astype(np.float32),
    )


def test_weighted_tree_mean_matches_oracle(stacked):
    tree, weights = stacked
    got = agg.weighted_tree_mean(
        {k: jnp.asarray(v) for k, v in tree.items()}, jnp.asarray(weights)
    )
    want = _oracle_mean(tree, weights)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]), want[k], rtol=1e-5)


def test_weighted_mean_uniform_weights_is_plain_mean(stacked):
    tree, _ = stacked
    got = agg.weighted_tree_mean(
        {k: jnp.asarray(v) for k, v in tree.items()},
        jnp.ones(tree["b"].shape[0]),
    )
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(got[k]), tree[k].mean(axis=0), rtol=1e-5
        )


def test_psum_weighted_mean_matches_oracle(stacked):
    tree, weights = stacked
    devices = jax.devices()
    assert len(devices) >= 8, "conftest must provide 8 virtual devices"
    mesh = Mesh(np.asarray(devices[:8]), ("clients",))

    def kernel(t, w):
        return agg.psum_weighted_mean(t, w, "clients")

    fn = jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P("clients"), P("clients")),
            out_specs=P(),
            check_vma=False,
        )
    )
    got = fn({k: jnp.asarray(v) for k, v in tree.items()}, jnp.asarray(weights))
    want = _oracle_mean(tree, weights)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]), want[k], rtol=1e-5)


def test_weighted_scalar_mean_matches_loss_aggregation(nprng):
    # Reference loss-history aggregation (manager.py:127-130)
    losses = nprng.standard_normal((5, 3)).astype(np.float32)  # [C, epochs]
    n = nprng.integers(1, 50, size=5).astype(np.float32)
    got = agg.weighted_scalar_mean(jnp.asarray(losses), jnp.asarray(n))
    want = (losses * n[:, None]).sum(0) / n.sum()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_tree_stack_unstack_roundtrip(nprng):
    trees = [
        {"a": nprng.standard_normal(3).astype(np.float32), "b": {"c": np.float32(i)}}
        for i in range(4)
    ]
    stacked = agg.tree_stack([jax.tree_util.tree_map(jnp.asarray, t) for t in trees])
    assert stacked["a"].shape == (4, 3)
    back = agg.tree_unstack(stacked)
    for orig, rt in zip(trees, back):
        np.testing.assert_allclose(np.asarray(rt["a"]), orig["a"])


def test_trimmed_mean_rejects_outlier(nprng):
    c = 10
    vals = np.ones((c, 4), np.float32)
    vals[0] = 1e6  # byzantine client
    got = agg.trimmed_mean({"p": jnp.asarray(vals)}, trim_ratio=0.2)["p"]
    np.testing.assert_allclose(np.asarray(got), np.ones(4), rtol=1e-5)


def test_coordinate_median(nprng):
    vals = nprng.standard_normal((9, 5)).astype(np.float32)
    got = agg.coordinate_median({"p": jnp.asarray(vals)})["p"]
    np.testing.assert_allclose(np.asarray(got), np.median(vals, axis=0), rtol=1e-5)


def test_global_sq_dist():
    a = {"x": jnp.ones((2, 2)), "y": jnp.zeros(3)}
    b = {"x": jnp.zeros((2, 2)), "y": jnp.ones(3)}
    assert float(agg.global_sq_dist(a, b)) == pytest.approx(7.0)
