"""The plain reference of one federated round, and the comparison that
decides ``correct``.

A Python loop over the clients; for each, one ``jax.value_and_grad`` of
the masked mean softmax cross-entropy over ``model.apply`` in float32
under ``jax.default_matmul_precision("highest")`` and one SGD step; then
the sample-weighted mean of the clients' parameters in NumPy. No
``vmap``, no ``LocalTrainer``, no ``ops/aggregation``, no
``core/losses``: independent of the engine, the trainer and the fold.
It still calls the program's ``model.apply`` (built with float32
compute) — a whole-model plain reference is ROADMAP R0's work.

Valid for a cohort whose clients hold at most one batch each (the probe
cohort): then one local epoch is one step and batch order cannot matter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _masked_mean_loss(apply, params, x, y, mask):
    logits = apply(params, {"x": x}, None).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - picked) * mask) / jnp.sum(mask)


def reference_round(apply, params, data, n_samples, learning_rate):
    """New global parameters (float32, same tree as ``params``) after one
    round of one local SGD step a client, and the sample-weighted mean
    loss before the step.

    The step and the running weighted mean are float32 ``jax.numpy``
    sums on the device, one client after the other. (In NumPy on the
    host they cost BERT-base 3.5 GB of transfers and half a minute of
    every run's set-up; elementwise float32 is exact on the TPU, only
    matrix products need ``precision="highest"``.)"""
    grad = jax.jit(jax.value_and_grad(
        lambda p, x, y, m: _masked_mean_loss(apply, p, x, y, m)))

    @jax.jit
    def add_stepped(mean, p, g, w):
        return jax.tree_util.tree_map(
            lambda m, a, d: m + w * (a.astype(jnp.float32)
                                     - learning_rate * d.astype(jnp.float32)),
            mean, p, g)

    n_samples = np.asarray(n_samples)
    capacity = data["x"].shape[1]
    total = float(n_samples.sum())
    mean = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)
    loss = 0.0
    with jax.default_matmul_precision("highest"):
        for c, n in enumerate(n_samples):
            mask = jnp.asarray(np.arange(capacity) < n, jnp.float32)
            l, g = grad(params, data["x"][c], data["y"][c], mask)
            mean = add_stepped(mean, params, g, float(n) / total)
            loss += float(n) / total * float(l)
    return mean, loss


@jax.jit
def _largest_gaps(before, got, want):
    def largest(a, b):
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]))

    return largest(got, want), largest(want, before)


def update_disagreement(before, got, want) -> float:
    """max |got - want| over max |want - before|: how far two rounds
    from the same parameters disagree, relative to the largest entry of
    the wanted update. Infinite where ``got`` is not finite."""
    gap, scale = (float(v) for v in _largest_gaps(before, got, want))
    if not scale > 0:
        raise ValueError("the reference round did not move the parameters")
    return gap / scale if gap == gap else float("inf")
