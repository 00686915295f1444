"""The plain reference of one federated round, and the comparison that
decides ``correct``.

A Python loop over the clients; for each, one ``jax.value_and_grad`` of
the configuration's own plain loss (``fedbench/references/<config>.py``:
float32 ``jax.numpy`` at ``precision="highest"``, the masked mean
cross-entropy of one client's batch) and one SGD step; then the
sample-weighted mean of the clients' parameters. No ``vmap``, no
``LocalTrainer``, no ``ops/aggregation``, no ``core/losses``, no
``model.apply``: nothing of the program.

Valid for a cohort whose clients hold at most one batch each (the probe
cohort), then one local epoch is one step and batch order cannot
matter; and for the program's default local optimizer, SGD.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def masked_mean_cross_entropy(logits, y, mask):
    """Softmax cross-entropy of ``logits [n, ..., classes]`` against
    ``y [n, ...]``: the mean over an example's positions (a next-token
    model has ``[n, l]`` of them, a classifier none), then the mean over
    the examples ``mask [n]`` keeps."""
    logits = logits.astype(jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    logz = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    per_example = (logz - picked).reshape(y.shape[0], -1).mean(axis=-1)
    return jnp.sum(per_example * mask) / jnp.sum(mask)


def rounded_to(dtype):
    """The control's ``cast``: a reference's matrix-product operands
    rounded to ``dtype`` and accumulated in float32, the step below the
    precision a configuration states (``float8_e4m3fn`` for bfloat16).
    The gradients that flow back through it are rounded the same way.
    Put in the program's place, a reference so computed has to come out
    as not correct (``fedbench/control.py``,
    ``tests/fedbench/test_fedbench_control.py``)."""
    return lambda a: a.astype(dtype).astype(jnp.float32)


def _path(key_path) -> str:
    """``blocks/0/attn/wq``: a leaf's keys joined as the program's
    ``trainable`` predicates expect them."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(
        k, "name", k)))) for k in key_path)


def reference_round(loss, params, data, n_samples, learning_rate,
                    trainable=None):
    """New global parameters (float32, same tree as ``params``) after one
    round of one local SGD step a client, and the sample-weighted mean
    loss before the step. ``loss(params, x, y, mask)`` is the
    configuration's plain loss. Where the cell's ``engine`` block holds a
    ``trainable(path, leaf)`` predicate, a leaf it rejects is returned as
    it came.

    The step and the running weighted mean are float32 ``jax.numpy``
    sums on the device, one client after the other. (In NumPy on the
    host they cost BERT-base 3.5 GB of transfers and half a minute of
    every run's set-up; elementwise float32 is exact on the TPU, only
    matrix products need ``precision="highest"``.)"""
    grad = jax.jit(jax.value_and_grad(loss))

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
    mean_loss = 0.0
    with jax.default_matmul_precision("highest"):
        for c, n in enumerate(n_samples):
            mask = jnp.asarray(np.arange(capacity) < n, jnp.float32)
            l, g = grad(params, data["x"][c], data["y"][c], mask)
            mean = add_stepped(mean, params, g, float(n) / total)
            mean_loss += float(n) / total * float(l)
    if trainable is not None:
        mean = jax.tree_util.tree_map_with_path(
            lambda path, m, a: m if trainable(_path(path), a)
            else a.astype(jnp.float32), mean, params)
    return mean, mean_loss


@jax.jit
def _gaps(before, got, want):
    """Over all leaves: the largest entry and the sum of squares of
    ``got - want`` and of ``want - before``."""
    def measure(a, b):
        diffs = [x.astype(jnp.float32) - y.astype(jnp.float32)
                 for x, y in zip(jax.tree_util.tree_leaves(a),
                                 jax.tree_util.tree_leaves(b))]
        return (jnp.max(jnp.stack([jnp.max(jnp.abs(d)) for d in diffs])),
                sum(jnp.sum(d * d) for d in diffs))

    return measure(got, want), measure(want, before)


def update_disagreement(before, got, want, norm: str = "max") -> float:
    """How far two rounds from the same parameters disagree, relative to
    the wanted update: ``"max"``, max |got - want| over max |want -
    before| (the largest entry); ``"l2"``, the same ratio of the
    Euclidean norms over all parameters, which swings less from seed to
    seed. Infinite where ``got`` is not finite."""
    (gap_max, gap_sq), (scale_max, scale_sq) = _gaps(before, got, want)
    gap, scale = ((float(gap_max), float(scale_max)) if norm == "max"
                  else (float(gap_sq) ** 0.5, float(scale_sq) ** 0.5))
    if not scale > 0:
        raise ValueError("the reference round did not move the parameters")
    return gap / scale if gap == gap else float("inf")
