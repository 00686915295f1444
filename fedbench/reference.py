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


def accepted(params, trainable=None) -> list:
    """One flag a leaf of ``params``, in ``tree_leaves`` order: whether
    ``trainable(path, leaf)`` accepts it. All true without a predicate."""
    with_path = jax.tree_util.tree_flatten_with_path(params)[0]
    return [trainable is None or bool(trainable(_path(path), leaf))
            for path, leaf in with_path]


def _chosen(tree, flags, keep: bool = True) -> list:
    """The leaves of ``tree`` whose flag is ``keep``, as a list."""
    return [leaf for leaf, flag in zip(jax.tree_util.tree_leaves(tree), flags)
            if flag is keep]


def trainable_grad(loss, params, trainable=None):
    """``(grad, moving, held, whole)``: the leaves of ``params`` that
    ``trainable`` accepts and rejects, as two lists; ``whole(moving,
    held)``, ``params``' tree put together again from two such lists; and
    the jitted ``grad(moving, held, x, y, mask) -> (loss, d loss / d
    moving)``. ``held`` is an argument that is not differentiated: no
    gradient of a rejected leaf is computed, none is returned, and a
    rejected leaf enters the loss in the dtype it came in."""
    flags = accepted(params, trainable)
    treedef = jax.tree_util.tree_structure(params)

    def whole(moving, held):
        moving, held = iter(moving), iter(held)
        return jax.tree_util.tree_unflatten(
            treedef, [next(moving if flag else held) for flag in flags])

    grad = jax.jit(jax.value_and_grad(
        lambda moving, held, x, y, mask: loss(whole(moving, held), x, y,
                                              mask)))
    return grad, _chosen(params, flags), _chosen(params, flags, False), whole


def reference_round(loss, params, data, n_samples, learning_rate,
                    trainable=None):
    """New global parameters (same tree as ``params``) after one round of
    one local SGD step a client, and the sample-weighted mean loss
    before the step. ``loss(params, x, y, mask)`` is the configuration's
    plain loss.

    Where the cell's ``engine`` block holds a ``trainable(path, leaf)``
    predicate, the round is over the leaves it accepts alone: the tree is
    split by it first, the gradient is taken with respect to the
    accepted leaves (``trainable_grad``), and only they are stepped and
    averaged. An accepted leaf comes back as a new float32 array; a
    rejected leaf comes back as the very array that came in, not cast
    and not copied. So the round's memory beyond the parameters and the
    loss's own activations is three float32 copies of the *trainable*
    part (one gradient and two running means, the new one made while
    the old is alive), whatever the frozen part weighs. The loss's own
    activations are the reference file's business: over a frozen base
    held in bfloat16 it has to cast each layer's weights where it uses
    them, since two float32 copies of the base (forward and backward) do
    not fit beside it either.

    The step and the running weighted mean are float32 ``jax.numpy``
    sums on the device, one client after the other. (In NumPy on the
    host they cost BERT-base 3.5 GB of transfers and half a minute of
    every run's set-up; elementwise float32 is exact on the TPU, only
    matrix products need ``precision="highest"``.)"""
    grad, moving, held, whole = trainable_grad(loss, params, trainable)

    @jax.jit
    def add_stepped(mean, p, g, w):
        return jax.tree_util.tree_map(
            lambda m, a, d: m + w * (a.astype(jnp.float32)
                                     - learning_rate * d.astype(jnp.float32)),
            mean, p, g)

    n_samples = np.asarray(n_samples)
    capacity = data["x"].shape[1]
    total = float(n_samples.sum())
    mean = [jnp.zeros(a.shape, jnp.float32) for a in moving]
    mean_loss = 0.0
    with jax.default_matmul_precision("highest"):
        for c, n in enumerate(n_samples):
            mask = jnp.asarray(np.arange(capacity) < n, jnp.float32)
            l, g = grad(moving, held, data["x"][c], data["y"][c], mask)
            mean = add_stepped(mean, moving, g, float(n) / total)
            mean_loss += float(n) / total * float(l)
    return whole(mean, held), mean_loss


def held_unchanged(before, after, trainable) -> tuple:
    """``(same, held)``: how many of the leaves that ``trainable``
    rejects are in ``after`` what they were in ``before``, exactly and in
    their own dtype, and how many it rejects."""
    flags = accepted(before, trainable)
    pairs = list(zip(_chosen(before, flags, False),
                     _chosen(after, flags, False)))
    alike = [(a, b) for a, b in pairs
             if a.shape == b.shape and a.dtype == b.dtype]
    same = jax.jit(lambda ab: [jnp.all(a == b) for a, b in ab])(alike)
    return sum(bool(s) for s in same), len(pairs)


@jax.jit
def _gaps(before, got, want):
    """Over three lists of leaves: the largest entry and the sum of
    squares of ``got - want`` and of ``want - before``."""
    def measure(a, b):
        diffs = [x.astype(jnp.float32) - y.astype(jnp.float32)
                 for x, y in zip(a, b)]
        return (jnp.max(jnp.stack([jnp.max(jnp.abs(d)) for d in diffs])),
                sum(jnp.sum(d * d) for d in diffs))

    return measure(got, want), measure(want, before)


def update_disagreement(before, got, want, norm: str = "max",
                        trainable=None) -> float:
    """How far two rounds from the same parameters disagree, relative to
    the wanted update: ``"max"``, max |got - want| over max |want -
    before| (the largest entry); ``"l2"``, the same ratio of the
    Euclidean norms over all parameters, which swings less from seed to
    seed. With a ``trainable`` predicate, over the leaves it accepts
    (``held_unchanged`` holds the others). Infinite where ``got`` is not
    finite."""
    flags = accepted(before, trainable)
    (gap_max, gap_sq), (scale_max, scale_sq) = _gaps(
        *(_chosen(tree, flags) for tree in (before, got, want)))
    gap, scale = ((float(gap_max), float(scale_max)) if norm == "max"
                  else (float(gap_sq) ** 0.5, float(scale_sq) ** 0.5))
    if not scale > 0:
        raise ValueError("the reference round did not move the parameters")
    return gap / scale if gap == gap else float("inf")
