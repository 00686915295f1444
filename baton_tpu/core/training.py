"""Jitted local training — the TPU replacement for the reference hot loop.

The reference's local training is a Python for-loop over epochs and
batches doing zero_grad/forward/MSE/backward/step on the worker's event
loop (reference: demo.py:29-49, worker.py:103-106 — it even blocks
heartbeats, SURVEY §2.9 item 7). Here the *entire* multi-epoch run is one
XLA program: ``lax.scan`` over epochs, ``lax.scan`` over batches, optax
update inline — so it can be vmapped over thousands of simulated clients
and sharded over a TPU mesh with zero Python in the hot path.

Static-shape discipline (XLA): client datasets are padded to a fixed
``capacity``, real rows first; a per-row validity mask derived from the
*dynamic* ``n_samples`` scalar zeroes the loss/grad contribution of
padding exactly. Shuffling is a ``jax.random.permutation`` of row
indices per epoch (replaces torch.randperm, demo.py:33). Any capacity
is taken: an epoch is ``ceil(capacity / batch_size)`` optimizer steps
(as many as ``torch.split`` makes slices, demo.py:34) and its rows are
shared equally among them, so every step has one shape and the epoch
stays one scan: ``batch_size`` rows a step where it divides the
capacity, 24 + 24 for 48 rows at batch 32 (the reference's last slice
is the short one: 32 + 16).

Loss accounting fixes the reference's biased running mean (utils.py:85-88,
SURVEY §2.6): per-epoch loss is the exact sample-weighted mean
``Σ loss_i / n_samples`` over real examples.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import io_callback

from baton_tpu.core.model import Batch, FedModel, Params, PRNGKey
from baton_tpu.core.partition import ParamPartition
from baton_tpu.ops.privacy import DPConfig, dp_sgd_grads

Regularizer = Callable[[Params, Params], jax.Array]


def num_batches(capacity: int, batch_size: int) -> int:
    """Optimizer steps an epoch over ``capacity`` rows takes: the fewest
    steps of at most ``batch_size`` rows."""
    return -(-capacity // batch_size)


@dataclasses.dataclass(frozen=True)
class LocalTrainer:
    """Compiled multi-epoch local training for one client.

    ``train(params, data, n_samples, rng, n_epochs)`` returns
    ``(params, opt_state, loss_history[n_epochs])``. ``data`` is a dict of
    arrays padded to a static capacity; ``n_samples`` is the dynamic count
    of real rows (the same number that weights this client in FedAvg,
    reference manager.py:119-126).

    When ``regularizer`` is set, ``train`` takes an ``anchor`` params
    pytree and the local objective becomes ``data_loss + regularizer(
    params, anchor)`` — the pluggable local-objective hook used for
    FedProx (anchor = the round's global params).

    When ``partition`` is set, ``params`` is only the *trainable* leaf
    list and the ``frozen`` leaf list must be supplied; the model sees
    ``partition.merge(params, frozen)`` while gradients, optimizer state,
    and the FedAvg payload stay trainable-only (LoRA fine-tuning: clients
    carry adapters, never the base model).
    """

    model: FedModel
    optimizer: optax.GradientTransformation
    batch_size: int
    regularizer: Optional[Regularizer] = None
    partition: Optional[ParamPartition] = None
    # example-level DP-SGD (ops/privacy.py): per-example clipping +
    # Gaussian noise replace the plain batch gradient when set
    dp: Optional[DPConfig] = None
    # Mid-training visibility (the reference streams tqdm batch progress
    # and a running loss during local training, reference utils.py:70-91,
    # demo.py:37-38; a jitted multi-epoch run is otherwise a black box).
    # When set, ``progress_fn(epoch_index, epoch_loss)`` fires on the HOST
    # after each epoch via ``jax.experimental.io_callback`` — the TPU-way
    # equivalent of the reference's progress bar. Ordered, so it is for
    # the single-client path (the HTTP worker, the manager's simulated
    # cohort participant); leave unset under vmap/shard_map.
    progress_fn: Optional[Callable[[int, float], None]] = None

    def init_opt_state(self, params: Params):
        return self.optimizer.init(params)

    # -- compute-plane accounting (baton_tpu/obs/compute.py) -----------
    def train_signature(self, data: Batch, n_epochs: int) -> tuple:
        """The jit-cache shape signature of one ``train`` call: data
        shapes/dtypes plus the static epoch count. A signature the
        compute probe's :class:`~baton_tpu.obs.compute.CompileTracker`
        has not seen means XLA compiled during that call."""
        shapes = tuple(sorted(
            (k, tuple(v.shape), str(getattr(v, "dtype", type(v).__name__)))
            for k, v in data.items()
        ))
        return (shapes, int(n_epochs), int(self.batch_size))

    def steps_per_round(self, capacity: int, n_epochs: int) -> int:
        """Optimizer steps one ``train`` call executes on device: the
        scan runs every batch of the padded capacity every epoch (masked
        no-ops included — they still cost the FLOPs)."""
        return int(n_epochs) * num_batches(int(capacity), self.batch_size)

    # donation decided no: params is the caller's broadcast anchor —
    # the engine re-reads it for every client in the wave
    @partial(jax.jit, static_argnums=(0, 5))  # batonlint: allow[BTL011]
    def train(
        self,
        params: Params,
        data: Batch,
        n_samples: jax.Array,
        rng: PRNGKey,
        n_epochs: int,
        anchor: Optional[Params] = None,
        frozen: Optional[Params] = None,
    ):
        opt_state = self.optimizer.init(params)
        return self.train_with_opt_state(
            params, opt_state, data, n_samples, rng, n_epochs, anchor, frozen
        )

    @partial(jax.jit, static_argnums=(0, 6), donate_argnums=(2,))
    def train_with_opt_state(
        self,
        params: Params,
        opt_state,
        data: Batch,
        n_samples: jax.Array,
        rng: PRNGKey,
        n_epochs: int,
        anchor: Optional[Params] = None,
        frozen: Optional[Params] = None,
    ):
        """Same as ``train`` but threads optimizer state (for stateful
        local optimizers persisted across rounds, or wave scheduling)."""
        leaves = jax.tree_util.tree_leaves(data)
        capacity = leaves[0].shape[0]
        nb = num_batches(capacity, self.batch_size)
        # rows a step: the batch size where it divides the capacity, else
        # the capacity shared equally among the same number of steps
        step_rows = -(-capacity // nb)
        n_short = nb * step_rows - capacity  # fewer than nb
        n_samples = jnp.asarray(n_samples, jnp.int32)

        def merged(p):
            return self.partition.merge(p, frozen) if self.partition else p

        def objective(p, batch, step_rng):
            loss_sum, count = self.model.loss_and_count(merged(p), batch, step_rng)
            denom = jnp.maximum(count, 1.0)
            loss = loss_sum / denom
            if self.regularizer is not None:
                loss = loss + self.regularizer(p, anchor)
            return loss, (loss_sum, count)

        grad_fn = jax.value_and_grad(objective, has_aux=True)

        def masked_loss_sum(p, batch, step_rng):
            """Masked data-loss sum only (no regularizer) — the per-example
            clipping target for DP-SGD; padding rows contribute exactly 0."""
            s, _ = self.model.loss_and_count(merged(p), batch, step_rng)
            return s

        def batch_step(carry, batch):
            p, os, step_rng = carry
            step_rng, sub = jax.random.split(step_rng)
            if self.dp is not None:
                with jax.named_scope("grad"):
                    grads, ex_losses = dp_sgd_grads(
                        masked_loss_sum, p, batch, sub, self.dp,
                        self.batch_size
                    )
                if self.regularizer is not None:
                    # the prox term is data-independent: its gradient is
                    # exact (un-noised) and consumes no privacy budget
                    reg_grads = jax.grad(
                        lambda q: self.regularizer(q, anchor)
                    )(p)
                    grads = jax.tree_util.tree_map(
                        lambda g, r: (g + r).astype(g.dtype), grads, reg_grads
                    )
                # ex_losses are already mask-zeroed (masked_loss_sum);
                # NOT privatized — see DPConfig docstring
                loss_sum = jnp.sum(ex_losses)
                count = jnp.sum(batch["mask"].astype(jnp.float32))
            else:
                with jax.named_scope("grad"):
                    (_, (loss_sum, count)), grads = grad_fn(p, batch, sub)
            # An all-padding batch yields exactly-zero grads; gate the
            # update so stateful optimizers (momentum/adam) don't mutate
            # state on phantom steps.
            nonempty = count > 0
            with jax.named_scope("optimizer"):
                updates, new_os = self.optimizer.update(grads, os, p)
                new_p = optax.apply_updates(p, updates)
                p = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(nonempty, new, old), new_p, p
                )
                os = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(nonempty, new, old), new_os, os
                )
            return (p, os, step_rng), (loss_sum, count)

        def epoch_step(carry, xs):
            epoch_rng, epoch_idx = xs
            p, os = carry
            perm_rng, step_rng = jax.random.split(epoch_rng)
            # a full copy of the client's data every epoch
            with jax.named_scope("shuffle"):
                perm = jax.random.permutation(perm_rng, capacity)
                mask = (perm < n_samples).astype(jnp.float32)
                shuffled = jax.tree_util.tree_map(
                    lambda a: jnp.take(a, perm, axis=0), data)
                shuffled = dict(shuffled)
                if "mask" in shuffled:
                    mask = mask * shuffled["mask"].astype(jnp.float32)
                shuffled["mask"] = mask
                if n_short:
                    # equal steps need a few rows more: masked zeros
                    shuffled = jax.tree_util.tree_map(
                        lambda a: jnp.concatenate([a, jnp.zeros(
                            (n_short,) + a.shape[1:], a.dtype)]), shuffled)
                batched = jax.tree_util.tree_map(
                    lambda a: a.reshape((nb, step_rows) + a.shape[1:]),
                    shuffled
                )
            (p, os, _), (loss_sums, counts) = jax.lax.scan(
                batch_step, (p, os, step_rng), batched
            )
            total = jnp.maximum(jnp.sum(counts), 1.0)
            epoch_loss = jnp.sum(loss_sums) / total
            if self.progress_fn is not None:
                io_callback(
                    self.progress_fn, None, epoch_idx, epoch_loss,
                    ordered=True,
                )
            return (p, os), epoch_loss

        epoch_rngs = jax.random.split(rng, n_epochs)
        (params, opt_state), loss_history = jax.lax.scan(
            epoch_step,
            (params, opt_state),
            (epoch_rngs, jnp.arange(n_epochs, dtype=jnp.int32)),
        )
        return params, opt_state, loss_history


def make_local_trainer(
    model: FedModel,
    optimizer: Optional[optax.GradientTransformation] = None,
    batch_size: int = 32,
    learning_rate: float = 1e-3,
    regularizer: Optional[Regularizer] = None,
    partition: Optional[ParamPartition] = None,
    dp: Optional[DPConfig] = None,
    progress_fn: Optional[Callable[[int, float], None]] = None,
) -> LocalTrainer:
    """Build a :class:`LocalTrainer`.

    Defaults mirror the reference demo: SGD, lr=0.001, batch_size=32
    (reference: demo.py:29,34).
    """
    if optimizer is None:
        optimizer = optax.sgd(learning_rate)
    return LocalTrainer(
        model=model,
        optimizer=optimizer,
        batch_size=batch_size,
        regularizer=regularizer,
        partition=partition,
        dp=dp,
        progress_fn=progress_fn,
    )


def make_evaluator(model: FedModel):
    """Jitted full-dataset evaluation: mean loss (+accuracy for int labels).
    The whole eval set goes through one apply; shard or chunk large sets
    at the call site."""

    # donation decided no: evaluation never owns its inputs
    @jax.jit  # batonlint: allow[BTL011]
    def evaluate(params: Params, data: Batch, rng: PRNGKey):
        losses = model.per_example_loss(params, data, rng)
        mask = data.get("mask")
        if mask is None:
            mask = jnp.ones_like(losses)
        mask = mask.astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        out = {"loss": jnp.sum(losses * mask) / denom}
        y = data.get("y")
        if y is not None and jnp.issubdtype(y.dtype, jnp.integer):
            logits = model.apply(params, data, rng)
            correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
            out["accuracy"] = jnp.sum(correct * mask) / denom
        return out

    return evaluate
