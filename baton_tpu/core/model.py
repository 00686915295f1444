"""Model contract for baton_tpu.

The reference's model contract is stateful PyTorch: ``state_dict()`` /
``load_state_dict()`` / ``train(*data, n_epoch=...) -> loss_history``
(reference: demo.py:15-49, worker.py:98,105, manager.py:123-126). The
TPU-native contract replaces it with pure functions over pytrees so that
local training can be jit-compiled, vmapped over a client axis, and
sharded over a device mesh:

  * ``init(rng) -> params``                       (replaces nn.Module ctor)
  * ``apply(params, batch, rng) -> outputs``      (replaces forward)
  * ``per_example_loss(params, batch, rng) -> [B]`` per-example losses

Per-example (rather than mean) losses are the contract on purpose: the
framework needs them for (a) exact sample-count masking of padded batches
— the sample-weighted FedAvg math (reference manager.py:119-126) demands
exact ``n_samples`` bookkeeping — and (b) per-example gradient clipping
for DP-SGD, which is a vmap over the same function.

Batches are dicts of arrays with a shared leading batch dimension, e.g.
``{"x": f32[B, ...], "y": ...[B, ...]}``. An optional ``"mask"`` entry
(f32[B], 1.0 = real sample) is consumed by the *framework*, never by the
model: losses/grads from masked-out rows are zeroed exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

Params = Any  # a pytree of arrays
Batch = Mapping[str, Any]
PRNGKey = jax.Array

# the ``vmap`` axis over the clients a wave trains side by side on one
# device (on a mesh: over a device's share of them)
WAVE_AXIS = "wave_clients"


def clients_in_wave() -> int:
    """How many clients the program being traced trains side by side on
    a device: the extent of the engine's client ``vmap`` where the trace
    runs under one, else 1 (one client's training, jitted alone). Under
    the ``vmap`` a model sees one client's shapes; what it holds in
    memory is this many times that."""
    try:
        return jax.lax.axis_size(WAVE_AXIS)
    except NameError:  # the axis is not bound
        return 1


@dataclasses.dataclass(frozen=True)
class FedModel:
    """A federated model: pure init/apply/per-example-loss functions.

    ``name`` mirrors the reference's ``model.name`` attribute used to
    derive experiment names (reference: manager.py:16, worker.py:14-16).
    """

    init: Callable[[PRNGKey], Params]
    apply: Callable[[Params, Batch, PRNGKey], Any]
    per_example_loss: Callable[[Params, Batch, PRNGKey], jax.Array]
    name: str = "fedmodel"
    # hashable model metadata (e.g. LoraSpec) — FedModel rides inside
    # jit-static trainer fields, so anything here must hash/eq by value
    aux: Any = None
    # static facts of the model's layers as ``(name, number)`` pairs,
    # written on the ``baton.round`` span beside the round's own
    # (``FedSim.run_round``): an expert layer's ``experts_held`` and
    # ``experts_total``. Read afresh every round: a decoder's also yield
    # what its last trace learned of the batch (``models/llama.py``)
    span_attrs: tuple = ()

    def masked_loss(self, params: Params, batch: Batch, rng: PRNGKey) -> jax.Array:
        """Mean loss over *real* (unmasked) examples.

        Fixes the reference's biased running mean (utils.py:70-91 — see
        SURVEY §2.6): this is the exact weighted mean, and all-padding
        batches contribute 0 with a guarded denominator.
        """
        losses = self.per_example_loss(params, batch, rng)
        mask = batch.get("mask")
        if mask is None:
            return jnp.mean(losses)
        mask = mask.astype(losses.dtype)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(losses * mask) / denom

    def loss_and_count(self, params: Params, batch: Batch, rng: PRNGKey):
        """Returns (sum of masked losses, number of real examples).

        Summing (rather than averaging) per batch lets callers form exact
        sample-weighted epoch means regardless of ragged final batches.
        """
        losses = self.per_example_loss(params, batch, rng)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(losses)
        mask = mask.astype(losses.dtype)
        return jnp.sum(losses * mask), jnp.sum(mask)

    @classmethod
    def from_flax(
        cls,
        module: Any,
        per_example_loss: Callable[[Any, Batch, PRNGKey], jax.Array],
        example_batch: Batch,
        name: Optional[str] = None,
    ) -> "FedModel":
        """Wrap a ``flax.linen.Module`` whose ``__call__(x)`` returns logits.

        ``per_example_loss(apply_out, batch, rng)`` maps model outputs to
        per-example losses (see :mod:`baton_tpu.core.losses`).

        Modules must be stateless (no BatchNorm running stats): federated
        aggregation of BN statistics is ill-defined under client drift, so
        the model zoo uses GroupNorm/LayerNorm throughout (the standard
        FL practice). A module carrying a ``batch_stats`` collection is
        rejected at init.
        """
        x = example_batch["x"]

        def init(rng: PRNGKey) -> Params:
            variables = module.init(rng, x)
            if "batch_stats" in variables:
                raise ValueError(
                    "module carries BatchNorm running stats; use GroupNorm/"
                    "LayerNorm for federated models (BN stats don't aggregate)"
                )
            return variables

        def apply(params: Params, batch: Batch, rng: PRNGKey):
            return module.apply(params, batch["x"])

        def loss(params: Params, batch: Batch, rng: PRNGKey) -> jax.Array:
            return per_example_loss(apply(params, batch, rng), batch, rng)

        return cls(
            init=init,
            apply=apply,
            per_example_loss=loss,
            name=name or type(module).__name__.lower(),
        )
