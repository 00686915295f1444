"""Asynchronous federated learning (FedBuff-style buffered aggregation).

Everything else in the framework is round-synchronous — the reference's
only mode (a round ends when every started client reports, reference
manager.py:109-110). Real cross-device federations are asynchronous:
clients start and finish at different times, so an update is computed
against a *stale* anchor (the globals as of when its client started).
The standard server rule (FedBuff) is: keep ``concurrency`` clients in
flight, buffer completed updates, and as soon as ``buffer_size`` have
arrived apply their staleness-discounted average and bump the global
version.

TPU-first shape of the simulation: the ``buffer_size`` completions of a
server step train as ONE vmapped dispatch — ``vmap`` runs over clients
AND their per-client stale anchors (stacked ``[K, ...]`` params), so the
whole async step is a single XLA program; the host only runs the queue
bookkeeping. Staleness weighting uses the standard polynomial discount
``(1 + s)**(-alpha)``.

Under a clients mesh the stacked buffer axis shards exactly like a
synchronous wave (``shard_map`` over ``Mesh(('clients',))``, each device
training ``K/n_dev`` in-flight completions) — numerically identical to
the single-device path, tested leaf-for-leaf in
tests/test_fedbuff.py::test_mesh_fedbuff_matches_single_device. The
queue/staleness bookkeeping stays host-side Python by design: it is
O(concurrency) integer work per step, invariant to model size, and runs
concurrently with the device's dispatched training step.

Semantics are validated two ways (tests/test_fedbuff.py): with
``concurrency == buffer_size == C`` and all clients starting at the same
version, one async step is EXACTLY one synchronous FedAvg round
(weighted-delta form); and under genuine staleness the model still
reaches the demo coefficients while plain averaging of stale deltas with
no discount diverges more.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Deque, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.core.model import WAVE_AXIS
from baton_tpu.ops import aggregation as agg
from baton_tpu.parallel.engine import FedSim
from baton_tpu.parallel.mesh import (
    CLIENT_AXIS,
    client_sharding,
    replicated_sharding,
    require_clients_mesh,
)
from baton_tpu.parallel.partition import kernel_specs

Params = Any


@dataclasses.dataclass
class AsyncResult:
    params: Params
    version: int                 # server steps applied
    mean_staleness: float        # average staleness of applied updates
    loss_history: np.ndarray     # [n_steps] mean completed-client loss


class FedBuff:
    """Buffered asynchronous server loop over a :class:`FedSim`'s trainer.

    ``concurrency`` clients are always in flight; each server step
    completes the ``buffer_size`` longest-running ones, applies the
    staleness-discounted weighted mean of their DELTAS to the globals,
    and backfills the pool with fresh clients anchored at the new
    version. Client completion order is the queue order (deterministic);
    staleness emerges from the overlap, exactly as in the FedBuff model.
    """

    def __init__(
        self,
        sim: FedSim,
        buffer_size: int = 4,
        concurrency: int = 8,
        alpha: float = 0.5,
        server_lr: float = None,
    ):
        """``server_lr`` scales the applied mean delta (the FedBuff
        paper's global learning rate). Under overlap, consecutive buffer
        flushes re-apply movement computed from the SAME anchor — up to
        ``concurrency / buffer_size`` times — so the effective step
        multiplies by that factor and full-strength application can
        diverge where synchronous FedAvg is stable. The default
        ``buffer_size / concurrency`` cancels exactly that multiplicity;
        pass 1.0 to reproduce plain buffered averaging."""
        if buffer_size <= 0 or concurrency < buffer_size:
            raise ValueError(
                f"need concurrency >= buffer_size >= 1, got "
                f"{concurrency} < {buffer_size}"
            )
        if sim.aggregator[0] != "mean":
            raise ValueError(
                "FedBuff applies a staleness-weighted mean; robust "
                "aggregators are a synchronous-round feature"
            )
        if sim.server_optimizer is not None:
            raise ValueError(
                "FedBuff applies server_lr-scaled mean deltas directly; "
                "a FedOpt server optimizer would be silently ignored — "
                "configure the FedSim without one for async runs"
            )
        if sim.mesh is not None:
            # the buffer axis is already stacked [K, ...] (anchors, data,
            # rngs), so a clients mesh shards it exactly like the engine
            # shards a synchronous wave — each device trains K/n_dev of
            # the in-flight completions, host keeps only the queue
            # bookkeeping. Hybrid clients x model meshes are out: the
            # anchor pool holds FULL per-client params, which is the
            # thing a model-sharded base exists to avoid.
            require_clients_mesh(sim.mesh, sim.aggregator, "FedBuff")
            n_dev = int(sim.mesh.devices.size)
            if buffer_size % n_dev != 0:
                raise ValueError(
                    f"buffer_size ({buffer_size}) must be a multiple of "
                    f"the clients-mesh size ({n_dev}) so each server "
                    "step shards evenly — phantom-padding an async "
                    "buffer would skew the staleness discount"
                )
        self.sim = sim
        self.buffer_size = buffer_size
        self.concurrency = concurrency
        self.alpha = alpha
        self.server_lr = (
            server_lr if server_lr is not None
            else buffer_size / concurrency
        )

    # one vmapped dispatch for a whole buffer of completions: clients
    # AND their stale anchors are stacked along the leading axis. Each
    # client's OWN stale anchor is also its FedProx anchor (the globals
    # it started from), and frozen leaves (LoRA partition) broadcast
    # unstacked — mirroring the engine's wave kernel
    # (engine.py::_wave_params_raw).
    def _train_buffer_raw(self, anchors, data, n_samples, rngs, n_epochs,
                          frozen):
        trainer = self.sim.trainer
        with_anchor = trainer.regularizer is not None

        def one(p, d, n, r):
            new_p, _, losses = trainer.train(
                p, d, n, r, n_epochs, p if with_anchor else None, frozen
            )
            return new_p, losses

        return jax.vmap(one, axis_name=WAVE_AXIS)(
            anchors, data, n_samples, rngs)

    def _train_buffer(self, anchors, data, n_samples, rngs, n_epochs,
                      frozen):
        mesh = self.sim.mesh
        if mesh is None:
            return self._train_buffer_raw(
                anchors, data, n_samples, rngs, n_epochs, frozen
            )
        # mesh path: shard the buffer axis, same math per shard. The
        # closure is cached per n_epochs — rebuilding it per step would
        # force an XLA recompile (mirrors engine._make_wave_sums_sharded).
        cache = getattr(self, "_sharded_cache", None)
        if cache is None:
            cache = self._sharded_cache = {}
        if n_epochs not in cache:
            def kernel(anchors, data, n_samples, rngs, frozen):
                return self._train_buffer_raw(
                    anchors, data, n_samples, rngs, n_epochs, frozen
                )

            in_specs, out_specs = kernel_specs("fedbuff.train")
            # donation decided no: the anchor stack is re-read
            # after the dispatch to form the staleness deltas
            cache[n_epochs] = jax.jit(jax.shard_map(  # batonlint: allow[BTL011]
                kernel,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ))
        shard = client_sharding(mesh)
        anchors = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, shard), anchors
        )
        data = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, shard), data
        )
        n_samples = jax.device_put(n_samples, shard)
        rngs = jax.device_put(rngs, shard)
        if frozen is not None:
            frozen = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, replicated_sharding(mesh)),
                frozen,
            )
        return cache[n_epochs](anchors, data, n_samples, rngs, frozen)

    def run(
        self,
        params: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: jax.Array,
        n_steps: int,
        n_epochs: int = 1,
    ) -> AsyncResult:
        """``data``/``n_samples`` in the engine's stacked ``[C, ...]``
        layout; clients are drawn round-robin from the cohort."""
        # honor the sim's trainable/frozen partition (LoRA): pool anchors
        # and deltas are trainable-only; frozen leaves broadcast into
        # every training dispatch and merge back at the end
        params, frozen = self.sim._split(params)
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])

        # in-flight pool: (client_index, anchor_params, start_version)
        version = 0
        next_client = 0
        pool: Deque[Tuple[int, Params, int]] = deque()

        def fill() -> None:
            nonlocal next_client
            while len(pool) < self.concurrency:
                pool.append((next_client % c, params, version))
                next_client += 1

        fill()
        losses = []
        staleness_sum = 0.0
        n_applied = 0
        for step in range(n_steps):
            done = [pool.popleft() for _ in range(self.buffer_size)]
            idx = jnp.asarray([d[0] for d in done])
            anchors = agg.tree_stack([d[1] for d in done])
            stale = np.asarray([version - d[2] for d in done], np.float32)

            d_k = jax.tree_util.tree_map(
                lambda a: jnp.take(a, idx, axis=0), data
            )
            n_k = jnp.take(n_samples, idx, axis=0)
            rng, sub = jax.random.split(rng)
            r_k = jax.random.split(sub, self.buffer_size)

            trained, client_losses = self._train_buffer(
                anchors, d_k, n_k, r_k, n_epochs, frozen
            )
            # staleness-discounted, sample-weighted mean of DELTAS
            # applied to the CURRENT globals (not the stale anchors)
            deltas = jax.tree_util.tree_map(
                lambda t, a: t.astype(jnp.float32) - a.astype(jnp.float32),
                trained, anchors,
            )
            disc = (1.0 + stale) ** (-self.alpha)
            w = n_k.astype(jnp.float32) * jnp.asarray(disc)
            mean_delta = agg.weighted_tree_mean(deltas, w)
            lr_g = self.server_lr
            params = jax.tree_util.tree_map(
                lambda p, d: (p.astype(jnp.float32) + lr_g * d).astype(p.dtype),
                params, mean_delta,
            )
            version += 1
            staleness_sum += float(stale.sum())
            n_applied += len(done)
            losses.append(float(jnp.mean(client_losses[:, -1])))
            fill()

        if self.sim.partition is not None:
            params = self.sim.partition.merge(params, frozen)
        return AsyncResult(
            params=params,
            version=version,
            mean_staleness=staleness_sum / max(n_applied, 1),
            loss_history=np.asarray(losses),
        )
