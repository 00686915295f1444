"""Clustered federated learning (IFCA-style): K global models, clients
self-select.

When the cohort is a MIXTURE of populations (different label maps,
different tasks), one global model fits none of them and per-client
personalization (FedPer) can't share strength within a population. The
iterative federated clustering answer (IFCA): keep K global models;
each round every client evaluates all K on its own data, trains the
best-fitting one, and each model aggregates only the clients that chose
it. Assignment and training improve each other until populations
separate.

TPU-first shape: cluster params are ONE stacked pytree ``[K, ...]``;
a round is two vmapped dispatches —

1. assignment: a ``vmap(clients) x vmap(clusters)`` masked-loss grid
   ``[C, K]``, argmin over K;
2. training: every client trains params GATHERED by its assignment
   (vmap over per-client param trees), then per-cluster aggregation is
   one one-hot weighted ``einsum`` — no Python loop over clusters.

Empty clusters keep their previous params (they can win clients later).
The caller threads ``cluster_params`` between rounds like any other
state and owns checkpointing it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.core.model import WAVE_AXIS
from baton_tpu.ops import aggregation as agg
from baton_tpu.parallel.engine import FedSim

Params = Any


@dataclasses.dataclass
class ClusteredRoundResult:
    cluster_params: Params      # [K, ...] stacked
    assignments: np.ndarray     # [C] int — chosen cluster per client
    loss_history: jax.Array     # [n_epochs] sample-weighted over clients
    client_losses: jax.Array    # [C, n_epochs]


def _masked_mean_loss(model, p, d, n, r):
    """One client's masked mean loss under ``p`` — the single assignment
    rule used both in rounds and at eval time (they must agree, or a
    client would train one cluster and be scored with another)."""
    losses = model.per_example_loss(p, d, r)
    mask = (jnp.arange(losses.shape[0]) < n).astype(jnp.float32)
    return jnp.sum(losses.astype(jnp.float32) * mask) / jnp.maximum(
        mask.sum(), 1.0
    )


class ClusteredFedSim:
    """IFCA rounds over a :class:`FedSim`'s trainer."""

    def __init__(self, sim: FedSim, n_clusters: int):
        if n_clusters < 2:
            raise ValueError("clustering needs n_clusters >= 2")
        if sim.trainable_predicate is not None:
            raise ValueError(
                "ClusteredFedSim trains full param trees; partitioned "
                "sims are not supported"
            )
        if sim.mesh is not None:
            from baton_tpu.parallel.mesh import require_clients_mesh

            require_clients_mesh(sim.mesh, sim.aggregator, "ClusteredFedSim")
        if sim.aggregator[0] != "mean":
            raise ValueError(
                "per-cluster aggregation is the sample-weighted mean; "
                "robust rules within tiny per-cluster cohorts are "
                "statistically meaningless — filter clients instead"
            )
        if sim.server_optimizer is not None:
            raise ValueError(
                "FedOpt server state per cluster is not threaded here; "
                "configure the FedSim without a server optimizer"
            )
        self.sim = sim
        self.n_clusters = n_clusters
        self._jit_cache: Dict[int, Any] = {}

    def init_clusters(self, rng: jax.Array) -> Params:
        """K independently-initialized models, stacked. Distinct inits
        are what lets assignment break symmetry in round 1."""
        keys = jax.random.split(rng, self.n_clusters)
        trees = [self.sim.model.init(k) for k in keys]
        return agg.tree_stack(trees)

    def _assign_train_combine(self, n_epochs: int, psum_axis=None):
        """The round body; with ``psum_axis`` the per-cluster sums
        reduce across mesh shards (the sharded combine is the same math
        with psums around the one-hot sums)."""
        trainer = self.sim.trainer
        model = self.sim.model
        k_clusters = self.n_clusters
        with_anchor = trainer.regularizer is not None

        def round_fn(cluster_params, data, n_samples, rngs):
            # -- 1. assignment: masked mean loss of every cluster on
            # every client's data ------------------------------------
            def client_losses_vs_clusters(d, n, r):
                return jax.vmap(
                    lambda p: _masked_mean_loss(model, p, d, n, r)
                )(cluster_params)  # [K]

            grid = jax.vmap(client_losses_vs_clusters)(
                data, n_samples, rngs
            )  # [C, K]
            assign = jnp.argmin(grid, axis=1)  # [C]

            # -- 2. train the chosen model per client ---------------
            my_params = jax.tree_util.tree_map(
                lambda a: jnp.take(a, assign, axis=0), cluster_params
            )

            def one(p, d, n, r):
                new_p, _, losses = trainer.train(
                    p, d, n, r, n_epochs, p if with_anchor else None
                )
                return new_p, losses

            trained, closs = jax.vmap(one, axis_name=WAVE_AXIS)(
                my_params, data, n_samples, rngs
            )

            # -- 3. per-cluster sample-weighted mean via one-hot ----
            w = n_samples.astype(jnp.float32)  # [C]
            onehot = jax.nn.one_hot(assign, k_clusters)  # [C, K]
            wk = onehot * w[:, None]  # [C, K]
            denom = jnp.sum(wk, axis=0)  # [K]
            if psum_axis is not None:
                denom = jax.lax.psum(denom, psum_axis)

            def combine(tr, old):
                tr32 = tr.astype(jnp.float32)
                sums = jnp.tensordot(wk, tr32, axes=(0, 0))  # [K, ...]
                if psum_axis is not None:
                    sums = jax.lax.psum(sums, psum_axis)
                mean = sums / jnp.maximum(denom, 1e-9).reshape(
                    (k_clusters,) + (1,) * (tr.ndim - 1)
                )
                keep_old = (denom <= 0).reshape(
                    (k_clusters,) + (1,) * (tr.ndim - 1)
                )
                return jnp.where(
                    keep_old, old.astype(jnp.float32), mean
                ).astype(old.dtype)

            new_clusters = jax.tree_util.tree_map(
                combine, trained, cluster_params
            )
            return new_clusters, assign, closs

        return round_fn

    def _round_fn(self, n_epochs: int):
        if n_epochs not in self._jit_cache:
            self._jit_cache[n_epochs] = jax.jit(
                self._assign_train_combine(n_epochs)
            )
        return self._jit_cache[n_epochs]

    def _round_fn_sharded(self, n_epochs: int):
        key = ("sharded", n_epochs)
        if key not in self._jit_cache:
            from baton_tpu.parallel.mesh import CLIENT_AXIS
            from baton_tpu.parallel.partition import kernel_specs

            in_specs, out_specs = kernel_specs("clustered.round")
            self._jit_cache[key] = jax.jit(jax.shard_map(
                self._assign_train_combine(n_epochs,
                                           psum_axis=CLIENT_AXIS),
                mesh=self.sim.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ))
        return self._jit_cache[key]

    def run_round(
        self,
        cluster_params: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: jax.Array,
        n_epochs: int = 1,
    ) -> ClusteredRoundResult:
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        rngs = jax.random.split(rng, c)
        if self.sim.mesh is not None:
            from baton_tpu.parallel.mesh import (
                CLIENT_AXIS,
                shard_client_arrays,
            )

            from baton_tpu.ops.padding import round_up

            n_dev = int(self.sim.mesh.shape[CLIENT_AXIS])
            target = round_up(c, n_dev)
            data_p, n_p, rngs_p = self.sim._pad_wave(
                data, n_samples, rngs, target
            )
            put = lambda t: shard_client_arrays(t, self.sim.mesh)
            new_clusters, assign, closs = self._round_fn_sharded(n_epochs)(
                cluster_params, put(data_p), put(n_p), put(rngs_p)
            )
            assign, closs = assign[:c], closs[:c]
        else:
            new_clusters, assign, closs = self._round_fn(n_epochs)(
                cluster_params, data, n_samples, rngs
            )
        w = n_samples.astype(jnp.float32)
        return ClusteredRoundResult(
            cluster_params=new_clusters,
            assignments=np.asarray(assign),
            loss_history=agg.weighted_scalar_mean(closs, w),
            client_losses=closs,
        )

    def evaluate(
        self,
        cluster_params: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: Optional[jax.Array] = None,
    ) -> Dict[str, float]:
        """Each client scored with its best-fitting cluster (fresh
        assignment) — the federation-wide example-weighted aggregate."""
        if rng is None:
            rng = jax.random.key(0)
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        rngs = jax.random.split(rng, c)
        totals = self._eval_fn()(cluster_params, data, n_samples, rngs)
        denom = max(float(totals["n"]), 1.0)
        out = {"loss": float(totals["loss_sum"]) / denom, "n": denom}
        if "correct_sum" in totals:
            out["accuracy"] = float(totals["correct_sum"]) / denom
        return out

    def _eval_fn(self):
        # cached like _round_fn (and FedPer._eval_fn): a fresh jit per
        # call would recompile the identical C x K eval program each time
        if "eval" in self._jit_cache:
            return self._jit_cache["eval"]
        from baton_tpu.parallel.engine import client_eval_sums

        model = self.sim.model

        # donation decided no: evaluation never owns its inputs
        @jax.jit  # batonlint: allow[BTL011]
        def eval_all(cluster_params, data, n_samples, rngs):
            def one(d, n, r):
                k = jnp.argmin(jax.vmap(
                    lambda p: _masked_mean_loss(model, p, d, n, r)
                )(cluster_params))
                mine = jax.tree_util.tree_map(
                    lambda a: a[k], cluster_params
                )
                return client_eval_sums(model, mine, d, n, r)

            sums = jax.vmap(one)(data, n_samples, rngs)
            return jax.tree_util.tree_map(jnp.sum, sums)

        self._jit_cache["eval"] = eval_all
        return eval_all
