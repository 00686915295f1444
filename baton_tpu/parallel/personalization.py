"""Partial personalization (FedPer-style): per-client personal layers.

Plain FedAvg forces every client onto one global model; under non-IID
shards the canonical fix is to PERSONALIZE part of the network — each
client keeps its own copy of some leaves (classically the head) that
never leaves the device, while the rest ("shared") is trained and
aggregated as usual. The reference has nothing like this (one global
state_dict, manager.py:119-126); it is standard FL-framework surface.

TPU-first shape: personal state is ONE stacked pytree ``[C, ...]`` on
the personal leaves — the same layout as the engine's client data — so a
personalized round is a single vmapped dispatch: vmap merges client c's
personal leaves with the replicated shared leaves, trains the full
model, and splits the result; shared halves aggregate with the sim's
configured rule (mean / trimmed / median via
:func:`baton_tpu.ops.aggregation.apply_aggregator`), personal halves
return as the new stack. On a ``clients`` mesh the same body runs under
``shard_map`` — personal stack and data sharded over chips, shared-leaf
aggregation and the warm-start mean as psum collectives over ICI
(numerically equal to the single-device round, tested).

The returned global params carry the unweighted mean of the personal
leaves purely as a warm start for clients joining later; it is never
trained on directly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.core.model import WAVE_AXIS
from baton_tpu.core.partition import PathPredicate, make_partition
from baton_tpu.ops import aggregation as agg
from baton_tpu.parallel.engine import FedSim, client_eval_sums

Params = Any


def _pad_stack(tree: Params, pad: int) -> Params:
    """Pad a ``[C, ...]`` stacked pytree with ``pad`` copies of row 0 —
    phantom rows' values never matter (masked training, weight 0,
    excluded from means) but must be shape/dtype-valid."""
    if pad <= 0:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.repeat(a[:1], pad, axis=0)], axis=0
        ),
        tree,
    )


@dataclasses.dataclass
class PersonalizedRoundResult:
    params: Params              # shared aggregated; personal leaves = warm-start mean
    personal_state: Params      # [C, ...] stacked personal leaves
    loss_history: jax.Array     # [n_epochs] sample-weighted
    client_losses: jax.Array    # [C, n_epochs]


class FedPer:
    """Personalized federated training over a :class:`FedSim`'s trainer.

    ``personal(path, leaf) -> bool`` marks the per-client leaves. The
    personal stack threads through rounds exactly like params do — the
    caller owns it (checkpoint it alongside the globals to resume).
    """

    def __init__(self, sim: FedSim, personal: PathPredicate):
        if sim.trainable_predicate is not None:
            raise ValueError(
                "FedPer and a trainable/frozen partition both re-plumb the "
                "param tree; compose by marking frozen leaves neither "
                "personal nor trained instead"
            )
        if sim.server_optimizer is not None:
            raise ValueError(
                "FedPer aggregates shared leaves directly; a FedOpt "
                "server optimizer would be silently ignored — configure "
                "the FedSim without one for personalized rounds"
            )
        if sim.mesh is not None:
            from baton_tpu.parallel.mesh import require_clients_mesh

            require_clients_mesh(sim.mesh, sim.aggregator, "FedPer")
        self.sim = sim
        self.personal_pred = personal
        self.partition = None
        self._jit_cache: Dict[int, Any] = {}

    def _ensure_partition(self, params) -> None:
        if self.partition is None:
            # "trainable" side of the partition = personal leaves
            self.partition = make_partition(params, self.personal_pred)

    def init_personal(self, params: Params, n_clients: int) -> Params:
        """Personal stack initialized by broadcasting the global leaves."""
        self._ensure_partition(params)
        personal, _ = self.partition.split(params)
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (n_clients,) + l.shape), personal
        )

    def _train_local(self, n_epochs: int):
        """The per-shard body shared by the vmap and shard_map paths."""
        part = self.partition
        trainer = self.sim.trainer
        with_anchor = trainer.regularizer is not None

        def train_local(personal_state, shared, data, n_samples, rngs):
            def one(pers, d, n, r):
                full = part.merge(pers, shared)
                # the client's round-start params are its FedProx
                # anchor (mirrors engine.py's wave kernels)
                new_full, _, losses = trainer.train(
                    full, d, n, r, n_epochs,
                    full if with_anchor else None,
                )
                new_pers, new_shared = part.split(new_full)
                return new_pers, new_shared, losses

            return jax.vmap(one, axis_name=WAVE_AXIS)(
                personal_state, data, n_samples, rngs)

        return train_local

    def _round_fn(self, n_epochs: int):
        if n_epochs not in self._jit_cache:
            self._jit_cache[n_epochs] = jax.jit(self._train_local(n_epochs))
        return self._jit_cache[n_epochs]

    def _round_fn_sharded(self, n_epochs: int):
        """Mesh path: personal stack / data / rngs sharded over the
        clients axis, shared leaves replicated; shared aggregation and
        the warm-start personal mean are psum collectives over ICI —
        the same layout rule as the engine's sharded wave kernel."""
        key = ("sharded", n_epochs)
        if key not in self._jit_cache:
            from baton_tpu.parallel.mesh import CLIENT_AXIS
            from baton_tpu.parallel.partition import kernel_specs

            train_local = self._train_local(n_epochs)

            def kernel(personal_state, shared, data, n_samples, rngs):
                new_pers, new_shared, closs = train_local(
                    personal_state, shared, data, n_samples, rngs
                )
                w = n_samples.astype(jnp.float32)
                # shared-leaf FedAvg: the one shared psum rule
                shared_agg = agg.tree_cast_like(
                    agg.psum_weighted_mean(new_shared, w, CLIENT_AXIS),
                    shared,
                )
                # warm start: mean over REAL clients only — phantom
                # zero-sample rows carry unchanged round-start leaves
                # and would bias the mean toward no-op
                m = (n_samples > 0).astype(jnp.float32)
                pers_sum = jax.lax.psum(
                    jax.tree_util.tree_map(
                        lambda l: jnp.tensordot(
                            m, l.astype(jnp.float32), axes=(0, 0)
                        ),
                        new_pers,
                    ),
                    CLIENT_AXIS,
                )
                n_real = jnp.maximum(
                    jax.lax.psum(jnp.sum(m), CLIENT_AXIS), 1.0
                )
                pers_mean = jax.tree_util.tree_map(
                    lambda s, ref: (s / n_real).astype(ref.dtype),
                    pers_sum, personal_state,
                )
                loss_hist = agg.psum_weighted_scalar_mean(closs, w,
                                                          CLIENT_AXIS)
                return new_pers, shared_agg, pers_mean, loss_hist, closs

            in_specs, out_specs = kernel_specs("personalization.round")
            # donation decided no: the personal stack is caller
            # state, threaded (and possibly re-read) across rounds
            self._jit_cache[key] = jax.jit(jax.shard_map(  # batonlint: allow[BTL011]
                kernel,
                mesh=self.sim.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ))
        return self._jit_cache[key]

    def run_round(
        self,
        params: Params,
        personal_state: Optional[Params],
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: jax.Array,
        n_epochs: int = 1,
    ) -> PersonalizedRoundResult:
        self._ensure_partition(params)
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        if personal_state is None:
            personal_state = self.init_personal(params, c)
        _, shared = self.partition.split(params)
        rngs = jax.random.split(rng, c)

        if self.sim.mesh is not None:
            from baton_tpu.parallel.mesh import (
                CLIENT_AXIS,
                shard_client_arrays,
            )

            from baton_tpu.ops.padding import round_up

            n_dev = int(self.sim.mesh.shape[CLIENT_AXIS])
            target = round_up(c, n_dev)
            # auto-pad with zero-weight phantoms like the engine's wave
            # path (_pad_wave): phantoms train on all-masked data, carry
            # FedAvg weight 0, and are excluded from the warm-start mean
            data_p, n_p, rngs_p = self.sim._pad_wave(
                data, n_samples, rngs, target
            )
            pers_p = _pad_stack(personal_state, target - c)
            put = lambda t: shard_client_arrays(t, self.sim.mesh)
            new_pers, shared_agg, pers_mean, loss_history, closs = (
                self._round_fn_sharded(n_epochs)(
                    put(pers_p), shared, put(data_p), put(n_p), put(rngs_p)
                )
            )
            unpad = lambda t: jax.tree_util.tree_map(lambda a: a[:c], t)
            return PersonalizedRoundResult(
                params=self.partition.merge(pers_mean, shared_agg),
                personal_state=unpad(new_pers),
                loss_history=loss_history,
                client_losses=closs[:c],
            )

        new_pers, new_shared, closs = self._round_fn(n_epochs)(
            personal_state, shared, data, n_samples, rngs
        )

        w = n_samples.astype(jnp.float32)
        shared_agg = agg.aggregate_stacked(
            self.sim.aggregator, new_shared, n_samples, shared
        )
        # warm start for future clients: mean of REAL clients' personal
        # leaves (zero-sample rows are unchanged broadcasts — excluding
        # them keeps meshless and sharded rounds equal under padding)
        m = (n_samples > 0).astype(jnp.float32)
        n_real = jnp.maximum(jnp.sum(m), 1.0)
        pers_mean = jax.tree_util.tree_map(
            lambda l: (
                jnp.tensordot(m, l.astype(jnp.float32), axes=(0, 0)) / n_real
            ).astype(l.dtype),
            new_pers,
        )
        new_params = self.partition.merge(pers_mean, shared_agg)

        loss_history = agg.weighted_scalar_mean(closs, w)
        return PersonalizedRoundResult(
            params=new_params,
            personal_state=new_pers,
            loss_history=loss_history,
            client_losses=closs,
        )

    def evaluate(
        self,
        params: Params,
        personal_state: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: Optional[jax.Array] = None,
    ) -> Dict[str, float]:
        """Personalized evaluation: each client scored on ITS OWN data
        with ITS OWN personal leaves — the metric personalization exists
        for. Returns the example-weighted federation aggregate."""
        self._ensure_partition(params)
        if rng is None:
            rng = jax.random.key(0)
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        _, shared = self.partition.split(params)
        rngs = jax.random.split(rng, c)
        eval_all = self._eval_fn()
        totals = eval_all(personal_state, shared, data, n_samples, rngs)
        denom = max(float(totals["n"]), 1.0)
        out = {"loss": float(totals["loss_sum"]) / denom, "n": denom}
        if "correct_sum" in totals:
            out["accuracy"] = float(totals["correct_sum"]) / denom
        return out

    def _eval_fn(self):
        # cached like _round_fn: a fresh jit per call would recompile the
        # identical eval program every round
        if "eval" in self._jit_cache:
            return self._jit_cache["eval"]
        model = self.sim.model
        part = self.partition

        # donation decided no: evaluation never owns its inputs
        @jax.jit  # batonlint: allow[BTL011]
        def eval_all(personal_state, shared, data, n_samples, rngs):
            def one(pers, d, n, r):
                # same sums kernel as FedSim's federated eval — one
                # definition of the accuracy-eligibility rule
                return client_eval_sums(model, part.merge(pers, shared),
                                        d, n, r)

            sums = jax.vmap(one)(personal_state, data, n_samples, rngs)
            return jax.tree_util.tree_map(jnp.sum, sums)

        self._jit_cache["eval"] = eval_all
        return eval_all
