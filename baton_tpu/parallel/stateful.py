"""Stateful clients — per-client optimizer state persisting across rounds.

Cross-DEVICE FedAvg resets each client's optimizer every round by design
(clients are anonymous and stateless — the engine's default, matching
the reference where a worker's ``train()`` builds a fresh optimizer each
call, reference demo.py:29-34). Cross-SILO federations are different:
the same few institutions participate every round, and letting each keep
its local Adam/momentum moments across rounds is the standard refinement
— local curvature information survives the round boundary.

TPU-first shape: the cohort's optimizer states live as ONE stacked
pytree ``[C, ...]`` (the same layout as client data and FedPer's
personal stack), so a round is a single vmapped dispatch of
``LocalTrainer.train_with_opt_state`` over (state, data, rng); trained
params aggregate with the sim's configured rule (mean / trimmed /
median) and a FedOpt server optimizer composes on top exactly as in the
synchronous engine. On a ``clients`` mesh the same body runs under
``shard_map`` with the state stack sharded over chips and psum FedAvg
over ICI (tested equal to the single-device rounds). The caller owns
the stack — checkpoint it next to the globals (the Checkpointer's
``extra`` slot) to resume a federation with its optimizer memory
intact.

Memory: C x optimizer state (≈ C x params for Adam) — the inherent cost
of statefulness, same scale as robust aggregation's stacked params.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.core.model import WAVE_AXIS
from baton_tpu.ops import aggregation as agg
from baton_tpu.parallel.engine import FedSim, _server_update

Params = Any


@dataclasses.dataclass
class StatefulRoundResult:
    params: Params
    opt_states: Params          # [C, ...] stacked, threads to next round
    loss_history: jax.Array     # [n_epochs] sample-weighted
    client_losses: jax.Array    # [C, n_epochs]
    server_opt_state: Any = None


class StatefulClients:
    """Synchronous rounds with persistent per-client optimizer state.

    Wraps a :class:`FedSim` (same model/trainer/aggregator config); use
    the sim's own ``run_round`` when clients should stay stateless.
    """

    def __init__(self, sim: FedSim):
        if sim.trainable_predicate is not None:
            raise ValueError(
                "StatefulClients threads full-param optimizer state; "
                "compose with LoRA by building the FedSim on the adapter "
                "pytree directly"
            )
        if sim.mesh is not None:
            from baton_tpu.parallel.mesh import require_clients_mesh

            require_clients_mesh(sim.mesh, sim.aggregator, "StatefulClients")
        self.sim = sim
        self._jit_cache: Dict[int, Any] = {}

    def init_opt_states(self, params: Params, n_clients: int) -> Params:
        """Stacked optimizer states, one per client, all initialized from
        the same global params."""
        opt0 = self.sim.trainer.optimizer.init(params)
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(
                jnp.asarray(l), (n_clients,) + jnp.shape(l)
            ),
            opt0,
        )

    def _train_local(self, n_epochs: int):
        trainer = self.sim.trainer
        with_anchor = trainer.regularizer is not None

        def train_local(params, opt_states, data, n_samples, rngs):
            def one(os, d, n, r):
                new_p, new_os, losses = trainer.train_with_opt_state(
                    params, os, d, n, r, n_epochs,
                    params if with_anchor else None,
                )
                return new_p, new_os, losses

            return jax.vmap(one, axis_name=WAVE_AXIS)(
                opt_states, data, n_samples, rngs)

        return train_local

    def _round_fn(self, n_epochs: int):
        if n_epochs not in self._jit_cache:
            self._jit_cache[n_epochs] = jax.jit(self._train_local(n_epochs))
        return self._jit_cache[n_epochs]

    def _round_fn_sharded(self, n_epochs: int):
        """Mesh path: the optimizer-state stack / data / rngs shard over
        the clients axis, globals replicated; aggregation is the
        engine's psum FedAvg over ICI (same layout rule as FedPer's
        sharded round)."""
        key = ("sharded", n_epochs)
        if key not in self._jit_cache:
            from baton_tpu.parallel.mesh import CLIENT_AXIS
            from baton_tpu.parallel.partition import kernel_specs

            train_local = self._train_local(n_epochs)

            def kernel(params, opt_states, data, n_samples, rngs):
                trained, new_os, closs = train_local(
                    params, opt_states, data, n_samples, rngs
                )
                w = n_samples.astype(jnp.float32)
                aggregate = agg.tree_cast_like(
                    agg.psum_weighted_mean(trained, w, CLIENT_AXIS), params
                )
                loss_hist = agg.psum_weighted_scalar_mean(closs, w,
                                                          CLIENT_AXIS)
                return aggregate, new_os, loss_hist, closs

            in_specs, out_specs = kernel_specs("stateful.round")
            # donation decided no: params is the retained anchor and
            # the optimizer-state stack is caller-threaded round state
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
        opt_states: Optional[Params],
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: jax.Array,
        n_epochs: int = 1,
        server_opt_state=None,
    ) -> StatefulRoundResult:
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        if opt_states is None:
            opt_states = self.init_opt_states(params, c)
        rngs = jax.random.split(rng, c)

        if self.sim.mesh is not None:
            from baton_tpu.parallel.mesh import (
                CLIENT_AXIS,
                shard_client_arrays,
            )
            from baton_tpu.parallel.personalization import _pad_stack

            from baton_tpu.ops.padding import round_up

            n_dev = int(self.sim.mesh.shape[CLIENT_AXIS])
            target = round_up(c, n_dev)
            # auto-pad with zero-weight phantoms like the engine's wave
            # path; phantom optimizer states are row-0 copies that the
            # all-masked training leaves untouched
            data_p, n_p, rngs_p = self.sim._pad_wave(
                data, n_samples, rngs, target
            )
            os_p = _pad_stack(opt_states, target - c)
            put = lambda t: shard_client_arrays(t, self.sim.mesh)
            aggregate, new_opt_states, loss_history, closs = (
                self._round_fn_sharded(n_epochs)(
                    params, put(os_p), put(data_p), put(n_p), put(rngs_p)
                )
            )
            new_opt_states = jax.tree_util.tree_map(
                lambda a: a[:c], new_opt_states
            )
            closs = closs[:c]
            if self.sim.server_optimizer is not None:
                if server_opt_state is None:
                    server_opt_state = self.sim.server_optimizer.init(params)
                new_params, server_opt_state = _server_update(
                    self.sim.server_optimizer, params, aggregate,
                    server_opt_state,
                )
            else:
                new_params = aggregate
            return StatefulRoundResult(
                params=new_params,
                opt_states=new_opt_states,
                loss_history=loss_history,
                client_losses=closs,
                server_opt_state=server_opt_state,
            )

        trained, new_opt_states, closs = self._round_fn(n_epochs)(
            params, opt_states, data, n_samples, rngs
        )

        w = n_samples.astype(jnp.float32)
        aggregate = agg.aggregate_stacked(
            self.sim.aggregator, trained, n_samples, params
        )

        if self.sim.server_optimizer is not None:
            if server_opt_state is None:
                server_opt_state = self.sim.server_optimizer.init(params)
            new_params, server_opt_state = _server_update(
                self.sim.server_optimizer, params, aggregate, server_opt_state
            )
        else:
            new_params = aggregate

        return StatefulRoundResult(
            params=new_params,
            opt_states=new_opt_states,
            loss_history=agg.weighted_scalar_mean(closs, w),
            client_losses=closs,
            server_opt_state=server_opt_state,
        )
