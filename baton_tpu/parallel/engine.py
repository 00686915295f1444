"""FedSim — the TPU-resident federated simulation engine.

This is the heart of the framework: the reference's
round = HTTP broadcast → N worker processes train → HTTP gather → Python
weighted sum (SURVEY §3.2) becomes

  round = replicate global params
        → ``vmap``-ped jitted local training over a client axis
        → sample-weighted psum/tensordot aggregation

with *zero* Python in the hot path. Three execution modes, all the same
math:

* **vmap** (single device): clients stacked on a leading axis.
* **shard_map** (mesh): the client axis sharded over a
  ``Mesh(('clients',))``; aggregation via ICI collectives
  (:func:`baton_tpu.ops.aggregation.psum_weighted_mean`).
* **waves**: when C clients × model size exceeds HBM, the cohort is
  processed in waves of ``wave_size``; each wave contributes weighted
  *sums* (params·w, losses·w, Σw) accumulated on device, with the divide
  at the end — numerically identical to one big FedAvg (the weighted
  mean is associative in its sums).

Server-side optimizers (FedOpt family) treat ``global − aggregate`` as a
pseudo-gradient fed to an optax transform — plain FedAvg is the identity
case (replaces the in-place assignment at reference manager.py:123-126).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from baton_tpu.core.model import WAVE_AXIS, FedModel
from baton_tpu.core.partition import PathPredicate, make_partition
from baton_tpu.core.training import LocalTrainer, make_local_trainer, make_evaluator
from baton_tpu.obs.compute import ComputeProbe
from baton_tpu.ops import aggregation as agg
from baton_tpu.ops.padding import round_up
from baton_tpu.parallel.mesh import CLIENT_AXIS, client_sharding, replicated_sharding
from baton_tpu.parallel.partition import kernel_specs
from baton_tpu.parallel.tensor_parallel import MODEL_AXIS, shard_params_tp
from baton_tpu.utils.profiling import (
    _plan_gb_of,
    annotate,
    hbm_budget_gb,
    is_oom_error,
)

Params = Any

# The size of FedSim.run_round's interpreter frame, in words (locals,
# cells and operand stack). CPython 3.12 keeps a thread's frames in 16 KiB
# chunks and maps a new chunk, and unmaps it again, on every call made
# from a frame that ends at a chunk's edge. JAX lowers a wave program by
# a recursion some 80 frames deep under the launch, so where its loops
# fall relative to those edges depends on the size of every frame above
# them, run_round's among them: at 76 words and more the shard_map
# program's lowering made 17,800 such calls where at 74 it makes 3,400,
# 2.7 s more for each of the two warm-up rounds of resnet18_c128_mesh4
# on the chip's host (PERF.md section 6, PR 37;
# scripts/lowering_faults/ counts them on the CPU). tests/
# test_round_settle.py holds run_round to this size: a change that needs
# another local counts the faults again and moves the number with it.
RUN_ROUND_FRAME_WORDS = 74

# Rows are staged in granules of a quarter batch, so that a ragged
# federation whose largest client changes from round to round compiles
# at most 4 x capacity / batch_size wave programs.
ROW_GRANULES_PER_BATCH = 4


@dataclasses.dataclass
class RoundResult:
    """Outcome of one federated round (replaces the reference's
    ``update_manager.client_responses`` dict + manager-side aggregation)."""

    params: Params
    loss_history: jax.Array  # [n_epochs] sample-weighted across clients
    client_losses: Optional[jax.Array]  # [C, n_epochs]
    n_samples_total: jax.Array
    server_opt_state: Any = None


@dataclasses.dataclass
class _PendingRound:
    """A round whose programs are queued and that no one has waited for
    yet: what :meth:`FedSim._settle` waits on, and what the round's
    compute record is written from."""

    index: int  # counted from the FedSim's first round, which is 1
    loss_sum: jax.Array  # ready when the round's waves are done
    t_waves0: float  # host clock, before the first wave was staged
    record: Dict[str, Any]  # ComputeProbe.record_round's, but the times
    # a launch of this round compiled or loaded a program, or its shape
    # is new to the compute tracker: the round settles itself
    own: bool


def _cache_entries(program) -> Optional[int]:
    """The jit's fast-path entries, where ``program`` counts them (a
    jitted function does). A count that a call left larger is a call
    that traced and then compiled, or loaded from the compile cache."""
    entries = getattr(program, "_cache_size", None)
    return None if entries is None else entries()


def client_eval_sums(model: FedModel, params, d, n, r):
    """One client's evaluation sums: masked loss sum, valid count, and —
    for rank-1 integer labels — correct-prediction sum. The single
    definition of the accuracy-eligibility rule, shared by FedSim's
    federated eval and FedPer's personalized eval
    (parallel/personalization.py)."""
    losses = model.per_example_loss(params, d, r)
    mask = (jnp.arange(losses.shape[0]) < n).astype(jnp.float32)
    out = {
        "loss_sum": jnp.sum(losses.astype(jnp.float32) * mask),
        "n": mask.sum(),
    }
    y = d.get("y")
    # accuracy only for rank-1 class labels (y [B] matching the
    # per-example losses); sequence targets (LM: y [B, L]) have no
    # single-label accuracy and would shape-mismatch the mask
    if (y is not None and jnp.issubdtype(y.dtype, jnp.integer)
            and y.ndim == losses.ndim):
        # model.apply here repeats per_example_loss's forward
        # structurally — XLA CSEs the shared subgraph (measured:
        # +2.6% flops vs loss-only, not 2x), so one jit is enough
        logits = model.apply(params, d, r)
        correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        out["correct_sum"] = jnp.sum(correct * mask)
    return out


class FedSim:
    """Simulated-clients federated training on one device or a mesh.

    Data layout: ``data`` is a dict of ``[C, capacity, ...]`` arrays
    (see :func:`baton_tpu.ops.padding.stack_client_datasets`) and
    ``n_samples`` is ``[C]`` — client ``c``'s true row count, which is
    also its FedAvg weight (reference manager.py:119-126 semantics).
    A client's real rows come first: rows ``[n_samples[c], capacity)``
    are padding, masked out of every loss and gradient and never read
    for their values. Any ``capacity`` is taken. A round computes only
    the rows its cohort holds: every wave is staged at the cohort's
    largest ``n_samples`` rounded up to a quarter of the batch size
    (:meth:`_rows_to_stage`), never more than ``capacity``, and the
    trainer shares those rows equally among an epoch's steps where the
    batch size does not divide them (core/training.py).
    """

    def __init__(
        self,
        model: FedModel,
        optimizer: Optional[optax.GradientTransformation] = None,
        batch_size: int = 32,
        learning_rate: float = 1e-3,
        server_optimizer: Optional[optax.GradientTransformation] = None,
        mesh: Optional[Mesh] = None,
        regularizer=None,
        trainable: Optional[PathPredicate] = None,
        dp=None,
        aggregator: str = "mean",
    ):
        """``aggregator`` selects the round combine rule:

        * ``"mean"`` (default) — sample-weighted FedAvg, the reference
          rule (manager.py:119-126); streams as per-wave weighted sums,
          so memory is O(model), not O(clients x model).
        * ``"trimmed:<ratio>"`` — coordinate-wise trimmed mean,
          ``"median"`` — coordinate-wise median (ops/aggregation.py):
          Byzantine-robust rules that need every client's params
          materialized ([C, model] HBM — the price of robustness) and
          are unweighted (standard formulations; a poisoned client
          could otherwise buy influence by claiming a huge n_samples).
          Zero-sample clients are excluded before the combine.
        """
        self.model = model
        self.aggregator = agg.parse_aggregator(aggregator)
        self.trainer: LocalTrainer = make_local_trainer(
            model,
            optimizer=optimizer,
            batch_size=batch_size,
            learning_rate=learning_rate,
            regularizer=regularizer,
            dp=dp,
        )
        self.server_optimizer = server_optimizer
        self.mesh = mesh
        self.evaluate = make_evaluator(model)
        # ``trainable(path, leaf) -> bool`` restricts training/aggregation
        # to a sub-pytree (LoRA adapters); frozen leaves are replicated
        # once, never per-client. Partition built lazily from the first
        # params seen (structure unknown until then).
        self.trainable_predicate = trainable
        self.partition = None
        # compute-plane probe: every round leaves its compute record
        # (MFU/compile/HBM, null-with-reason) in ``last_compute`` for
        # the caller (the manager's simulated-cohort path ships it into
        # the round's SLO record). The record needs the round's end. A
        # round whose launches all hit the jit's fast path does not wait
        # for its own: it stays in ``_pending`` until the next run_round
        # has queued its programs, or until ``last_compute`` is read
        # (:meth:`_settle`). A round that compiled or loaded a program
        # waits at its own end. One scalar sync per round either way, a
        # round late in a steady run.
        self.compute_probe = ComputeProbe(model=model)
        self._last_compute: Optional[dict] = None
        self._pending: Optional[_PendingRound] = None
        self._rounds_queued = 0
        # the last settled round's end as the host saw it (perf_counter)
        self._last_done = float("-inf")
        self._fold_program = self._make_fold_program()
        # _param_shapes' result and the tree structures it is of
        self._param_shape_attrs: Optional[tuple] = None

    @property
    def last_compute(self) -> Optional[dict]:
        """The compute record of the newest round this ``FedSim`` ran
        (``None`` before the first). Reading it waits for that round if
        nothing has yet, and raises that round's device error if it had
        one."""
        self._settle()
        return self._last_compute

    def _pend(self, loss_sum, t_waves0, own, signature, n_host, n_epochs,
              c, rows) -> None:
        """Keep the round just queued as the one pending round (its
        programs are on the device's queue; nothing has waited for it).

        A method of its own so that :meth:`run_round`'s frame stays the
        size it was (``RUN_ROUND_FRAME_WORDS``): building the record
        inline deepens that frame's operand stack, and everything JAX
        traces and lowers under a launch then sits elsewhere on the
        interpreter's data stack (PERF.md section 6, PR 37)."""
        self._rounds_queued += 1
        self._pending = _PendingRound(
            index=self._rounds_queued,
            loss_sum=loss_sum,
            t_waves0=t_waves0,
            record=dict(
                key="run_round",
                signature=signature,
                n_samples=float(n_host.sum()),
                n_epochs=n_epochs,
                steps=c * self.trainer.steps_per_round(rows, n_epochs),
                n_chips=(int(self.mesh.devices.size)
                         if self.mesh is not None else 1),
            ),
            own=own)

    def _settle(self) -> None:
        """Wait for the pending round, if there is one, and write its
        compute record: the one sync a round has. Called by the next
        :meth:`run_round` once its own waves and fold are queued, so the
        device goes from one round into the next; by a round that
        compiled or loaded a program, for itself, before it returns
        (``own``); and by a read of ``last_compute``. The pending round
        is forgotten before the wait: an error the wait raises is raised
        once.

        ``train_s`` is the round's end as the host saw it less the later
        of its own start and the end of the round before it: the host
        runs a round ahead, and a wall time from the round's own start
        would count the wave before it twice. For a round that settles
        itself with nothing pending before it, which every round that
        compiled is, that is its own wall time. Where the loss sum was
        ready when the host arrived the round ended earlier than it was
        seen to, and ``train_s_source`` says that the time is an upper
        bound."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        ready = pending.loss_sum.is_ready()
        # own 0 and ready 0 on every round of a run: the host's head is
        # hidden behind the wave before it and the chip sets the pace;
        # ready 1: the host does
        with annotate("baton.round.sync", settles=pending.index,
                      ready=int(ready), own=int(pending.own)):
            jax.block_until_ready(pending.loss_sum)
        done = time.perf_counter()
        train_s = done - max(pending.t_waves0, self._last_done)
        self._last_done = done
        with annotate("baton.round.record"):
            self._last_compute = self.compute_probe.record_round(
                train_s=train_s,
                train_s_source=("found_ready_upper_bound" if ready
                                else "host_waited"),
                **pending.record)

    def _ensure_partition(self, params):
        if self.trainable_predicate is None or self.partition is not None:
            return
        self.partition = make_partition(params, self.trainable_predicate)
        self.trainer = dataclasses.replace(self.trainer, partition=self.partition)

    def _split(self, params):
        """(trainable, frozen) — identity when no partition is configured."""
        if self.trainable_predicate is None:
            return params, None
        self._ensure_partition(params)
        return self.partition.split(params)

    def _param_shapes(self, params, frozen):
        """``(leaves, trainable_bytes, frozen_bytes)`` of a round's two
        parameter trees, for its spans' attributes. Shapes do not change
        from round to round: the trees are walked when their structure
        is new to this ``FedSim`` (its first round, or a model rebuilt
        with other leaves) and otherwise not, in or out of a profiler
        session."""
        structure = jax.tree_util.tree_structure((params, frozen))
        if (self._param_shape_attrs is None
                or self._param_shape_attrs[0] != structure):
            self._param_shape_attrs = (
                structure,
                (structure.num_leaves, _tree_bytes(params),
                 _tree_bytes(frozen)))
        return self._param_shape_attrs[1]

    # ------------------------------------------------------------------
    @property
    def is_hybrid(self) -> bool:
        """True for a ``('clients', 'model')``-style hybrid mesh: the
        frozen base rides tensor-parallel shardings on the ``model`` axis
        while per-client work spreads over ``clients`` (BASELINE config 4
        — a Llama-8B base physically cannot replicate per chip)."""
        return self.mesh is not None and MODEL_AXIS in self.mesh.axis_names

    @property
    def partition_rule_set(self) -> str:
        """Name of the :data:`~baton_tpu.parallel.partition.DEFAULT_RULE_SETS`
        table governing this sim's placement."""
        if self.is_hybrid:
            return "transformer-tp"
        if self.mesh is not None:
            return "client-stacked"
        return "replicated"

    def _clients_per_wave_unit(self) -> int:
        """Wave sizes must be a multiple of the client-axis extent."""
        if self.mesh is None:
            return 1
        if self.is_hybrid:
            return int(self.mesh.shape[CLIENT_AXIS])
        return int(self.mesh.devices.size)

    def _place_hybrid(self, params, frozen):
        """GSPMD placement for the hybrid mesh: trainable globals
        replicated, frozen base tensor-parallel over ``model``. Data is
        placed per-wave (client_sharding). XLA's GSPMD partitioner then
        derives the whole round program — per-client compute partitioned
        over ``clients``, every frozen-base matmul Megatron-sharded over
        ``model`` — with no shard_map or manual collectives."""
        params = jax.device_put(params, replicated_sharding(self.mesh))
        if frozen is not None:
            # frozen is a flat leaf list (partition.split); shard each
            # leaf by its ORIGINAL tree path so the Megatron name rules
            # (wq/wo/w_gate/…) still apply
            from baton_tpu.parallel.tensor_parallel import (
                leaf_tp_sharding,
            )

            paths = self.partition.frozen_paths if self.partition else None
            if paths and len(paths) == len(frozen):
                frozen = [
                    jax.device_put(
                        leaf, leaf_tp_sharding(path, leaf, self.mesh)
                    )
                    for path, leaf in zip(paths, frozen)
                ]
            else:
                frozen = shard_params_tp(frozen, self.mesh)
        return params, frozen

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        return self.model.init(rng)

    def init_server_opt_state(self, params: Params):
        if self.server_optimizer is None:
            return None
        trainable, _ = self._split(params)
        return self.server_optimizer.init(trainable)

    # ------------------------------------------------------------------
    # wave kernels: return (Σ w·params, Σ w·losses, Σ w, per-client losses)
    def _wave_sums_raw(self, params, frozen, data, n_samples, rngs, n_epochs):
        anchor = params if self.trainer.regularizer is not None else None

        def one_client(d, n, r):
            p, _, losses = self.trainer.train(
                params, d, n, r, n_epochs, anchor, frozen
            )
            return p, losses

        with jax.named_scope("local_train"):
            client_params, client_losses = jax.vmap(
                one_client, axis_name=WAVE_AXIS)(data, n_samples, rngs)
        with jax.named_scope("wave_sums"):
            w = n_samples.astype(jnp.float32)
            psum = agg.weighted_tree_sum(client_params, w)
            lsum = jnp.tensordot(w, client_losses.astype(jnp.float32),
                                 axes=(0, 0))
            wtot = jnp.sum(w)
        return psum, lsum, wtot, client_losses

    # HBM note on donation: wave INPUTS are deliberately not donated.
    # `params` is reused by every wave of the round (and as the FedProx
    # anchor), and the per-wave data/rng slices alias the caller's arrays
    # when a round fits in one wave (jnp identity slices return the same
    # buffer), so donating them would invalidate data the caller reuses
    # across rounds. Donation lives where it is safe and large: the
    # wave loop donates its model-sized psum accumulator
    # (_acc_tree_add, then the fold program), and
    # LocalTrainer.train_with_opt_state donates the per-client optimizer
    # state (training.py) — the buffers that would otherwise be
    # double-buffered per round.
    # donation decided no: params is the round's retained anchor,
    # re-read by every wave (and by FedProx as the prox center)
    @partial(jax.jit, static_argnums=(0, 6))  # batonlint: allow[BTL011]
    def _wave_sums_vmap(self, params, frozen, data, n_samples, rngs, n_epochs):
        return self._wave_sums_raw(params, frozen, data, n_samples, rngs, n_epochs)

    # robust-aggregation wave kernel: returns every client's trained
    # params ([C_wave, ...] stacked) instead of streaming weighted sums —
    # trimmed mean/median are order statistics and cannot be computed
    # from sums (engine __init__ docstring on the memory trade)
    def _wave_params_raw(self, params, frozen, data, n_samples, rngs, n_epochs):
        anchor = params if self.trainer.regularizer is not None else None

        def one_client(d, n, r):
            p, _, losses = self.trainer.train(
                params, d, n, r, n_epochs, anchor, frozen
            )
            return p, losses

        return jax.vmap(one_client, axis_name=WAVE_AXIS)(
            data, n_samples, rngs)

    # donation decided no: same retained-anchor contract as
    # _wave_sums_vmap
    @partial(jax.jit, static_argnums=(0, 6))  # batonlint: allow[BTL011]
    def _wave_params_vmap(self, params, frozen, data, n_samples, rngs, n_epochs):
        return self._wave_params_raw(params, frozen, data, n_samples, rngs,
                                     n_epochs)

    def _make_wave_params_sharded(self, n_epochs: int):
        cache = getattr(self, "_sharded_params_cache", None)
        if cache is None:
            cache = self._sharded_params_cache = {}
        if n_epochs not in cache:
            mesh = self.mesh

            def kernel(params, frozen, data, n_samples, rngs):
                return self._wave_params_raw(
                    params, frozen, data, n_samples, rngs, n_epochs
                )

            in_specs, out_specs = kernel_specs("engine.wave_params")
            # donation decided no: params is the caller-retained
            # anchor, re-read across waves
            cache[n_epochs] = jax.jit(jax.shard_map(  # batonlint: allow[BTL011]
                kernel,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ))
        return cache[n_epochs]

    def _make_wave_sums_sharded(self, n_epochs: int):
        # Cache per n_epochs: rebuilding the shard_map closure every round
        # would hand jit a fresh function and force an XLA recompile.
        cache = getattr(self, "_sharded_cache", None)
        if cache is None:
            cache = self._sharded_cache = {}
        if n_epochs not in cache:
            mesh = self.mesh

            def kernel(params, frozen, data, n_samples, rngs):
                # per-shard wave math is _wave_sums_raw verbatim; only the
                # three ICI reductions are mesh-specific
                local_psum, local_lsum, local_w, client_losses = (
                    self._wave_sums_raw(
                        params, frozen, data, n_samples, rngs, n_epochs
                    )
                )
                with jax.named_scope("wave_psum"):
                    psum = jax.lax.psum(local_psum, CLIENT_AXIS)
                    lsum = jax.lax.psum(local_lsum, CLIENT_AXIS)
                    wtot = jax.lax.psum(local_w, CLIENT_AXIS)
                return psum, lsum, wtot, client_losses

            in_specs, out_specs = kernel_specs("engine.wave_sums")
            # donation decided no: params is the caller-retained
            # anchor, re-read across waves
            cache[n_epochs] = jax.jit(jax.shard_map(  # batonlint: allow[BTL011]
                kernel,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ))
        return cache[n_epochs]

    def _wave_program(self, n_epochs: int, robust: bool,
                      per_shard: bool = False):
        """``(program, bind)``: the jitted wave program of this layout
        and aggregator, and ``bind(params, frozen, data, n_samples,
        rngs)`` giving its arguments, so that ``program(*bind(...))``
        runs a wave and ``program.lower(*bind(...))`` lowers the same
        program. The one place that chooses it, for :meth:`run_round`,
        :meth:`lower_wave` and :meth:`wave_plan_gb`. ``per_shard`` gives
        the plain ``vmap`` program on a mesh too: what one device runs
        of a wave, the collectives aside."""
        if self.mesh is not None and not self.is_hybrid and not per_shard:
            program = (self._make_wave_params_sharded(n_epochs) if robust
                       else self._make_wave_sums_sharded(n_epochs))
            return program, lambda *wave: wave
        program = (type(self)._wave_params_vmap if robust
                   else type(self)._wave_sums_vmap)
        return program, lambda *wave: (self, *wave, n_epochs)

    def _make_fold_program(self):
        """The mean round's fold as one jitted program of ``(psum_acc,
        w_acc, lsum_acc, params, server_opt_state)``: :func:`_fold_mean`
        with this sim's server optimizer closed over, built once per
        ``FedSim`` as the wave programs are. On the hybrid clients x
        model mesh its parameters come out replicated."""
        server_optimizer = self.server_optimizer
        pin = replicated_sharding(self.mesh) if self.is_hybrid else None

        def fold(psum_acc, w_acc, lsum_acc, params, server_opt_state):
            new_params, server_opt_state, loss_history = _fold_mean(
                server_optimizer, psum_acc, w_acc, lsum_acc, params,
                server_opt_state)
            if pin is not None:
                # GSPMD is free to leave the trainable aggregate
                # model-sharded (it flows out of matmuls against the
                # TP base), but the global state is logically
                # replicated — pin it back to the partition layer's
                # replicated rule so round outputs carry the same
                # layout contract as inputs
                new_params = jax.lax.with_sharding_constraint(new_params, pin)
            return new_params, server_opt_state, loss_history

        # donation decided yes for psum_acc only: it is the wave loop's
        # own (a wave program's output or _acc_tree_add's, rebound by
        # run_round and read nowhere after the fold), so a float32 leaf's
        # aggregate aliases its accumulator and the fold, queued behind
        # the last wave, allocates nothing model-sized. params and
        # server_opt_state are the caller's; lsum_acc and w_acc are read
        # after the dispatch (the sync, RoundResult).
        return jax.jit(fold, donate_argnums=(0,))

    def _resolve_wave_size(self, wave_size: Optional[int], c: int) -> int:
        """Whole cohort when ``None``; a multiple of the wave unit."""
        n_dev = self._clients_per_wave_unit()
        return round_up(c if wave_size is None else wave_size, n_dev)

    # ------------------------------------------------------------------
    def _pad_wave(self, data, n_samples, rngs, target: int):
        """Pad a short/unaligned wave with zero-weight phantom clients —
        they train on all-masked data (exactly-zero grads) and carry
        FedAvg weight 0, so they cannot perturb the aggregate."""
        c = n_samples.shape[0]
        if c == target:
            return data, n_samples, rngs
        pad = target - c

        def pad_leaf(a):
            return jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
            )

        data = jax.tree_util.tree_map(pad_leaf, data)
        n_samples = jnp.concatenate(
            [n_samples, jnp.zeros((pad,), n_samples.dtype)]
        )
        # Phantom clients have weight 0, so their rng only needs a valid
        # shape — repeat the first key rather than slicing (a short wave
        # can have fewer real clients than the pad amount).
        rngs = jnp.concatenate(
            [rngs, jnp.repeat(rngs[:1], pad, axis=0)], axis=0
        )
        return data, n_samples, rngs

    def _rows_to_stage(self, data, n_samples: np.ndarray):
        """``(rows, capacity)``: the rows a client every wave of this
        cohort is staged at, and the rows a client ``data`` holds.
        ``n_samples`` is the cohort's, on the host."""
        capacity = int(jax.tree_util.tree_leaves(data)[0].shape[1])
        granule = max(1, self.trainer.batch_size // ROW_GRANULES_PER_BATCH)
        largest = max(1, int(n_samples.max(initial=0)))
        return min(capacity, round_up(largest, granule)), capacity

    def _stage_wave(self, data, n_samples, rngs, start: int, stop: int,
                    wave_size: int, in_shard, rows: int):
        """Clients ``[start, stop)`` as one wave's inputs: sliced to
        their first ``rows`` rows, padded to ``wave_size`` with phantom
        clients and, on a mesh, placed on ``in_shard``."""
        d = jax.tree_util.tree_map(lambda a: a[start:stop, :rows], data)
        n = n_samples[start:stop]
        r = rngs[start:stop]
        d, n, r = self._pad_wave(d, n, r, wave_size)
        if in_shard is not None:
            d = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, in_shard), d
            )
            n = jax.device_put(n, in_shard)
            r = jax.device_put(r, in_shard)
        return d, n, r

    def _first_wave(self, params, data, n_samples, rng, n_epochs: int,
                    wave_size: Optional[int], per_shard: bool = False):
        """``(program, args)`` of the first wave of this round at
        ``wave_size`` clients (the whole cohort when ``None``), unrun:
        the weighted-sums program and the staging are
        :meth:`run_round`'s own (``_wave_program``, ``_stage_wave``).
        With ``per_shard`` it is one device's share of that wave:
        ``wave_size`` over the client-axis extent, inputs unplaced."""
        params, frozen = self._split(params)
        rows, _ = self._rows_to_stage(data, np.asarray(n_samples))
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        rngs = jax.random.split(rng, c)
        wave_size = self._resolve_wave_size(wave_size, c)
        in_shard = client_sharding(self.mesh) if self.mesh is not None else None
        if per_shard:
            wave_size = max(1, wave_size // self._clients_per_wave_unit())
            in_shard = None
        program, bind = self._wave_program(n_epochs, robust=False,
                                           per_shard=per_shard)
        d, n, r = self._stage_wave(data, n_samples, rngs, 0,
                                   min(wave_size, c), wave_size, in_shard,
                                   rows)
        return program, bind(params, frozen, d, n, r)

    def wave_plan_gb(self, params, data, n_samples, key,
                     wave_size: Optional[int] = None,
                     n_epochs: int = 1) -> Optional[float]:
        """XLA's static HBM plan (GiB) of one device's share of a wave
        of ``wave_size`` clients (the whole cohort when ``None``),
        compiled WITHOUT executing — the OOM guard behind
        :meth:`auto_wave_size`. On a mesh that share is the per-shard
        program at ``wave_size`` over the client-axis extent. Returns
        ``None`` when analysis is unavailable (proceed) and
        ``float("inf")`` when the compile itself RESOURCE_EXHAUSTs (a
        definitive does-not-fit — guards must skip). A ``wave_size``
        larger than the cohort is padded to size as ``run_round`` pads
        its last wave, so no trace error reads as "no analysis"."""
        try:
            return _plan_gb_of(*self._first_wave(
                params, data, n_samples, key, n_epochs, wave_size,
                per_shard=True))
        except Exception as e:
            return float("inf") if is_oom_error(e) else None

    # ------------------------------------------------------------------
    def auto_wave_size(self, params, data, n_samples, key=None,
                       n_epochs: int = 1,
                       budget_gb: Optional[float] = None) -> Optional[int]:
        """Largest wave size whose XLA static memory plan fits the
        device budget: size waves from the compiler's own plan instead
        of running programs until one does not fit. Compiles wave
        kernels (cached persistently) but never executes them.

        Returns ``None`` when the full cohort fits as one wave, else
        the halved-until-it-fits wave size (a multiple of the wave
        unit). Raises ``RuntimeError`` when no wave down to one wave
        unit fits, and ``NotImplementedError`` for robust aggregators
        (their per-client-params-stacking kernel has a different, much
        larger footprint than the weighted-sums kernel this probes —
        sizing from the wrong kernel would admit waves that OOM; set
        wave_size explicitly there). When the backend surfaces no
        memory analysis (some CPU configs), the full cohort is assumed
        to fit — matching the pre-auto behavior. ``budget_gb``
        overrides the per-device-kind plan budget
        (profiling.hbm_budget_gb), and is required on a device that
        table does not hold (the CPU).

        On a clients mesh the probe lowers the PER-SHARD program (each
        device executes wave/n_dev clients under shard_map,
        :meth:`wave_plan_gb`), so the plan is compared against one
        device's budget."""
        if self.aggregator[0] != "mean":
            raise NotImplementedError(
                "auto_wave_size probes the weighted-sums wave kernel; "
                f"aggregator={self.aggregator[0]!r} executes the "
                "per-client-params-stacking kernel with a different "
                "footprint — pass an explicit wave_size")
        if budget_gb is None:
            # every device of a mesh is of one kind; without a mesh the
            # round runs on the default device
            budget_gb = hbm_budget_gb(
                self.mesh.devices.flat[0] if self.mesh is not None
                else jax.devices()[0])
        if key is None:
            key = jax.random.key(0)
        n_samples = jnp.asarray(n_samples)
        unit = self._clients_per_wave_unit()
        full = w = round_up(int(n_samples.shape[0]), unit)
        while True:
            plan = self.wave_plan_gb(params, data, n_samples, key,
                                     wave_size=w, n_epochs=n_epochs)
            if plan is None or plan <= budget_gb:
                break
            if w <= unit:
                raise RuntimeError(
                    f"no wave size down to {unit} fits the "
                    f"{budget_gb:.1f} GiB plan budget (smallest plan "
                    f"{plan:.1f} GiB) — shrink the per-client batch or "
                    "dataset instead of risking an OOM")
            w = round_up(max(unit, w // 2), unit)
        return None if w >= full else w

    def run_round(
        self,
        params: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: jax.Array,
        n_epochs: int = 1,
        wave_size=None,
        server_opt_state=None,
        client_indices: Optional[np.ndarray] = None,
        collect_client_losses: bool = True,
        progress_fn=None,
    ) -> RoundResult:
        """Run one federated round; returns the new global params.

        ``client_indices`` selects a cohort (client sampling — the
        simulated analogue of only some registered clients acking a
        round, reference manager.py:87-92).

        ``progress_fn(waves_done, n_waves)`` is the simulated-cohort
        analogue of the worker's per-epoch hook (core/training.py):
        called on the host after each wave's device work completes.
        Costs a per-wave sync (blocks on the wave's loss scalar), so the
        host stops dispatching ahead of the device — leave unset for
        maximum-throughput runs, set it for long rounds that need
        mid-round visibility (reference utils.py:70-91 streamed
        progress; a multi-wave round is otherwise a black box).

        ``wave_size="auto"`` sizes waves from XLA's static memory plan
        (:meth:`auto_wave_size`); the decision is cached per cohort
        shape, so repeated rounds pay the plan compiles once.

        **A steady round does not wait for itself.** A round whose
        launches all hit the jit's fast path returns when its waves and
        fold are on the device's queue, with a :class:`RoundResult` of
        arrays the device has yet to fill, and the next ``run_round`` on
        this ``FedSim`` waits for it only after queueing its own programs
        (:meth:`_settle`): called back to back with the parameters fed
        forward, the device goes from one round into the next and the
        host is never more than a round ahead of it. A caller that reads
        a result, or ``last_compute``, waits there. A round in which a
        launch compiled or loaded a program (its ``cache_entries`` grew),
        or whose shape the compute tracker had not seen, waits for itself
        before it returns and leaves nothing pending: nothing is queued
        behind the first execution of a program just loaded, and
        ``compile_s`` is that round's own wall time; a shape new to the
        tracker also settles the round before it ahead of its own first
        wave, since what follows compiles. So **a device error of a
        steady round is raised where the round is settled**: in the next
        ``run_round`` (whose own round is then lost with it), at a read
        of ``last_compute``, or wherever the result is fetched; one of a
        round that settles itself is raised here. An error on the host
        is raised here, as ever, after the round before this one is
        settled, and nothing of this round stays pending.
        """
        with annotate("baton.round") as round_span:
            try:
                with annotate("baton.round.prepare"):
                    orig_params = params
                    params, frozen = self._split(params)
                    # the round's one host copy of n_samples (a fetch only
                    # where the caller brought a device array): the rows to
                    # stage here, the compute record after the sync
                    n_host = np.asarray(n_samples)
                    n_samples = jnp.asarray(n_samples)
                    if client_indices is not None:
                        with annotate("baton.round.prepare.select"):
                            idx = jnp.asarray(client_indices)
                            data = jax.tree_util.tree_map(
                                lambda a: jnp.take(a, idx, axis=0), data)
                            n_samples = jnp.take(n_samples, idx, axis=0)
                        n_host = n_host[np.asarray(client_indices)]
                    c = int(n_samples.shape[0])
                    with annotate("baton.round.prepare.keys"):
                        rngs = jax.random.split(rng, c)
                    rows, capacity = self._rows_to_stage(data, n_host)

                    if wave_size == "auto":
                        cache_key = (
                            c, n_epochs, rows,
                            tuple(sorted((k, v.shape, str(v.dtype))
                                         for k, v in data.items())),
                        )
                        cache = getattr(self, "_auto_wave_cache", None)
                        if cache is None:
                            cache = self._auto_wave_cache = {}
                        if cache_key not in cache:
                            cache[cache_key] = self.auto_wave_size(
                                orig_params, data, n_samples,
                                n_epochs=n_epochs)
                        wave_size = cache[cache_key]
                    wave_size = self._resolve_wave_size(wave_size, c)

                    robust = self.aggregator[0] != "mean"
                    if robust and self.is_hybrid:
                        raise NotImplementedError(
                            "robust aggregators need per-client params "
                            "stacked along the client axis; the hybrid "
                            "clients x model mesh shards params over "
                            "'model' — run robust rounds on a pure clients "
                            "mesh"
                        )
                    if self.is_hybrid:
                        # hybrid clients×model mesh: plain jit + GSPMD (see
                        # _place_hybrid) — shard_map would force manual TP
                        # collectives
                        params, frozen = self._place_hybrid(params, frozen)
                    program, bind = self._wave_program(n_epochs, robust)
                    in_shard = (client_sharding(self.mesh)
                                if self.mesh is not None else None)
                    n_waves = -(-c // wave_size)
                    # shapes only, no device fetch: what a round holds once
                    # for all clients, and what it holds (and folds) a client
                    param_leaves, trainable_bytes, frozen_bytes = (
                        self._param_shapes(params, frozen))
                    round_span.set_metadata(
                        clients=c, waves=n_waves, wave_size=int(wave_size),
                        frozen_bytes=frozen_bytes,
                        trainable_bytes=trainable_bytes,
                        **dict(self.model.span_attrs))
                    # the arrays a wave program is handed: both parameter
                    # trees, the wave's data, its n_samples and its keys
                    launch_leaves = (
                        param_leaves
                        + jax.tree_util.tree_structure(data).num_leaves + 2)
                    signature = (
                        c, int(wave_size), int(n_epochs), robust, rows,
                        tuple(sorted((k, tuple(v.shape), str(v.dtype))
                                     for k, v in data.items())))

                # A launch that compiles or loads its program makes this
                # round settle itself (below). The tracker knows a new
                # shape before anything is launched: such a round waits
                # for the round before it here, ahead of the compile, so
                # its own wall time is its own.
                missed = not self.compute_probe.tracker.seen(
                    "run_round", signature)
                if missed:
                    self._settle()

                psum_acc = None
                lsum_acc = None
                w_acc = None
                stacked_parts = [] if robust else None
                per_client = [] if collect_client_losses else None
                t_waves0 = time.perf_counter()
                for wave, start in enumerate(range(0, c, wave_size)):
                    stop = min(start + wave_size, c)
                    real = stop - start
                    with annotate("baton.round.stage", wave=wave, real=real,
                                  padded=wave_size - real, rows=rows,
                                  capacity=capacity):
                        d, n, r = self._stage_wave(
                            data, n_samples, rngs, start, stop, wave_size,
                            in_shard, rows)
                    with annotate("baton.round.dispatch", wave=wave):
                        args = bind(params, frozen, d, n, r)
                        with annotate("baton.round.dispatch.launch", wave=wave,
                                      leaves=launch_leaves) as launch_span:
                            entries = _cache_entries(program)
                            out = program(*args)
                            # a count that the call left larger is a call
                            # that missed the fast path: it compiled, or
                            # loaded from the compile cache (read twice and
                            # kept in no local: RUN_ROUND_FRAME_WORDS)
                            if entries is not None:
                                missed |= _cache_entries(program) > entries
                                launch_span.set_metadata(
                                    cache_entries=_cache_entries(program))
                        with annotate("baton.round.dispatch.accumulate",
                                      wave=wave):
                            if robust:
                                cp, closs = out
                                stacked_parts.append(jax.tree_util.tree_map(
                                    lambda a: a[:real], cp))
                                w_wave = n[:real].astype(jnp.float32)
                                lsum = jnp.tensordot(
                                    w_wave, closs[:real].astype(jnp.float32),
                                    axes=(0, 0))
                                wtot = jnp.sum(w_wave)
                            else:
                                psum, lsum, wtot, closs = out
                                psum_acc = (
                                    psum if psum_acc is None
                                    else _acc_tree_add(psum_acc, psum)
                                )
                            lsum_acc = (lsum if lsum_acc is None
                                        else lsum_acc + lsum)
                            w_acc = wtot if w_acc is None else w_acc + wtot
                            if per_client is not None:
                                per_client.append(closs[:real])
                        if progress_fn is not None:
                            jax.block_until_ready(lsum)
                            progress_fn(wave + 1, n_waves)

                # The fold goes to the device before the host waits: it is
                # queued behind the last wave, so the chip runs it the moment
                # the wave ends instead of idling while the host dispatches.
                with annotate("baton.round.fold") as fold_span:
                    if robust:
                        stacked = jax.tree_util.tree_map(
                            lambda *xs: jnp.concatenate(xs, axis=0),
                            *stacked_parts
                        )
                        new_params = agg.aggregate_stacked(
                            self.aggregator, stacked, n_samples, params
                        )
                        loss_history = lsum_acc / jnp.maximum(w_acc, 1e-9)
                        if self.server_optimizer is not None:
                            new_params, server_opt_state = _server_update(
                                self.server_optimizer, params, new_params,
                                server_opt_state
                            )
                    else:
                        entries = _cache_entries(self._fold_program)
                        new_params, server_opt_state, loss_history = (
                            self._fold_program(psum_acc, w_acc, lsum_acc,
                                               params, server_opt_state))
                        fold_span.set_metadata(programs=1)
                        if entries is not None:
                            missed |= (
                                _cache_entries(self._fold_program) > entries)

            finally:
                # The one wait of a steady round is for the round before
                # it, and comes after this round's waves and fold are
                # queued: the device has its next programs before the
                # running wave ends, and the host is never more than a
                # round ahead of it. A round that raised on the host
                # settles the round before it too and leaves nothing
                # pending of its own.
                self._settle()

            # --- compute record (obs/compute.py) --------------------------
            # What the record needs is kept with the loss sum, which is
            # ready when the waves are done (the fold is behind them in
            # the device's queue); _settle writes it from there. A model
            # with no FLOPs accounting is a reason string inside it.
            self._pend(lsum_acc, t_waves0, missed, signature, n_host,
                       n_epochs, c, rows)
            if missed:
                # the first execution of a program just compiled or
                # loaded: wait for it here, with nothing queued behind it
                self._settle()

            with annotate("baton.round.update"):
                if self.partition is not None:
                    new_params = self.partition.merge(new_params, frozen)
                return RoundResult(
                    params=new_params,
                    loss_history=loss_history,
                    client_losses=jnp.concatenate(per_client, axis=0)
                    if per_client
                    else None,
                    n_samples_total=w_acc,
                    server_opt_state=server_opt_state,
                )

    def lower_wave(self, params, data, n_samples, rng, n_epochs: int = 1,
                   wave_size: Optional[int] = None):
        """Lower, without running it, the program :meth:`run_round`
        dispatches for the first wave of this round (``.compile()
        .as_text()`` then holds the instruction names a device trace of
        the round shows, with their ``op_name`` scopes). The program and
        the staging of its inputs are ``run_round``'s own
        (``_wave_program``, ``_stage_wave``). Plain ``vmap`` and
        clients-mesh weighted-sums paths only."""
        robust = self.aggregator[0] != "mean"
        if robust or self.is_hybrid or wave_size == "auto":
            raise NotImplementedError(
                "lower_wave lowers the weighted-sums wave program of the "
                "single-device and clients-mesh layouts at a given wave "
                "size")
        program, args = self._first_wave(params, data, n_samples, rng,
                                         n_epochs, wave_size)
        return program.lower(*args)

    # ------------------------------------------------------------------
    # federated evaluation: sample-weighted mean loss/accuracy over the
    # client axis — the eval-side analogue of the FedAvg weighting
    # donation decided no: evaluation never owns its inputs
    @partial(jax.jit, static_argnums=(0,))  # batonlint: allow[BTL011]
    def _eval_sums_vmap(self, params, data, n_samples, rngs):
        def one(d, n, r):
            return client_eval_sums(self.model, params, d, n, r)

        sums = jax.vmap(one)(data, n_samples, rngs)
        return jax.tree_util.tree_map(jnp.sum, sums)

    def evaluate_round(
        self,
        params: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: Optional[jax.Array] = None,
        wave_size: Optional[int] = None,
    ) -> Dict[str, float]:
        """Evaluate global ``params`` on every client's local data
        (``[C, capacity, ...]`` layout) and return the example-weighted
        federation-wide ``{"loss": …, "accuracy": …}``.

        Memory scales like training's: ``wave_size`` chunks the client
        axis (host-accumulated sums — exact, the mean is associative),
        and under a mesh each wave's inputs are client-sharded so the
        vmapped forward runs shard-wise via GSPMD. Zero-sample phantom
        rows used for padding carry mask 0 and contribute nothing.
        """
        if rng is None:
            rng = jax.random.key(0)
        rows, _ = self._rows_to_stage(data, np.asarray(n_samples))
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        rngs = jax.random.split(rng, c)
        n_dev = self._clients_per_wave_unit()
        wave = round_up(wave_size if wave_size is not None else c, n_dev)
        in_shard = client_sharding(self.mesh) if self.mesh is not None else None

        totals: Dict[str, float] = {}
        for start in range(0, c, wave):
            stop = min(start + wave, c)
            d, n, r = self._stage_wave(data, n_samples, rngs, start, stop,
                                       wave, in_shard, rows)
            sums = self._eval_sums_vmap(params, d, n, r)
            for k, v in sums.items():
                totals[k] = totals.get(k, 0.0) + float(v)

        denom = max(totals.get("n", 0.0), 1.0)
        out = {"loss": totals.get("loss_sum", 0.0) / denom, "n": denom}
        if "correct_sum" in totals:
            out["accuracy"] = totals["correct_sum"] / denom
        return out

    # donation decided no: evaluation never owns its inputs
    @partial(jax.jit, static_argnums=(0,))  # batonlint: allow[BTL011]
    def _eval_sums_per_client(self, params, data, n_samples, rngs):
        def one(d, n, r):
            return client_eval_sums(self.model, params, d, n, r)

        return jax.vmap(one)(data, n_samples, rngs)  # [C]-leaved sums

    def evaluate_clients(
        self,
        params: Params,
        data: Dict[str, jax.Array],
        n_samples: jax.Array,
        rng: Optional[jax.Array] = None,
        wave_size: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Per-client evaluation + a fairness summary.

        The federation-wide mean (:meth:`evaluate_round`) hides exactly
        what non-IID federations care about: how unevenly the global
        model serves individual clients. Returns ``per_client`` arrays
        (loss, accuracy when defined, n — NaN for zero-sample clients)
        and a ``fairness`` block with mean/std plus direction-aware tail
        stats — ``worst`` and ``worst_decile`` are min/p10 for accuracy
        but max/p90 for loss, so they always describe the struggling
        clients. Waved and mesh-sharded like :meth:`evaluate_round`.
        """
        if rng is None:
            rng = jax.random.key(0)
        rows, _ = self._rows_to_stage(data, np.asarray(n_samples))
        n_samples = jnp.asarray(n_samples)
        c = int(n_samples.shape[0])
        rngs = jax.random.split(rng, c)
        n_dev = self._clients_per_wave_unit()
        wave = round_up(wave_size if wave_size is not None else c, n_dev)
        in_shard = client_sharding(self.mesh) if self.mesh is not None else None

        parts = []
        for start in range(0, c, wave):
            stop = min(start + wave, c)
            # same client-sharded placement as evaluate_round: the
            # vmapped forward partitions over the mesh via GSPMD
            d, n, r = self._stage_wave(data, n_samples, rngs, start, stop,
                                       wave, in_shard, rows)
            sums = self._eval_sums_per_client(params, d, n, r)
            parts.append(jax.tree_util.tree_map(
                lambda a: np.asarray(a[: stop - start]), sums
            ))
        sums = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *parts
        )

        n_arr = sums["n"]
        valid = n_arr > 0
        denom = np.where(valid, n_arr, 1.0)
        per_client: Dict[str, Any] = {
            "loss": np.where(valid, sums["loss_sum"] / denom, np.nan),
            "n": n_arr,
        }
        metric = "loss"
        if "correct_sum" in sums:
            per_client["accuracy"] = np.where(
                valid, sums["correct_sum"] / denom, np.nan
            )
            metric = "accuracy"
        vals = per_client[metric][valid]
        # direction-aware tail: "worst" must mean the struggling clients
        # whichever the metric — min/p10 for accuracy, max/p90 for loss
        higher_is_better = metric == "accuracy"
        if vals.size:
            worst = float(np.min(vals) if higher_is_better else np.max(vals))
            worst_decile = float(
                np.percentile(vals, 10 if higher_is_better else 90)
            )
        else:
            worst = worst_decile = float("nan")
        fairness = {
            "metric": metric,
            "mean": float(np.mean(vals)) if vals.size else float("nan"),
            "std": float(np.std(vals)) if vals.size else float("nan"),
            "worst": worst,
            "worst_decile": worst_decile,
            "n_clients": int(valid.sum()),
        }
        return {"per_client": per_client, "fairness": fairness}

    # ------------------------------------------------------------------
    def run_rounds(
        self,
        params: Params,
        data,
        n_samples,
        rng: jax.Array,
        n_rounds: int,
        n_epochs: int = 1,
        checkpointer=None,
        checkpoint_every: int = 1,
        return_server_opt_state: bool = False,
        **kw,
    ):
        """Convenience loop over rounds; returns (params, loss_history list)
        — plus the final FedOpt server optimizer state when
        ``return_server_opt_state`` is set, so chained calls continue the
        server optimizer instead of silently resetting its moments.

        With a :class:`baton_tpu.utils.checkpoint.Checkpointer` the loop
        saves params/server-opt-state/history every ``checkpoint_every``
        rounds and resumes from the latest step on restart. Per-round
        rngs come from ``fold_in(rng, round_idx)`` so a resumed run
        replays the identical randomness it would have had uninterrupted.
        """
        history = []
        server_opt_state = kw.pop("server_opt_state", None)
        start = 0
        if checkpointer is not None:
            restored = checkpointer.restore(
                params,
                server_opt_template=self.init_server_opt_state(params),
            )
            if restored is not None:
                params = restored.params
                server_opt_state = restored.server_opt_state
                history = list(restored.meta.get("loss_history", []))
                start = restored.step
        for i in range(start, n_rounds):
            res = self.run_round(
                params,
                data,
                n_samples,
                jax.random.fold_in(rng, i),
                n_epochs=n_epochs,
                server_opt_state=server_opt_state,
                **kw,
            )
            params = res.params
            server_opt_state = res.server_opt_state
            history.extend(np.asarray(res.loss_history).tolist())
            if checkpointer is not None and (i + 1) % checkpoint_every == 0:
                # history items are already Python floats (np tolist)
                checkpointer.save(
                    i + 1,
                    params,
                    server_opt_state=server_opt_state,
                    meta={"loss_history": history},
                )
        if return_server_opt_state:
            return params, history, server_opt_state
        return params, history


def _tree_bytes(tree) -> int:
    """Bytes of the arrays of ``tree`` (0 for ``None``), from shapes."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


# The model-sized accumulator of the wave loop: the previous
# partial sum is donated into the add, so the loop holds ONE psum buffer
# instead of two (old + new) at the accumulation point. Safe by
# construction — the donated array is the previous wave's kernel output,
# owned solely by the loop and rebound immediately.
@partial(jax.jit, donate_argnums=(0,))
def _acc_tree_add(acc, delta):
    return agg.tree_add(acc, delta)


def _fold_mean(server_optimizer, psum, wtot, lsum, params, server_opt_state):
    """The mean round's fold, pure: the weighted sums over their weight
    (a float32 divide, cast to the parameter's dtype), the loss history,
    and the FedOpt step where there is a server optimizer (its state
    made there when the caller brought none). Returns ``(new_params,
    server_opt_state, loss_history)``. Traced inside
    :meth:`FedSim.run_round`'s fold program."""
    denom = jnp.maximum(wtot, 1e-9)
    aggregate = jax.tree_util.tree_map(
        lambda s, ref: (s / denom).astype(ref.dtype), psum, params
    )
    if server_optimizer is not None:
        aggregate, server_opt_state = _server_update(
            server_optimizer, params, aggregate, server_opt_state)
    return aggregate, server_opt_state, lsum / denom


def _server_update(server_optimizer, params, aggregate, opt_state):
    """FedOpt: pseudo-gradient = global − aggregate, fed to optax
    (``opt_state`` None: the optimizer's initial state for ``params``).
    With optax.sgd(1.0) this reduces exactly to FedAvg assignment."""
    if opt_state is None:
        opt_state = server_optimizer.init(params)
    pseudo_grad = jax.tree_util.tree_map(
        lambda g, a: (g.astype(jnp.float32) - a.astype(jnp.float32)).astype(g.dtype),
        params,
        aggregate,
    )
    updates, opt_state = server_optimizer.update(pseudo_grad, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    return new_params, opt_state
