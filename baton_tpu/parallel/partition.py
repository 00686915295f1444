"""One declarative sharding layer for every parallel path.

Sharding decisions used to live in three places — the client-axis
helpers in ``parallel/mesh.py``, the Megatron-style per-leaf heuristics
in ``parallel/tensor_parallel.py``, and the hybrid clients×model code in
``parallel/engine.py``. This module unifies them behind one mechanism:
an ordered table of ``(regex, PartitionSpec)`` rules matched against the
param pytree's slash-joined key paths (core/partition.py:path_str),
producing ``NamedSharding``s for any mesh.

Matching is first-match-wins over the ordered rules; a rule may further
constrain the leaf rank (``ndim``) so e.g. stacked MoE expert weights
``[E, D, F]`` and a plain 2-D ``w_gate`` get different specs under the
same name. Scalar leaves are always replicated. Leaves no rule matches
fall back to replicated and bump a module-level warning counter so CI
tests can assert complete coverage. A spec whose sharded dims don't
divide the mesh axis sizes also falls back to replicated (correct, just
not sharded) — the same safety valve the old per-leaf heuristics had.

Every other ``parallel/`` module builds its specs from the helpers here
(``replicated_spec`` / ``client_spec`` / ``dim_spec``);
``tests/test_partition_rules.py`` enforces that no ``PartitionSpec`` is
constructed ad hoc outside this file.
"""

from __future__ import annotations

import dataclasses
import logging
import re
import threading
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from baton_tpu.core.partition import path_str

logger = logging.getLogger(__name__)

Params = Any

# Mesh axis names — defined HERE (the root of the parallel/ import
# graph); mesh.py and tensor_parallel.py re-export them for back-compat.
CLIENT_AXIS = "clients"
MODEL_AXIS = "model"


# ---------------------------------------------------------------------------
# spec helpers — the only sanctioned PartitionSpec constructors
# ---------------------------------------------------------------------------

def replicated_spec() -> PartitionSpec:
    """Fully-replicated spec (the global model each round)."""
    return PartitionSpec()


def client_spec(axis: str = CLIENT_AXIS) -> PartitionSpec:
    """``[C, ...]`` stacked client arrays: dim 0 over the client axis."""
    return PartitionSpec(axis)


def dim_spec(axis: str, dim: int, ndim: int) -> PartitionSpec:
    """Shard a single dimension ``dim`` of an ``ndim``-rank array over
    ``axis`` — e.g. ``dim_spec('seq', 2, 4)`` for [B, H, L, Dh]
    sequence-sharded attention blocks."""
    if not 0 <= dim < ndim:
        raise ValueError(f"dim {dim} out of range for ndim {ndim}")
    return PartitionSpec(*(axis if i == dim else None for i in range(ndim)))


def axes_spec(*axes: Optional[str]) -> PartitionSpec:
    """General escape hatch: PartitionSpec(*axes), so callers with a
    genuinely bespoke layout still route construction through here."""
    return PartitionSpec(*axes)


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    """One ordered sharding rule.

    ``pattern`` is an uncompiled regex ``re.search``-ed against the
    slash-joined tree path; ``ndim``, when given, additionally requires
    the leaf rank to match (so stacked-expert and plain variants of the
    same leaf name can coexist in one table).
    """

    pattern: str
    spec: PartitionSpec
    ndim: Optional[int] = None

    def matches(self, path: str, leaf: Any) -> bool:
        if self.ndim is not None and getattr(leaf, "ndim", None) != self.ndim:
            return False
        return re.search(self.pattern, path) is not None


class _UnmatchedCounter:
    """Thread-safe counter of leaves that fell through every rule."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._count = 0

    def bump(self, rule_set: str, path: str) -> None:
        with self._lock:
            self._count += 1
        logger.warning(
            "partition: no rule in %r matched leaf %r; replicating", rule_set, path
        )

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def reset(self) -> None:
        with self._lock:
            self._count = 0


#: Module-level tally of unmatched leaves across every RuleSet — tests
#: assert it stays at zero for the shipped rule tables.
UNMATCHED = _UnmatchedCounter()


def unmatched_leaf_count() -> int:
    return UNMATCHED.count


def reset_unmatched_leaf_count() -> None:
    UNMATCHED.reset()


def _is_scalar(leaf: Any) -> bool:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return True
    n = 1
    for d in shape:
        n *= d
    return len(shape) == 0 or n == 1


def _divisible(leaf: Any, spec: PartitionSpec, mesh: Mesh) -> bool:
    """Can ``leaf`` actually be split per ``spec`` on ``mesh``? Each
    sharded dim must divide the product of its mesh axis sizes."""
    for dim, names in zip(leaf.shape, spec):
        if names is None:
            continue
        axes = names if isinstance(names, tuple) else (names,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if dim % size:
            return False
    return True


@dataclasses.dataclass(frozen=True)
class RuleSet:
    """A named, ordered rule table — the declarative partition config.

    ``name`` is what ``FedSim.partition_rule_set`` reports, so a record
    can name the sharding policy that produced it.
    """

    name: str
    rules: Tuple[Rule, ...]

    def spec_for(self, path: str, leaf: Any) -> PartitionSpec:
        """First-match-wins spec for one leaf. Scalars are always
        replicated; unmatched leaves replicate and bump ``UNMATCHED``."""
        if _is_scalar(leaf):
            return replicated_spec()
        for rule in self.rules:
            if rule.matches(path, leaf):
                return rule.spec
        UNMATCHED.bump(self.name, path)
        return replicated_spec()

    def leaf_sharding(self, path: str, leaf: Any, mesh: Mesh) -> NamedSharding:
        """NamedSharding for one leaf, with the divisibility fallback."""
        spec = self.spec_for(path, leaf)
        if spec != replicated_spec() and not _divisible(leaf, spec, mesh):
            spec = replicated_spec()
        return NamedSharding(mesh, spec)

    def tree_specs(self, params: Params) -> Params:
        """The PartitionSpec pytree for ``params`` (mesh-independent —
        no divisibility fallback applied)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree_util.tree_unflatten(
            treedef, [self.spec_for(path_str(p), leaf) for p, leaf in flat]
        )

    def shardings(self, params: Params, mesh: Mesh) -> Params:
        """The NamedSharding pytree for ``params`` on ``mesh`` — usable
        as jit's ``in_shardings``/``out_shardings`` so updated params
        KEEP the layout across steps instead of decaying to replicated."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree_util.tree_unflatten(
            treedef,
            [self.leaf_sharding(path_str(p), leaf, mesh) for p, leaf in flat],
        )

    def place(self, params: Params, mesh: Mesh) -> Params:
        """Device-put ``params`` onto ``mesh`` per the rules. Any jitted
        function consuming the result inherits the layout — GSPMD
        propagates it and inserts the collectives."""
        return jax.tree_util.tree_map(
            jax.device_put, params, self.shardings(params, mesh)
        )

    def describe(self, params: Params, mesh: Optional[Mesh] = None) -> Dict[str, str]:
        """{path: spec-string} — introspection and the spec-equality
        test (tests/test_partition_rules.py). With a mesh, the
        divisibility fallback is applied (what would actually be
        placed); without, the raw rule outcome."""
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        out: Dict[str, str] = {}
        for p, leaf in flat:
            path = path_str(p)
            if mesh is not None:
                out[path] = str(self.leaf_sharding(path, leaf, mesh).spec)
            else:
                out[path] = str(self.spec_for(path, leaf))
        return out


def match_partition_rules(
    rules: Iterable[Tuple[str, PartitionSpec]],
    params: Params,
    name: str = "ad-hoc",
) -> Params:
    """The SNIPPETS-idiom entry point: ordered ``(regex, spec)`` pairs →
    PartitionSpec pytree. Sugar for ``RuleSet(...).tree_specs(...)``."""
    rs = RuleSet(name, tuple(Rule(pat, spec) for pat, spec in rules))
    return rs.tree_specs(params)


# ---------------------------------------------------------------------------
# default rule tables per model family
# ---------------------------------------------------------------------------

def transformer_rules(axis: str = MODEL_AXIS) -> RuleSet:
    """Megatron-style table for the transformer zoo (Llama swiglu,
    BERT/ViT gelu MLP, MoE, and LoRA-wrapped variants).

    Rules are anchored on the FINAL path component (``(^|/)name$``) so
    they apply uniformly at any nesting depth — but NOT to LoRA adapter
    factors, whose paths end in ``.../a`` / ``.../b`` and correctly fall
    to the replicated catch-all (adapters are per-client state riding
    the clients axis, never the model axis).

    * stacked MoE experts ``[E, D, F]``: expert dim sharded;
    * column-parallel (output features): wq/wk/wv, w_gate/w_up, w1
      (+ bias b1), lm_head;
    * row-parallel (contraction dim, where GSPMD places the Megatron
      all-reduce): wo, w_down, w2;
    * vocab-sharded embedding rows: tok_emb;
    * everything else (norms, other biases, small heads): replicated.
    """
    return RuleSet(
        name=f"transformer-tp[{axis}]",
        rules=(
            Rule(r"(^|/)(w_gate|w_up|w_down)$", PartitionSpec(axis, None, None), ndim=3),
            Rule(r"(^|/)(wq|wk|wv|w_gate|w_up|w1|lm_head)$", PartitionSpec(None, axis), ndim=2),
            Rule(r"(^|/)(wo|w_down|w2|tok_emb)$", PartitionSpec(axis, None), ndim=2),
            Rule(r"(^|/)b1$", PartitionSpec(axis), ndim=1),
            Rule(r".*", replicated_spec()),
        ),
    )


def client_stacked_rules(axis: str = CLIENT_AXIS) -> RuleSet:
    """``[C, ...]`` per-client stacked state (params/opt-state/rngs):
    every leaf rides the client axis on dim 0."""
    return RuleSet(name=f"client-stacked[{axis}]", rules=(Rule(r".*", client_spec(axis)),))


def replicated_rules() -> RuleSet:
    """Everything replicated — the broadcast global model."""
    return RuleSet(name="replicated", rules=(Rule(r".*", replicated_spec()),))


#: The default rule tables, keyed by ``RuleSet.name``.
DEFAULT_RULE_SETS: Dict[str, Callable[[], RuleSet]] = {
    "transformer-tp": transformer_rules,
    "client-stacked": client_stacked_rules,
    "replicated": replicated_rules,
}


# ---------------------------------------------------------------------------
# shard_map kernel layout table
# ---------------------------------------------------------------------------

def kernel_specs(
    name: str, axis: str = CLIENT_AXIS
) -> Tuple[Tuple[PartitionSpec, ...], Tuple[PartitionSpec, ...]]:
    """``(in_specs, out_specs)`` for every shard_map kernel in the
    algorithm paths — the one place the layouts live. The modules
    consume these verbatim (tests assert the table against the intended
    layouts, and the no-ad-hoc-PartitionSpec lint keeps construction
    out of the call sites), so a layout change is a one-line table edit
    that every path and test sees at once.

    The invariant across all kernels: per-client stacked inputs/outputs
    (data, n_samples, rngs, per-client params/opt/personal state,
    per-client losses) ride the client axis; broadcast global state
    (params, frozen leaves, shared halves) and psum-folded aggregates
    are replicated.
    """
    cli, rep = client_spec(axis), replicated_spec()
    table = {
        # (params, frozen, data, n, rngs) -> (psum, lsum, wsum, closs)
        "engine.wave_sums": ((rep, rep, cli, cli, cli),
                             (rep, rep, rep, cli)),
        # (params, frozen, data, n, rngs) -> (client_params, closs)
        "engine.wave_params": ((rep, rep, cli, cli, cli), (cli, cli)),
        # (params_stack, data, n, rngs, frozen) -> (client_params, closs)
        "fedbuff.train": ((cli, cli, cli, cli, rep), (cli, cli)),
        # (cluster_params, data, n, rngs)
        #   -> (new_cluster_params, assignments, closs)
        "clustered.round": ((rep, cli, cli, cli), (rep, cli, cli)),
        # (params, opt_states, data, n, rngs)
        #   -> (psums, new_opt_states, lsum_w_wsum, closs)
        "stateful.round": ((rep, cli, cli, cli, cli),
                           (rep, cli, rep, cli)),
        # (personal_state, shared, data, n, rngs)
        #   -> (new_pers, shared_agg, pers_mean, loss_hist, closs)
        "personalization.round": ((cli, rep, cli, cli, cli),
                                  (cli, rep, rep, rep, cli)),
    }
    return table[name]
