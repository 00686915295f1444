"""The declarative partition layer (parallel/partition.py): rule
ordering, unmatched fallback + counter, regex matching over nested and
LoRA paths, NamedSharding placement round-trips, leaf-for-leaf equality
with the recorded pre-unification specs, and the repo-wide ban on
ad-hoc ``PartitionSpec`` construction outside the one module."""

import ast
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from baton_tpu.parallel.partition import (
    CLIENT_AXIS,
    MODEL_AXIS,
    DEFAULT_RULE_SETS,
    Rule,
    RuleSet,
    client_spec,
    client_stacked_rules,
    match_partition_rules,
    replicated_spec,
    reset_unmatched_leaf_count,
    transformer_rules,
    unmatched_leaf_count,
)


def _mesh(n, axis=CLIENT_AXIS):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (axis,))


def test_first_match_wins_ordering():
    """Rules apply in table order — a later, more specific pattern never
    fires once an earlier one matched, so precedence is the author's
    explicit ordering, not regex specificity."""
    leaf = jnp.zeros((8, 4))
    broad = Rule(r"w", PartitionSpec(MODEL_AXIS, None))
    narrow = Rule(r"(^|/)w1$", PartitionSpec(None, MODEL_AXIS))
    assert RuleSet("broad-first", (broad, narrow)).spec_for(
        "blk/w1", leaf) == PartitionSpec(MODEL_AXIS, None)
    assert RuleSet("narrow-first", (narrow, broad)).spec_for(
        "blk/w1", leaf) == PartitionSpec(None, MODEL_AXIS)


def test_ndim_constraint_disambiguates_same_name():
    """An ``ndim``-constrained rule skips leaves of other ranks, so the
    stacked-expert [E, D, F] and plain 2-D variants of one leaf name
    coexist in a single ordered table (the MoE w_gate case)."""
    rs = transformer_rules()
    stacked = jnp.zeros((4, 8, 16))   # [E, D, F] stacked experts
    plain = jnp.zeros((8, 16))
    assert rs.spec_for("moe/w_gate", stacked) == PartitionSpec(
        MODEL_AXIS, None, None)
    assert rs.spec_for("moe/w_gate", plain) == PartitionSpec(
        None, MODEL_AXIS)


def test_unmatched_leaf_falls_back_replicated_and_counts():
    """A leaf no rule matches replicates (correct, just not sharded) and
    bumps the module counter CI asserts on; scalars replicate silently —
    they are never sharded, so they are not coverage gaps."""
    rs = RuleSet("partial", (Rule(r"(^|/)w$", PartitionSpec(CLIENT_AXIS)),))
    reset_unmatched_leaf_count()
    specs = rs.tree_specs({"w": jnp.zeros((8, 2)),
                           "stray": jnp.zeros((8,)),
                           "step": jnp.zeros(())})
    assert specs["w"] == PartitionSpec(CLIENT_AXIS)
    assert specs["stray"] == replicated_spec()
    assert specs["step"] == replicated_spec()
    assert unmatched_leaf_count() == 1  # stray only; the scalar is free
    reset_unmatched_leaf_count()
    assert unmatched_leaf_count() == 0


def test_default_tables_cover_model_zoo_params():
    """The shipped rule tables leave no unmatched leaves on real model
    params (each ends in a catch-all) — the coverage invariant the
    UNMATCHED counter exists to police."""
    from baton_tpu.models.llama import LlamaConfig, llama_lm_model

    model = llama_lm_model(LlamaConfig.tiny())
    params = model.init(jax.random.key(0))
    reset_unmatched_leaf_count()
    for make in DEFAULT_RULE_SETS.values():
        make().tree_specs(params)
    assert unmatched_leaf_count() == 0


#: the specs the pre-unification ``transformer_tp_spec`` produced,
#: recorded once before the per-path implementations were deleted
LEGACY_SPECS = pathlib.Path(__file__).with_name("legacy_partition_specs.json")


def _family_params(family):
    """The param tree the legacy baseline was recorded from (same tiny
    config, same init key)."""
    from baton_tpu.models.bert import BertConfig, bert_classifier_model
    from baton_tpu.models.llama import (
        LlamaConfig,
        llama_lm_model,
        llama_lora_target,
    )
    from baton_tpu.models.lora import lora_wrap
    from baton_tpu.models.moe import MoEConfig

    model = {
        "llama_tiny": lambda: llama_lm_model(LlamaConfig.tiny()),
        "llama_tiny_moe": lambda: llama_lm_model(
            LlamaConfig.tiny(moe=MoEConfig(n_experts=4, top_k=2))),
        "bert_tiny": lambda: bert_classifier_model(BertConfig.tiny()),
        "llama_tiny_lora": lambda: lora_wrap(
            llama_lm_model(LlamaConfig.tiny()), rank=4,
            target=llama_lora_target),
    }[family]()
    return model.init(jax.random.key(0))


@pytest.mark.parametrize(
    "family",
    ["llama_tiny", "llama_tiny_moe", "bert_tiny", "llama_tiny_lora"])
def test_unified_rules_reproduce_the_recorded_legacy_specs(family):
    """The unified rule tables give every leaf of the recorded model
    families the PartitionSpec the deleted per-path implementations
    gave it, and none falls through to the unmatched-replicated
    fallback — a diverged leaf is a silently different sharding."""
    legacy = json.loads(LEGACY_SPECS.read_text())
    assert str(client_spec()) == legacy["client_axis_spec"]
    assert str(replicated_spec()) == legacy["replicated_spec"]
    reset_unmatched_leaf_count()
    got = transformer_rules().describe(_family_params(family))
    want = legacy["families"][family]
    diverged = {path: (want.get(path), got.get(path))
                for path in sorted(set(want) | set(got))
                if want.get(path) != got.get(path)}
    assert not diverged, f"{family}: {{path: (legacy, unified)}} {diverged}"
    assert unmatched_leaf_count() == 0


def test_transformer_rules_over_nested_and_lora_paths():
    """Patterns anchor on the final path component, so nesting depth is
    irrelevant — and LoRA adapter factors (paths ending ``/a``, ``/b``)
    fall to the replicated catch-all, never onto the model axis (they
    are per-client state riding the clients axis)."""
    rs = transformer_rules()
    w2 = jnp.zeros((16, 8))
    tree = {
        "blocks": {"b0": {"attn": {"wq": jnp.zeros((8, 8))},
                          "mlp": {"w1": jnp.zeros((8, 16)), "w2": w2},
                          "lora": {"wq": {"a": jnp.zeros((8, 4)),
                                          "b": jnp.zeros((4, 8))}}}},
        "tok_emb": jnp.zeros((64, 8)),
    }
    reset_unmatched_leaf_count()
    d = rs.describe(tree)
    assert d["blocks/b0/attn/wq"] == str(PartitionSpec(None, MODEL_AXIS))
    assert d["blocks/b0/mlp/w1"] == str(PartitionSpec(None, MODEL_AXIS))
    assert d["blocks/b0/mlp/w2"] == str(PartitionSpec(MODEL_AXIS, None))
    assert d["tok_emb"] == str(PartitionSpec(MODEL_AXIS, None))
    assert d["blocks/b0/lora/wq/a"] == str(replicated_spec())
    assert d["blocks/b0/lora/wq/b"] == str(replicated_spec())
    assert unmatched_leaf_count() == 0


def test_match_partition_rules_entry_point():
    """The SNIPPETS-idiom sugar: ordered (regex, spec) pairs straight to
    a spec pytree, structure preserved."""
    params = {"enc": {"kernel": jnp.zeros((8, 8)),
                      "bias": jnp.zeros((8,))},
              "head": {"kernel": jnp.zeros((8, 2))}}
    specs = match_partition_rules(
        [(r"head/kernel", PartitionSpec(None, MODEL_AXIS)),
         (r"kernel", PartitionSpec(MODEL_AXIS, None)),
         (r".*", PartitionSpec())],
        params)
    assert specs["head"]["kernel"] == PartitionSpec(None, MODEL_AXIS)
    assert specs["enc"]["kernel"] == PartitionSpec(MODEL_AXIS, None)
    assert specs["enc"]["bias"] == PartitionSpec()


def test_named_sharding_round_trip_single_device_mesh():
    """place() on a 1-device mesh (the CPU-CI shape): values bitwise
    intact, every leaf carrying a NamedSharding whose spec is the rule
    outcome — the layout jit inherits via in_shardings."""
    mesh = _mesh(1)
    rs = client_stacked_rules()
    params = {"w": jnp.arange(24, dtype=jnp.float32).reshape(8, 3),
              "b": jnp.ones((8,))}
    placed = rs.place(params, mesh)
    for k in params:
        np.testing.assert_array_equal(np.asarray(placed[k]),
                                      np.asarray(params[k]))
        s = placed[k].sharding
        assert isinstance(s, NamedSharding)
        assert s.spec == PartitionSpec(CLIENT_AXIS)
    shardings = rs.shardings(params, mesh)
    out = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: 2.0 * x, t),
                  in_shardings=(shardings,))(placed)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  2.0 * np.asarray(params["w"]))


def test_indivisible_leaf_falls_back_replicated_on_mesh():
    """The divisibility safety valve: a spec whose sharded dim does not
    divide the mesh axis placates to replicated instead of erroring —
    and only on meshes where it actually cannot split."""
    rs = client_stacked_rules()
    odd = jnp.zeros((6, 3))  # 6 % 8 != 0 on the full host mesh
    assert rs.leaf_sharding("odd", odd, _mesh(8)).spec == replicated_spec()
    assert rs.leaf_sharding("odd", odd, _mesh(2)).spec == PartitionSpec(
        CLIENT_AXIS)


def _partition_spec_calls(path: pathlib.Path):
    """(line, source) of every PartitionSpec construction in a file —
    direct calls, attribute calls, and any ``import ... as`` alias."""
    tree = ast.parse(path.read_text())
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == "PartitionSpec":
                    aliases.add(a.asname or a.name)
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if ((isinstance(f, ast.Name) and f.id in aliases | {"PartitionSpec"})
                or (isinstance(f, ast.Attribute)
                    and f.attr == "PartitionSpec")):
            hits.append(node.lineno)
    return hits


def test_no_ad_hoc_partition_spec_outside_partition_module():
    """parallel/partition.py is the ONE place PartitionSpecs are built;
    everywhere else routes through its helpers/tables so a layout change
    is a table edit, not a grep hunt. (Imports for type annotations are
    fine — construction is what's banned.)"""
    pkg = pathlib.Path(__file__).resolve().parent.parent / "baton_tpu"
    offenders = []
    for py in sorted(pkg.rglob("*.py")):
        if py.relative_to(pkg).as_posix() == "parallel/partition.py":
            continue
        offenders += [f"{py.relative_to(pkg)}:{ln}"
                      for ln in _partition_spec_calls(py)]
    assert not offenders, (
        "ad-hoc PartitionSpec construction outside parallel/partition.py "
        f"(use its spec helpers / rule tables): {offenders}")
