"""Plan + partition probes for the FedSim wave kernels.

Two modes:

``--specs`` — spec-equality gate for the unified partition layer.
Rebuilds the four recorded model families (llama_tiny, llama_tiny_moe,
bert_tiny, llama_tiny_lora), runs
:func:`baton_tpu.parallel.partition.transformer_rules` over each param
tree, and compares every leaf's PartitionSpec against
``benchmarks/baselines/legacy_partition_specs.json`` — the specs the
pre-unification ``transformer_tp_spec`` produced, recorded once before
the per-path implementations were deleted. Any diverging leaf (or any
leaf falling through to the unmatched-replicated fallback) is a
regression: exits nonzero and writes the full per-leaf report to
``--out`` (CI uploads it as the ``plan-probe`` artifact).

Default (no flag) — XLA's static memory plan for the direct-conv
FedSim wave-32/64 kernels, component by component (args, outputs,
temps, aliases), to set beside what the runtime reserves when the same
kernel runs (``device.memory_stats()``; PR 21 chip run, v5e: wave-32
plans 14.95 GiB, the runtime reserved 13.49 GiB). The anchored guard
tier (profiling.ANCHORED_DIRECT_CONV_BUDGET_GB, ROADMAP D13) rests on
that overcount.

Measures EXACTLY the kernel the sweep/guard protect: the workload comes
from wave_sweep.build_benchmark_fedsim and the byte accounting from
profiling.plan_breakdown_gb — the same code paths, not copies.

Prints one JSON line per kernel; compiles only, never executes the
programs. A kernel that fails to compile fails the probe.
"""

from __future__ import annotations

import json
import os
import sys
import time

# runnable as `python benchmarks/plan_probe.py` without an installed
# package: the repo root is one level up
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)


_LEGACY_SPECS = os.path.join(
    _REPO, "benchmarks", "baselines", "legacy_partition_specs.json"
)


def _family_params():
    """The exact four param trees the legacy baseline was recorded from
    (same tiny configs, same init key)."""
    import jax

    from baton_tpu.models.bert import BertConfig, bert_classifier_model
    from baton_tpu.models.llama import (
        LlamaConfig,
        llama_lm_model,
        llama_lora_target,
    )
    from baton_tpu.models.lora import lora_wrap
    from baton_tpu.models.moe import MoEConfig

    rng = jax.random.key(0)
    return {
        "llama_tiny": llama_lm_model(LlamaConfig.tiny()).init(rng),
        "llama_tiny_moe": llama_lm_model(
            LlamaConfig.tiny(moe=MoEConfig(n_experts=4, top_k=2))
        ).init(rng),
        "bert_tiny": bert_classifier_model(BertConfig.tiny()).init(rng),
        "llama_tiny_lora": lora_wrap(
            llama_lm_model(LlamaConfig.tiny()), rank=4,
            target=llama_lora_target,
        ).init(rng),
    }


def specs_report() -> dict:
    """Compare unified-RuleSet specs against the recorded legacy specs.

    Returns the full report dict; ``report["diverged"]`` is the flat
    list of mismatches (empty == the refactor preserved every layout).
    """
    from baton_tpu.parallel import partition as pt

    with open(_LEGACY_SPECS) as f:
        legacy = json.load(f)

    rules = pt.transformer_rules()
    pt.reset_unmatched_leaf_count()
    report = {
        "baseline": os.path.relpath(_LEGACY_SPECS, _REPO),
        "rule_set": rules.name,
        "client_axis_spec": {
            "legacy": legacy["client_axis_spec"],
            "unified": str(pt.client_spec()),
        },
        "replicated_spec": {
            "legacy": legacy["replicated_spec"],
            "unified": str(pt.replicated_spec()),
        },
        "families": {},
        "diverged": [],
    }
    for scope in ("client_axis_spec", "replicated_spec"):
        if report[scope]["legacy"] != report[scope]["unified"]:
            report["diverged"].append(
                {"family": "<axis>", "path": scope, **report[scope]}
            )

    for fam, params in _family_params().items():
        want = legacy["families"][fam]
        got = rules.describe(params)
        fam_rec = {"leaves": len(got), "matched": 0}
        for path in sorted(set(want) | set(got)):
            if want.get(path) != got.get(path):
                report["diverged"].append({
                    "family": fam, "path": path,
                    "legacy": want.get(path), "unified": got.get(path),
                })
            else:
                fam_rec["matched"] += 1
        report["families"][fam] = fam_rec

    report["unmatched_leaves"] = pt.unmatched_leaf_count()
    report["ok"] = (
        not report["diverged"] and report["unmatched_leaves"] == 0
    )
    return report


def main_specs(out_path: str) -> int:
    report = specs_report()
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    total = sum(v["leaves"] for v in report["families"].values())
    print(
        f"plan_probe --specs: {total} leaves over "
        f"{len(report['families'])} families, "
        f"{len(report['diverged'])} diverged, "
        f"{report['unmatched_leaves']} unmatched -> {out_path}",
        flush=True,
    )
    for d in report["diverged"][:20]:
        print(f"  DIVERGED {d['family']}:{d['path']}: "
              f"legacy={d['legacy']} unified={d['unified']}")
    return 0 if report["ok"] else 1


def main() -> None:
    import jax

    from baton_tpu.utils.profiling import (
        _lower_wave_kernel,
        enable_compile_cache,
        plan_breakdown_gb,
    )
    from wave_sweep import build_benchmark_fedsim

    enable_compile_cache()
    dev = jax.devices()[0]
    sim, params, data, n_samples, key = build_benchmark_fedsim()

    for w in (32, 64):
        t0 = time.perf_counter()
        rec = {"kernel": f"resnet18_bf16_wave{w}_b32_spc48",
               "platform": dev.platform,
               "device_kind": getattr(dev, "device_kind", dev.platform)}
        jitted, args = _lower_wave_kernel(sim, params, data, n_samples,
                                          key, wave_size=w)
        rec.update(plan_breakdown_gb(jitted, args))
        rec["compile_s"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--specs", action="store_true",
                    help="spec-equality gate vs the recorded legacy "
                         "partition specs (exits nonzero on divergence)")
    ap.add_argument("--out", default="artifacts/plan_probe.json",
                    help="report path for --specs mode")
    ns = ap.parse_args()
    if ns.specs:
        sys.exit(main_specs(ns.out))
    main()
