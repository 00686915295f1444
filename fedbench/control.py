"""The control of ``correct``, read beside the program, at a cell's own
probe size.

    python3 fedbench/control.py --workload <cell> --seeds 11 12 13

For each seed: the probe round of ``run.py`` (``FedSim`` against the
configuration's plain float32 reference) and the control (the same
reference with every matrix product's operands rounded to float8,
against itself in float32, through the same comparison). The program has to stay under the configuration's
``probe_tolerance`` and the control over it. The benchmark's own runs do
not run this; PERF.md section 2 has the readings the tolerance stands
between. The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedbench import manifest, reference, run  # noqa: E402


def readings(root: str, cell: str, seed: int, tiny: bool) -> dict:
    """``{"program": {...}, "control": {...}}``: update disagreements
    of one seed (``reference.update_disagreement``)."""
    import jax
    import jax.numpy as jnp

    bench = manifest.load_manifest(root)
    entry = manifest.cell_entry(bench, cell)
    config = manifest.load_config(root, bench, entry["config"])
    job = run.job_of(manifest.load_workload(root, cell), tiny)
    _, params, _, _, _, mesh, sim = run.build_cell(
        root, config, job, entry["chips"], seed, tiny)
    _, compared = run.probe(root, config, job, tiny, seed, sim, params, mesh)
    program = {name: value for name, (value, _) in compared.items()}
    pdata, sizes = run.probe_cohort(root, config, job, tiny, seed)
    make_loss = manifest.load_module(
        root, "references", entry["config"]).make_loss
    sizes_of = manifest.sized(config, tiny)
    trainable = manifest.engine_args(config, job).get("trainable")
    rounds = [reference.reference_round(
        fn, params, pdata, sizes, job["learning_rate"], trainable)[0]
        for fn in (make_loss(sizes_of),
                   make_loss(sizes_of, reference.rounded_to(
                       jnp.float8_e4m3fn)))]
    control = {norm: reference.update_disagreement(
        params, rounds[1], rounds[0], norm, trainable)
        for norm in ("max", "l2")}
    jax.clear_caches()  # every seed builds its programs anew: let them go
    return {"program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    root = manifest.ROOT
    if not args.rehearse_cpu:
        run.configure_cache(root)
    out = {"workload": args.workload, "seeds": args.seeds, "readings": []}
    for seed in args.seeds:
        out["readings"].append(readings(root, args.workload, seed,
                                        args.rehearse_cpu))
        run.say(f"seed {seed}: {out['readings'][-1]}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
