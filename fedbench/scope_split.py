"""Look at a kept trace, or at the wave program's compiled text, by hand.

    python3 fedbench/run.py --workload <cell> --seed 1 --trace 1 \\
        --keep-trace DIR
    python3 fedbench/scope_split.py --trace DIR/<cell>.xplane.pb [--hlo wave.txt]
    python3 fedbench/scope_split.py --hlo-of <cell> --seed 1 --out wave.txt

``--trace`` prints, as one JSON object on the last line, what
``trace_reduce.reduce_rows`` makes of the trace and every layer metric
reads: per device the idle time by host span and the wave program by
phase, part, block and category of its ops' scopes; for the host each
span's own time and summed attributes (``op_s``, thousands of ops, is
left out). ``--hlo-of`` writes the compiled text of the wave program
``run.py`` runs in a cell, which is where to look for what a fusion
decision did (PERF.md section 7). ``--hlo`` joins that text's
``op_name``s to ops whose event names no scope (a trace read from a
program cached before its scopes were added). Neither needs the other;
only ``--hlo-of`` needs the cell's devices.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedbench import manifest, trace_reduce  # noqa: E402

_HLO_OP_NAME = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[^\s=]+) = .*?metadata=\{[^}]*?"
    r'op_name="(?P<scope>[^"]*)"', re.M)


def scopes_from_hlo(text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled module's text."""
    return {m.group("name"): m.group("scope")
            for m in _HLO_OP_NAME.finditer(text)}


def join_scopes(rows: list, hlo_scopes: dict) -> int:
    """Give each op row without a scope the ``op_name`` of the
    instruction of the same name; returns how many rows got one."""
    joined = 0
    for row in rows:
        if (row["line"] == trace_reduce.OP_LINE and not row.get("scope")
                and row["name"] in hlo_scopes):
            row["scope"] = hlo_scopes[row["name"]]
            joined += 1
    return joined


def hlo_of(cell: str, seed: int, rehearse_cpu: bool = False) -> str:
    """The compiled text of the wave program ``run.py`` runs in ``cell``:
    ``run.py::build_cell``, then ``FedSim.lower_wave(...).compile()
    .as_text()``. Needs the cell's devices: instruction names are the
    compiler's, for the device it compiles for."""
    import jax

    from fedbench.run import build_cell, job_of

    root = manifest.ROOT
    bench = manifest.load_manifest(root)
    entry = manifest.cell_entry(bench, cell)
    config = manifest.load_config(root, bench, entry["config"])
    job = job_of(manifest.load_workload(root, cell), rehearse_cpu)
    _, params, n_samples, _, data, _, sim = build_cell(
        root, config, job, entry["chips"], seed, rehearse_cpu)
    return sim.lower_wave(params, data, n_samples, jax.random.key(seed + 2),
                          job["local_epochs"], job["wave_size"]
                          ).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", metavar="XPLANE_PB",
                    help="a trace kept by run.py --keep-trace")
    ap.add_argument("--hlo", metavar="TXT",
                    help="the wave program's compiled text (from --hlo-of), "
                         "for ops whose event names no scope")
    ap.add_argument("--hlo-of", metavar="CELL",
                    help="write the compiled text of CELL's wave program")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", metavar="TXT")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="with --hlo-of: tiny sizes on any platform")
    args = ap.parse_args(argv)
    if bool(args.trace) == bool(args.hlo_of):
        ap.error("give one of --trace and --hlo-of")
    if args.hlo_of:
        if not args.out:
            ap.error("--hlo-of needs --out")
        text = hlo_of(args.hlo_of, args.seed, args.rehearse_cpu)
        with open(args.out, "w") as f:
            f.write(text)
        print(f"[scope_split] {len(scopes_from_hlo(text))} instructions with "
              f"an op_name written to {args.out}", flush=True)
        return 0
    names = manifest.load_trace_names(manifest.ROOT)
    rows = trace_reduce.read_events(args.trace, names["span_prefixes"])
    joined = 0
    if args.hlo:
        with open(args.hlo) as f:
            joined = join_scopes(rows, scopes_from_hlo(f.read()))
    print(f"[scope_split] {len(rows)} rows; {joined} ops took their scope "
          f"from --hlo", flush=True)
    reduced = trace_reduce.reduce_rows(
        rows, manifest.load_op_categories(manifest.ROOT), names)
    if reduced is None:
        print(json.dumps({"error": "no device plane or no fedbench.round "
                                   "span in this trace"}), flush=True)
        return 1
    for device in reduced["devices"].values():
        del device["op_s"]
    print(json.dumps(reduced), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
