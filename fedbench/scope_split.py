"""Split a kept trace by the program's own spans and scopes.

    python3 fedbench/run.py --workload <cell> --seed 1 --trace 1 \\
        --keep-trace DIR
    python3 fedbench/scope_split.py --trace DIR/<cell>.xplane.pb [--hlo wave.txt]
    python3 fedbench/scope_split.py --hlo-of <cell> --seed 1 --out wave.txt

Reads what ``trace_reduce.read_events`` leaves out: the ``baton.round.*``
host spans of ``FedSim.run_round`` (with their attributes) and the scope
(``op_name``) of every device op. The last line of standard output is
one JSON object:

* ``idle_by_phase``: per device plane, the idle gaps of the traced
  window (the window and the busy/idle arithmetic of
  ``trace_reduce.reduce_device``), each cut at the edges of the
  ``baton.*``/``fedbench.*`` host spans it crosses and every piece given
  to the innermost span that holds its midpoint, in ms a round;
  ``host_ms_by_phase`` is each span's own time (its duration less its
  children's), in ms a round;
* ``wave_by_scope``: per device plane, the self time of every op of the
  wave program by phase x part, by block and by category x part, in ms
  a round, with ``unscoped_share``.

Where an op's scope comes from, first that gives one: the ``tf_op`` stat
of the event's *metadata* (the ``op_name``; read with the ``xplane_pb2``
of the installed tensorflow, ``jax.profiler.ProfileData`` does not show
metadata stats), else the ``op_name`` of the instruction of the same
name in the wave program's compiled text (``--hlo``, written by
``--hlo-of``). A fusion carries its root instruction's ``op_name``, so a
fusion's time goes to its root's scope.

This file only adds: it edits nothing of the benchmark and no metric
reads it yet (PERF.md section 7 has the work list).
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedbench import manifest  # noqa: E402
from fedbench.trace_reduce import (  # noqa: E402
    DEVICE_PLANE_PREFIX, HOST_PLANE, MODULE_LINE, OP_LINE, classify, gaps,
    merge, module_name, parse_op, self_times, wave_module)

SPAN_PREFIXES = ("baton.", "fedbench.")
HARNESS_ROUND = "fedbench.round"
PROGRAM_ROUND = "baton.round"
BETWEEN = "(between spans)"

# a scope's path is a name stack, ``jit(f)/local_train/.../grad/
# transpose(jvp(s0b1))/norm/mul``: the phase is read off JAX's own
# wrappers and the trainer's and engine's scopes, first rule that holds
PHASE_RULES = (
    ("recompute", ("rematted_computation",)),
    ("backward", ("transpose(",)),
    ("forward", ("jvp(",)),
    ("shuffle", ("/shuffle/",)),
    ("optimizer", ("/optimizer/",)),
    ("psum", ("/wave_psum/",)),
    ("wave_sums", ("/wave_sums/",)),
)
PARTS = ("stem", "conv", "norm", "shortcut", "attention", "mlp", "embed",
         "head")
OUR_SCOPES = ("local_train", "wave_sums", "wave_psum")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_BLOCK = re.compile(r"^(s\d+b\d+|block\d+|stem|head|embed)$")
_HLO_OP_NAME = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[^\s=]+) = .*?metadata=\{[^}]*?"
    r'op_name="(?P<scope>[^"]*)"', re.M)


# ------------------------------------------------------------ the scope rule
def phase_of(scope: str) -> str:
    path = f"/{scope}/"
    for phase, marks in PHASE_RULES:
        if any(m in path for m in marks):
            return phase
    return "other"


def part_of(scope: str) -> str:
    """The innermost of ``PARTS`` on the path (``stem/conv`` is ``conv``,
    ``s1b0/shortcut/norm`` is ``norm``), else ``other``."""
    found = [w for w in _WORD.findall(scope) if w in PARTS]
    return found[-1] if found else "other"


def block_of(scope: str) -> str:
    """The outermost model block on the path: ``s<stage>b<block>``,
    ``block<i>``, ``stem``, ``head``, ``embed``; else ``(none)``."""
    for w in _WORD.findall(scope):
        if _BLOCK.match(w):
            return w
    return "(none)"


def is_scoped(scope: str) -> bool:
    return any(s in scope for s in OUR_SCOPES)


def scopes_from_hlo(text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled module's text."""
    return {m.group("name"): m.group("scope")
            for m in _HLO_OP_NAME.finditer(text)}


# ---------------------------------------------------------------- reading
def _load_xplane_pb2():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:  # not installed, or it cannot load here
        return None
    return xplane_pb2


def read_rows(xplane_path: str) -> tuple:
    """``(rows, source)``. Rows as ``trace_reduce.read_events`` makes
    them, of the device planes' module and op lines and of the host
    spans under ``SPAN_PREFIXES``; a host span also has ``stats``, an op
    row ``scope`` (``""`` where the event has none). ``source`` says
    where scopes came from: ``"tf_op"`` or ``"none"``."""
    pb2 = _load_xplane_pb2()
    if pb2 is None:
        return _rows_profile_data(xplane_path), "none"
    space = pb2.XSpace()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    rows = []
    for plane in space.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}

        def stats(holder):
            out = {}
            for st in holder.stats:
                kind = st.WhichOneof("value")
                value = getattr(st, kind)
                if kind == "ref_value":
                    value = stat_names.get(value, value)
                out[stat_names.get(st.metadata_id)] = value
            return out

        op_scope = {}  # metadata id -> scope, once for each instruction
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                if not device and not md.name.startswith(SPAN_PREFIXES):
                    continue
                row = {"plane": plane.name, "line": line.name,
                       "name": md.name,
                       "start_ns": line.timestamp_ns + ev.offset_ps / 1e3,
                       "dur_ns": ev.duration_ps / 1e3}
                if not device:
                    row["stats"] = stats(ev)
                elif line.name == OP_LINE:
                    row.update(parse_op(md.name))
                    if ev.metadata_id not in op_scope:
                        tf_op = stats(md).get("tf_op", "")
                        if isinstance(tf_op, bytes):
                            tf_op = tf_op.decode("utf-8", "replace")
                        # "<op_name>:<op type>"
                        op_scope[ev.metadata_id] = tf_op.rpartition(":")[0]
                    row["scope"] = op_scope[ev.metadata_id]
                rows.append(row)
    return rows, "tf_op"


def _rows_profile_data(xplane_path: str) -> list:
    """The same rows with nothing but JAX, and so with no scope."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIXES):
                    continue
                row = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if not device:
                    row["stats"] = dict(ev.stats)
                elif line.name == OP_LINE:
                    row.update(parse_op(ev.name), scope="")
                rows.append(row)
    return rows


# --------------------------------------------------------------- reducing
def _end(row) -> float:
    return row["start_ns"] + row["dur_ns"]


def traced_window(spans: list) -> tuple:
    """``(window, n_rounds)``: as ``trace_reduce.traced_window``, from
    the first ``fedbench.round`` span's start to the last harness span's
    end; for a trace of another harness, over the ``baton.round``
    spans. ``(None, 0)`` without either."""
    harness = [r for r in spans if r["name"].startswith("fedbench.")]
    rounds = [r for r in harness if r["name"] == HARNESS_ROUND]
    if not rounds:
        harness = rounds = [r for r in spans if r["name"] == PROGRAM_ROUND]
    if not rounds:
        return None, 0
    return ((min(r["start_ns"] for r in rounds), max(map(_end, harness))),
            len(rounds))


def innermost(point: float, spans: list) -> str:
    """The name of the shortest span that holds ``point``."""
    inside = [r for r in spans if r["start_ns"] <= point <= _end(r)]
    if not inside:
        return BETWEEN
    return min(inside, key=lambda r: r["dur_ns"])["name"]


def place(gap: tuple, spans: list, edges: list):
    """``(span name, ns)`` for the pieces of one idle gap. A gap is cut
    at every span's start and end that falls inside it, and each piece
    goes to the innermost span that holds its midpoint: ``reduce_device``
    places a whole gap by its midpoint, which is this rule for a gap
    that crosses no span edge. ``edges`` is the sorted list of the
    spans' starts and ends."""
    cuts = ([gap[0]]
            + edges[bisect.bisect_right(edges, gap[0]):
                    bisect.bisect_left(edges, gap[1])]
            + [gap[1]])
    for a, b in zip(cuts, cuts[1:]):
        if b > a:
            yield innermost(0.5 * (a + b), spans), b - a


def _inside(rows: list, window: tuple) -> list:
    return [r for r in rows
            if r["start_ns"] >= window[0] and _end(r) <= window[1]]


def idle_by_phase(device_rows: list, spans: list, window: tuple,
                  n_rounds: int) -> dict:
    """One device plane's idle time by host span, ms a round."""
    ops = _inside([r for r in device_rows if r["line"] == OP_LINE], window)
    busy = merge((r["start_ns"], _end(r)) for r in ops)
    edges = sorted({t for r in spans for t in (r["start_ns"], _end(r))})
    by_span = {}
    for gap in gaps(busy, *window):
        for name, ns in place(gap, spans, edges):
            by_span[name] = by_span.get(name, 0.0) + ns
    total = sum(by_span.values())
    narrower = sum(v for k, v in by_span.items()
                   if k.startswith(PROGRAM_ROUND + "."))
    program = narrower + by_span.get(PROGRAM_ROUND, 0.0)
    return {
        "idle_ms_per_round": total / 1e6 / n_rounds,
        "ms_per_round": {k: v / 1e6 / n_rounds
                         for k, v in sorted(by_span.items(),
                                            key=lambda kv: -kv[1])},
        # of all idle time, and of the idle time inside run_round (the
        # rest is the harness's own: its spans and the time between them)
        "share_in_a_span_narrower_than_baton_round":
            narrower / total if total else None,
        "share_of_baton_round_idle_in_a_narrower_span":
            narrower / program if program else None,
    }


def host_ms_by_phase(spans: list, window: tuple, n_rounds: int) -> dict:
    """Each span name's own host time (duration less its children's)
    inside the window, ms a round."""
    out = {}
    for row, self_ns, _ in self_times(_inside(spans, window)):
        out[row["name"]] = out.get(row["name"], 0.0) + self_ns
    return {k: v / 1e6 / n_rounds for k, v in out.items()}


def _add(table: dict, key: str, value: float) -> None:
    table[key] = table.get(key, 0.0) + value


def wave_by_scope(device_rows: list, window: tuple, n_rounds: int,
                  rules: dict, hlo_scopes: dict) -> dict:
    """One device plane's wave program by scope, ms a round."""
    modules = _inside([r for r in device_rows if r["line"] == MODULE_LINE],
                      window)
    module_s = {}
    for r in modules:
        _add(module_s, module_name(r["name"]), r["dur_ns"])
    wave = wave_module({"module_s": module_s})
    runs = sorted((r["start_ns"], _end(r)) for r in modules
                  if module_name(r["name"]) == wave)
    starts = [s for s, _ in runs]

    def in_wave(row):
        i = bisect.bisect_right(starts, row["start_ns"]) - 1
        return i >= 0 and row["start_ns"] < runs[i][1]

    ops = _inside([r for r in device_rows if r["line"] == OP_LINE], window)
    phase_part, by_block, category_part = {}, {}, {}
    total = unscoped = joined = 0.0
    for row, self_ns, _ in self_times(ops):
        if not in_wave(row):
            continue
        scope = row.get("scope", "")
        if not scope and row["name"] in hlo_scopes:
            scope = hlo_scopes[row["name"]]
            joined += self_ns
        total += self_ns
        if not is_scoped(scope):
            unscoped += self_ns
        phase, part = phase_of(scope), part_of(scope)
        _add(phase_part.setdefault(phase, {}), part, self_ns)
        _add(by_block.setdefault(block_of(scope), {}), phase, self_ns)
        _add(category_part.setdefault(classify(row, rules), {}), part,
             self_ns)

    def ms(table):
        return {k: (ms(v) if isinstance(v, dict) else v / 1e6 / n_rounds)
                for k, v in table.items()}

    return {
        "wave_module": wave,
        "wave_runs_per_round": len(runs) / n_rounds,
        "module_ms_per_round": module_s[wave] / 1e6 / n_rounds,
        "ops_self_ms_per_round": total / 1e6 / n_rounds,
        "unscoped_share": unscoped / total if total else None,
        "scope_from_hlo_join_share": joined / total if total else None,
        "phase_ms": ms({phase: sum(parts.values())
                        for phase, parts in phase_part.items()}),
        "phase_x_part_ms": ms(phase_part),
        "block_x_phase_ms": ms(by_block),
        "category_x_part_ms": ms(category_part),
    }


def split(rows: list, rules: dict, hlo_scopes: dict) -> dict:
    """All device planes of one trace -> the JSON object of the last
    line; ``{"error": ...}`` without a device plane or a round span."""
    spans = [r for r in rows if r["plane"] == HOST_PLANE]
    window, n_rounds = traced_window(spans)
    planes = sorted({r["plane"] for r in rows
                     if r["plane"].startswith(DEVICE_PLANE_PREFIX)})
    if window is None or not planes:
        return {"error": "no device plane or no round span in this trace"}
    out = {"n_rounds": n_rounds, "window_ms": (window[1] - window[0]) / 1e6,
           "host_ms_by_phase": host_ms_by_phase(spans, window, n_rounds),
           "waves": [r["stats"] for r in _inside(spans, window)
                     if r["name"] == PROGRAM_ROUND + ".stage"][:8],
           "fusion_rule": "a fusion's time goes to its root instruction's "
                          "scope (the fusion carries the root's op_name)",
           "idle_by_phase": {}, "wave_by_scope": {}}
    for plane in planes:
        device_rows = [r for r in rows if r["plane"] == plane]
        out["idle_by_phase"][plane] = idle_by_phase(
            device_rows, spans, window, n_rounds)
        out["wave_by_scope"][plane] = wave_by_scope(
            device_rows, window, n_rounds, rules, hlo_scopes)
    return out


# ------------------------------------------------- the wave program's text
def hlo_of(cell: str, seed: int, rehearse_cpu: bool = False) -> str:
    """The compiled text of the wave program ``run.py`` runs in ``cell``:
    model, cohort and ``FedSim`` built with the functions ``run.py::main``
    uses, then ``FedSim.lower_wave(...).compile().as_text()``. Needs the
    cell's devices: instruction names are the compiler's, for the device
    it compiles for."""
    import jax

    from baton_tpu.parallel.engine import FedSim
    from baton_tpu.parallel.mesh import make_mesh, shard_client_arrays
    from fedbench import data as cohort
    from fedbench.run import job_of

    root = manifest.ROOT
    bench = manifest.load_manifest(root)
    entry = manifest.cell_entry(bench, cell)
    config = manifest.load_config(root, bench, entry["config"])
    job = job_of(manifest.load_workload(root, cell), rehearse_cpu)
    model = manifest.build_model(config, rehearse_cpu)
    params = jax.jit(model.init)(jax.random.key(seed))
    n_samples = cohort.client_sizes(
        root, job["samples_per_client"], job["clients"], seed)
    data = cohort.make_cohort(
        manifest.input_spec(config, rehearse_cpu), n_samples,
        cohort.capacity_for(n_samples, job["batch"]), job.get("seq_len"),
        cohort.data_key(seed + 1))
    mesh = make_mesh(entry["chips"]) if entry["chips"] > 1 else None
    if mesh is not None:
        data = shard_client_arrays(data, mesh)
    sim = FedSim(model, batch_size=job["batch"],
                 learning_rate=job["learning_rate"], mesh=mesh)
    return sim.lower_wave(params, data, n_samples, jax.random.key(seed + 2),
                          job["local_epochs"], job["wave_size"]
                          ).compile().as_text()


# -------------------------------------------------------------------- main
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
    hlo_scopes = {}
    if args.hlo:
        with open(args.hlo) as f:
            hlo_scopes = scopes_from_hlo(f.read())
    rows, source = read_rows(args.trace)
    print(f"[scope_split] {len(rows)} rows; op scopes from the events: "
          f"{source}; from --hlo: {len(hlo_scopes)} instructions", flush=True)
    result = split(rows, manifest.load_op_categories(manifest.ROOT),
                   hlo_scopes)
    result["scope_source"] = source
    print(json.dumps(result), flush=True)
    return 1 if "error" in result else 0


if __name__ == "__main__":
    sys.exit(main())
