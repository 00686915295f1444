"""From a profiler trace (``.xplane.pb``) to numbers.

``read_events`` walks planes, lines and events and returns plain rows;
``reduce_rows`` turns rows into what the layer metrics read: per device
the busy time, the idle time by the host span it fell in, per-module and
per-category device time and the wave program's time by phase, part and
block of its ops' scopes; for the host each span's own time and its
attributes summed by span name. The two halves are apart so that the
arithmetic can be tested on rows written by hand or recorded once on
the chip (``fedbench/testdata/``).

What a TPU v5e trace of this program looks like (read by hand, PR 22,
a ``resnet18_c32_w1`` and a ``bert_base`` trace; PERF.md section 3 has
the account):

* one plane a chip, ``/device:TPU:<n>``; host threads on ``/host:CPU``;
* on a device plane the line ``XLA Modules`` has one event for each
  execution of a compiled program, named ``<jit name>(<fingerprint>)``,
  and the line ``XLA Ops`` one event for each HLO instruction that ran.
  (``Async XLA Ops`` repeats the start/done pairs as one long event and
  is not read; ``Steps`` repeats the modules.)
* an op event carries no category stat: its *name* is the instruction's
  HLO text, ``%fusion.890 = bf16[...] fusion(...), kind=kLoop,
  calls=...``. The reader keeps the instruction's name, opcode, fusion
  kind and result shape. On this compiler a convolution never runs
  bare and a ``dot`` is a convolution too: every fusion of kind
  ``kOutput`` holds one and no other fusion does (checked against the
  compiled wave programs' HLO text, PR 22) — that is the ``mxu``
  category of ``fedbench/op_categories.json``;
* a ``while`` op's event spans its body's ops, which are events of
  their own on the same line, so an op's time is its *self* time:
  duration less its direct children's;
* ``TraceAnnotation`` spans of the harness (``fedbench.round``,
  ``fedbench.sync``) and of the program (``baton.round.*``) are events
  of the host thread's line under their own name, on the same clock as
  the device events; their keyword attributes are the event's stats;
* an op's scope (its ``op_name``) is the ``tf_op`` stat,
  ``<op_name>:<type>``, of the event's *metadata*, which
  ``jax.profiler.ProfileData`` does not show: the file is read with the
  installed tensorflow's ``xplane_pb2``, loaded by its path so that
  tensorflow itself is not imported into the process that holds the
  chip. A fusion carries its root instruction's ``op_name``.
"""

from __future__ import annotations

import bisect
import gzip
import importlib.util
import json
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HARNESS_PREFIX = "fedbench."
HARNESS_ROUND = HARNESS_PREFIX + "round"
BETWEEN = "(between spans)"
XPLANE_PB2 = "tsl/profiler/protobuf/xplane_pb2.py"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
# ``%name = shape opcode(operands), kind=kLoop, calls=...``
_INSTRUCTION = re.compile(
    r"^%?(?P<name>[^\s=]+) = (?P<shape>\(.*?\)|\S+) (?P<opcode>[\w\-]+)\(")
_KIND = re.compile(r"\bkind=(\w+)")


# ---------------------------------------------------------------- reading
def parse_op(text: str) -> dict:
    """``{"name", "opcode", "kind", "shape"}`` of one op event's name.
    Text that is no HLO instruction is kept whole as the name."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return {"name": text[:120], "opcode": "", "kind": "", "shape": ""}
    kind = _KIND.search(text)
    return {"name": m.group("name"), "opcode": m.group("opcode"),
            "kind": kind.group(1) if kind else "",
            "shape": m.group("shape")[:80]}


def load_xplane_pb2():
    """The ``xplane_pb2`` of the installed tensorflow, executed from its
    file: ``import tensorflow`` takes 12 s and brings a second runtime
    into the process that holds the chip; the generated module needs
    only ``google.protobuf``."""
    found = importlib.util.find_spec("tensorflow")
    places = list(found.submodule_search_locations or ()) if found else []
    for place in places:
        path = os.path.join(place, XPLANE_PB2)
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "fedbench_xplane_pb2", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    raise ImportError(
        f"no {XPLANE_PB2} under an installed tensorflow: an op's scope is "
        "in its event metadata, which only that schema shows")


def read_events(xplane_path: str, span_prefixes: Iterable) -> list:
    """Rows ``{"plane", "line", "name", "start_ns", "dur_ns"}`` of the
    device planes' module and op lines (op rows also ``opcode``,
    ``kind``, ``shape`` and ``scope``, ``""`` where the event names
    none) and of the host spans whose name starts with one of
    ``span_prefixes`` (also ``stats``, the span's attributes).
    Everything else in the trace is left out."""
    span_prefixes = tuple(span_prefixes)
    space = load_xplane_pb2().XSpace()
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
                elif kind == "bytes_value":
                    value = value.decode("utf-8", "replace")
                out[stat_names.get(st.metadata_id)] = value
            return out

        scope_of = {}  # metadata id -> scope, once for each instruction
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                if not device and not md.name.startswith(span_prefixes):
                    continue
                row = {"plane": plane.name, "line": line.name,
                       "name": md.name,
                       "start_ns": line.timestamp_ns + ev.offset_ps / 1e3,
                       "dur_ns": ev.duration_ps / 1e3}
                if not device:
                    row["stats"] = stats(ev)
                elif line.name == OP_LINE:
                    row.update(parse_op(md.name))
                    if ev.metadata_id not in scope_of:
                        # "<op_name>:<op type>"
                        scope_of[ev.metadata_id] = str(
                            stats(md).get("tf_op", "")).rpartition(":")[0]
                    row["scope"] = scope_of[ev.metadata_id]
                rows.append(row)
    return rows


def write_rows(rows: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load_rows(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------- intervals
def merge(intervals: Iterable) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points as
    ``intervals`` (``(start, end)`` pairs; empty ones are dropped)."""
    out = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def gaps(merged: list, start: float, end: float) -> list:
    """The parts of ``[start, end]`` that ``merged`` does not cover."""
    out, at = [], start
    for s, e in merged:
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
        if at >= end:
            break
    if at < end:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


def _end(row) -> float:
    return row["start_ns"] + row["dur_ns"]


def _span(row) -> tuple:
    return (row["start_ns"], _end(row))


def _inside(rows: list, window: tuple) -> list:
    return [r for r in rows
            if r["start_ns"] >= window[0] and _end(r) <= window[1]]


def _add(table: dict, key, value: float) -> None:
    table[key] = table.get(key, 0.0) + value


def module_name(event_name: str) -> str:
    """``jit__wave_sums_vmap(1234)`` -> ``jit__wave_sums_vmap``."""
    return event_name.split("(", 1)[0]


# ------------------------------------------------------------ the scope rule
def phase_of(scope: str, names: dict) -> str:
    """The phase of a scope: the first rule of ``names["phases"]`` with a
    mark inside ``/<scope>/``, else ``other``."""
    path = f"/{scope}/"
    for phase, marks in names["phases"]:
        if any(m in path for m in marks):
            return phase
    return "other"


def part_of(scope: str, names: dict) -> str:
    """The innermost of ``names["parts"]`` on the path (``stem/conv`` is
    ``conv``, ``s1b0/shortcut/norm`` is ``norm``), else ``other``."""
    found = [w for w in _WORD.findall(scope) if w in names["parts"]]
    return found[-1] if found else "other"


def block_of(scope: str, names: dict) -> str:
    """The outermost model block on the path (a name that matches
    ``names["blocks"]``: ``s<stage>b<block>``, ``block<i>``, ``stem``,
    ``head``, ``embed``), else ``(none)``."""
    for w in _WORD.findall(scope):
        if re.fullmatch(names["blocks"], w):
            return w
    return "(none)"


def is_scoped(scope: str, names: dict) -> bool:
    return any(s in scope for s in names["program_scopes"])


# -------------------------------------------------------------- reducing
def classify(row: dict, rules: dict) -> str:
    """The category of one op row: the first rule of ``rules``
    (``fedbench/op_categories.json``) that lists the row's opcode or
    its fusion kind, else ``"other"``."""
    for category, rule in rules.items():
        if (row.get("opcode") in rule.get("opcode", ())
                or row.get("kind") in rule.get("fusion_kind", ())):
            return category
    return "other"


def describe(row: dict) -> str:
    """``fusion.890 fusion kLoop bf16[32,...]``: an op for the log."""
    return " ".join(x for x in (row["name"], row.get("opcode"),
                                row.get("kind"), row.get("shape")) if x)


def innermost(point: float, spans: list) -> str:
    """The name of the shortest span that holds ``point``."""
    inside = [r for r in spans if r["start_ns"] <= point <= _end(r)]
    if not inside:
        return BETWEEN
    return min(inside, key=lambda r: r["dur_ns"])["name"]


def place(gap: tuple, spans: list, edges: list):
    """``(span name, ns)`` for the pieces of one idle gap. A gap is cut
    at every span's start and end that falls inside it, and each piece
    goes to the innermost span that holds its midpoint. ``edges`` is the
    sorted list of the spans' starts and ends."""
    cuts = ([gap[0]]
            + edges[bisect.bisect_right(edges, gap[0]):
                    bisect.bisect_left(edges, gap[1])]
            + [gap[1]])
    for a, b in zip(cuts, cuts[1:]):
        if b > a:
            yield innermost(0.5 * (a + b), spans), b - a


def self_times(ops: list) -> list:
    """``(row, self_ns, is_leaf)`` for the op rows of one line. An op that holds
    others (a ``while`` around its body, a fusion's root around its
    parts) is an event that spans theirs; its self time is its duration
    less its direct children's, so that times add up to the busy time
    and nothing is counted twice."""
    order = sorted(ops, key=lambda r: (r["start_ns"], -r["dur_ns"]))
    selfs = [r["dur_ns"] for r in order]
    leaf = [True] * len(order)
    stack = []  # indices of the open enclosing events
    for i, r in enumerate(order):
        while stack and _end(order[stack[-1]]) <= r["start_ns"]:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= r["dur_ns"]
            leaf[stack[-1]] = False
        stack.append(i)
    return [(r, max(0.0, s), l) for r, s, l in zip(order, selfs, leaf)]


def split_wave(timed_ops: list, modules: list, wave: str, rules: dict,
               names: dict) -> dict:
    """The wave program's ops by the scope each ran under, in seconds
    over all its executions: self time by phase, phase x part, block x
    phase and category x part. ``timed_ops`` is ``self_times`` of the
    window's op rows; an op belongs to the wave program when it starts
    inside one of its module events."""
    runs = sorted(_span(r) for r in modules if module_name(r["name"]) == wave)
    starts = [s for s, _ in runs]
    phase_part, block_phase, category_part = {}, {}, {}
    total = unscoped = 0.0
    labels = {}  # scope -> its labels: an instruction runs many times
    for row, self_ns, _ in timed_ops:
        i = bisect.bisect_right(starts, row["start_ns"]) - 1
        if i < 0 or row["start_ns"] >= runs[i][1]:
            continue
        scope, s = row.get("scope", ""), self_ns / 1e9
        if scope not in labels:
            labels[scope] = (phase_of(scope, names), part_of(scope, names),
                             block_of(scope, names), is_scoped(scope, names))
        phase, part, block, scoped = labels[scope]
        total += s
        if not scoped:
            unscoped += s
        _add(phase_part.setdefault(phase, {}), part, s)
        _add(block_phase.setdefault(block, {}), phase, s)
        _add(category_part.setdefault(classify(row, rules), {}), part, s)
    return {
        "module": wave,
        "runs": len(runs),
        "self_s": total,
        "unscoped_s": unscoped,
        "phase_s": {ph: sum(parts.values())
                    for ph, parts in phase_part.items()},
        "phase_part_s": phase_part,
        "block_phase_s": block_phase,
        "category_part_s": category_part,
    }


def reduce_device(rows: list, spans: list, window: tuple, rules: dict,
                  names: dict) -> dict:
    """One device plane's rows -> its numbers, all in seconds.

    ``window`` is the traced span on the trace's clock: from the start
    of the first traced round's ``fedbench.round`` span to the end of
    the last harness span. Only events wholly inside it are read (the
    harness syncs before it opens the window, so none straddles it)."""
    w0, w1 = window
    ops = _inside([r for r in rows if r["line"] == OP_LINE], window)
    modules = _inside([r for r in rows if r["line"] == MODULE_LINE], window)
    busy = merge(_span(r) for r in ops)
    idle = gaps(busy, w0, w1)

    module_s, runs = {}, {}
    for r in modules:
        name = module_name(r["name"])
        _add(module_s, name, r["dur_ns"] / 1e9)
        runs[name] = runs.get(name, 0) + 1
    category_s, op_s = {}, {}
    collective, compute = [], []
    timed_ops = self_times(ops)
    for r, self_ns, is_leaf in timed_ops:
        category = classify(r, rules)
        _add(category_s, category, self_ns / 1e9)
        _add(op_s, describe(r), self_ns / 1e9)
        if category == "collective":
            collective.append(_span(r))
        elif is_leaf:
            compute.append(_span(r))
    # a collective's time with no other (leaf) op running beside it
    exposed = sum(e - s for g in merge(collective)
                  for s, e in gaps(merge(
                      c for c in compute if c[1] > g[0] and c[0] < g[1]),
                      g[0], g[1]))

    # idle time by what the host was doing: each gap cut at the span
    # edges it crosses, every piece to the innermost span that holds it
    edges = sorted({t for r in spans for t in _span(r)})
    idle_by_span_s = {}
    for g in idle:
        for name, ns in place(g, spans, edges):
            _add(idle_by_span_s, name, ns / 1e9)

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "idle_s": sum(e - s for s, e in idle) / 1e9,
        "module_s": module_s,
        "module_runs": runs,
        "category_s": category_s,
        "collective_exposed_s": exposed / 1e9,
        "op_s": op_s,
        "idle_by_span_s": idle_by_span_s,
        "longest_gap_s": max((e - s for s, e in idle), default=0.0) / 1e9,
        "wave": (split_wave(timed_ops, modules,
                            wave_module({"module_s": module_s}), rules, names)
                 if module_s else None),
    }


def traced_window(spans: list) -> Optional[tuple]:
    """From the first ``fedbench.round`` span's start to the last
    harness span's end, or ``None`` without one."""
    harness = [r for r in spans if r["name"].startswith(HARNESS_PREFIX)]
    rounds = [r for r in harness if r["name"] == HARNESS_ROUND]
    if not rounds:
        return None
    return (min(r["start_ns"] for r in rounds), max(map(_end, harness)))


def reduce_host(spans: list) -> dict:
    """The host spans of the window by name: how often each ran
    (``span_runs``), its own seconds (``host_self_s``: its duration less
    its children's) and its numeric attributes summed (``span_attrs``:
    ``baton.round.stage`` -> ``{"wave", "real", "padded"}``), which is
    how a count travels from the program to a reader."""
    span_runs, host_self_s, span_attrs = {}, {}, {}
    for row, self_ns, _ in self_times(spans):
        name = row["name"]
        span_runs[name] = span_runs.get(name, 0) + 1
        _add(host_self_s, name, self_ns / 1e9)
        for key, value in row.get("stats", {}).items():
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                _add(span_attrs.setdefault(name, {}), key, value)
    return {"span_runs": span_runs, "host_self_s": host_self_s,
            "span_attrs": span_attrs}


def reduce_rows(rows: list, rules: dict, names: dict) -> Optional[dict]:
    """All device planes of one trace -> ``{"devices": {plane: {...}},
    "n_rounds", "window_s", "span_runs", "host_self_s", "span_attrs"}``;
    ``None`` where the trace holds no device plane or no harness span (a
    CPU trace): a reader then finds nothing to read. ``rules`` is
    ``fedbench/op_categories.json``'s, ``names`` ``trace_names.json``."""
    spans = [r for r in rows if r["plane"] == HOST_PLANE]
    window = traced_window(spans)
    planes = sorted({r["plane"] for r in rows
                     if r["plane"].startswith(DEVICE_PLANE_PREFIX)})
    if window is None or not planes:
        return None
    spans = _inside(spans, window)
    devices = {p: reduce_device([r for r in rows if r["plane"] == p],
                                spans, window, rules, names)
               for p in planes}
    return {
        "devices": devices,
        "n_rounds": sum(r["name"] == HARNESS_ROUND for r in spans),
        "window_s": (window[1] - window[0]) / 1e9,
        **reduce_host(spans),
    }


# ------------------------------------------------- helpers for the readers
def device_mean(reduced: dict, key: str) -> float:
    values = [d[key] for d in reduced["devices"].values()]
    return sum(values) / len(values)


def wave_module(device: dict) -> str:
    """The wave program of a traced round: the compiled module that took
    most of the device's time. (No program code names it with a scope
    yet; by time it cannot be missed, it is > 90 % of every cell.)"""
    return max(device["module_s"], key=device["module_s"].get)


def idle_ms_in(reduced: Optional[dict], *span_names: str) -> Optional[float]:
    """Device idle milliseconds a round that fell inside the host spans
    ``span_names``, mean over the cell's devices; ``None`` without a
    trace or where no such span ran."""
    if reduced is None or not any(
            n in reduced["span_runs"] for n in span_names):
        return None
    per_device = [sum(d["idle_by_span_s"].get(n, 0.0) for n in span_names)
                  for d in reduced["devices"].values()]
    return 1e3 * sum(per_device) / len(per_device) / reduced["n_rounds"]


def wave_ms_under(reduced: Optional[dict], phase: Optional[str] = None,
                  part: Optional[str] = None) -> Optional[float]:
    """Device milliseconds of one execution of the wave program spent in
    ops of ``phase`` and (or) ``part``, mean over the cell's devices;
    ``None`` without a trace or where no op of the wave program carried
    such a scope."""
    if reduced is None:
        return None
    per_device = []
    for d in reduced["devices"].values():
        wave = d["wave"]
        tables = [] if wave is None else [
            parts for ph, parts in wave["phase_part_s"].items()
            if phase in (None, ph)]
        found = [t[p] for t in tables for p in t if part in (None, p)]
        if not found:
            return None
        per_device.append(sum(found) / wave["runs"])
    return 1e3 * sum(per_device) / len(per_device)


# ------------------------------------------------------ for the last line
def _top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``, read on the first device: the ten
    device ops with most self time, and the idle time by the host span
    (the harness's or the program's) it fell in."""
    device = reduced["devices"][sorted(reduced["devices"])[0]]
    return {"device_ops": _top(device["op_s"]),
            "idle_gaps": _top(device["idle_by_span_s"])}


def commentary(reduced: dict) -> list:
    """Lines for the log: per device busy, idle, modules, categories,
    and the wave program by phase."""
    out = []
    for plane, d in sorted(reduced["devices"].items()):
        wave = wave_module(d)
        out.append(
            f"{plane}: window {d['window_s']:.4f} s, busy {d['busy_s']:.4f}, "
            f"idle {d['idle_s']:.4f} (longest gap {d['longest_gap_s']:.5f}); "
            f"wave program {wave} x{d['module_runs'][wave]} "
            f"{d['module_s'][wave]:.4f} s; other modules "
            f"{sum(v for k, v in d['module_s'].items() if k != wave):.5f} s; "
            f"categories {({k: round(v, 5) for k, v in d['category_s'].items()})}; "
            f"collective with no compute beside it "
            f"{d['collective_exposed_s']:.5f} s; wave program by phase "
            f"{({k: round(v, 5) for k, v in d['wave']['phase_s'].items()})}, "
            f"outside the program's scopes {d['wave']['unscoped_s']:.5f} s")
    return out
