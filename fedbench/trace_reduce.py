"""From a profiler trace (``.xplane.pb``) to numbers.

``read_events`` walks planes, lines and events with
``jax.profiler.ProfileData`` and returns plain rows; ``reduce_rows``
turns rows into the per-device busy time, idle gaps, per-module and
per-category device time that the layer metrics read. The two halves
are apart so that the arithmetic can be tested on rows written by hand
or recorded once on the chip (``fedbench/testdata/``).

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
* ``TraceAnnotation`` spans of the harness are events of the host
  thread's line under their own name (``fedbench.round``,
  ``fedbench.sync``), on the same clock as the device events.
"""

from __future__ import annotations

import gzip
import json
import re
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "fedbench."
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


def read_events(xplane_path: str) -> list:
    """Rows ``{"plane", "line", "name", "start_ns", "dur_ns"}`` of the
    device planes' module and op lines (op rows also ``opcode``,
    ``kind``, ``shape``) and of the harness's host spans. Everything
    else in the trace is left out."""
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
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                row = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if line.name == OP_LINE:
                    row.update(parse_op(ev.name))
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


def _span(row) -> tuple:
    return (row["start_ns"], row["start_ns"] + row["dur_ns"])


def module_name(event_name: str) -> str:
    """``jit__wave_sums_vmap(1234)`` -> ``jit__wave_sums_vmap``."""
    return event_name.split("(", 1)[0]


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


def _attribute(gap: tuple, spans: list) -> str:
    """Which harness span the middle of an idle gap falls in."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [r for r in spans
              if r["start_ns"] <= mid <= r["start_ns"] + r["dur_ns"]]
    if not inside:
        return "between fedbench spans"
    # the innermost: the shortest span that holds the point
    return "inside " + min(inside, key=lambda r: r["dur_ns"])["name"]


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
        while stack and (order[stack[-1]]["start_ns"]
                         + order[stack[-1]]["dur_ns"]) <= r["start_ns"]:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= r["dur_ns"]
            leaf[stack[-1]] = False
        stack.append(i)
    return [(r, max(0.0, s), l) for r, s, l in zip(order, selfs, leaf)]


def reduce_device(rows: list, spans: list, window: tuple, rules: dict) -> dict:
    """One device plane's rows -> its numbers, all in seconds.

    ``window`` is the traced span on the trace's clock: from the start
    of the first traced round's ``fedbench.round`` span to the end of
    the last harness span. Only events wholly inside it are read (the
    harness syncs before it opens the window, so none straddles it)."""
    w0, w1 = window

    def inside(r):
        return r["start_ns"] >= w0 and r["start_ns"] + r["dur_ns"] <= w1

    ops = [r for r in rows if r["line"] == OP_LINE and inside(r)]
    modules = [r for r in rows if r["line"] == MODULE_LINE and inside(r)]
    busy = merge(_span(r) for r in ops)
    idle = gaps(busy, w0, w1)

    module_s, runs = {}, {}
    for r in modules:
        name = module_name(r["name"])
        module_s[name] = module_s.get(name, 0.0) + r["dur_ns"] / 1e9
        runs[name] = runs.get(name, 0) + 1
    category_s, op_s = {}, {}
    collective, compute = [], []
    for r, self_ns, is_leaf in self_times(ops):
        category = classify(r, rules)
        category_s[category] = category_s.get(category, 0.0) + self_ns / 1e9
        label = describe(r)
        op_s[label] = op_s.get(label, 0.0) + self_ns / 1e9
        if category == "collective":
            collective.append(_span(r))
        elif is_leaf:
            compute.append(_span(r))
    # a collective's time with no other (leaf) op running beside it
    exposed = sum(e - s for g in merge(collective)
                  for s, e in gaps(merge(
                      c for c in compute if c[1] > g[0] and c[0] < g[1]),
                      g[0], g[1]))

    gap_s = {}
    for g in idle:
        label = _attribute(g, spans)
        gap_s[label] = gap_s.get(label, 0.0) + (g[1] - g[0]) / 1e9

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "idle_s": sum(e - s for s, e in idle) / 1e9,
        "module_s": module_s,
        "module_runs": runs,
        "category_s": category_s,
        "collective_exposed_s": exposed / 1e9,
        "op_s": op_s,
        "gap_s": gap_s,
        "longest_gap_s": max((e - s for s, e in idle), default=0.0) / 1e9,
    }


def traced_window(spans: list) -> Optional[tuple]:
    """From the first ``fedbench.round`` span's start to the last
    harness span's end, or ``None`` without spans."""
    rounds = [r for r in spans if r["name"] == SPAN_PREFIX + "round"]
    if not rounds:
        return None
    return (min(r["start_ns"] for r in rounds),
            max(r["start_ns"] + r["dur_ns"] for r in spans))


def reduce_rows(rows: list, rules: dict) -> Optional[dict]:
    """All device planes of one trace -> ``{"devices": {plane: {...}},
    "n_rounds", "window_s"}``; ``None`` where the trace holds no device
    plane or no harness span (a CPU trace): a reader then finds nothing
    to read."""
    spans = [r for r in rows if r["plane"] == HOST_PLANE]
    window = traced_window(spans)
    planes = sorted({r["plane"] for r in rows
                     if r["plane"].startswith(DEVICE_PLANE_PREFIX)})
    if window is None or not planes:
        return None
    devices = {p: reduce_device([r for r in rows if r["plane"] == p],
                                spans, window, rules)
               for p in planes}
    return {
        "devices": devices,
        "n_rounds": sum(r["name"] == SPAN_PREFIX + "round" for r in spans),
        "window_s": (window[1] - window[0]) / 1e9,
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


# ------------------------------------------------------ for the last line
def _top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``, read on the first device: the ten
    device ops with most self time, and the idle time by what the
    harness was doing (its spans are the only ones there are)."""
    device = reduced["devices"][sorted(reduced["devices"])[0]]
    return {"device_ops": _top(device["op_s"]),
            "idle_gaps": _top(device["gap_s"])}


def commentary(reduced: dict) -> list:
    """Lines for the log: per device busy, idle, modules, categories."""
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
            f"{d['collective_exposed_s']:.5f} s")
    return out
