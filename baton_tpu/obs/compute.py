"""Compute-plane probe: analytic FLOPs/MFU accounting, jit compile
tracking, and peak-HBM reading for the LIVE training path.

Without it the round loop is blind on the compute plane (MFU, compile
seconds, peak HBM). The probe instruments every local
training call (worker ``_run_round``, the manager's simulated cohort via
``parallel/engine.py``) and emits one *compute record* per round, which
rides the update metadata to the root, lands in the round's
``rounds.jsonl`` SLO record (``compute`` section), feeds the per-client
fleet ledger, and gates ``compute:*`` SLO metrics in CI.

Three design rules, each a recorded postmortem:

* **One FLOPs implementation.** The per-model analytic FLOPs constants
  and the MFU formula of the program live HERE, for every reporter
  (worker, edge, manager). The benchmark keeps its own yardstick
  (``fedbench/flops/``, ``fedbench/peaks.json``) and imports nothing
  from here.
* **Null-with-reason.** Every ``None`` metric in a compute record
  carries a sibling ``<name>_reason`` / ``<name>_source`` string
  (:func:`validate_record` enforces it): a silent null reads as
  "stopped measuring" and hides regressions.
* **Compile visibility.** :class:`CompileTracker` watches the shape
  signatures each jitted callable is invoked with: a new signature is a
  cache miss, and repeated new signatures within a short window are a
  *recompile storm* — the shape-churn pathology that silently
  multiplies round latency. It owns no time: what a build cost is
  JAX's to say, and :class:`BuildLedger` hears it.

``compile_s`` is the seconds JAX spent tracing, lowering and building
(compiling, or loading from the persistent cache) programs inside the
round, as ``jax.monitoring`` reported them (``compile_s_source:
"jax_monitoring"``); ``compile_cold_s`` is the part of it that the
persistent cache did not serve. A round in which nothing was built
reads an exact 0.0 (``"cache_hit"``).

The FLOPs/MFU math and the tracker touch no backend: they import and
unit-test without an accelerator.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from jax import monitoring

from baton_tpu.utils.profiling import annotate

__all__ = [
    "RESNET18_CIFAR_FWD_FLOPS_PER_IMG",
    "TRAIN_FLOPS_PER_IMG",
    "TPU_PEAK_FLOPS",
    "MODEL_FAMILY_FLOPS",
    "register_model_flops",
    "model_family_of",
    "train_flops_per_sample",
    "peak_flops_for",
    "compute_mfu",
    "CompileTracker",
    "Build",
    "BuildLedger",
    "builds",
    "ComputeProbe",
    "build_record",
    "validate_record",
    "summarize_round",
    "RECOMPILE_STORM_THRESHOLD",
    "RECOMPILE_STORM_WINDOW",
]

# ---------------------------------------------------------------------------
# Analytic FLOPs accounting (the program's ONE copy).
#
# ResNet-18 (CIFAR-10 variant, 32x32 input): 0.557 GMAC forward per
# image = 1.11 GFLOP (x2 MAC->FLOP); training ~3x forward (fwd + 2x
# bwd).
RESNET18_CIFAR_FWD_FLOPS_PER_IMG = 1.11e9
TRAIN_FLOPS_PER_IMG = 3.0 * RESNET18_CIFAR_FWD_FLOPS_PER_IMG

# Peak dense-matmul throughput by device kind (bf16, FLOP/s) — the MFU
# denominator. Source: public TPU spec sheets. Prefix-matched against
# ``device.device_kind`` (the v5e runtime reports "TPU v5 lite"; the
# documentation says "TPU v5e").
TPU_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # Trillium / v6e
    "TPU v6e": 918e12,
}

#: analytic *training* FLOPs per sample, by model family
MODEL_FAMILY_FLOPS: Dict[str, float] = {
    "resnet18_cifar": TRAIN_FLOPS_PER_IMG,
}

# model-name prefix -> family key in MODEL_FAMILY_FLOPS (FedModel.name
# is free-form; bench's model is named "resnet18*")
_FAMILY_PREFIXES: List[Tuple[str, str]] = [
    ("resnet18", "resnet18_cifar"),
]


def register_model_flops(
    family: str,
    train_flops_per_sample: float,
    name_prefixes: Sequence[str] = (),
) -> None:
    """Register a model family's analytic training FLOPs per sample so
    live rounds on that family get measured MFU. ``name_prefixes`` maps
    ``FedModel.name`` values to the family."""
    if not (train_flops_per_sample > 0):
        raise ValueError("train_flops_per_sample must be > 0")
    MODEL_FAMILY_FLOPS[family] = float(train_flops_per_sample)
    for p in name_prefixes:
        _FAMILY_PREFIXES.append((str(p), family))


def model_family_of(model: Any) -> Tuple[Optional[str], Optional[str]]:
    """``(family, reason)`` for a model (a :class:`FedModel`, anything
    with a ``name``, or a bare name string). ``family`` is a key of
    :data:`MODEL_FAMILY_FLOPS`; unknown models return
    ``(None, reason)`` — an unknown family is *expected* (linear smoke
    models, custom nets) and downstream MFU is null-with-reason."""
    name = model if isinstance(model, str) else getattr(model, "name", None)
    if not name:
        return None, "model has no name attribute"
    for prefix, family in _FAMILY_PREFIXES:
        if name.startswith(prefix):
            return family, None
    return None, f"no FLOPs accounting registered for model {name!r}"


def train_flops_per_sample(
    family: Optional[str],
) -> Tuple[Optional[float], Optional[str]]:
    """Analytic training FLOPs per sample for ``family``, or
    ``(None, reason)``."""
    if family is None:
        return None, "model family unknown"
    flops = MODEL_FAMILY_FLOPS.get(family)
    if flops is None:
        return None, f"no FLOPs accounting for family {family!r}"
    return flops, None


def peak_flops_for(
    device_kind: str,
) -> Tuple[Optional[float], Optional[str]]:
    """Chip peak FLOP/s for a ``device_kind`` string (prefix-matched).
    A kind the table does not hold gets ``(None, reason)``, never a
    default peak: the live record carries the reason, and a benchmark
    turns it into an error."""
    for prefix, peak in TPU_PEAK_FLOPS.items():
        if device_kind.startswith(prefix):
            return peak, None
    return None, f"no peak-FLOPs spec for device kind {device_kind!r}"


def compute_mfu(
    samples_per_sec_per_chip: Optional[float],
    flops_per_sample: Optional[float],
    device_kind: str,
) -> Tuple[Optional[float], Optional[str]]:
    """MFU = delivered analytic training FLOPs / chip peak.
    ``(None, reason)`` when any input is unavailable."""
    if samples_per_sec_per_chip is None:
        return None, "throughput unmeasured"
    if flops_per_sample is None:
        return None, "model FLOPs unavailable"
    peak, why = peak_flops_for(device_kind)
    if peak is None:
        return None, why
    return samples_per_sec_per_chip * flops_per_sample / peak, None


# ---------------------------------------------------------------------------
# Compile tracking

#: new shape signatures within the window that flag a recompile storm —
#: one compile per new (cohort, epochs) shape is expected; three in a
#: window of eight rounds means the shapes are churning
RECOMPILE_STORM_THRESHOLD = 3
RECOMPILE_STORM_WINDOW = 8


class CompileTracker:
    """Shape-signature watcher for jitted callables.

    The live path controls a jit's cache key: a call with a signature
    this tracker has not seen for ``key`` builds a program. The engine's
    control flow asks ``seen`` before a round, and ``observe`` returns
    the round's ``cache_hit``, ``recompiles`` and ``recompile_storm``.
    What the build cost is the :class:`BuildLedger`'s to say.
    """

    def __init__(
        self,
        storm_window: int = RECOMPILE_STORM_WINDOW,
        storm_threshold: int = RECOMPILE_STORM_THRESHOLD,
    ) -> None:
        self.storm_window = max(2, int(storm_window))
        self.storm_threshold = max(2, int(storm_threshold))
        self._sigs: Dict[Any, set] = {}
        self._recent: Dict[Any, deque] = {}

    def seen(self, key: Any, signature: Any) -> bool:
        """Whether ``observe`` has had ``signature`` for ``key``: a call
        with one it has not had will compile."""
        return signature in self._sigs.get(key, ())

    def observe(self, key: Any, signature: Any) -> dict:
        """Record one invocation of callable ``key`` with shape
        ``signature``."""
        sigs = self._sigs.setdefault(key, set())
        miss = signature not in sigs
        if miss:
            sigs.add(signature)
        recent = self._recent.setdefault(
            key, deque(maxlen=self.storm_window)
        )
        recent.append(miss)
        return {
            "cache_hit": not miss,
            "recompiles": max(0, len(sigs) - 1),
            "recompile_storm": sum(recent) >= self.storm_threshold,
        }


# ---------------------------------------------------------------------------
# The build ledger: what JAX itself says of every program it makes

#: JAX's three build events (``jax/_src/dispatch.py``), each a scalar
#: on entry and a duration on exit, both with ``fun_name``
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: fired inside a backend event that the persistent cache served
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

#: outermost events kept one by one; the totals count every one
_EVENTS_KEPT = 4096


class Build(NamedTuple):
    """One outermost build event. ``ended`` is the host's
    ``time.perf_counter()`` at its end; ``cache_hit`` is a ``backend``
    event's (whether the persistent cache served it), else ``None``."""

    program: str
    phase: str
    seconds: float
    ended: float
    cache_hit: Optional[bool] = None


def _new_totals() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cold_s": 0.0, "builds": 0, "cache_hits": 0}


def _program_of(fun_name: str) -> str:
    """A trace names its function (``outer``), the lowering and the
    build that follow name the module (``jit(outer)``): one program."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _Open(threading.local):
    """A thread's open build events: how many, and what the outermost
    one is (``span`` is ``None`` while none is open)."""

    depth = 0
    phase = program = ""
    span: Any = None
    cache_hit = False


class BuildLedger:
    """Every jaxpr trace, lowering and backend build of this process,
    by program, from ``jax.monitoring``'s own events.

    The events nest: tracing a decoder enters thousands of inner jits,
    a lowering rule may trace, an eager operation inside a trace builds
    a program of its own. One depth a thread runs over the three phases
    and only an event entered at depth 0 is recorded, under its own
    phase with everything it holds, so no second is counted twice and
    the three totals sum to no more than the wall time they took. An
    inner event costs its two listeners an addition each.

    Across each recorded event the ledger holds open a
    ``baton.build.<phase>`` span (:func:`~baton_tpu.utils.profiling.
    annotate`, attribute ``program``; ``cache_hit`` on ``.backend``):
    in a profiler session a build lies on the device planes' clock
    inside whatever ``baton.round.*`` span made it."""

    def __init__(self) -> None:
        self._open = _Open()
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        """Forget what was recorded (for tests). Events that are open
        stay open and are recorded when they end."""
        with self._lock:
            self._totals = _new_totals()
            self._by_program: Dict[str, dict] = {}
            self._events: deque = deque(maxlen=_EVENTS_KEPT)

    def listen(self) -> None:
        """Register with ``jax.monitoring``; JAX keeps its listeners for
        the life of the process, so once a ledger."""
        monitoring.register_scalar_listener(self._on_scalar)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    # -- the three listeners -------------------------------------------
    def _on_scalar(self, event: str, value, fun_name: str = "", **_) -> None:
        phase = _PHASES.get(event)
        if phase is None:
            return
        opened = self._open
        opened.depth += 1
        if opened.depth > 1:
            return
        opened.phase, opened.program = phase, _program_of(fun_name)
        opened.cache_hit = False
        opened.span = annotate("baton.build." + phase, program=opened.program)
        opened.span.__enter__()

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self._open.cache_hit = True

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        opened = self._open
        if event not in _PHASES:
            return
        if not opened.depth:  # entered before this ledger listened
            return
        opened.depth -= 1
        if opened.depth:
            return
        ended = time.perf_counter()
        span, opened.span = opened.span, None
        if span is None:  # its entry raised before the span opened
            return
        phase, program = opened.phase, opened.program
        backend = phase == "backend"
        hit = opened.cache_hit if backend else None
        if backend:
            span.set_metadata(cache_hit=int(hit))
        span.__exit__(None, None, None)
        with self._lock:
            self._events.append(Build(program, phase, seconds, ended, hit))
            for totals in (self._totals,
                           self._by_program.setdefault(program,
                                                       _new_totals())):
                totals[phase + "_s"] += seconds
                if backend:
                    totals["builds"] += 1
                    if hit:
                        totals["cache_hits"] += 1
                    else:
                        totals["cold_s"] += seconds

    # -- what it answers -----------------------------------------------
    def totals(self) -> dict:
        """``{trace_s, lower_s, backend_s, cold_s, builds, cache_hits}``
        of the whole process: seconds in outermost traces, lowerings and
        backend builds; of ``backend_s``, the seconds of builds the
        persistent cache did not serve; the backend builds, and how many
        of them the cache served."""
        with self._lock:
            return dict(self._totals)

    def by_program(self) -> Dict[str, dict]:
        """The same six numbers for each program."""
        with self._lock:
            return {p: dict(t) for p, t in self._by_program.items()}

    def events(self) -> List[Build]:
        """The last outermost events, oldest first."""
        with self._lock:
            return list(self._events)

    def since(self, t: float) -> List[Build]:
        """The outermost events that ended after host time ``t``
        (``time.perf_counter()``), newest first."""
        out = []
        with self._lock:
            for build in reversed(self._events):
                if build.ended <= t:
                    break
                out.append(build)
        return out

    def summary(self, top: int = 5) -> List[str]:
        """Lines a person can read: the totals, then the ``top``
        programs by seconds."""
        totals, programs = self.totals(), self.by_program()

        def line(name: str, t: dict) -> str:
            return (f"{name}: trace {t['trace_s']:.3f} s, lower "
                    f"{t['lower_s']:.3f} s, backend {t['backend_s']:.3f} s "
                    f"({t['cold_s']:.3f} s not from the cache; "
                    f"{t['builds']} builds, {t['cache_hits']} cache hits)")

        def cost(item) -> float:
            return item[1]["trace_s"] + item[1]["lower_s"] + item[1][
                "backend_s"]

        return [line(f"{len(programs)} programs", totals)] + [
            line(name, t)
            for name, t in sorted(programs.items(), key=cost,
                                  reverse=True)[:top]]


_LEDGER = BuildLedger()
_LEDGER.listen()


def builds() -> BuildLedger:
    """The process's one build ledger; it has listened since this
    module was first imported."""
    return _LEDGER


# ---------------------------------------------------------------------------
# Record building + the null-with-reason invariant

def validate_record(record: dict) -> List[str]:
    """The null-with-reason invariant: every ``None`` value must have a
    non-empty ``<key>_reason`` or ``<key>_source`` sibling string.
    Returns the violations (empty = valid)."""
    bad = []
    for key, val in record.items():
        if val is not None:
            continue
        if key.endswith(("_reason", "_source")):
            bad.append(f"{key}: reason/source field itself is null")
            continue
        excuse = record.get(f"{key}_reason") or record.get(f"{key}_source")
        if not (isinstance(excuse, str) and excuse):
            bad.append(f"{key}: null without {key}_reason/{key}_source")
    return bad


def build_record(
    *,
    train_s: float,
    n_samples: float,
    n_epochs: int = 1,
    steps: Optional[int] = None,
    device_kind: str = "unknown",
    n_chips: int = 1,
    model_family: Optional[str] = None,
    model_family_reason: Optional[str] = None,
    compile_fields: Optional[dict] = None,
    peak_hbm_gb: Optional[float] = None,
    peak_hbm_source: Optional[str] = None,
    peak_hbm_reason: Optional[str] = None,
    train_s_source: Optional[str] = None,
) -> dict:
    """Assemble one round's compute record, deriving throughput and MFU
    and enforcing the null-with-reason invariant by construction.
    ``train_s_source`` says how ``train_s`` was taken, where the caller
    has more than one way (the engine: ``FedSim._settle``)."""
    train_s = float(train_s)
    n_chips = max(1, int(n_chips))
    rec: dict = {
        "train_s": round(train_s, 6),
        "steps": int(steps) if steps is not None else int(max(1, n_epochs)),
        "n_chips": n_chips,
        "device_kind": device_kind,
    }
    if train_s_source:
        rec["train_s_source"] = train_s_source
    if model_family is not None:
        rec["model_family"] = model_family
    else:
        rec["model_family"] = None
        rec["model_family_reason"] = (
            model_family_reason or "model family unknown"
        )
    if train_s > 0 and n_samples > 0:
        sps = float(n_samples) * max(1, int(n_epochs)) / train_s
        rec["samples_per_sec"] = round(sps, 3)
        rec["samples_per_sec_per_chip"] = round(sps / n_chips, 3)
    else:
        why = "zero training wall time" if n_samples > 0 else "no samples"
        rec["samples_per_sec"] = None
        rec["samples_per_sec_reason"] = why
        rec["samples_per_sec_per_chip"] = None
        rec["samples_per_sec_per_chip_reason"] = why
    flops, flops_why = train_flops_per_sample(rec.get("model_family"))
    if flops is not None:
        rec["flops_per_sample"] = flops
    else:
        rec["flops_per_sample"] = None
        rec["flops_per_sample_reason"] = flops_why or "model FLOPs unavailable"
    mfu, mfu_why = compute_mfu(
        rec.get("samples_per_sec_per_chip"), flops, device_kind
    )
    if mfu is not None:
        rec["mfu"] = round(mfu, 6)
    else:
        rec["mfu"] = None
        rec["mfu_reason"] = mfu_why or "mfu unavailable"
    rec.update(compile_fields or {
        "compile_s": None,
        "compile_s_reason": "compile tracking not wired for this path",
    })
    if peak_hbm_gb is not None:
        rec["peak_hbm_gb"] = round(float(peak_hbm_gb), 6)
        rec["peak_hbm_gb_source"] = peak_hbm_source or "unspecified"
    else:
        rec["peak_hbm_gb"] = None
        rec["peak_hbm_gb_reason"] = (
            peak_hbm_reason or "no allocator stats or memory plan available"
        )
    violations = validate_record(rec)
    if violations:  # by-construction guard; unreachable via this builder
        raise ValueError(f"compute record breaks null-with-reason: "
                         f"{violations}")
    return rec


class ComputeProbe:
    """Per-process probe instrumenting one training call site.

    One probe per worker / engine; :meth:`record_round` is called once
    per round with that round's wall time + shape signature and returns
    the compute record (compile fields via the shared tracker, HBM via
    the runtime allocator falling back to reasons)."""

    def __init__(
        self,
        model: Any = None,
        model_family: Optional[str] = None,
        storm_window: int = RECOMPILE_STORM_WINDOW,
        storm_threshold: int = RECOMPILE_STORM_THRESHOLD,
    ) -> None:
        if model_family is not None:
            self.model_family: Optional[str] = model_family
            self.model_family_reason: Optional[str] = None
        else:
            self.model_family, self.model_family_reason = (
                model_family_of(model) if model is not None
                else (None, "no model attached to probe")
            )
        self.tracker = CompileTracker(storm_window, storm_threshold)
        # the default device is fixed for the life of the process; cache
        # the lookup so record_round stays off the jax client per round
        self._cached_device: Any = None

    @staticmethod
    def _peak_hbm(device) -> Tuple[Optional[float], Optional[str],
                                   Optional[str]]:
        """(gb, source, reason) from the runtime allocator
        (:func:`baton_tpu.utils.profiling.peak_hbm_gb`). A backend that
        keeps no allocator statistics (the CPU) is a reason; a JAX
        error is an error."""
        from baton_tpu.utils.profiling import peak_hbm_gb

        gb = peak_hbm_gb(device)
        if gb is not None:
            return gb, "allocator", None
        return None, None, (
            f"backend {device.platform!r} keeps no allocator statistics"
        )

    def record_round(
        self,
        *,
        key: Any,
        signature: Any,
        train_s: float,
        n_samples: float,
        n_epochs: int = 1,
        steps: Optional[int] = None,
        device: Any = None,
        n_chips: int = 1,
        train_s_source: Optional[str] = None,
    ) -> dict:
        """``n_chips`` is the number of devices the timed work ran on —
        one unless the caller spread it over a mesh — not the number the
        host has: throughput per chip divides by it. ``train_s_source``
        goes into the record beside ``train_s``."""
        if device is not None:
            dev = device
        else:
            if self._cached_device is None:
                import jax

                self._cached_device = jax.devices()[0]
            dev = self._cached_device
        compile_fields = self.tracker.observe(key, signature)
        # both callers come here at their round's end with its wall time
        built = _LEDGER.since(time.perf_counter() - train_s)
        compile_fields.update(
            compile_s=round(sum(b.seconds for b in built), 6),
            compile_s_source="jax_monitoring" if built else "cache_hit",
            # of compile_s, what the persistent cache did not serve
            compile_cold_s=round(sum(b.seconds for b in built
                                     if b.cache_hit is False), 6))
        hbm_gb, hbm_src, hbm_why = self._peak_hbm(dev)
        return build_record(
            train_s=train_s,
            n_samples=n_samples,
            n_epochs=n_epochs,
            steps=steps,
            device_kind=dev.device_kind,
            n_chips=n_chips,
            model_family=self.model_family,
            model_family_reason=self.model_family_reason,
            compile_fields=compile_fields,
            peak_hbm_gb=hbm_gb,
            peak_hbm_source=hbm_src,
            peak_hbm_reason=hbm_why,
            train_s_source=train_s_source,
        )


# ---------------------------------------------------------------------------
# Round-level aggregation (the rounds.jsonl ``compute`` section)

def _nums(records: Sequence[dict], key: str) -> List[float]:
    return [
        float(r[key]) for r in records
        if isinstance(r.get(key), (int, float))
        and not isinstance(r.get(key), bool)
        and math.isfinite(float(r[key]))
    ]


def _first_reason(records: Sequence[dict], key: str, default: str) -> str:
    for r in records:
        why = r.get(f"{key}_reason") or r.get(f"{key}_source")
        if isinstance(why, str) and why:
            return why
    return default


def summarize_round(records: Sequence[dict]) -> dict:
    """Fold the reporters' per-client compute records into one round
    ``compute`` section. Aggregates keep the null-with-reason rule: a
    value no reporter measured is null with the first reporter's reason
    (or an explicit "no compute records")."""
    records = [r for r in records if isinstance(r, dict)]
    out: dict = {"reporters": len(records)}
    if not records:
        for key in ("compile_s", "compile_cold_s", "steps",
                    "samples_per_sec_per_chip", "mfu", "peak_hbm_gb"):
            out[key] = None
            out[f"{key}_reason"] = "no compute records this round"
        out["recompile_storms"] = 0
        return out

    def put(key: str, vals: List[float], agg) -> None:
        if vals:
            out[key] = round(agg(vals), 6)
        else:
            out[key] = None
            out[f"{key}_reason"] = _first_reason(
                records, key, f"no reporter measured {key}"
            )

    put("compile_s", _nums(records, "compile_s"), max)
    put("compile_cold_s", _nums(records, "compile_cold_s"), max)
    steps = _nums(records, "steps")
    out["steps"] = int(sum(steps)) if steps else None
    if not steps:
        out["steps_reason"] = "no reporter measured steps"
    put("samples_per_sec_per_chip",
        _nums(records, "samples_per_sec_per_chip"),
        lambda v: sum(v) / len(v))
    put("mfu", _nums(records, "mfu"), lambda v: sum(v) / len(v))
    hbm = _nums(records, "peak_hbm_gb")
    if hbm:
        out["peak_hbm_gb"] = round(max(hbm), 6)
        out["peak_hbm_gb_source"] = _first_reason(
            records, "peak_hbm_gb", "allocator"
        )
    else:
        out["peak_hbm_gb"] = None
        out["peak_hbm_gb_reason"] = _first_reason(
            records, "peak_hbm_gb", "no reporter measured peak HBM"
        )
    out["recompile_storms"] = sum(
        1 for r in records if r.get("recompile_storm")
    )
    return out
