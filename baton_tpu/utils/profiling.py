"""JAX profiler hooks and memory-plan helpers (SURVEY §5
"Tracing/profiling: absent" — new).

Thin, always-importable wrappers around ``jax.profiler`` and XLA's
memory analysis; nothing here knows the engine (``parallel/``):

* :func:`annotate` — named host span (with attributes) inside traces.
* :func:`forensics_trace` — the alert plane's one-shot armed capture.
* :func:`timed` — wall-clock a function with ``block_until_ready`` on
  its outputs, so async XLA dispatch doesn't fake instant completion.
* :func:`enable_compile_cache` — the persistent compilation cache.
* :func:`plan_breakdown_gb`, :func:`peak_hbm_gb`, :func:`hbm_budget_gb`
  — XLA's static memory plan of any ``(jitted, args)``, the allocator's
  measured peak, and the per-device-kind plan budget.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Any, Callable, Optional, Tuple

import jax


# ---------------------------------------------------------------------------
# Forensics arming: the alerting plane (obs/alerts.py) arms a one-shot
# profiler capture when a `capture: true` rule fires; the NEXT training
# step that reaches a `forensics_trace()` call site consumes the arm and
# traces itself into the armed directory. Consume-once under a lock so
# an alert storm cannot stack traces, and every jax.profiler failure is
# swallowed — forensics is advisory, it must never break the step.

import threading as _threading

_FORENSICS_LOCK = _threading.Lock()
_FORENSICS_DIR: Optional[str] = None


def arm_forensics_trace(log_dir: str) -> None:
    """Arm the next :func:`forensics_trace` call site to capture a
    ``jax.profiler`` trace into ``log_dir``. Re-arming before the
    previous arm is consumed just re-points the directory."""
    global _FORENSICS_DIR
    with _FORENSICS_LOCK:
        _FORENSICS_DIR = log_dir


def forensics_armed() -> bool:
    with _FORENSICS_LOCK:
        return _FORENSICS_DIR is not None


@contextmanager
def forensics_trace():
    """Consume a pending forensics arm around the enclosed block,
    yielding the trace directory (or None when unarmed / the profiler
    refused to start). Graceful no-op off-TPU and on profiler errors."""
    global _FORENSICS_DIR
    with _FORENSICS_LOCK:
        log_dir, _FORENSICS_DIR = _FORENSICS_DIR, None
    if not log_dir:
        yield None
        return
    trace = None
    try:
        os.makedirs(log_dir, exist_ok=True)
        trace = jax.profiler.trace(log_dir)
        trace.__enter__()
    except Exception:
        trace = None
    try:
        yield log_dir if trace is not None else None
    finally:
        if trace is not None:
            try:
                trace.__exit__(None, None, None)
            except Exception:
                pass


def annotate(name: str, **attrs: Any):
    """Named host span in the profiler's own trace
    (``jax.profiler.TraceAnnotation``); keyword ``attrs`` become the
    event's stats. The one host-span primitive of the simulator path
    (the engine's ``baton.round.*`` spans): the span is written
    by the profiler session that writes the device planes, so it is on
    their clock, and outside a session it costs a flag test. The
    ``Tracer`` of ``utils/tracing.py`` and ``utils/metrics.Metrics`` are
    the HTTP server tier's, on ``time.time()``; no device trace sees
    them."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def timed(fn: Callable, *args: Any, **kwargs: Any) -> Tuple[Any, float]:
    """Run ``fn`` and return ``(result, seconds)``, blocking on all array
    outputs so the measurement covers device execution, not just
    dispatch."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


#: where compiled programs are kept when the environment names no place
#: for them: a fixed directory inside the checkout (the path is part of
#: what makes a cache entry findable again, so it must not move)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> Tuple[str, bool]:
    """Turn on JAX's persistent compilation cache; every entry point
    (``chip_smoke.py``, ``fedbench/run.py``, ``benchmarks/*.py``,
    ``demo.py``, ``examples/*.py``, ``python -m baton_tpu.loadgen``)
    calls this first. Returns ``(directory, came_from_environment)``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it itself
    and the directory is not touched here — whoever placed the cache
    from outside keeps it. Otherwise the cache lives in
    :data:`REPO_CACHE_DIR`, which ``.gitignore`` lists."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir, from_env = env_dir, True
    else:
        cache_dir, from_env = REPO_CACHE_DIR, False
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # low enough that every wave kernel is stored (they compile in tens
    # of seconds on the chip); high enough to skip one-op dispatches
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir, from_env


def is_oom_error(e: Exception) -> bool:
    """True when an exception is XLA saying the program cannot fit in
    device memory. On TPU backends an over-HBM program fails at COMPILE
    time with RESOURCE_EXHAUSTED and an allocation breakdown — that is
    a definitive "over budget", not an "analysis unavailable".

    A bare RESOURCE_EXHAUSTED is NOT enough: gRPC/transport reuse the
    same status for quota, rate-limit, and message-size failures.
    Require corroborating memory/compile evidence ("memory space hbm",
    "Ran out of memory", an allocation breakdown)."""
    msg = str(e).lower()
    if "out of memory" in msg or "allocation type: hlo temp" in msg:
        return True
    if "resource_exhausted" not in msg:
        return False
    return any(s in msg for s in (
        "hbm", "out of memory", "memory space", "allocation",
        "ran out of", "tpu compile",
    ))


def plan_breakdown_gb(jitted, args) -> dict:
    """Components of XLA's static memory plan for ``jitted(*args)``,
    in GiB — the single byte-accounting rule every plan consumer
    shares (``total = arguments + outputs + temps - aliases``).
    Compiles (never executes); raises on compile failure — callers that
    need the OOM-vs-unavailable distinction use :func:`_plan_gb_of`."""
    # a CompiledMemoryStats on jax 0.9, CPU and TPU alike
    ma = jitted.lower(*args).compile().memory_analysis()
    tot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return {
        "argument_gb": round(ma.argument_size_in_bytes / 2**30, 6),
        "output_gb": round(ma.output_size_in_bytes / 2**30, 6),
        "temp_gb": round(ma.temp_size_in_bytes / 2**30, 6),
        "alias_gb": round(ma.alias_size_in_bytes / 2**30, 6),
        "generated_code_gb": round(
            ma.generated_code_size_in_bytes / 2**30, 6),
        "plan_gb": round(tot / 2**30, 6),
    }


def _plan_gb_of(jitted, args) -> Optional[float]:
    """XLA's static memory plan for ``jitted(*args)`` in GiB (total).
    Compiles (never executes).

    Returns ``float("inf")`` when the compile itself dies with
    RESOURCE_EXHAUSTED: the plan is then *known* to exceed HBM even
    though no byte count is available, and OOM-guard callers must treat
    it as over any finite budget rather than as missing analysis."""
    try:
        # 6 decimals (inside the breakdown): tiny test programs must not
        # round to a deceptive 0.0 GiB (real wave kernels are >= MBs)
        tot = plan_breakdown_gb(jitted, args)["plan_gb"]
        return tot if tot > 0 else None
    except Exception as e:
        return float("inf") if is_oom_error(e) else None


def peak_hbm_gb(device) -> Optional[float]:
    """The runtime allocator's high-water mark for this process, in
    GiB; ``None`` on a backend that keeps no allocator statistics (the
    CPU). A measurement — XLA's static plan (:func:`plan_breakdown_gb`)
    is only ever a plan and is never returned under this name.

    The TPU runtime counts live arrays and a running program's
    temporaries apart: ``peak_bytes_in_use`` is arrays only, the
    temporaries are ``peak_bytes_reserved`` (PR 21 chip run, v5e: a
    program planned with 2.000 GiB of temporaries moved
    ``peak_bytes_reserved`` by 2.000 GiB and ``peak_bytes_in_use`` by
    0.002). The figure is their sum — an upper bound on the
    simultaneous peak, as the two marks need not coincide in time."""
    stats = device.memory_stats()
    if not stats:
        return None
    peak = (stats["peak_bytes_in_use"]
            + stats.get("peak_bytes_reserved", 0))
    return round(peak / 2**30, 6)


# Plan-space budgets for the OOM guard (ROADMAP D13): HBM capacity
# minus runtime/framework headroom, to set against XLA's static plan.
HBM_BUDGET_GB = {
    "TPU v4": 29.0,       # 32 GB
    "TPU v5 lite": 13.5,  # v5e, 16 GB
    "TPU v5e": 13.5,
    "TPU v5": 90.0,       # v5p, 95 GB
    "TPU v5p": 90.0,
    "TPU v6 lite": 28.0,  # v6e, 32 GB
    "TPU v6e": 28.0,
}


def hbm_budget_gb(device) -> float:
    """Plan-space OOM-guard budget for ``device``.

    A ``device_kind`` the table does not hold is a ``ValueError``, never
    a default: a budget guessed for an unknown device guards nothing."""
    kind = device.device_kind
    for prefix, budget in HBM_BUDGET_GB.items():
        if kind.startswith(prefix):
            return budget
    raise ValueError(
        f"no HBM budget for device kind {kind!r}: add it to "
        "profiling.HBM_BUDGET_GB with its source, or pass the caller an "
        "explicit budget_gb")
