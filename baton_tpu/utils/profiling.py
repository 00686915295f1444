"""JAX profiler hooks (SURVEY §5 "Tracing/profiling: absent" — new).

Thin, always-importable wrappers around ``jax.profiler``:

* :func:`profile_trace` — context manager writing an XLA/TensorBoard
  trace (HLO timelines, per-op device time) to a directory. Enabled
  explicitly or via ``BATON_TPU_PROFILE=<dir>``; a no-op otherwise, so
  call sites can wrap hot paths unconditionally.
* :func:`annotate` — named host span (with attributes) inside traces.
* :func:`timed` — wall-clock a function with ``block_until_ready`` on
  its outputs, so async XLA dispatch doesn't fake instant completion.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Any, Callable, Optional, Tuple

import jax

ENV_VAR = "BATON_TPU_PROFILE"


@contextmanager
def profile_trace(log_dir: Optional[str] = None):
    """Trace the enclosed block to ``log_dir`` (or ``$BATON_TPU_PROFILE``).

    No-op when neither is set — safe to leave in production paths.
    """
    log_dir = log_dir or os.environ.get(ENV_VAR)
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


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
    (``FedSim.run_round``'s ``baton.round.*`` spans): the span is written
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
    (``chip_smoke.py``, ``bench.py``, ``benchmarks/*.py``, ``demo.py``,
    ``examples/*.py``, ``python -m baton_tpu.loadgen``) calls this
    first. Returns ``(directory, came_from_environment)``.

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


def resolve_artifact_path(out_path: str, run_has_tpu_success: bool,
                          prior_has_tpu_success) -> str:
    """Shared artifact-clobber policy for the hardware sweeps
    (wave_sweep.py, attention_sweep.py): never overwrite an artifact
    holding TPU measurements with a run that produced none — every cell
    failing, or a CPU smoke run with plausible-looking numbers. The
    lesser run is still evidence: it goes to a ``*_failed`` sibling
    instead.

    ``prior_has_tpu_success`` is a callable applied to the parsed prior
    JSON (artifact shapes differ per sweep); unreadable/foreign priors
    are treated as clobber-safe."""
    import json as _json

    if run_has_tpu_success:
        return out_path
    try:
        with open(out_path) as f:
            prior = _json.load(f)
        keep = bool(prior_has_tpu_success(prior))
    except (OSError, ValueError, TypeError, AttributeError, KeyError):
        return out_path
    if not keep:
        return out_path
    base, ext = os.path.splitext(out_path)
    return f"{base}_failed{ext or '.json'}"


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


def _lower_wave_kernel(sim, params, data, n_samples, key,
                       wave_size: Optional[int] = None, n_epochs: int = 1):
    """(jitted, args) for ONE wave of ``sim``'s round, honoring a
    trainable/frozen partition — the program whose memory plan stands in
    for the round's footprint. A ``wave_size`` larger than the cohort is
    PADDED to size (run_round pads its last wave the same way) — slicing
    alone would hand vmap mismatched leading axes, and the resulting
    trace error must not read as "no analysis, assume it fits"."""
    import jax
    import jax.numpy as jnp

    tr, fz = sim._split(params)
    n_samples = jnp.asarray(n_samples)
    c = int(n_samples.shape[0])
    w = wave_size or c
    take = min(w, c)
    d0 = jax.tree_util.tree_map(lambda a: a[:take], data)
    n0 = n_samples[:take]
    r0 = jax.random.split(key, take)
    if take < w:
        d0, n0, r0 = sim._pad_wave(d0, n0, r0, w)
    jitted = jax.jit(lambda a, b, d, n, r: sim._wave_sums_raw(
        a, b, d, n, r, n_epochs))
    return jitted, (tr, fz, d0, n0, r0)


def peak_hbm_gb(device) -> Optional[float]:
    """The runtime allocator's high-water mark for this process, in
    GiB; ``None`` on a backend that keeps no allocator statistics (the
    CPU). A measurement — XLA's static plan (:func:`plan_breakdown_gb`,
    :func:`fedsim_wave_plan_gb`) is only ever a plan and is never
    returned under this name.

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


# Plan-space budgets for the OOM guard, in two tiers (ROADMAP D13).
#
# Default tier: HBM capacity minus runtime/framework headroom — for
# kernels whose XLA memory plan tracks the true allocation
# (matmul-shaped programs: im2col convs, transformers).
#
# Anchored tier (ANCHORED_DIRECT_CONV_BUDGET_GB): for the direct-conv
# ResNet wave kernels the plan overcounts what the runtime reserves.
# PR 21 chip run, v5e: the wave-32 kernel plans at 14.95 GiB (14.82 of
# it temporaries) and the runtime reserved 13.49 GiB to run it. The
# 17.5 GiB figure itself is builder-recorded 2026-07-30/31 (a wave-64
# plan of 17.42 GiB that executed, a wave-128 that did not) on a stack
# that no longer exists; record deleted in PR 21.
HBM_BUDGET_GB = {
    "TPU v4": 29.0,       # 32 GB
    "TPU v5 lite": 13.5,  # v5e, 16 GB
    "TPU v5e": 13.5,
    "TPU v5": 90.0,       # v5p, 95 GB
    "TPU v5p": 90.0,
    "TPU v6 lite": 28.0,  # v6e, 32 GB
    "TPU v6e": 28.0,
}
# The anchored overlay applies ONLY to the direct-conv ResNet wave
# kernel class. It must NOT be used for matmul-shaped kernels (im2col,
# transformers) whose plans track real allocation.
ANCHORED_DIRECT_CONV_BUDGET_GB = {
    "TPU v5 lite": 17.5,
    "TPU v5e": 17.5,
}

# The exact kernel identity the anchor covers: the direct-lowering
# ResNet wave kernel at per-client batch 32. The plan-overcount evidence
# extends no further — a direct_b48 kernel is a different program whose
# 16-17.5 GiB plan could be a real over-HBM demand.
ANCHORED_CONV_KERNEL = {"impl": "direct", "batch_size": 32}


def conv_kernel_class(impl: str, batch_size: int = 32) -> str:
    """OOM-guard kernel class for a per-client-conv wave kernel.

    Returns ``"anchored_direct_conv"`` only for the FULL anchored
    kernel identity (lowering impl AND per-client batch size matching
    :data:`ANCHORED_CONV_KERNEL`); every other conv config — im2col,
    shift, or an unanchored direct batch — gets the conservative
    ``"default"`` tier."""
    if (impl == ANCHORED_CONV_KERNEL["impl"]
            and int(batch_size) == ANCHORED_CONV_KERNEL["batch_size"]):
        return "anchored_direct_conv"
    return "default"


def hbm_budget_gb(device, kernel_class: str = "default") -> float:
    """Plan-space OOM-guard budget for ``device``.

    ``kernel_class="anchored_direct_conv"`` selects the calibrated
    overlay for the direct-conv ResNet wave kernels (see
    ANCHORED_DIRECT_CONV_BUDGET_GB); every other kernel class gets the
    conservative capacity-minus-headroom budget, because for
    matmul-shaped programs the plan is close to the true allocation and
    admitting plans above physical HBM would execute a real OOM.

    A ``device_kind`` the table does not hold is a ``ValueError``, never
    a default: a budget guessed for an unknown device guards nothing."""
    kind = device.device_kind
    if kernel_class == "anchored_direct_conv":
        for prefix, budget in ANCHORED_DIRECT_CONV_BUDGET_GB.items():
            if kind.startswith(prefix):
                return budget
    for prefix, budget in HBM_BUDGET_GB.items():
        if kind.startswith(prefix):
            return budget
    raise ValueError(
        f"no HBM budget for device kind {kind!r}: add it to "
        "profiling.HBM_BUDGET_GB with its source, or pass an explicit "
        "budget (FedSim.auto_wave_size(budget_gb=...))")


def fedsim_wave_plan_gb(sim, params, data, n_samples, key,
                        wave_size: Optional[int] = None,
                        n_epochs: int = 1) -> Optional[float]:
    """XLA's static HBM plan (GiB) for one wave's kernel, compiled
    WITHOUT executing. The OOM guard: benchmark stages check the
    compiler's own budget first and skip — recording the plan — instead
    of running a program that cannot fit. Returns None when analysis is
    unavailable (proceed) and ``float("inf")`` when the compile itself
    RESOURCE_EXHAUSTs (a definitive does-not-fit — guards must skip)."""
    try:
        jitted, args = _lower_wave_kernel(sim, params, data, n_samples,
                                          key, wave_size, n_epochs)
        return _plan_gb_of(jitted, args)
    except Exception as e:
        return float("inf") if is_oom_error(e) else None


def fedsim_fused_donation_plan(sim, params, data, n_samples, key,
                               n_rounds: int = 2, n_epochs: int = 1,
                               wave_size: Optional[int] = None) -> dict:
    """XLA static memory plans for the fused multi-round program
    compiled WITH and WITHOUT buffer donation — the measured answer to
    "what does ``donate_argnums`` on the round step actually buy".

    Compiles both variants (never executes); donation shows up in the
    plan's ``alias_gb`` (the donated params/server-opt inputs alias the
    outputs, so the globals stop being double-buffered across the
    dispatch). Returns ``{"donate_on": breakdown, "donate_off":
    breakdown, "delta_gb": off - on}`` with :func:`plan_breakdown_gb`
    dicts; raises on compile failure — callers decide whether an
    unmeasured delta is skippable (and must record why).
    """
    import jax
    import jax.numpy as jnp

    from baton_tpu.ops.padding import round_up

    tr, fz = sim._split(params)
    n_samples = jnp.asarray(n_samples)
    c = int(n_samples.shape[0])
    unit = sim._clients_per_wave_unit()
    wave = round_up(wave_size if wave_size is not None else c, unit)
    n_waves = -(-c // wave)
    rngs = jax.random.split(key, c)
    data, n_samples, _ = sim._pad_wave(data, n_samples, rngs,
                                       n_waves * wave)
    data_w = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a).reshape((n_waves, wave) + a.shape[1:]),
        data,
    )
    n_w = n_samples.reshape(n_waves, wave)
    sos = (sim.server_optimizer.init(tr)
           if sim.server_optimizer is not None else None)
    args = (tr, fz, data_w, n_w, key, sos)
    out = {}
    for label, donate in (("donate_on", True), ("donate_off", False)):
        fn = sim._make_rounds_fused(n_epochs, n_rounds, donate=donate)
        out[label] = plan_breakdown_gb(fn, args)
    out["delta_gb"] = round(
        out["donate_off"]["plan_gb"] - out["donate_on"]["plan_gb"], 6
    )
    return out
