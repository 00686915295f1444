"""Run one cell of the benchmark once.

    python fedbench/run.py --workload resnet18_c32_w1 --seed 1 \\
        --seconds 30 --trace 0

Builds the cell's model and cohort from the seed, stages them, checks
one probe round against the configuration's plain reference
(``fedbench/references/<config>.py`` through ``fedbench/reference.py``),
warms up the cell's own shapes, then measures: with ``--trace 0`` a
window of ``--seconds`` giving the end-to-end metrics, with ``--trace
1`` a few rounds under ``jax.profiler.trace`` giving the layer metrics
and the breakdown. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, traced
also ``breakdown``, and last ``compared``: each number that decided
``correct`` beside its limit, which are also the last lines of standard
error); everything before it is commentary.

Without ``--rehearse-cpu`` the run needs a TPU with at least the cell's
chips and otherwise exits non-zero with no result. ``--rehearse-cpu``
runs the same control flow at each file's ``tiny`` sizes on whatever
JAX has, and reports counts only: every time-valued metric is null.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # as near the process's start as Python gets

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedbench import manifest  # noqa: E402

NO_DEVICE_RC = 3
# backend compile *or* load from the persistent cache: JAX times both
# under this event, once for every program it builds
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(text: str) -> None:
    print(f"[fedbench] {text}", flush=True)


class CompileCounter:
    """Counts the programs JAX builds while ``counting`` is set."""

    def __init__(self):
        self.n = 0
        self.counting = False

    def __call__(self, event: str, duration: float, **_):
        if self.counting and event == COMPILE_EVENT:
            self.n += 1


def job_of(workload: dict, tiny: bool) -> dict:
    """The cell's sizes, with its ``tiny`` block laid over them for a
    rehearsal."""
    job = {k: v for k, v in workload.items() if k != "tiny"}
    if tiny:
        job.update(workload["tiny"])
    return job


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``, storing every program: the slice, pad,
    fold and probe programs compile in under a second each and would
    otherwise be compiled again by every run."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


# ------------------------------------------------------------------ rounds
def run_rounds(sim, params, data, n_samples, key, job, first_index: int,
               keep_going):
    """Call ``FedSim.run_round`` back to back through the normal path,
    parameters fed forward, until ``keep_going(n_dispatched)`` is false.

    Before dispatching round i the host blocks on the loss of round i-2
    and stamps that round's completion there, so the harness never
    imposes a per-round sync the program does not have. Stamps are
    taken only there, with two rounds dispatched behind the one
    settled: the last two rounds are settled after the loop for their
    losses and carry no stamp (today ``run_round`` syncs itself, so
    both would be stamped at the same instant). Returns ``(params,
    stamps, losses, attempted, failed)``."""
    import jax

    pending = collections.deque()
    stamps, losses = [], []
    attempted = failed = 0

    def settle(stamp: bool):
        with jax.profiler.TraceAnnotation("fedbench.sync"):
            loss = float(pending.popleft())  # host fetch: waits for the round
        if stamp:
            stamps.append(time.perf_counter())
        losses.append(loss)

    while keep_going(attempted):
        if len(pending) == 2:
            settle(stamp=True)
        try:
            with jax.profiler.TraceAnnotation("fedbench.round"):
                res = sim.run_round(
                    params, data, n_samples,
                    jax.random.fold_in(key, first_index + attempted),
                    n_epochs=job["local_epochs"], wave_size=job["wave_size"],
                    collect_client_losses=False)
        except Exception:  # a round that raises is a failed round
            traceback.print_exc()
            attempted += 1
            failed += 1
            break
        attempted += 1
        params = res.params
        pending.append(res.loss_history[-1])
    while pending:
        settle(stamp=False)
    failed += sum(not (l == l and abs(l) != float("inf")) for l in losses)
    return params, stamps, losses, attempted, failed


def traced(work, span_prefixes, keep_dir, cell: str):
    """``(work(), rows)``: run ``work`` under ``jax.profiler.trace`` (the
    Python tracer off: it slows the host it is measuring) and read the
    trace's rows on the spot, before the trace is deleted; ``rows`` is
    ``None`` if the profiler left no file. With ``keep_dir`` the rows
    and the raw trace are copied there."""
    import jax

    from fedbench import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="fedbench_trace_") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            result = work()
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            return result, None
        rows = trace_reduce.read_events(found[0], span_prefixes)
        if keep_dir:
            os.makedirs(keep_dir, exist_ok=True)
            trace_reduce.write_rows(
                rows, os.path.join(keep_dir, f"{cell}.rows.json.gz"))
            shutil.copy(found[0], os.path.join(keep_dir, f"{cell}.xplane.pb"))
    return result, rows


# ------------------------------------------------------------------ set-up
def build_cell(root, config, job, chips, seed, tiny):
    """The cell's program and inputs from the seed: ``(model, params,
    n_samples, capacity, data, mesh, sim)``. ``FedSim`` takes the batch
    size, the learning rate, the mesh and whatever the ``engine`` blocks
    of the configuration and the workload state."""
    import jax

    from baton_tpu.parallel.engine import FedSim
    from baton_tpu.parallel.mesh import make_mesh, shard_client_arrays
    from fedbench import data as cohort

    model = manifest.build_model(config, tiny)
    params = jax.jit(model.init)(jax.random.key(seed))
    n_samples = cohort.client_sizes(
        root, job["samples_per_client"], job["clients"], seed)
    capacity = cohort.capacity_for(n_samples, job["batch"])
    data = cohort.make_cohort(
        root, manifest.input_spec(config, tiny), n_samples, capacity,
        job.get("seq_len"), cohort.data_key(seed + 1))
    mesh = make_mesh(chips) if chips > 1 else None
    if mesh is not None:
        data = shard_client_arrays(data, mesh)
    sim = FedSim(model, batch_size=job["batch"],
                 learning_rate=job["learning_rate"], mesh=mesh,
                 **manifest.engine_args(config, job))
    return model, params, n_samples, capacity, data, mesh, sim


# ------------------------------------------------------------------- probe
def probe_cohort(root, config, job, tiny, seed):
    """``(data, sizes)``: the seeded probe cohort, 4 clients holding 1/4,
    2/4, 3/4 and 4/4 of one batch of the cell's inputs, rounded down, and
    no client fewer than one sample: 1, 1, 2, 3 at batch 3, and at batch
    1 four clients of one sample each, whose four weights are equal."""
    import numpy as np

    from fedbench import data as cohort

    batch = job["batch"]
    sizes = np.asarray([max(1, batch * k // 4) for k in (1, 2, 3, 4)],
                       np.int32)
    return cohort.make_cohort(
        root, manifest.input_spec(config, tiny), sizes, batch,
        job.get("seq_len"), cohort.data_key(seed + 7919)), sizes


def probe(root, config, job, tiny, seed, sim, params, mesh):
    """Correctness rule 1 (and 3 on a mesh): one round of the probe
    cohort through ``FedSim.run_round`` in the cell's layout against the
    configuration's plain reference (``fedbench/references/<config>.py``),
    and on a mesh against the same round on one device, all from the
    run's initial ``params``. Where the cell's ``engine`` block holds a
    ``trainable`` predicate the disagreements are over the leaves it
    accepts, and every leaf it rejects has to come out of the round
    exactly as it went in. Returns ``(ok, {name: (number compared, its
    limit)})``; a limit of ``None`` is a number printed and not held."""
    import jax

    from baton_tpu.parallel.engine import FedSim
    from baton_tpu.parallel.mesh import shard_client_arrays
    from fedbench import reference

    pdata, sizes = probe_cohort(root, config, job, tiny, seed)
    key = jax.random.key(seed + 1299709)
    batch, lr = job["batch"], job["learning_rate"]

    def one_round(engine, placed):
        return engine.run_round(params, placed, sizes, key, n_epochs=1,
                                collect_client_losses=False)

    placed = shard_client_arrays(pdata, mesh) if mesh is not None else pdata
    got = one_round(sim, placed)
    # a mesh round's parameters are replicated; compare on one device
    got_params = jax.device_put(got.params, jax.devices()[0])
    engine = manifest.engine_args(config, job)
    trainable = engine.get("trainable")
    loss = manifest.load_module(root, "references", config["name"]
                                ).make_loss(manifest.sized(config, tiny))
    want, want_loss = reference.reference_round(
        loss, params, pdata, sizes, lr, trainable)
    found = {"reference": reference.update_disagreement(
        params, got_params, want, trainable=trainable),
        "reference_l2": reference.update_disagreement(
            params, got_params, want, "l2", trainable)}
    loss_gap = abs(float(got.loss_history[-1]) - want_loss)
    if mesh is not None:
        one = one_round(FedSim(sim.model, batch_size=batch, learning_rate=lr,
                               mesh=None, **engine), pdata)
        found["one_device"] = reference.update_disagreement(
            params, got_params, one.params, trainable=trainable)
    tol = config["probe_tolerance"]
    compared = {k: (v, tol) for k, v in found.items()}
    compared["reference_l2"] = (found["reference_l2"],
                                config.get("probe_l2_tolerance"))
    said = "probe: update disagreement " + ", ".join(
        f"{k} {v:.4g} (limit {limit})" for k, (v, limit) in compared.items())
    compared["loss_gap"] = (loss_gap, tol)
    said += f"; loss gap {loss_gap:.3g} (limit {tol})"
    if trainable is not None:
        same, n_held = reference.held_unchanged(params, got_params, trainable)
        compared["frozen_leaves_changed"] = (n_held - same, 0)
        said += f"; frozen leaves unchanged: {same} of {n_held}"
    ok = all(limit is None or v <= limit for v, limit in compared.values())
    say(f"{said}: {'ok' if ok else 'FAILED'}")
    return ok, compared


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on any platform; counts only")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also write the trace's event rows (gzipped JSON) "
                         "and the raw .xplane.pb into DIR")
    args = ap.parse_args(argv)
    tiny = args.rehearse_cpu
    root = manifest.ROOT

    bench = manifest.load_manifest(root)
    entry = manifest.cell_entry(bench, args.workload)
    workload = manifest.load_workload(root, args.workload)
    config = manifest.load_config(root, bench, entry["config"])
    job = job_of(workload, tiny)
    chips = entry["chips"]

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if len(devices) < chips or (platform != "tpu" and not tiny):
        print(f"fedbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"reports {len(devices)} {platform} device(s). No result.",
              file=sys.stderr)
        return NO_DEVICE_RC
    if not tiny:
        # a rehearsal runs in-process under the tests and leaves the
        # process's JAX configuration as it found it
        cache_dir = configure_cache(root)
    peaks = None if tiny else manifest.load_peaks(root, kind)
    say(f"cell {args.workload} seed {args.seed} on {len(devices)} x {kind} "
        f"({platform})" + (" REHEARSAL: tiny sizes, no time is reported"
                           if tiny else f", compile cache {cache_dir}"))

    from fedbench import trace_reduce

    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    used = devices[:chips]

    # ---- set-up: model, cohort, staging
    t_init = time.perf_counter()
    model, params, n_samples, capacity, data, mesh, sim = build_cell(
        root, config, job, chips, args.seed, tiny)
    jax.block_until_ready((params, data))
    init_s = time.perf_counter() - t_init

    samples_per_round = int(n_samples.sum()) * job["local_epochs"]
    n_waves = -(-job["clients"] // (job["wave_size"] or job["clients"]))
    required = manifest.load_module(root, "flops", entry["config"]).required(
        config, dict(job, n_samples=[int(n) for n in n_samples]))
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    param_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    say(f"{model.name}: {n_params:,} parameters; {job['clients']} clients, "
        f"{int(n_samples.sum())} real samples in {job['clients'] * capacity} "
        f"slots, batch {job['batch']}, {job['local_epochs']} epoch(s), "
        f"{n_waves} wave(s) a round; required "
        f"{required['flops_per_sample'] / 1e9:.4g} GFLOP a sample")

    # ---- correctness probe, outside the window
    t_probe = time.perf_counter()
    probe_ok, compared = probe(root, config, job, tiny, args.seed, sim,
                               params, mesh)
    probe_s = time.perf_counter() - t_probe
    probe_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used), default=0)
    say(f"after the probe: allocator peak_bytes_in_use {probe_peak / 2**30:.3f}"
        f" GiB on the fullest device (0 where the backend keeps no "
        f"statistics); the parameters are {param_bytes / 2**30:.3f} GiB")

    # ---- warm-up: the cell's own shapes, first round compiles or loads
    key = jax.random.key(args.seed + 2)
    t_first = time.perf_counter()
    n_warm = job["warmup_rounds"]  # at least 1
    params, _, warm_losses, _, warm_failed = run_rounds(
        sim, params, data, n_samples, key, job, 0, lambda n: n < 1)
    first_round_s = time.perf_counter() - t_first
    params, _, losses, _, failed = run_rounds(
        sim, params, data, n_samples, key, job, 1, lambda n: n < n_warm - 1)
    warm_losses += losses
    warm_failed += failed
    setup_s = time.perf_counter() - _T_PROCESS

    # ---- the window
    compiles.counting = True
    reduced = None
    if args.trace:
        names = manifest.load_trace_names(root, config)
        n_trace = job["trace_rounds"]
        (params, stamps, losses, attempted, failed), rows = traced(
            lambda: run_rounds(sim, params, data, n_samples, key, job, n_warm,
                               lambda n: n < n_trace),
            names["span_prefixes"], args.keep_trace, args.workload)
        if rows is not None:
            reduced = trace_reduce.reduce_rows(
                rows, manifest.load_op_categories(root), names)
    else:
        deadline = time.perf_counter() + args.seconds
        params, stamps, losses, attempted, failed = run_rounds(
            sim, params, data, n_samples, key, job, n_warm,
            lambda n: time.perf_counter() < deadline)
    compiles.counting = False
    jax.block_until_ready(params)
    failed += warm_failed

    # ---- what was seen
    stats = [d.memory_stats() or {} for d in used]
    peak_bytes = max((s.get("peak_bytes_in_use", 0)
                      + s.get("peak_bytes_reserved", 0) for s in stats),
                     default=0) or None
    say("allocator, fullest device: " + (
        "no statistics on this backend" if peak_bytes is None else
        " + ".join(f"{k} {max(s.get(k, 0) for s in stats) / 2**30:.3f} GiB"
                   for k in ("peak_bytes_in_use", "peak_bytes_reserved"))
        + f" of bytes_limit {stats[0].get('bytes_limit', 0) / 2**30:.2f} GiB"))
    all_losses = warm_losses + losses
    say("loss by round: " + " ".join(f"{l:.4f}" for l in all_losses[:12])
        + (f" ... {all_losses[-1]:.4f} (round {len(all_losses)})"
           if len(all_losses) > 12 else ""))
    # round 12 against round 1: at these learning rates the loss rises
    # for its first three or four rounds, so a traced run's four to seven
    # rounds cannot be judged by it and rest on the probe
    falling = len(all_losses) < 12 or all_losses[11] < all_losses[0]
    correct = bool(probe_ok and failed == 0 and falling)
    compared["failed_rounds"] = (failed, 0)
    if len(all_losses) >= 12:
        # has to lie below its limit, round 1's loss, and not on it
        compared["round_12_loss"] = (all_losses[11], all_losses[0])

    intervals = [b - a for a, b in zip(stamps, stamps[1:])]
    end_to_end = {}
    if intervals and not tiny:
        span = stamps[-1] - stamps[0]
        end_to_end = {
            "samples_per_s_per_chip":
                samples_per_round * len(intervals) / span / chips,
            "round_s": statistics.median(intervals),
            "setup_s": setup_s,
        }
        mfu = (end_to_end["samples_per_s_per_chip"]
               * required["flops_per_sample"] / peaks["flops_per_s_bf16"])
        say(f"{len(intervals)} round intervals over {span:.3f} s: median "
            f"{end_to_end['round_s']:.5f} s, min {min(intervals):.5f}, max "
            f"{max(intervals):.5f}; model-FLOP utilisation "
            f"{100 * mfu:.2f} % of {peaks['flops_per_s_bf16'] / 1e12:.0f} "
            f"TFLOP/s (required FLOPs of real samples only); set-up "
            f"{setup_s:.2f} s (imports and files {t_init - _T_PROCESS:.2f}, "
            f"init {init_s:.2f}, probe {probe_s:.2f}, first round "
            f"{first_round_s:.2f})")
        # a stalled host shows here: the mean above carries it, the
        # median does not
        slow = [(i, round(v, 5)) for i, v in enumerate(intervals)
                if v > 1.01 * end_to_end["round_s"]]
        say(f"intervals over 1.01 x the median (index, s): {slow or 'none'}")
    else:
        say(f"{attempted} rounds attempted and settled, {len(stamps)} stamped")

    counters = {
        "compiles_in_window": compiles.n,
        "peak_hbm_bytes": peak_bytes,
        "n_waves": n_waves,
        # a round's real samples, and the sample slots its clients hold
        # (clients x capacity); the slots of clients that pad a wave are
        # the program's to count (baton.round.stage's attributes)
        "real_samples": int(n_samples.sum()),
        "sample_slots": job["clients"] * capacity,
    }
    if not tiny:
        counters.update(init_s=init_s, first_round_s=first_round_s)
    cell = {"name": args.workload, "chips": chips, "job": job,
            "required": required, "peaks": peaks}

    if args.trace:
        entries = manifest.metrics_for(bench["per_layer"], args.workload)
        values = {}
        for m in entries:
            reader = manifest.load_module(root, "layer_metrics", m["name"])
            values[m["name"]] = reader.read(reduced, counters, cell)
    else:
        entries = manifest.metrics_for(bench["end_to_end"], args.workload)
        values = {m["name"]: end_to_end.get(m["name"]) for m in entries}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in entries
               if values[m["name"]] is not None or tiny}

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = trace_reduce.device_mean(reduced, "busy_s")
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
        for line in trace_reduce.commentary(reduced):
            say(line)
    # what decided ``correct``, each number beside its limit: the last
    # lines of standard error and the last key of the result's line,
    # which is all the driver keeps of a run that came out not correct
    for name, (value, limit) in compared.items():
        print(f"fedbench compared: {name} {value} (limit {limit})",
              file=sys.stderr, flush=True)
    # JSON has no word for a number that is not finite
    result["compared"] = {
        name: {"value": value if math.isfinite(value) else str(value),
               "limit": limit}
        for name, (value, limit) in compared.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
