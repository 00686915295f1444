"""Wave-scheduling sweep under real HBM pressure (SURVEY §7 "hard parts").

A 128-client ResNet-18/CIFAR cohort doesn't need waves for *compute* —
one chip can vmap all 128 — but per-client params + optimizer state +
activations scale linearly with the wave, so ``wave_size`` is the knob
that trades peak HBM against dispatch overhead. This sweep measures that
trade on the real chip: rounds/sec and peak HBM for wave_size ∈
{16, 32, 64} (the full 128-client wave does not fit one 16 GB chip).

Each setting runs in its OWN subprocess because the allocator's peaks
(``device.memory_stats()``) are high-water marks for the process
lifetime — the only way to attribute a peak to one setting is process
isolation. This parent never touches the JAX backend, so each child
has the chip to itself. Exits non-zero if any setting failed.

Usage:
    python benchmarks/wave_sweep.py             # full sweep -> table +
                                                # chiprun_out/wave_sweep_tpu.json
    python benchmarks/wave_sweep.py --wave 32   # one setting, one JSON line
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    # invoked as `python benchmarks/wave_sweep.py`: sys.path[0] is
    # benchmarks/, so the baton_tpu package needs the repo root added
    sys.path.insert(0, _REPO)

N_CLIENTS = 128
SAMPLES_PER_CLIENT = 48
BATCH_SIZE = 32
N_EPOCHS = 1
WAVES = (16, 32, 64)
CHILD_TIMEOUT_S = 420.0


def build_benchmark_fedsim(n_clients: int = N_CLIENTS,
                           samples_per_client: int = SAMPLES_PER_CLIENT,
                           batch_size: int = BATCH_SIZE):
    """The canonical benchmark workload every plan/sweep tool must agree
    on: CIFAR-shaped `default_rng(0)` clients + ResNet-18 bf16 FedSim.
    Returns ``(sim, params, data, n_samples, key)``. Shared by
    ``run_one`` and ``plan_probe.py`` so the guard-calibration probe
    measures exactly the kernel the sweep executes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from baton_tpu.models.resnet import resnet18_cifar_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    rng = np.random.default_rng(0)
    datasets = [
        {
            "x": rng.normal(
                size=(samples_per_client, 32, 32, 3)
            ).astype(np.float32),
            "y": rng.integers(
                0, 10, size=(samples_per_client,)
            ).astype(np.int32),
        }
        for _ in range(n_clients)
    ]
    data, n_samples = stack_client_datasets(datasets, batch_size=batch_size)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    model = resnet18_cifar_model(compute_dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0))
    sim = FedSim(model, batch_size=batch_size, learning_rate=0.05)
    return sim, params, data, n_samples, jax.random.key(1)


def run_one(wave_size: int) -> dict:
    import jax

    from baton_tpu.utils.profiling import enable_compile_cache, peak_hbm_gb

    enable_compile_cache()
    dev = jax.devices()[0]
    sim, params, data, n_samples, key = build_benchmark_fedsim()

    t_c = time.perf_counter()
    res = sim.run_round(params, data, n_samples, key, n_epochs=N_EPOCHS,
                        wave_size=wave_size, collect_client_losses=False)
    float(res.loss_history[-1])
    compile_s = time.perf_counter() - t_c

    iters = 8
    p = res.params
    t0 = time.perf_counter()
    for i in range(iters):
        res = sim.run_round(p, data, n_samples, jax.random.fold_in(key, i),
                            n_epochs=N_EPOCHS, wave_size=wave_size,
                            collect_client_losses=False)
        p = res.params
    float(res.loss_history[-1])
    dt = time.perf_counter() - t0

    rec = {
        "wave_size": wave_size,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "clients": N_CLIENTS,
        "rounds_per_sec": round(iters / dt, 3),
        "peak_hbm_gb": peak_hbm_gb(dev),
        "peak_hbm_source": "allocator",
        "compile_s": round(compile_s, 1),
    }
    return rec


def _has_tpu_success(results) -> bool:
    return any("rounds_per_sec" in r and r.get("platform") == "tpu"
               for r in results)


def resolve_out_path(out_path: str, results: list) -> str:
    """Artifact-clobber guard — the shared policy lives in
    profiling.resolve_artifact_path; this wrapper supplies the
    wave-sweep artifact shape."""
    from baton_tpu.utils.profiling import resolve_artifact_path

    return resolve_artifact_path(
        out_path,
        _has_tpu_success(results),
        lambda prior: _has_tpu_success(prior.get("results", ())),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--wave", type=int, default=None,
                    help="run one setting and print its JSON line (child mode)")
    ap.add_argument("--waves", default=None,
                    help="comma-separated sweep settings (default "
                         f"{','.join(map(str, WAVES))})")
    ap.add_argument("--out", default=os.path.join(
        _REPO, "chiprun_out", "wave_sweep_tpu.json"))
    args = ap.parse_args()

    if args.wave is not None:
        print(json.dumps(run_one(args.wave)))
        return

    waves = (tuple(int(x) for x in args.waves.split(","))
             if args.waves else WAVES)
    results = []
    for w in waves:
        t0 = time.perf_counter()
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--wave", str(w)],
                capture_output=True, text=True, timeout=CHILD_TIMEOUT_S,
                env=env,
            )
        except subprocess.TimeoutExpired as e:
            # a hung child must not discard the settings already measured
            results.append({
                "wave_size": w, "failed": "timeout",
                "timeout_s": CHILD_TIMEOUT_S,
                "wall_s": round(time.perf_counter() - t0, 1),
            })
            print(f"wave {w}: TIMEOUT after {CHILD_TIMEOUT_S:.0f}s",
                  file=sys.stderr)
            continue
        if proc.returncode != 0:
            # the failure is recorded with its cause, and fails the sweep
            tail = proc.stderr.strip()[-2000:]
            reason = "oom" if (
                "RESOURCE_EXHAUSTED" in tail or "OOM" in tail
                or "memory" in tail.lower()
            ) else "error"
            results.append({
                "wave_size": w, "failed": reason,
                "stderr_tail": tail[-600:],
                "wall_s": round(time.perf_counter() - t0, 1),
            })
            print(f"wave {w}: FAILED ({reason})\n{tail}", file=sys.stderr)
            continue
        try:
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            results.append({
                "wave_size": w, "failed": "bad-output",
                "stdout_tail": proc.stdout.strip()[-300:],
                "wall_s": round(time.perf_counter() - t0, 1),
            })
            print(f"wave {w}: unparseable child output", file=sys.stderr)
            continue
        rec["wall_s"] = round(time.perf_counter() - t0, 1)
        results.append(rec)
        hbm = rec.get("peak_hbm_gb")
        hbm_txt = f"{hbm:6.3f} GB" if hbm is not None else "   n/a"
        print(f"wave {w:4d}: {rec['rounds_per_sec']:6.3f} rounds/s  "
              f"peak HBM {hbm_txt}  "
              f"(compile {rec['compile_s']}s)", file=sys.stderr)

    out = {
        "config": {
            "model": "resnet18_bf16", "clients": N_CLIENTS,
            "samples_per_client": SAMPLES_PER_CLIENT,
            "batch_size": BATCH_SIZE, "n_epochs": N_EPOCHS,
        },
        "results": results,
    }
    dest = resolve_out_path(args.out, results)
    if dest != args.out:
        print(f"all waves failed; keeping recorded artifact, "
              f"writing failures to {dest}", file=sys.stderr)
    os.makedirs(os.path.dirname(dest) or ".", exist_ok=True)
    with open(dest, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    failed = [r["wave_size"] for r in results if "failed" in r]
    if failed:
        raise SystemExit(f"wave sweep: settings {failed} failed")


if __name__ == "__main__":
    main()
