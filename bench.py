"""Benchmark: FedAvg rounds/sec, ResNet-18/CIFAR-10 simulated clients.

One process on the device JAX gives it. The workload is one chip's
shard of a 1024-client cohort — 32 simulated clients, 48 CIFAR-shaped
samples each, 1 local epoch, batch 32, bf16 compute — and the result is
steady-state rounds/sec, with compile time measured and reported
separately, never inside the timed window.

Prints ONE JSON line on success. Exits non-zero, printing no result,
when the device is not a TPU or when any stage raises: a stage that is
attempted and fails fails the run. A stage skipped because the
wall-clock budget (``BATON_BENCH_BUDGET_S``, default 420 s) ran short
says so in its ``*_skip_reason`` / ``*_reason`` field. Progress goes to
stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

T0 = time.perf_counter()
BUDGET_S = float(os.environ.get("BATON_BENCH_BUDGET_S", "420"))

N_CLIENTS = 32           # one chip's shard of 1024 clients over 32 chips
SAMPLES_PER_CLIENT = 48  # ~50_000 / 1024
# 48-sample clients at batch 32 train one full and one half-padded
# batch per epoch: 64 sample-slots of conv FLOPs for 48 real samples
BATCH_SIZE = 32
N_EPOCHS = 1
TARGET_ROUNDS_PER_SEC = 10.0

# Analytic FLOPs accounting and the peak-FLOPs table live in the shared
# compute probe (baton_tpu/obs/compute.py) — the live round loop reports
# MFU with the exact same constants, so bench and live numbers cannot
# diverge.
from baton_tpu.obs.compute import (  # noqa: E402
    TRAIN_FLOPS_PER_IMG,
    compute_mfu,
)


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def remaining() -> float:
    return BUDGET_S - (time.perf_counter() - T0)


def main() -> None:
    log(f"budget {BUDGET_S:.0f}s")

    import jax

    from baton_tpu.utils.profiling import (
        enable_compile_cache,
        fedsim_fused_donation_plan,
        peak_hbm_gb,
    )

    cache_dir, _ = enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from baton_tpu.models.resnet import resnet18_cifar_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    devs = jax.devices()
    platform = devs[0].platform
    device_kind = devs[0].device_kind
    log(f"platform={platform} device_kind={device_kind!r} "
        f"n_devices={len(devs)} compile cache {cache_dir}")
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; JAX reports platform "
            f"{platform!r} — nothing was measured")

    rng = np.random.default_rng(0)
    datasets = []
    for _ in range(N_CLIENTS):
        datasets.append({
            "x": rng.normal(size=(SAMPLES_PER_CLIENT, 32, 32, 3)).astype(np.float32),
            "y": rng.integers(0, 10, size=(SAMPLES_PER_CLIENT,)).astype(np.int32),
        })
    data, n_samples = stack_client_datasets(datasets, batch_size=BATCH_SIZE)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)
    log("client data staged on device")

    model = resnet18_cifar_model(compute_dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0))
    # no mesh: the cell is one chip's shard, so on a host with several
    # chips it still runs on (and reports) device 0 alone
    sim = FedSim(model, batch_size=BATCH_SIZE, learning_rate=0.05)
    key = jax.random.key(1)

    # --- compile (reported separately, never inside the timed window) ---
    t_c = time.perf_counter()
    res = sim.run_round(params, data, n_samples, key, n_epochs=N_EPOCHS,
                        collect_client_losses=False)
    first_loss = float(res.loss_history[-1])  # host fetch = hard sync point
    compile_s = time.perf_counter() - t_c
    log(f"round program compiled+ran in {compile_s:.1f}s "
        f"(loss {first_loss:.3f})")

    # --- steady state: single-round program, re-dispatched ---
    # One round to estimate cost, then as many as fit the remaining budget.
    t_e = time.perf_counter()
    res = sim.run_round(res.params, data, n_samples,
                        jax.random.fold_in(key, 1), n_epochs=N_EPOCHS,
                        collect_client_losses=False)
    float(res.loss_history[-1])
    est = time.perf_counter() - t_e
    # Reserve budget for the fused stage BEFORE sizing the dispatch
    # loop, or the loop eats the window and the fused stage is skipped.
    # The reserve covers the fused compile (scan shell over the wave
    # kernel) plus two k_f-round executions.
    fused_reserve = min(90.0, 1.5 * compile_s + 25.0 * est + 15.0)
    timed_rounds = int(max(
        3, min(50, (remaining() - 20.0 - fused_reserve) / max(est, 1e-3))
    ))
    log(f"steady-state estimate {est:.3f}s/round -> timing {timed_rounds} "
        f"rounds (fused reserve {fused_reserve:.0f}s)")

    p = res.params
    t0 = time.perf_counter()
    for i in range(timed_rounds):
        res = sim.run_round(p, data, n_samples, jax.random.fold_in(key, 2 + i),
                            n_epochs=N_EPOCHS, collect_client_losses=False)
        p = res.params
    final_loss = float(res.loss_history[-1])  # forces the whole chain
    dt = time.perf_counter() - t0
    rounds_per_sec = timed_rounds / dt
    log(f"{timed_rounds} rounds in {dt:.2f}s -> {rounds_per_sec:.3f} rounds/s "
        f"(final loss {final_loss:.3f})")
    if not (np.isfinite(first_loss) and np.isfinite(final_loss)):
        raise RuntimeError(
            f"non-finite loss: first {first_loss}, final {final_loss}")

    # peak HBM of the dispatch rounds, read before the later stages can
    # raise the process's high-water mark
    hbm_gb = peak_hbm_gb(devs[0])

    # --- fused fast path: lax.scan over rounds, one dispatch total ---
    # Only attempted when budget remains. donate_buffers=True invalidates
    # the params handed in, so `p` is rebound to what comes back.
    fused_rps = None
    fused_skip_reason = None
    k_f = min(timed_rounds, 10)
    fused_need = 1.2 * compile_s + 2.0 * k_f * est + 10.0
    if remaining() > fused_need:
        t_fc = time.perf_counter()
        p, hist = sim.run_rounds_fused(
            p, data, n_samples, jax.random.fold_in(key, 999),
            n_rounds=k_f, n_epochs=N_EPOCHS, donate_buffers=True)
        fused_compile_s = time.perf_counter() - t_fc
        log(f"fused {k_f}-round program compiled+ran in {fused_compile_s:.1f}s")
        if remaining() > 1.5 * fused_compile_s * 0.2 + 10:
            t_f = time.perf_counter()
            p, hist = sim.run_rounds_fused(
                p, data, n_samples, jax.random.fold_in(key, 1000),
                n_rounds=k_f, n_epochs=N_EPOCHS, donate_buffers=True)
            fused_dt = time.perf_counter() - t_f
            fused_rps = k_f / fused_dt
            log(f"fused steady state: {k_f} rounds in {fused_dt:.2f}s "
                f"-> {fused_rps:.3f} rounds/s")
        else:
            fused_skip_reason = (
                f"budget after fused compile: {remaining():.0f}s left"
            )
        if not np.isfinite(hist[-1]):
            raise RuntimeError(f"fused rounds: non-finite loss {hist[-1]}")
    else:
        fused_skip_reason = (
            f"budget: {remaining():.0f}s left < {fused_need:.0f}s needed"
        )
        log(f"fused path skipped ({fused_skip_reason})")

    # --- donation on/off HBM plan delta ---
    # XLA's static memory plan for the fused round program, compiled
    # once with donate_argnums armed and once without: the delta is the
    # retained input copy donation frees. A plan, not a measurement.
    donation_hbm = None
    donation_hbm_reason = None
    if remaining() > 30.0:
        donation_hbm = fedsim_fused_donation_plan(
            sim, p, data, n_samples, key,
            n_rounds=min(k_f, 3), n_epochs=N_EPOCHS)
        log(f"donation plan: on {donation_hbm['donate_on']['plan_gb']} "
            f"GiB / off {donation_hbm['donate_off']['plan_gb']} GiB "
            f"(delta {donation_hbm['delta_gb']} GiB)")
    else:
        donation_hbm_reason = f"budget: {remaining():.0f}s left < 30s needed"
        log(f"donation plan probe skipped ({donation_hbm_reason})")

    # --- flash-attention micro-bench: Pallas kernel vs dense einsum ---
    # The model zoo defaults to the flash kernel on TPU at L >= 4096
    # (models/transformer.py::default_attention).
    attn_bench = None
    attn_bench_reason = None
    if remaining() > 45.0:
        from baton_tpu.models.transformer import dot_product_attention
        from baton_tpu.ops.flash_attention import flash_attention

        def time_attn(fn, L, iters=10):
            kq, kk, kv = jax.random.split(jax.random.key(7), 3)
            shape = (4, 8, L, 64)  # [B, H, L, Dh]
            q = jax.random.normal(kq, shape, jnp.bfloat16)
            k = jax.random.normal(kk, shape, jnp.bfloat16)
            v = jax.random.normal(kv, shape, jnp.bfloat16)

            def loss(q):
                return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32))

            g = jax.jit(jax.grad(loss))
            g(q).block_until_ready()  # compile
            t = time.perf_counter()
            for _ in range(iters):
                out = g(q)
            out.block_until_ready()
            return (time.perf_counter() - t) / iters * 1e3  # ms

        attn_bench = {}
        for L in (2048, 4096):
            if remaining() < 25.0:
                attn_bench_reason = (
                    f"budget: L={L} and up skipped, {remaining():.0f}s left")
                break
            dense_ms = time_attn(dot_product_attention, L)
            flash_ms = time_attn(flash_attention, L)
            attn_bench[f"L{L}"] = {
                "dense_ms": round(dense_ms, 2),
                "flash_ms": round(flash_ms, 2),
                "speedup": round(dense_ms / flash_ms, 2),
            }
            log(f"attention fwd+bwd L={L}: dense {dense_ms:.2f}ms "
                f"flash {flash_ms:.2f}ms")
    else:
        attn_bench_reason = f"budget: {remaining():.0f}s left < 45s needed"
        log(f"attention micro-bench skipped ({attn_bench_reason})")

    best = max(rounds_per_sec, fused_rps or 0.0)
    samples_per_sec = best * N_CLIENTS * SAMPLES_PER_CLIENT * N_EPOCHS

    # MFU = analytic training FLOPs delivered / chip peak. The cell runs
    # on one chip, so samples_per_sec IS the per-chip throughput. A
    # device the peak table does not hold is an error, not a null.
    mfu, mfu_reason = compute_mfu(
        samples_per_sec, TRAIN_FLOPS_PER_IMG, device_kind)
    if mfu is None:
        raise RuntimeError(f"mfu: {mfu_reason}")

    print(json.dumps({
        "metric": "fedavg_rounds_per_sec_resnet18_cifar10_32clients_1chip",
        "value": round(best, 3),
        "unit": "rounds/sec",
        "vs_baseline": round(best / TARGET_ROUNDS_PER_SEC, 3),
        "platform": platform,
        "device_kind": device_kind,
        "n_devices": len(devs),
        "model": "resnet18_bf16",
        "clients": N_CLIENTS,
        "samples_per_client": SAMPLES_PER_CLIENT,
        "batch_size": BATCH_SIZE,
        "conv_impl": "direct",
        "wave_size": None,  # the whole cohort in one wave
        "compile_s": round(compile_s, 1),
        "samples_per_sec_per_chip": round(samples_per_sec, 1),
        "mfu": round(mfu, 4),
        "peak_hbm_gb": hbm_gb,
        "peak_hbm_source": "allocator",
        "dispatch_rounds_per_sec": round(rounds_per_sec, 3),
        "fused_rounds_per_sec": round(fused_rps, 3) if fused_rps else None,
        "fused_skip_reason": fused_skip_reason,
        # the fused stage above always arms donate_buffers; the on/off
        # comparison quantifies what that buys in the static HBM plan
        "donation_enabled": True,
        "donation_hbm": donation_hbm,
        "donation_hbm_reason": donation_hbm_reason,
        "partition_rule_set": sim.partition_rule_set,
        "attention_bench": attn_bench,
        "attention_bench_reason": attn_bench_reason,
    }))


if __name__ == "__main__":
    main()
