"""Hardware measurement suite: the full-width drivers for the model
families (ResNet-18, BERT-base, the 0.9 B decoder, ViT-B/16) until the
benchmark's own workload list replaces them (ROADMAP S0).

One process for each chip: this parent never touches the JAX backend —
it imports the package (imports initialise nothing) and starts one
child per stage, so each child has the chip to itself and
``memory_stats()`` peaks belong to one stage. Children share the
persistent compile cache (``profiling.enable_compile_cache``). Results
append to ``chiprun_out/tpu_results.jsonl`` as they land. The suite
exits non-zero if any stage failed or timed out.

Stages:

1. ``headline``      — bench.py itself (ResNet-18 bf16, 32 clients)
2. ``conv``          — per-client-conv lowering shootout: vmap-direct
                       (grouped conv) vs vmap-im2col (batched matmul) vs
                       shift-GEMM vs stacked batch_group_count, layer
                       micro + full round
3. ``bert``          — BERT-base federated round, FLOPs from XLA cost
                       analysis beside the analytic count
4. ``llama``         — ~0.9B-param decoder, LoRA adapters-only federated
                       fine-tune, remat on
5. ``vit`` / ``vit_dp`` — ViT-B/16, plain and with DP-SGD + remat
6. ``wave1024``      — 1024 clients in waves of {32, 64}
7. ``wave1024_fused`` — 3 rounds of the 1024-client round as ONE
                       lax.scan dispatch
8. ``wave128``       — the 128-client wave sweep (wave_sweep.py)
9. ``attn``          — attention_sweep.py, L in {1024..8192} x blocks
10. ``auto_wave``    — ``wave_size="auto"`` choosing and running a wave

A stage whose static HBM plan exceeds the device budget is skipped and
says so (``skipped`` + ``plan_gb``); that is a result, not a failure.

Usage:
    python benchmarks/tpu_suite.py                 # all stages
    python benchmarks/tpu_suite.py --stages conv   # subset
    python benchmarks/tpu_suite.py --child conv    # one stage, this process
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    # invoked as `python benchmarks/tpu_suite.py`: sys.path[0] is
    # benchmarks/, so the baton_tpu package needs the repo root added
    sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, "chiprun_out")
OUT_JSONL = os.path.join(OUT_DIR, "tpu_results.jsonl")

# FLOPs constants come from the shared compute probe (one accounting
# for bench, live rounds, and this suite)
from baton_tpu.obs.compute import (  # noqa: E402
    TRAIN_FLOPS_PER_IMG as RESNET_TRAIN_FLOPS_PER_IMG,
    peak_flops_for,
)
from baton_tpu.utils.profiling import (  # noqa: E402
    enable_compile_cache,
    hbm_budget_gb,
    peak_hbm_gb,
)

# BATON_SUITE_SMOKE=1 shrinks every stage to CPU-compilable sizes so a
# stage body can be debugged before chip time is spent on it; a smoke
# run measures nothing and its records carry no MFU.
SMOKE = os.environ.get("BATON_SUITE_SMOKE") == "1"


def _jax_setup():
    import jax

    enable_compile_cache()
    return jax


def _mfu(flops_per_sec, dev):
    """``flops_per_sec`` as a share of ``dev``'s bf16 peak, the peak
    looked up by ``device_kind``. A device the table does not hold is an
    error — except in a smoke run, which gets no MFU at all."""
    if flops_per_sec is None:
        return None
    peak, why = peak_flops_for(dev.device_kind)
    if peak is None:
        if SMOKE:
            return None
        raise RuntimeError(why)
    return round(flops_per_sec / peak, 4)


def _over_budget(plan_gb, dev, kernel_class: str = "default",
                 margin_gb: float = 0.0) -> bool:
    """Does a static plan exceed ``dev``'s plan-space budget? A smoke
    run has no device budget to hold it to."""
    if plan_gb is None or SMOKE:
        return False
    return plan_gb + margin_gb > hbm_budget_gb(dev, kernel_class)


def _timed_rounds(sim, params, data, n_samples, key, iters, **round_kw):
    """Shared measurement core for the model stages: one compile round
    (timed separately), then ``iters`` steady-state rounds. Returns
    (final_params, seconds_per_round, compile_s)."""
    import jax

    t_c = time.perf_counter()
    res = sim.run_round(params, data, n_samples, key,
                        collect_client_losses=False, **round_kw)
    float(res.loss_history[-1])
    compile_s = time.perf_counter() - t_c
    p = res.params
    t0 = time.perf_counter()
    for i in range(iters):
        res = sim.run_round(p, data, n_samples, jax.random.fold_in(key, i),
                            collect_client_losses=False, **round_kw)
        p = res.params
    float(res.loss_history[-1])
    dt = (time.perf_counter() - t0) / iters
    return p, dt, compile_s


def _cost_flops(jitted, *args):
    """XLA's own FLOP count for one dispatch of ``jitted`` (a dict with
    a ``flops`` entry on jax 0.9, CPU and TPU alike). None when the
    analysis carries no positive count."""
    f = jitted.lower(*args).compile().cost_analysis().get("flops")
    return float(f) if f and f > 0 else None


def _flagship_oom_guard(sim, params, data, n_samples, key, dev,
                        kernel_class: str = "default"):
    """Shared static-plan OOM guard for the flagship stages
    (bert/vit/llama and their batch-push variants): returns None when
    the plan fits the device budget, else the skip-record fields."""
    from baton_tpu.utils.profiling import fedsim_wave_plan_gb

    plan_gb = fedsim_wave_plan_gb(sim, params, data, n_samples, key)
    if _over_budget(plan_gb, dev, kernel_class):
        return _plan_skip_fields(plan_gb)
    return None


def _flagship_flop_probe(sim, p, data, n_samples, key, n_clients,
                         t_child, budget_s, split_frozen=False):
    """XLA's FLOP count for the flagship stages' wave kernel, or None
    when the child's budget is spent (the probe compiles a fresh
    program and must not starve the already-measured result)."""
    import jax

    if time.perf_counter() - t_child >= budget_s:
        return None
    rngs = jax.random.split(key, n_clients)
    if split_frozen:
        tr, fz = sim._split(p)
        jitted = jax.jit(
            lambda a, b, d, n, r: sim._wave_sums_raw(a, b, d, n, r, 1))
        return _cost_flops(jitted, tr, fz, data, n_samples, rngs)
    jitted = jax.jit(
        lambda pr, d, n, r: sim._wave_sums_raw(pr, None, d, n, r, 1))
    return _cost_flops(jitted, p, data, n_samples, rngs)


# ======================================================================
# stage: conv — the grouped-conv shootout
def child_conv() -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    C, B = (2, 4) if SMOKE else (32, 32)
    out = {"stage": "conv", "platform": dev.platform,
           "device_kind": getattr(dev, "device_kind", dev.platform),
           "clients": C, "batch": B, "layers": [], "full_model": {}}

    from baton_tpu.models.resnet import (_conv_direct, _conv_im2col,
                                         _conv_shift)

    def conv_bgc(xs, ws, stride):
        """Per-client conv via batch_group_count: lhs [C*B,H,W,cin],
        rhs [kh,kw,cin,C*cout], G=C — XLA's weight-gradient lowering
        path."""
        c, b, h, w, cin = xs.shape
        kh, kw, _, cout = ws.shape[1:5] if ws.ndim == 5 else ws.shape
        lhs = xs.reshape(c * b, h, w, cin)
        rhs = jnp.moveaxis(ws, 0, 3).reshape(kh, kw, cin, c * cout)
        o = jax.lax.conv_general_dilated(
            lhs, rhs.astype(lhs.dtype), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            batch_group_count=c,
        )
        oh, ow = o.shape[1:3]
        return jnp.moveaxis(o.reshape(b, oh, ow, c, cout), 3, 0)

    def time_fn(f, *args, iters=20):
        jax.block_until_ready(f(*args))  # compile
        t = time.perf_counter()
        for _ in range(iters):
            o = f(*args)
        jax.block_until_ready(o)
        return (time.perf_counter() - t) / iters

    # --- layer microbench: fwd+bwd of sum(conv(x, w)) per strategy ---
    layer_shapes = ([(8, 8, 8, 1)] if SMOKE else
                    [(64, 64, 32, 1), (128, 128, 16, 1),
                     (256, 256, 8, 1), (64, 128, 32, 2)])
    for cin, cout, hw, stride in layer_shapes:
        kx, kw_ = jax.random.split(jax.random.key(cin + hw))
        xs = jax.random.normal(kx, (C, B, hw, hw, cin), jnp.bfloat16)
        ws = jax.random.normal(kw_, (C, 3, 3, cin, cout), jnp.bfloat16)
        oh = -(-hw // stride)
        flops = 2 * C * B * oh * oh * 9 * cin * cout * 3  # fwd+bwd~3x

        rec = {"cin": cin, "cout": cout, "hw": hw, "stride": stride}
        strategies = {
            "vmap_direct": jax.vmap(
                lambda x, w: _conv_direct(x, w, stride)),
            "vmap_im2col": jax.vmap(
                lambda x, w: _conv_im2col(x, w, stride)),
            "vmap_shift": jax.vmap(
                lambda x, w: _conv_shift(x, w, stride)),
            "batch_group_count": lambda xs, ws: conv_bgc(xs, ws, stride),
        }
        for name, fn in strategies.items():
            g = jax.jit(jax.grad(
                lambda a, b2: jnp.sum(fn(a, b2).astype(jnp.float32)),
                argnums=(0, 1)))
            dt = time_fn(lambda a, b2: g(a, b2), xs, ws)
            rec[name] = {"ms": round(dt * 1e3, 3),
                         "mfu": _mfu(flops / dt, dev)}
        out["layers"].append(rec)

    # --- full federated round: direct vs im2col ResNet-18 ---
    from baton_tpu.models.resnet import resnet18_cifar_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    rng = np.random.default_rng(0)
    img, spc = (8, 8) if SMOKE else (32, 48)
    datasets = [{
        "x": rng.normal(size=(spc, img, img, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(spc,)).astype(np.int32),
    } for _ in range(C)]

    _staged = {}

    def stage(bs):
        # cache per distinct batch size: both impls reuse one staging
        if bs not in _staged:
            d, n = stack_client_datasets(datasets, batch_size=bs)
            _staged[bs] = (
                {k: jax.device_put(jnp.asarray(v)) for k, v in d.items()},
                jnp.asarray(n),
            )
        return _staged[bs]

    key = jax.random.key(1)

    from baton_tpu.models.resnet import resnet_model

    # two lowering impls x two batchings. batch=32 over 48-sample
    # clients (the bench headline config) trains one full batch + one
    # HALF-PADDED batch per epoch — 64 sample-slots of conv FLOPs for
    # 48 real samples (25% waste); batch=48 removes the padding batch
    # entirely. Identical FedAvg semantics, different SGD batching —
    # reported as separate configs.
    batch_sizes = (spc,) if SMOKE else (32, 48)
    # full-model im2col is left out: its wave-32 plan was recorded over
    # physical HBM (compile-time RESOURCE_EXHAUSTED); the layer
    # microbench above keeps its per-layer record
    for impl in ("direct", "shift"):
        model = (resnet_model(blocks_per_stage=(1,), n_groups=4,
                              conv_impl=impl)
                 if SMOKE else
                 resnet18_cifar_model(compute_dtype=jnp.bfloat16,
                                      conv_impl=impl))
        params = model.init(jax.random.key(0))
        for bs in batch_sizes:
            data, n_samples = stage(bs)  # capacity rounds to the batch
            sim = FedSim(model, batch_size=bs, learning_rate=0.05)
            tag = impl if bs == 32 or SMOKE else f"{impl}_b{bs}"
            # OOM guard: im2col's kh*kw patch blowup can exceed HBM at
            # the full 32-client wave — check the compiler's plan first
            from baton_tpu.utils.profiling import (
                conv_kernel_class, fedsim_wave_plan_gb)

            plan_gb = fedsim_wave_plan_gb(sim, params, data, n_samples, key)
            kclass = conv_kernel_class(impl, bs)
            wave_kw = {}
            if _over_budget(plan_gb, dev, kclass):
                out["full_model"][tag] = {
                    "batch_size": bs, **_plan_skip_fields(plan_gb),
                }
                # a half-cohort wave still yields a throughput datapoint
                # for the lowering comparison instead of a bare skip;
                # the "@w16" key marks it as not a full-wave config
                half_plan = fedsim_wave_plan_gb(sim, params, data,
                                                n_samples, key,
                                                wave_size=16)
                if half_plan is None or _over_budget(half_plan, dev, kclass):
                    continue
                tag = f"{tag}@w16"
                plan_gb = half_plan
                wave_kw = {"wave_size": 16}
            _, dt, compile_s = _timed_rounds(
                sim, params, data, n_samples, key, 2 if SMOKE else 12,
                **wave_kw)
            sps = C * spc / dt
            out["full_model"][tag] = {
                "batch_size": bs,
                **({"wave_size": wave_kw["wave_size"]} if wave_kw else {}),
                "rounds_per_sec": round(1 / dt, 3),
                "samples_per_sec_per_chip": round(sps, 1),
                "mfu_analytic": _mfu(sps * RESNET_TRAIN_FLOPS_PER_IMG, dev),
                "compile_s": round(compile_s, 1),
                "plan_gb": round(plan_gb, 2) if plan_gb else None,
            }
    out["peak_hbm_gb"] = peak_hbm_gb(dev)
    return out


# ======================================================================
# stage: bert — transformer flagship MFU
def child_bert() -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    from baton_tpu.models.bert import BertConfig, bert_classifier_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    # BERT-base: per-client matmuls lower to batched matmuls over the
    # client axis. Batch override: the bert_b64 stage doubles the
    # per-client batch.
    B = int(os.environ.get("BATON_SUITE_BERT_BATCH", "32"))
    C, B, L = (2, 4, 16) if SMOKE else (8, B, 128)
    cfg = (BertConfig.tiny(max_len=L) if SMOKE else
           BertConfig(vocab_size=30522, max_len=L, d_model=768,
                      n_layers=12, n_heads=12, d_ff=3072, n_classes=4))
    model = bert_classifier_model(cfg, compute_dtype=jnp.bfloat16,
                                  name="bert_base_bf16")
    params = model.init(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    rng = np.random.default_rng(0)
    datasets = [{
        "x": rng.integers(0, cfg.vocab_size, size=(B, L)).astype(np.int32),
        "y": rng.integers(0, 4, size=(B,)).astype(np.int32),
    } for _ in range(C)]
    data, n_samples = stack_client_datasets(datasets, batch_size=B)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    sim = FedSim(model, batch_size=B, learning_rate=0.01)
    key = jax.random.key(1)
    stage_name = "bert" if B == 32 or SMOKE else f"bert_b{B}"
    # matmul-shaped kernel: the conservative default budget applies
    skip = _flagship_oom_guard(sim, params, data, n_samples, key, dev)
    if skip is not None:
        return {"stage": stage_name, "platform": dev.platform,
                "model": "bert_base_bf16", "clients": C, "batch": B,
                "seq_len": L, **skip}
    t_child = time.perf_counter()
    p, dt, compile_s = _timed_rounds(sim, params, data, n_samples, key,
                                     2 if SMOKE else 10)

    # measured-FLOP probe, gated at 600 s of the 900 s child timeout
    xla_flops = _flagship_flop_probe(
        sim, p, data, n_samples, key, C, t_child, 600.0)

    tokens_per_round = C * B * L
    analytic_flops = 6.0 * n_params * tokens_per_round
    flops = xla_flops or analytic_flops
    sps = C * B / dt
    return {
        "stage": stage_name,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "model": "bert_base_bf16", "n_params": n_params,
        "clients": C, "batch": B, "seq_len": L,
        "rounds_per_sec": round(1 / dt, 3),
        "samples_per_sec_per_chip": round(sps, 1),
        "tokens_per_sec_per_chip": round(sps * L, 1),
        "flops_per_round_xla": xla_flops,
        "flops_per_round_analytic": analytic_flops,
        "mfu": _mfu(flops / dt, dev),
        "mfu_analytic": _mfu(analytic_flops / dt, dev),
        "compile_s": round(compile_s, 1),
        "peak_hbm_gb": peak_hbm_gb(dev),
    }


# ======================================================================
# stage: vit — the config-5 flagship: ViT-B/16 federated rounds.
# Per-client weights live entirely in matmuls (patchify is a
# reshape/transpose — no conv), so vmapped training lowers to batched
# matmuls like BERT.
def child_vit() -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    from baton_tpu.models.vit import ViTConfig, vit_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    # BATON_SUITE_VIT_DP=1 measures the config-5 shape instead: DP-SGD
    # per-example clipped gradients (vmapped over the batch — still
    # batched matmuls) + remat (per-example grads multiply activation
    # memory by the batch; recompute-not-store pays FLOPs to fit)
    dp_mode = os.environ.get("BATON_SUITE_VIT_DP") == "1"
    if SMOKE:
        C, B = 2, 4
        cfg = ViTConfig.tiny()
    else:
        C, B = (4, 8) if dp_mode else (4, 16)
        cfg = ViTConfig.b16(n_classes=100)  # 224px, patch 16 -> 196 tokens
    model = vit_model(cfg, compute_dtype=jnp.bfloat16, remat=dp_mode,
                      name="vit_b16_bf16")
    params = model.init(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    rng = np.random.default_rng(0)
    datasets = [{
        "x": rng.normal(size=(B, cfg.image_size, cfg.image_size,
                              cfg.channels)).astype(np.float32),
        "y": rng.integers(0, cfg.n_classes, size=(B,)).astype(np.int32),
    } for _ in range(C)]
    data, n_samples = stack_client_datasets(datasets, batch_size=B)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    dp_cfg = None
    if dp_mode:
        from baton_tpu.ops.privacy import DPConfig

        dp_cfg = DPConfig(clip_norm=1.0, noise_multiplier=0.5)
        sim = FedSim(model, batch_size=B, learning_rate=0.01, dp=dp_cfg)
    else:
        sim = FedSim(model, batch_size=B, learning_rate=0.01)
    stage_name = "vit_dp" if dp_mode else "vit"
    model_name = "vit_b16_bf16_dp_remat" if dp_mode else "vit_b16_bf16"
    key = jax.random.key(1)
    skip = _flagship_oom_guard(sim, params, data, n_samples, key, dev)
    if skip is not None:
        return {"stage": stage_name, "platform": dev.platform,
                "model": model_name, "clients": C, "batch": B, **skip}
    t_child = time.perf_counter()
    p, dt, compile_s = _timed_rounds(sim, params, data, n_samples, key,
                                     2 if SMOKE else 10)

    xla_flops = _flagship_flop_probe(
        sim, p, data, n_samples, key, C, t_child, 600.0)

    tokens = cfg.n_patches + 1  # + class token
    analytic_flops = 6.0 * n_params * C * B * tokens
    sps = C * B / dt
    rec = {
        "stage": stage_name, "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "model": model_name, "n_params": n_params,
        "clients": C, "batch": B, "n_tokens": tokens,
        "rounds_per_sec": round(1 / dt, 3),
        "samples_per_sec_per_chip": round(sps, 1),
        "flops_per_round_analytic": analytic_flops,
        "mfu_analytic": _mfu(analytic_flops / dt, dev),
        "compile_s": round(compile_s, 1),
        "peak_hbm_gb": peak_hbm_gb(dev),
    }
    if dp_mode:
        # remat recompute is inside XLA's count: that ratio is HFU, not
        # MFU — report model-FLOP mfu and the hardware count separately
        # (the llama stage's convention)
        rec.update({
            "mfu": _mfu(analytic_flops / dt, dev),
            "flops_per_round_xla_hw": xla_flops,
            "hfu_xla": _mfu(xla_flops / dt if xla_flops else None, dev),
            "dp": {"clip_norm": dp_cfg.clip_norm,
                   "noise_multiplier": dp_cfg.noise_multiplier},
            "remat": True,
        })
    else:
        flops = xla_flops or analytic_flops
        rec.update({
            "flops_per_round_xla": xla_flops,
            "mfu": _mfu(flops / dt, dev),
        })
    return rec


# ======================================================================
# stage: llama — the config-4 flagship: LoRA federated fine-tune of a
# ~0.9B-param decoder (the largest that fits one v5e with its fp32 base
# replicated once), adapters-only training, remat seams on
def child_llama() -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    from baton_tpu.models.llama import (
        LlamaConfig,
        llama_lm_model,
        llama_lora_target,
    )
    from baton_tpu.models.lora import lora_trainable, lora_wrap
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    if SMOKE:
        C, B, L = 2, 2, 16
        cfg = LlamaConfig.tiny(max_len=L)
    else:
        # batch override: the llama_b8 stage doubles the batch
        C, B, L = 4, int(os.environ.get("BATON_SUITE_LLAMA_BATCH", "4")), 512
        cfg = LlamaConfig(vocab_size=32000, max_len=L, d_model=2048,
                          n_layers=16, n_heads=16, n_kv_heads=8,
                          d_ff=5632, rope_theta=500000.0)
    model = lora_wrap(
        llama_lm_model(cfg, compute_dtype=jnp.bfloat16, remat=True,
                       name="llama0.9b_bf16"),
        rank=16, target=llama_lora_target)
    params = model.init(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    rng = np.random.default_rng(0)
    datasets = [{
        "x": rng.integers(0, cfg.vocab_size, size=(B, L)).astype(np.int32),
        "y": rng.integers(0, cfg.vocab_size, size=(B, L)).astype(np.int32),
    } for _ in range(C)]
    data, n_samples = stack_client_datasets(datasets, batch_size=B)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    sim = FedSim(model, batch_size=B, learning_rate=1e-3,
                 trainable=lora_trainable)
    key = jax.random.key(1)
    stage_name = "llama" if B == 4 or SMOKE else f"llama_b{B}"
    # matmul-shaped kernel: the conservative default budget applies
    skip = _flagship_oom_guard(sim, params, data, n_samples, key, dev)
    if skip is not None:
        return {"stage": stage_name, "platform": dev.platform,
                "model": "llama0.9b_lora_bf16_remat", "clients": C,
                "batch": B, "seq_len": L, **skip}
    t_child = time.perf_counter()
    p, dt, compile_s = _timed_rounds(sim, params, data, n_samples, key,
                                     2 if SMOKE else 6)

    # XLA FLOP probe: gated on the child's 1200 s budget so its compile
    # cannot discard the already-measured rounds
    xla_flops = _flagship_flop_probe(
        sim, p, data, n_samples, key, C, t_child, 900.0 - compile_s,
        split_frozen=True)

    tokens = C * B * L
    # Model-FLOPs for an adapters-only LoRA step: fwd 2PN + activation
    # backprop through the frozen base 2PN, NO base weight gradients
    # => ~4PN (6PN would overstate by ~1.5x). XLA's count additionally
    # includes the remat forward recompute, so it is HFU, not MFU —
    # reported under its own key, never blended into mfu.
    analytic_flops = 4.0 * n_params * tokens
    return {
        "stage": stage_name,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "model": "llama0.9b_lora_bf16_remat", "n_params": n_params,
        "clients": C, "batch": B, "seq_len": L, "lora_rank": 16,
        "rounds_per_sec": round(1 / dt, 3),
        "tokens_per_sec_per_chip": round(tokens / dt, 1),
        "flops_per_round_xla_hw": xla_flops,
        "flops_per_round_model": analytic_flops,
        "mfu": _mfu(analytic_flops / dt, dev),
        "hfu_xla": _mfu(xla_flops / dt if xla_flops else None, dev),
        "compile_s": round(compile_s, 1),
        "peak_hbm_gb": peak_hbm_gb(dev),
        "remat": True,
    }


# ======================================================================
# stage: wave1024 — the north-star cohort on one chip
def child_wave1024(wave_size: int, conv_impl: str = "direct",
                   batch_size: int = 32) -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    from baton_tpu.models.resnet import resnet18_cifar_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    C, S = (8, 4) if SMOKE else (1024, 48)
    img = 8 if SMOKE else 32
    rng = np.random.default_rng(0)
    datasets = [{
        "x": rng.normal(size=(S, img, img, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(S,)).astype(np.int32),
    } for _ in range(C)]
    bs = S if SMOKE else batch_size
    data, n_samples = stack_client_datasets(datasets, batch_size=bs)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    if SMOKE:
        from baton_tpu.models.resnet import resnet_model
        model = resnet_model(blocks_per_stage=(1,), n_groups=4,
                             conv_impl=conv_impl)
        wave_size = min(wave_size, 4)
    else:
        model = resnet18_cifar_model(compute_dtype=jnp.bfloat16,
                                     conv_impl=conv_impl)
    params = model.init(jax.random.key(0))
    # batch_size comes from the conv shootout's winner (48 removes the
    # half-padded second batch of the 48-sample clients; 32 mirrors the
    # original headline config)
    sim = FedSim(model, batch_size=bs, learning_rate=0.05)
    key = jax.random.key(1)
    from baton_tpu.utils.profiling import (conv_kernel_class,
                                           fedsim_wave_plan_gb)

    plan_gb = fedsim_wave_plan_gb(sim, params, data, n_samples, key,
                                  wave_size=wave_size)
    kclass = conv_kernel_class(conv_impl, bs)
    if _over_budget(plan_gb, dev, kclass):
        return {
            "stage": "wave1024", "platform": dev.platform,
            "model": f"resnet18_bf16_{conv_impl}", "clients": C,
            "wave_size": wave_size, "batch_size": bs,
            **_plan_skip_fields(plan_gb),
        }
    p, dt, compile_s = _timed_rounds(sim, params, data, n_samples, key, 3,
                                     wave_size=wave_size)
    sps = C * S / dt

    return {
        "stage": "wave1024", "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "model": f"resnet18_bf16_{conv_impl}", "clients": C,
        "batch_size": bs,
        "samples_per_client": S, "wave_size": wave_size,
        "n_waves": -(-C // wave_size),
        "rounds_per_sec": round(1 / dt, 4),
        "seconds_per_round": round(dt, 2),
        "samples_per_sec_per_chip": round(sps, 1),
        "mfu_analytic": _mfu(sps * RESNET_TRAIN_FLOPS_PER_IMG, dev),
        "compile_s": round(compile_s, 1),
        "plan_gb": round(plan_gb, 2) if plan_gb else None,
        "peak_hbm_gb": peak_hbm_gb(dev),
        # the honest extrapolation: a v4-32 runs 32 of these shards in
        # parallel (one 32-client shard each) + one psum round boundary
        "v4_32_extrapolation_note": (
            "1024 clients sharded 32/chip over a v4-32 mesh runs one "
            "32-client wave per chip in parallel; this single-chip waved "
            "number is the degenerate 1-chip layout"),
    }


# ======================================================================
# stage: wave1024_fused — the whole 16-wave round inside lax.scan,
# multi-round, one dispatch
def child_wave1024_fused(wave_size: int, conv_impl: str = "direct",
                         batch_size: int = 32) -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    from baton_tpu.models.resnet import resnet18_cifar_model, resnet_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    C, S = (8, 4) if SMOKE else (1024, 48)
    img = 8 if SMOKE else 32
    rng = np.random.default_rng(0)
    datasets = [{
        "x": rng.normal(size=(S, img, img, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(S,)).astype(np.int32),
    } for _ in range(C)]
    bs = S if SMOKE else batch_size
    data, n_samples = stack_client_datasets(datasets, batch_size=bs)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    if SMOKE:
        model = resnet_model(blocks_per_stage=(1,), n_groups=4,
                             conv_impl=conv_impl)
        wave_size = min(wave_size, 4)
    else:
        model = resnet18_cifar_model(compute_dtype=jnp.bfloat16,
                                     conv_impl=conv_impl)
    params = model.init(jax.random.key(0))
    sim = FedSim(model, batch_size=bs, learning_rate=0.05)
    key = jax.random.key(1)
    n_rounds = 2 if SMOKE else 3

    # guard with one wave's plan + margin (the fused scan adds only the
    # params/opt/accumulator carries, ~3 model-sized buffers)
    from baton_tpu.utils.profiling import (conv_kernel_class,
                                           fedsim_wave_plan_gb)

    plan_gb = fedsim_wave_plan_gb(sim, params, data, n_samples, key,
                                  wave_size=wave_size)
    kclass = conv_kernel_class(conv_impl, bs)
    if _over_budget(plan_gb, dev, kclass, margin_gb=0.5):
        return {
            "stage": "wave1024_fused", "platform": dev.platform,
            "model": f"resnet18_bf16_{conv_impl}", "clients": C,
            "wave_size": wave_size, "batch_size": bs,
            **_plan_skip_fields(plan_gb),
        }
    t_c = time.perf_counter()
    p, hist = sim.run_rounds_fused(params, data, n_samples, key,
                                   n_rounds=n_rounds, wave_size=wave_size,
                                   donate_buffers=True)
    compile_s = time.perf_counter() - t_c

    t0 = time.perf_counter()
    p, hist = sim.run_rounds_fused(p, data, n_samples,
                                   jax.random.fold_in(key, 1),
                                   n_rounds=n_rounds, wave_size=wave_size,
                                   donate_buffers=True)
    dt = (time.perf_counter() - t0) / n_rounds
    sps = C * S / dt

    return {
        "stage": "wave1024_fused", "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "model": f"resnet18_bf16_{conv_impl}", "clients": C,
        "batch_size": bs,
        "samples_per_client": S, "wave_size": wave_size,
        "n_rounds_fused": n_rounds,
        "rounds_per_sec": round(1 / dt, 4),
        "samples_per_sec_per_chip": round(sps, 1),
        "mfu_analytic": _mfu(sps * RESNET_TRAIN_FLOPS_PER_IMG, dev),
        "compile_s": round(compile_s, 1),
        # one wave's kernel plan: the fused scan adds only the
        # params/opt accumulators on top of it
        "wave_plan_gb": round(plan_gb, 2) if plan_gb else None,
        "peak_hbm_gb": peak_hbm_gb(dev),
        "final_loss": float(hist[-1]),
    }


# ======================================================================
# stage: auto_wave — wave_size="auto" on hardware: the plan guard must be
# seen choosing a wave for a cohort that cannot run full-width on one
# chip, and then actually executing rounds at its choice.
def child_auto_wave() -> dict:
    jax = _jax_setup()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    from baton_tpu.models.resnet import resnet18_cifar_model, resnet_model
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    C, S = (8, 4) if SMOKE else (128, 48)
    img = 8 if SMOKE else 32
    rng = np.random.default_rng(0)
    datasets = [{
        "x": rng.normal(size=(S, img, img, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(S,)).astype(np.int32),
    } for _ in range(C)]
    bs = S if SMOKE else 32
    data, n_samples = stack_client_datasets(datasets, batch_size=bs)
    data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    model = (resnet_model(blocks_per_stage=(1,), n_groups=4)
             if SMOKE else
             resnet18_cifar_model(compute_dtype=jnp.bfloat16))
    params = model.init(jax.random.key(0))
    sim = FedSim(model, batch_size=bs, learning_rate=0.05)
    key = jax.random.key(1)

    t_a = time.perf_counter()
    chosen = sim.auto_wave_size(params, data, n_samples, key)
    choose_s = time.perf_counter() - t_a
    rec = {
        "stage": "auto_wave", "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "model": "resnet18_bf16", "clients": C, "batch_size": bs,
        "samples_per_client": S,
        "auto_wave_size": chosen,  # None = full cohort fits in one wave
        "choose_s": round(choose_s, 1),
    }
    if chosen is None and not SMOKE:
        # on a 16 GB chip the full 128-client kernel cannot fit — auto
        # must NOT have admitted it
        raise RuntimeError("auto_wave_size admitted the full 128-client "
                           "wave on this device")
    p, dt, compile_s = _timed_rounds(sim, params, data, n_samples, key,
                                     2 if SMOKE else 5,
                                     wave_size="auto")
    sps = C * S / dt
    rec.update({
        "rounds_per_sec": round(1 / dt, 4),
        "samples_per_sec_per_chip": round(sps, 1),
        "compile_s": round(compile_s, 1),
    })
    return rec


# ======================================================================
STAGES = ("headline", "conv", "bert", "llama", "wave1024",
          "wave1024_fused", "wave128", "attn", "auto_wave")


def _plan_skip_fields(plan_gb: float) -> dict:
    """Skip-record fields for an OOM-guard rejection; owns the
    ``float("inf")`` sentinel convention (= the compile itself
    RESOURCE_EXHAUSTed, so no byte count exists)."""
    oom = plan_gb == float("inf")
    return {
        "skipped": ("compile-time RESOURCE_EXHAUSTED" if oom
                    else "static HBM plan exceeds budget"),
        "plan_gb": None if oom else round(plan_gb, 2),
    }


def append_result(rec: dict) -> None:
    rec = dict(rec)
    rec["t_wall"] = time.strftime("%Y-%m-%dT%H:%M:%S%z")
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(OUT_JSONL, "a") as f:
        f.write(json.dumps(rec) + "\n")


def run_child(args, timeout_s, tag, extra_env=None,
              artifact: str | None = None) -> bool:
    """Run one stage in a child process and append its record. Returns
    whether the stage produced a result; the suite exits non-zero if any
    did not.

    ``artifact``: for children whose stdout is a human-readable table
    (attention_sweep.py), don't parse stdout — success means the named
    artifact file was their real output."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    t0 = time.perf_counter()
    print(f"[suite] {tag}: starting (timeout {timeout_s:.0f}s)",
          file=sys.stderr, flush=True)
    try:
        proc = subprocess.run(args, capture_output=True, text=True,
                              timeout=timeout_s, env=env, cwd=REPO)
    except subprocess.TimeoutExpired:
        append_result({"stage": tag, "failed": "timeout",
                       "timeout_s": timeout_s})
        print(f"[suite] {tag}: TIMEOUT", file=sys.stderr, flush=True)
        return False
    wall = round(time.perf_counter() - t0, 1)
    if proc.returncode != 0:
        append_result({"stage": tag, "failed": f"rc={proc.returncode}",
                       "stderr_tail": proc.stderr.strip()[-1500:],
                       "wall_s": wall})
        print(f"[suite] {tag}: FAILED rc={proc.returncode}\n"
              f"{proc.stderr.strip()[-800:]}", file=sys.stderr, flush=True)
        return False
    if artifact is not None:
        rec = {"stage": tag, "artifact": artifact,
               "stdout_tail": proc.stdout.strip()[-1200:]}
        if not os.path.exists(os.path.join(REPO, artifact)):
            rec["failed"] = "artifact missing"
    else:
        line = (proc.stdout.strip().splitlines()[-1]
                if proc.stdout.strip() else "")
        try:
            rec = json.loads(line)
            if not isinstance(rec, dict):  # a JSON scalar is not a result
                raise ValueError(f"non-object JSON: {line[:80]}")
            # children emitting foreign JSON (bench.py) carry no stage
            # key — tag them so the JSONL rows are self-describing
            rec.setdefault("stage", tag)
        except ValueError:
            rec = {"stage": tag, "failed": "bad-output",
                   "stdout_tail": proc.stdout.strip()[-500:]}
    rec["wall_s"] = wall
    append_result(rec)
    ok = "failed" not in rec
    print(f"[suite] {tag}: {'done' if ok else 'FAILED'} in {wall}s",
          file=sys.stderr, flush=True)
    return ok


CHILDREN = {
    "conv": child_conv,
    "bert": child_bert,
    "llama": child_llama,
    "vit": child_vit,
    "auto_wave": child_auto_wave,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", default=",".join(STAGES))
    ap.add_argument("--child", default=None)
    ap.add_argument("--wave", type=int, default=64)
    ap.add_argument("--conv-impl", default="direct")
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    if args.child:
        if args.child in CHILDREN:
            print(json.dumps(CHILDREN[args.child]()))
        elif args.child == "wave1024":
            print(json.dumps(child_wave1024(args.wave, args.conv_impl,
                                            args.batch)))
        elif args.child == "wave1024_fused":
            print(json.dumps(child_wave1024_fused(args.wave, args.conv_impl,
                                                  args.batch)))
        else:
            raise SystemExit(f"unknown child {args.child}")
        return

    me = os.path.abspath(__file__)
    py = sys.executable
    impl, bs = args.conv_impl, args.batch
    failed = []

    def run(tag, *a, **kw):
        if not run_child(*a, tag=tag, **kw):
            failed.append(tag)

    for stage in args.stages.split(","):
        if stage == "headline":
            run("headline", [py, os.path.join(REPO, "bench.py")], 600,
                extra_env={"BATON_BENCH_BUDGET_S": "420"})
        elif stage in ("conv", "bert", "vit", "auto_wave"):
            run(stage, [py, me, "--child", stage], 900)
        elif stage == "bert_b64":
            run("bert_b64", [py, me, "--child", "bert"], 900,
                extra_env={"BATON_SUITE_BERT_BATCH": "64"})
        elif stage == "llama":
            run("llama", [py, me, "--child", "llama"], 1200)
        elif stage == "llama_b8":
            run("llama_b8", [py, me, "--child", "llama"], 1200,
                extra_env={"BATON_SUITE_LLAMA_BATCH": "8"})
        elif stage == "vit_dp":
            # config-5 shape: DP-SGD per-example clipped grads + remat
            run("vit_dp", [py, me, "--child", "vit"], 900,
                extra_env={"BATON_SUITE_VIT_DP": "1"})
        elif stage == "wave1024":
            # only the anchored kernel identity
            # (profiling.ANCHORED_CONV_KERNEL) skips the 16-wave rung;
            # the children static-plan-guard each setting, smallest
            # wave first, so SOME 1024-client point lands even if the
            # larger waves only record skips
            from baton_tpu.utils.profiling import conv_kernel_class
            waves = ((32, 64)
                     if conv_kernel_class(impl, bs) == "anchored_direct_conv"
                     else (16, 32, 64))
            for w in waves:
                run(f"wave1024_w{w}_{impl}_b{bs}",
                    [py, me, "--child", "wave1024", "--wave", str(w),
                     "--conv-impl", impl, "--batch", str(bs)], 900)
        elif stage == "wave1024_fused":
            # wave 32, not 64: the fused guard adds a 0.5 GiB carry
            # margin to one wave's plan
            run(f"wave1024_fused_{impl}_b{bs}",
                [py, me, "--child", "wave1024_fused", "--wave", "32",
                 "--conv-impl", impl, "--batch", str(bs)], 1200)
        elif stage == "wave128":
            # no wave 128: the full cohort does not fit one 16 GB chip
            run("wave128",
                [py, os.path.join(REPO, "benchmarks", "wave_sweep.py"),
                 "--waves", "16,32,64"], 1500,
                artifact="chiprun_out/wave_sweep_tpu.json")
        elif stage == "attn":
            run("attn",
                [py, os.path.join(REPO, "benchmarks", "attention_sweep.py")],
                1800, artifact="chiprun_out/attention_sweep_tpu.json")
        else:
            raise SystemExit(f"unknown stage {stage}")
    print(f"[suite] all stages done -> {OUT_JSONL}", file=sys.stderr)
    if failed:
        raise SystemExit(f"[suite] failed stages: {', '.join(failed)}")


if __name__ == "__main__":
    main()
