"""Flash vs dense attention sweep — measurements for the
default_attention dispatch policy (models/transformer.py, the
``_FLASH_MIN_LEN`` constant) and the flash kernel's default block sizes
(ops/flash_attention.py).

Writes chiprun_out/attention_sweep_tpu.json in addition to the
human-readable table. Nothing reads that file back: a threshold or a
block shape changes by editing the constant, citing the sweep.

Usage:  python benchmarks/attention_sweep.py [--lens 1024,2048,4096,8192] \
            [--blocks 256x256,512x512,512x1024] [--dense-max 4096]

``--dense-max`` caps the lengths at which the DENSE kernel is timed: its
[B, H, L, L] fp32 score tensor is 8.6 GB at L=8192 (B=4, H=8) and a
backward pass would not fit a 16 GB chip.

A cell that fails is recorded with its error and the remaining cells
still run; the sweep then exits non-zero.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from baton_tpu.models.transformer import dot_product_attention  # noqa: E402
from baton_tpu.ops.flash_attention import flash_attention  # noqa: E402
from baton_tpu.utils.profiling import enable_compile_cache  # noqa: E402


def _has_tpu_timing(payload) -> bool:
    """True when the artifact carries at least one real TPU timing —
    the 'success' predicate of the clobber guard."""
    if payload.get("platform") != "tpu":
        return False
    for r in payload.get("results", ()):
        if isinstance(r.get("dense_ms"), (int, float)):
            return True
        if isinstance(r.get("jax_pallas_ms"), (int, float)):
            return True
        if any(isinstance(v, (int, float))
               for v in (r.get("flash") or {}).values()):
            return True
    return False


def resolve_artifact_path(out_path: str, payload) -> str:
    """Where this run's ``payload`` may be written: never over an
    artifact holding TPU timings with a run that produced none — every
    cell failing, or a CPU smoke run with plausible-looking numbers. The
    lesser run is still evidence: it goes to a ``*_failed`` sibling
    instead. An unreadable or foreign prior is clobber-safe."""
    if _has_tpu_timing(payload):
        return out_path
    try:
        with open(out_path) as f:
            keep = _has_tpu_timing(json.load(f))
    except (OSError, ValueError, TypeError, AttributeError, KeyError):
        return out_path
    if not keep:
        return out_path
    base, ext = os.path.splitext(out_path)
    return f"{base}_failed{ext or '.json'}"


def timeit(fn, L, b=4, h=8, d=64, iters=10):
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    shape = (b, h, L, d)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    # grad wrt ALL of q/k/v: differentiating only q would let XLA
    # dead-code-eliminate dense attention's dk/dv contractions while the
    # flash custom VJP always computes them — biasing the comparison
    g = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2),
    ))
    jax.block_until_ready(g(q, k, v))  # compile
    t = time.perf_counter()
    for _ in range(iters):
        out = g(q, k, v)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters * 1e3


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--lens", default="1024,2048,4096,8192")
    p.add_argument("--blocks",
                   default="128x128,128x256,256x256,256x512,512x512,"
                           "512x1024,1024x1024")
    p.add_argument("--dense-max", type=int, default=4096)
    p.add_argument("--out", default=os.path.join(
        _REPO, "chiprun_out", "attention_sweep_tpu.json"))
    args = p.parse_args()
    # this sweep compiles dozens of kernel variants: a second run skips
    # straight to timing
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"backend: {jax.default_backend()}")
    results = []
    failed = []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    # artifact destination is resolved ONCE per run (promoting from the
    # *_failed sibling to the real artifact at most once, never back):
    # the old per-write resolve flipped to args.out on the first TPU
    # success and clobbered the committed artifact with only the lengths
    # measured so far in THIS run.
    dest = None
    prior_results = []
    for L in (int(x) for x in args.lens.split(",")):
        rec = {"L": L, "flash": {}}
        if L <= args.dense_max:
            # a failed cell must not discard the cells already measured
            # or still measurable; it fails the sweep at the end
            try:
                d = timeit(dot_product_attention, L)
            except Exception as e:
                rec["dense_error"] = f"{type(e).__name__}: {e}"[:200]
                failed.append(f"L{L}:dense")
                d = None
                print(f"L={L} dense FAILED: {e}")
            else:
                rec["dense_ms"] = round(d, 2)
                print(f"L={L} dense fwd+bwd {d:.2f} ms")
        else:
            d = None
            print(f"L={L} dense skipped (scores tensor would not fit; "
                  f"--dense-max {args.dense_max})")
        # reference point: the Pallas TPU flash kernel SHIPPED WITH JAX
        # (jax.experimental.pallas.ops.tpu) at its default block sizes —
        # if the library kernel beats ours at a length, the dispatch in
        # models/transformer.py should route there instead
        try:
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as jax_flash,
            )

            def jf(q, k, v, causal=False, **kw):
                return jax_flash(q, k, v, causal=causal,
                                 sm_scale=q.shape[-1] ** -0.5)

            jm = timeit(jf, L)
            rec["jax_pallas_ms"] = round(jm, 2)
            ratio = f" ({d / jm:.2f}x vs dense)" if d else ""
            print(f"  jax-shipped pallas kernel: {jm:.2f} ms{ratio}")
        except Exception as e:
            rec["jax_pallas_error"] = f"{type(e).__name__}: {e}"[:200]
            failed.append(f"L{L}:jax_pallas")
            print(f"  jax-shipped pallas kernel failed: {e}")
        for spec in args.blocks.split(","):
            bq, bk = (int(x) for x in spec.split("x"))
            if bq > L or bk > L:
                continue
            try:
                f = timeit(
                    lambda q, k, v, **kw: flash_attention(
                        q, k, v, block_q=bq, block_k=bk, **kw
                    ),
                    L,
                )
            except Exception as e:
                rec.setdefault("flash_errors", {})[spec] = (
                    f"{type(e).__name__}: {e}"[:200])
                failed.append(f"L{L}:flash_{spec}")
                print(f"  flash bq={bq} bk={bk} FAILED: {e}")
                continue
            rec["flash"][spec] = round(f, 2)
            ratio = f" ({d / f:.2f}x)" if d else ""
            print(f"  flash bq={bq} bk={bk}: {f:.2f} ms{ratio}")
        results.append(rec)
        # write after every length: a run cut off at its time limit
        # keeps the lengths already measured. Clobber-guarded per write
        # (resolve_artifact_path): an all-failure TPU run or a CPU smoke
        # run is diverted to *_failed instead of overwriting recorded
        # hardware timings.
        payload = {
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", dev.platform),
            "shape": {"batch": 4, "heads": 8, "head_dim": 64,
                      "dtype": "bfloat16", "causal": True,
                      "measure": "fwd+bwd(q,k,v), mean of 10"},
            "results": results,
        }
        if dest != args.out:
            new_dest = resolve_artifact_path(args.out, payload)
            if new_dest == args.out:
                # promoted to the real artifact: carry the prior run's
                # per-length records forward so lengths this run does
                # not re-measure survive, and drop the *_failed sibling
                # this run may have written before the promotion
                try:
                    with open(args.out) as f:
                        prior = json.load(f)
                    if _has_tpu_timing(prior):
                        prior_results = [
                            r for r in prior.get("results", ())
                            if isinstance(r, dict)
                            and isinstance(r.get("L"), int)
                        ]
                except (OSError, ValueError, AttributeError):
                    prior_results = []
                if dest is not None and os.path.exists(dest):
                    try:
                        os.unlink(dest)
                    except OSError:
                        pass
            dest = new_dest
        merged = {r["L"]: r for r in prior_results}
        for r in results:
            merged[r["L"]] = r
        payload["results"] = [merged[k] for k in sorted(merged)]
        # temp-file + atomic replace: a mid-dump kill must not leave a
        # truncated artifact behind
        tmp = f"{dest}.tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2)
        os.replace(tmp, dest)
    if failed:
        raise SystemExit(f"attention sweep: failed cells {failed}")


if __name__ == "__main__":
    main()
