"""Bandwidth-efficient HTTP federation: sparse uplink, quantized
downlink, sampled cohorts.

The reference ships the FULL pickled state dict both directions to every
client every round (reference manager.py:77-86, worker.py:108-124). This
recipe runs a real manager + workers federation (in one process, over
real sockets) with all three bandwidth levers on, and prints measured
wire sizes:

* workers upload top-k sparse round deltas with error feedback
  (``compress="topk:0.1:q16"`` — ops/compression.py);
* the manager broadcasts 16-bit stochastically quantized weights
  (``broadcast_quantize_bits=16``);
* only a fraction of registered clients is notified per round
  (``cohort_fraction``).

Convergence target: >80% accuracy on the workers' own shards of a
linearly-separable classification task (an ~3.4 KB-per-upload MLP,
where compression ratios mean something) — the same federation, a
fraction of the bytes.
"""

import argparse
import asyncio
import socket

import numpy as np


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run(n_workers=4, n_rounds=10, cohort_fraction=1.0, seed=0,
        compress="topk:0.1:q16", quantize_bits=16):
    import jax
    import jax.numpy as jnp
    from aiohttp import web

    from baton_tpu.core.training import make_evaluator, make_local_trainer
    from baton_tpu.data.synthetic import synthetic_classification_clients
    from baton_tpu.models.mlp import mlp_classifier_model
    from baton_tpu.server import wire
    from baton_tpu.server.http_manager import Manager
    from baton_tpu.server.http_worker import ExperimentWorker
    from baton_tpu.server.state import params_to_state_dict

    async def main():
        model = mlp_classifier_model(16, (48,), 6, name="bw")
        nprng = np.random.default_rng(seed)
        shards, _ = synthetic_classification_clients(
            nprng, n_workers, n_per_client=96, in_dim=16, n_classes=6)
        mport = free_port()

        # wire accounting: an app middleware sees every upload's size
        sizes = {"up": []}

        @web.middleware
        async def meter(request, handler):
            if request.path.endswith("/update"):
                sizes["up"].append(request.content_length or 0)
            return await handler(request)

        mapp = web.Application(middlewares=[meter])
        manager = Manager(mapp)
        exp = manager.register_experiment(
            model, name="bw", round_timeout=60.0,
            cohort_fraction=cohort_fraction,
            broadcast_quantize_bits=quantize_bits,
        )
        mrunner = web.AppRunner(mapp)
        await mrunner.setup()
        await web.TCPSite(mrunner, "127.0.0.1", mport).start()

        runners = [mrunner]
        shared = make_local_trainer(model, batch_size=32, learning_rate=0.1)
        for i, data in enumerate(shards):
            wport = free_port()
            wapp = web.Application()
            ExperimentWorker(
                wapp, model, f"127.0.0.1:{mport}", name="bw", port=wport,
                heartbeat_time=30.0, trainer=shared, compress=compress,
                get_data=lambda d=data: (d, d["x"].shape[0]),
                # distinct seeds: workers' stochastic-rounding noise must
                # be independent for the cohort mean to average it down
                rng_seed=seed * 1000 + i + 1,
            )
            wrunner = web.AppRunner(wapp)
            await wrunner.setup()
            await web.TCPSite(wrunner, "127.0.0.1", wport).start()
            runners.append(wrunner)

        for _ in range(200):
            if len(exp.registry) == n_workers:
                break
            await asyncio.sleep(0.05)
        assert len(exp.registry) == n_workers

        import aiohttp

        async with aiohttp.ClientSession() as session:
            for _ in range(n_rounds):
                async with session.get(
                    f"http://127.0.0.1:{mport}/bw/start_round?n_epoch=4"
                ) as resp:
                    assert resp.status == 200
                for _ in range(200):
                    if not exp.rounds.in_progress:
                        break
                    await asyncio.sleep(0.05)
                assert not exp.rounds.in_progress

        # reference-equivalent sizes for comparison
        full_up = len(wire.encode(
            params_to_state_dict(exp.params),
            {"update_name": "x", "n_samples": 1, "loss_history": []},
        ))
        mean_up = float(np.mean(sizes["up"])) if sizes["up"] else float("nan")
        # accuracy of the aggregated globals over every worker's shard
        evaluate = make_evaluator(model)
        correct = total = 0.0
        for d in shards:
            ev = evaluate(exp.params,
                          {k: jnp.asarray(v) for k, v in d.items()},
                          jax.random.key(0))
            correct += float(ev["accuracy"]) * d["y"].shape[0]
            total += d["y"].shape[0]
        acc = correct / total
        snap = exp.metrics.snapshot()["counters"]
        print(f"rounds: {n_rounds}, cohort_fraction: {cohort_fraction}, "
              f"compress: {compress}, downlink: int{quantize_bits}")
        print(f"uplink: mean {mean_up:.0f} B vs full {full_up} B "
              f"({full_up / mean_up:.1f}x smaller), "
              f"{int(snap.get('compressed_updates_received', 0))} sparse uploads")
        print(f"federated accuracy after {n_rounds} rounds: {acc:.3f}")
        for r in runners:
            await r.cleanup()
        return {
            "mean_upload_bytes": mean_up,
            "full_upload_bytes": full_up,
            "accuracy": acc,
        }

    return asyncio.run(main())


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    args = p.parse_args()
    if args.scale == "full":
        out = run(n_workers=16, n_rounds=30, cohort_fraction=0.5)
    else:
        out = run()
    assert out["accuracy"] > 0.8
    assert out["mean_upload_bytes"] < out["full_upload_bytes"] / 2
