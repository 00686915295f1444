"""Pull data plane at fan-out scale: downlink bytes/round, broadcast
latency, and uplink ingest for C co-located workers on loopback.

Three sections (``--sections downlink,uplink,resume``; skipped sections
keep their previous numbers in the JSON):

* ``downlink`` — pull+delta vs the push-everything equivalent (below);
* ``uplink`` — C concurrent uploads into a streaming manager, measured
  twice: ``ingest_workers=0`` (the old fully-inline path — decode,
  validate, and fold all run on the event loop) vs the off-loop ingest
  pipeline. A heartbeat probe runs through the same HTTP stack during
  the burst; the section reports updates/s, MB/s, and heartbeat/ack
  p50/p95 for both, plus the p95 ratio;
* ``resume`` — a ~100 MB chunked upload killed at ~90% by a transport
  drop, then resumed by a fresh worker from the manager's committed
  offset; reports the fraction of the body transferred twice.
* ``edge`` — flat (every worker direct to root) vs a hierarchical tier
  of edge aggregators at the same cohort size: each edge fetches the
  round blob from the root once and serves its cohort from cache, folds
  cohort updates into one weighted partial, and ships that upstream.
  Reports root downlink bytes/round for both topologies (the reduction
  factor is the point), heartbeat p50/p95 through each route, root and
  edge ingest-fold percentiles, and verifies the edge-tier aggregate
  equals the flat fold within streaming-mean tolerance.
* ``roots`` — control-plane sharding: 1 root vs N root replicas
  carrying E experiments spread over the :class:`ExperimentTopology`
  hash ring, at C>=1024 clients. Every client first contacts root-0 and
  learns its experiment's owner through the live 307-redirect contract
  (one redirect per misrouted client, never more), then the whole fleet
  runs concurrent heartbeat waves against its learned root. Reports the
  per-root registry occupancy and heartbeats served (count-exact — the
  sharding claim), redirects followed vs the topology's prediction, and
  heartbeat p50/p95 for both configurations. All roots share this one
  process/event loop, so the latency columns show protocol cost only;
  the load-division columns are the point.

What runs: a manager with ``broadcast_delta`` on and C ``EchoWorker``s
(no jit training — each "round" perturbs local params slightly so every
round's blob digest changes, like a real federation). Round 1 every
worker pulls the full blob; later rounds they pull only the delta blob
and reconstruct against their anchor, verifying by digest. Recorded per
cohort size into ``benchmarks/dataplane_scale.json``:

* ``bytes_down_per_round`` (served blob bytes + notify envelopes, from
  the manager's ``bytes_broadcast`` counter) vs ``push_equiv`` — the
  C × full_blob bytes the v1 push broadcast would have sent;
* notify→ack latency p50/p95 across the cohort (the ack covers the
  whole pull: envelope parse, blob/delta fetch, digest verify, load);
* manager aggregation memory: tracemalloc peak during the upload wave —
  streaming FedAvg folds each upload on arrival, so this stays
  O(model), flat in C (the buffered path grew O(C · model)).

Caveat in the artifact: C workers share this one process/event loop, so
latency percentiles measure protocol + loopback scheduling, not a real
network. The byte counts are exact either way.

Run anywhere (no TPU needed):
    python benchmarks/dataplane_scale.py [--cohorts 16,64,128] [--dim 65536]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import socket
import sys
import time
import tracemalloc

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from baton_tpu.utils.profiling import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402
from aiohttp import web  # noqa: E402

from baton_tpu.models.linear import linear_regression_model  # noqa: E402
from baton_tpu.server import wire  # noqa: E402
from baton_tpu.server import replication  # noqa: E402
from baton_tpu.server.edge import EdgeAggregator  # noqa: E402
from baton_tpu.server.http_manager import Manager  # noqa: E402
from baton_tpu.server.http_worker import ExperimentWorker  # noqa: E402
from baton_tpu.server.topology import EdgeTopology  # noqa: E402
from baton_tpu.server.state import (  # noqa: E402
    params_to_state_dict,
    state_dict_to_params,
)
from baton_tpu.utils.metrics import LoopLagProbe, Metrics  # noqa: E402


def _timer_stats(metrics: Metrics, name: str) -> dict:
    """p50/p95 + count for one histogram timer (PR 6: latency
    percentiles come from the shared fixed-bucket histograms, not
    ad-hoc sorted-list math — same quantile code as ``/metrics``)."""
    st = metrics.snapshot()["timers"].get(name)
    if st is None:
        return {"p50_s": None, "p95_s": None, "count": 0, "max_s": None}
    return {"p50_s": st["p50_s"], "p95_s": st["p95_s"],
            "count": st["count"], "max_s": st["max_s"]}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class EchoWorker(ExperimentWorker):
    """No jit training: a round nudges local params with seeded noise
    (every round's aggregate — and therefore blob digest — changes,
    exercising the delta path) and reports immediately. Also stamps the
    notify→ack instant so the harness can compute broadcast latency."""

    def __init__(self, *args, ack_log=None, noise_seed=0, **kwargs):
        super().__init__(*args, **kwargs)
        self._ack_log = ack_log if ack_log is not None else []
        self._noise_rng = np.random.default_rng(noise_seed)

    async def handle_round_start(self, request):
        resp = await super().handle_round_start(request)
        if resp.status == 200:
            self._ack_log.append(time.perf_counter())
        return resp

    async def _run_round(self, round_name, n_epoch):
        try:
            sd = params_to_state_dict(self.params)
            noisy = {
                k: np.asarray(v, np.float32)
                + np.float32(0.001)
                * self._noise_rng.standard_normal(np.shape(v)).astype(
                    np.float32)
                for k, v in sd.items()
            }
            self.params = state_dict_to_params(self.params, noisy)
            await self.report_update(round_name, 32, [0.0])
        finally:
            self.round_in_progress = False


async def _one_cohort(c: int, dim: int, rounds: int, delta_spec) -> dict:
    model = linear_regression_model(dim, name="dpbench")
    mport = _free_port()
    mapp = web.Application()
    exp = Manager(mapp).register_experiment(
        model, name="dpbench", round_timeout=600.0,
        broadcast_delta=delta_spec,
    )
    mrunner = web.AppRunner(mapp)
    await mrunner.setup()
    await web.TCPSite(mrunner, "127.0.0.1", mport).start()

    runners, workers, ack_log = [mrunner], [], []
    for i in range(c):
        wport = _free_port()
        wapp = web.Application()
        w = EchoWorker(
            wapp, model, f"127.0.0.1:{mport}", name="dpbench", port=wport,
            heartbeat_time=120.0, ack_log=ack_log, noise_seed=i,
            get_data=lambda: ({}, 32),
        )
        wrunner = web.AppRunner(wapp)
        await wrunner.setup()
        await web.TCPSite(wrunner, "127.0.0.1", wport).start()
        workers.append(w)
        runners.append(wrunner)
    for _ in range(600):
        if len(exp.registry) == c:
            break
        await asyncio.sleep(0.05)
    assert len(exp.registry) == c, f"registered {len(exp.registry)}/{c}"

    full_size = len(wire.encode(
        {k: np.ascontiguousarray(np.asarray(v))
         for k, v in params_to_state_dict(exp.params).items()}, {}))

    import aiohttp

    per_round = []
    bench = Metrics()
    lag_probe = LoopLagProbe(bench, interval=0.05)
    lag_probe.start()
    timeout = aiohttp.ClientTimeout(total=600.0)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        for r in range(rounds):
            before = exp.metrics.snapshot()["counters"]
            ack_log.clear()
            tracemalloc.start()
            t0 = time.perf_counter()
            async with session.get(
                f"http://127.0.0.1:{mport}/dpbench/start_round?n_epoch=1"
            ) as resp:
                assert resp.status == 200
            for _ in range(12000):
                if not exp.rounds.in_progress:
                    break
                await asyncio.sleep(0.05)
            assert not exp.rounds.in_progress, f"round {r} hung"
            _, agg_peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            after = exp.metrics.snapshot()["counters"]
            # one fresh histogram per round: the JSON keys stay
            # per-round, but the quantiles come from the shared
            # fixed-bucket implementation
            round_hist = Metrics()
            for t in ack_log:
                round_hist.observe("notify_ack_s", t - t0)
            ack_stats = _timer_stats(round_hist, "notify_ack_s")

            per_round.append({
                "round": r,
                "bytes_down": after.get("bytes_broadcast", 0.0)
                - before.get("bytes_broadcast", 0.0),
                "bytes_up": after.get("bytes_uploaded", 0.0)
                - before.get("bytes_uploaded", 0.0),
                "blob_hits_full": after.get("blob_hits_full", 0.0)
                - before.get("blob_hits_full", 0.0),
                "blob_hits_delta": after.get("blob_hits_delta", 0.0)
                - before.get("blob_hits_delta", 0.0),
                "range_resumes": after.get("range_resumes", 0.0)
                - before.get("range_resumes", 0.0),
                "acks": ack_stats["count"],
                "notify_ack_p50_s": ack_stats["p50_s"],
                "notify_ack_p95_s": ack_stats["p95_s"],
                "round_wall_s": time.perf_counter() - t0,
                "manager_round_python_peak_bytes": agg_peak,
            })
            print(f"[C={c}] round {r}: down={per_round[-1]['bytes_down']:.0f}B"
                  f" (push_equiv={c * full_size}B)"
                  f" delta_hits={per_round[-1]['blob_hits_delta']:.0f}"
                  f" p95={per_round[-1]['notify_ack_p95_s']:.3f}s",
                  file=sys.stderr, flush=True)

    lag_probe.stop()
    for r in runners:
        await r.cleanup()

    # steady state excludes round 0 (every worker's first pull is full)
    steady = per_round[1:] or per_round
    mean_down = sum(p["bytes_down"] for p in steady) / len(steady)
    push_equiv = float(c * full_size)
    lag = _timer_stats(bench, "loop_lag_s")
    return {
        "cohort": c,
        "model_dim": dim,
        "full_blob_bytes": full_size,
        "push_equiv_bytes_per_round": push_equiv,
        "steady_bytes_down_per_round": mean_down,
        "downlink_reduction_x": push_equiv / max(mean_down, 1.0),
        "loop_lag_p95_s": lag["p95_s"],
        "loop_lag_max_s": lag["max_s"],
        "rounds": per_round,
    }


async def _uplink_once(
    c: int, dim: int, ingest_workers: int, bursts: int = 3
) -> dict:
    """``bursts`` C-client concurrent upload waves into hand-driven
    rounds, with a heartbeat probe hammering the same HTTP stack during
    each wave — the probe's latency IS the event-loop responsiveness
    the pipeline buys. Samples accumulate across waves so the p95 rests
    on more than a handful of heartbeats."""
    import aiohttp

    model = linear_regression_model(dim, name="upbench")
    mport = _free_port()
    mapp = web.Application()
    exp = Manager(mapp).register_experiment(
        model, name="upbench", start_background_tasks=False,
        streaming_aggregation=True, ingest_workers=ingest_workers,
        ingest_queue_depth=max(64, 2 * c),
    )
    mrunner = web.AppRunner(mapp)
    await mrunner.setup()
    await web.TCPSite(mrunner, "127.0.0.1", mport).start()
    base = f"http://127.0.0.1:{mport}/upbench"

    timeout = aiohttp.ClientTimeout(total=600.0)
    session = aiohttp.ClientSession(timeout=timeout)
    creds = []
    for i in range(c):
        async with session.get(f"{base}/register", json={"port": i + 1}) as r:
            creds.append(await r.json())

    rng = np.random.default_rng(0)
    template = params_to_state_dict(exp.params)
    # probe + ack latencies land in histogram timers; the event-loop
    # lag probe runs through every burst — its max IS the worst stall
    # the inline/pipelined ingest imposed on the loop
    bench = Metrics()
    lag_probe = LoopLagProbe(bench, interval=0.05)
    lag_probe.start()
    walls = []
    total_mb = 0.0
    for burst in range(bursts):
        round_name = exp.rounds.start_round(n_epoch=1)
        exp._broadcast_anchor_sd = {
            k: np.ascontiguousarray(np.asarray(v))
            for k, v in params_to_state_dict(exp.params).items()
        }
        exp._stream_acc = exp._new_stream_acc()
        for cr in creds:
            exp.rounds.client_start(cr["client_id"])
        bodies = []
        for cr in creds:
            sd = {k: rng.standard_normal(np.shape(v)).astype(np.float32)
                  for k, v in template.items()}
            bodies.append(wire.encode(sd, {
                "update_name": round_name, "n_samples": 32.0,
                "loss_history": [0.0],
                "update_id": f"u{burst}-{cr['client_id']}",
            }))
        total_mb += sum(len(b) for b in bodies) / 1e6

        stop = asyncio.Event()

        async def probe():
            hb_json = {"client_id": creds[0]["client_id"],
                       "key": creds[0]["key"]}
            while not stop.is_set():
                with bench.timer("heartbeat_s"):
                    async with session.get(
                        f"{base}/heartbeat", json=hb_json
                    ) as r:
                        assert r.status == 200
                await asyncio.sleep(0.003)

        async def post_one(cr, body):
            with bench.timer("ack_s"):
                async with session.post(
                    f"{base}/update?client_id={cr['client_id']}"
                    f"&key={cr['key']}",
                    data=body, headers={"Content-Type": wire.CONTENT_TYPE},
                ) as resp:
                    assert resp.status == 200, await resp.text()

        probe_task = asyncio.ensure_future(probe())
        t0 = time.perf_counter()
        await asyncio.gather(*[
            post_one(cr, body) for cr, body in zip(creds, bodies)
        ])
        walls.append(time.perf_counter() - t0)
        stop.set()
        await probe_task

    snap = exp.metrics.snapshot()["counters"]
    assert snap.get("updates_received", 0) == c * bursts
    assert snap.get("ingest_rejected_429", 0) == 0
    lag_probe.stop()
    await session.close()
    await mrunner.cleanup()
    wall = sum(walls)
    hb = _timer_stats(bench, "heartbeat_s")
    ack = _timer_stats(bench, "ack_s")
    lag = _timer_stats(bench, "loop_lag_s")
    return {
        "ingest_workers": ingest_workers,
        "bursts": bursts,
        "updates_per_s": c * bursts / wall,
        "uplink_mb_per_s": total_mb / wall,
        "burst_wall_s": wall / bursts,
        "heartbeat_p50_s": hb["p50_s"],
        "heartbeat_p95_s": hb["p95_s"],
        "heartbeat_samples": hb["count"],
        "ack_p50_s": ack["p50_s"],
        "ack_p95_s": ack["p95_s"],
        "loop_lag_p95_s": lag["p95_s"],
        "loop_lag_max_s": lag["max_s"],
    }


async def _uplink_section(c: int, dim: int) -> dict:
    body_bytes = (dim + 1) * 4  # w + b, float32 (+ header noise)
    print(f"[uplink] C={c}, ~{body_bytes / 1e6:.1f}MB/update, "
          "ingest_workers=0 (inline baseline)...",
          file=sys.stderr, flush=True)
    baseline = await _uplink_once(c, dim, ingest_workers=0)
    print("[uplink] pipelined (ingest_workers=4)...",
          file=sys.stderr, flush=True)
    pipelined = await _uplink_once(c, dim, ingest_workers=4)
    out = {
        "cohort": c,
        "model_dim": dim,
        "baseline_inline": baseline,
        "pipelined": pipelined,
        "heartbeat_p95_speedup_x":
            baseline["heartbeat_p95_s"] / pipelined["heartbeat_p95_s"],
        "ack_p95_speedup_x":
            baseline["ack_p95_s"] / pipelined["ack_p95_s"],
    }
    print(f"[uplink] heartbeat p95: inline "
          f"{baseline['heartbeat_p95_s'] * 1e3:.1f}ms -> pipelined "
          f"{pipelined['heartbeat_p95_s'] * 1e3:.1f}ms "
          f"({out['heartbeat_p95_speedup_x']:.1f}x)",
          file=sys.stderr, flush=True)
    return out


async def _resume_section(resume_mb: int, chunk_mb: int) -> dict:
    """Kill a ~resume_mb chunked upload at ~90% (transport drop, twice —
    the client auto-retries an idempotent PUT once), restart the worker,
    and measure how much of the body crossed the wire twice."""
    from baton_tpu.server.http_worker import _PendingUpdate
    from baton_tpu.utils.faults import FaultInjector

    dim = resume_mb * (1 << 20) // 4
    chunk = chunk_mb << 20
    model = linear_regression_model(dim, name="resbench")
    inj = FaultInjector()
    mport = _free_port()
    mapp = web.Application(middlewares=[inj.middleware])
    exp = Manager(mapp).register_experiment(
        model, name="resbench", start_background_tasks=False,
        streaming_aggregation=True,
    )
    mrunner = web.AppRunner(mapp)
    await mrunner.setup()
    await web.TCPSite(mrunner, "127.0.0.1", mport).start()

    w1 = ExperimentWorker(
        web.Application(), model, f"127.0.0.1:{mport}", name="resbench",
        auto_register=False, upload_chunk_bytes=chunk,
    )
    await w1.register_with_manager()
    round_name = exp.rounds.start_round(n_epoch=1)
    exp._broadcast_anchor_sd = {
        k: np.ascontiguousarray(np.asarray(v))
        for k, v in params_to_state_dict(exp.params).items()
    }
    exp._stream_acc = exp._new_stream_acc()
    exp.rounds.client_start(w1.client_id)

    rng = np.random.default_rng(1)
    template = params_to_state_dict(exp.params)
    sd = {k: rng.standard_normal(np.shape(v)).astype(np.float32)
          for k, v in template.items()}
    body = wire.encode(sd, {
        "update_name": round_name, "n_samples": 32.0,
        "loss_history": [0.0], "update_id": "uid-resume",
    })
    total = len(body)
    p = _PendingUpdate(round_name=round_name, update_id="uid-resume",
                       body=body)
    kill_offset = chunk * int(0.9 * total / chunk)
    inj.drop(f"offset={kill_offset}&", times=2)

    print(f"[resume] uploading {total / 1e6:.0f}MB in {chunk_mb}MB frames, "
          f"killing at offset {kill_offset} "
          f"({100 * kill_offset / total:.0f}%)...",
          file=sys.stderr, flush=True)
    bench = Metrics()
    lag_probe = LoopLagProbe(bench, interval=0.05)
    lag_probe.start()
    t0 = time.perf_counter()
    status, _ = await w1._post_update_chunked(p)
    first_wall = time.perf_counter() - t0
    assert status is None, f"kill did not land (status={status})"
    committed = exp._chunks[(w1.client_id, "uid-resume")].offset

    w2 = ExperimentWorker(
        web.Application(), model, f"127.0.0.1:{mport}", name="resbench",
        auto_register=False, upload_chunk_bytes=chunk,
    )
    w2.client_id, w2.key = w1.client_id, w1.key
    t0 = time.perf_counter()
    status, _ = await w2._post_update_chunked(p)
    resume_wall = time.perf_counter() - t0
    assert status == 200, f"resume failed (status={status})"

    def _ctr(w, name):
        return w.metrics.snapshot()["counters"].get(name, 0.0)

    lag_probe.stop()
    lag = _timer_stats(bench, "loop_lag_s")
    put_total = _ctr(w1, "chunk_bytes_put") + _ctr(w2, "chunk_bytes_put")
    retransfer = (put_total - total) / total
    out = {
        "body_bytes": total,
        "chunk_bytes": chunk,
        "killed_at_offset": kill_offset,
        "killed_at_fraction": kill_offset / total,
        "committed_at_kill": committed,
        "resume_skipped_bytes": _ctr(w2, "chunk_bytes_resume_skipped"),
        "bytes_put_total": put_total,
        "retransfer_fraction": retransfer,
        "first_attempt_wall_s": first_wall,
        "resume_wall_s": resume_wall,
        "loop_lag_p95_s": lag["p95_s"],
        "loop_lag_max_s": lag["max_s"],
        "assembled": exp.metrics.snapshot()["counters"].get(
            "chunked_uploads_assembled", 0.0),
    }
    print(f"[resume] resumed from {committed} "
          f"({100 * committed / total:.0f}%), retransferred "
          f"{100 * retransfer:.1f}% of the body",
          file=sys.stderr, flush=True)
    await w1._on_cleanup()
    await w2._on_cleanup()
    await mrunner.cleanup()
    return out


async def _edge_topology_once(
    c: int, dim: int, n_edges: int, rounds: int
) -> tuple:
    """One topology configuration: C EchoWorkers either direct to the
    root (``n_edges=0``) or sharded over ``n_edges`` edge aggregators by
    the consistent-hash topology. Drives ``rounds`` rounds, runs a
    heartbeat probe through worker 0's route (root or its edge — the
    probe latency is what a worker actually sees), and returns
    ``(stats, final_state_dict)`` so the caller can compare aggregates
    across configurations bit-for-bit."""
    import aiohttp

    model = linear_regression_model(dim, name="edgebench")
    mport = _free_port()
    mapp = web.Application()
    exp = Manager(mapp).register_experiment(
        model, name="edgebench", round_timeout=600.0,
    )
    mrunner = web.AppRunner(mapp)
    await mrunner.setup()
    await web.TCPSite(mrunner, "127.0.0.1", mport).start()

    runners = [mrunner]
    edge_metrics = Metrics()
    edge_ports = {}
    topo = None
    if n_edges:
        topo = EdgeTopology([f"e{i}" for i in range(n_edges)])
        for i in range(n_edges):
            eport = _free_port()
            eapp = web.Application()
            EdgeAggregator(
                eapp, f"127.0.0.1:{mport}", name="edgebench", port=eport,
                edge_name=f"e{i}", ship_settle_s=0.25, flush_after_s=60.0,
                heartbeat_time=120.0, metrics=edge_metrics,
            )
            erunner = web.AppRunner(eapp)
            await erunner.setup()
            await web.TCPSite(erunner, "127.0.0.1", eport).start()
            edge_ports[f"e{i}"] = eport
            runners.append(erunner)

    workers, ack_log = [], []
    for i in range(c):
        wport = _free_port()
        wapp = web.Application()
        route = None
        if topo is not None:
            route = f"127.0.0.1:{edge_ports[topo.assign(f'w{i}')]}"
        w = EchoWorker(
            wapp, model, f"127.0.0.1:{mport}", name="edgebench",
            port=wport, heartbeat_time=120.0, ack_log=ack_log,
            noise_seed=i, get_data=lambda: ({}, 32), edge=route,
        )
        wrunner = web.AppRunner(wapp)
        await wrunner.setup()
        await web.TCPSite(wrunner, "127.0.0.1", wport).start()
        workers.append(w)
        runners.append(wrunner)
    # each edge registers at the root as a client of its own
    expect = c + n_edges
    for _ in range(1200):
        if len(exp.registry) == expect:
            break
        await asyncio.sleep(0.05)
    assert len(exp.registry) == expect, \
        f"registered {len(exp.registry)}/{expect}"

    full_size = len(wire.encode(
        {k: np.ascontiguousarray(np.asarray(v))
         for k, v in params_to_state_dict(exp.params).items()}, {}))

    bench = Metrics()
    lag_probe = LoopLagProbe(bench, interval=0.05)
    lag_probe.start()
    stop = asyncio.Event()
    timeout = aiohttp.ClientTimeout(total=600.0)
    session = aiohttp.ClientSession(timeout=timeout)
    w0 = workers[0]
    probe_base = w0.edge_url or f"http://127.0.0.1:{mport}/edgebench/"

    async def probe():
        hb_json = {"client_id": w0.client_id, "key": w0.key}
        while not stop.is_set():
            with bench.timer("heartbeat_s"):
                async with session.get(
                    f"{probe_base}heartbeat", json=hb_json
                ) as r:
                    assert r.status == 200
            await asyncio.sleep(0.005)

    probe_task = asyncio.ensure_future(probe())
    per_round = []
    for r in range(rounds):
        before = exp.metrics.snapshot()["counters"]
        ack_log.clear()
        t0 = time.perf_counter()
        async with session.get(
            f"http://127.0.0.1:{mport}/edgebench/start_round?n_epoch=1"
        ) as resp:
            assert resp.status == 200
        for _ in range(12000):
            if not exp.rounds.in_progress:
                break
            await asyncio.sleep(0.05)
        assert not exp.rounds.in_progress, f"round {r} hung"
        after = exp.metrics.snapshot()["counters"]
        round_hist = Metrics()
        for t in ack_log:
            round_hist.observe("notify_ack_s", t - t0)
        ack_stats = _timer_stats(round_hist, "notify_ack_s")
        per_round.append({
            "round": r,
            "root_bytes_down": after.get("bytes_broadcast", 0.0)
            - before.get("bytes_broadcast", 0.0),
            "root_bytes_up": after.get("bytes_uploaded", 0.0)
            - before.get("bytes_uploaded", 0.0),
            "edge_partials": after.get("updates_received_edge_partial", 0.0)
            - before.get("updates_received_edge_partial", 0.0),
            "acks": ack_stats["count"],
            "notify_ack_p50_s": ack_stats["p50_s"],
            "notify_ack_p95_s": ack_stats["p95_s"],
            "round_wall_s": time.perf_counter() - t0,
        })
        print(f"[edge n={n_edges}] round {r}: "
              f"root_down={per_round[-1]['root_bytes_down']:.0f}B "
              f"ack_p95={per_round[-1]['notify_ack_p95_s']:.3f}s "
              f"wall={per_round[-1]['round_wall_s']:.2f}s",
              file=sys.stderr, flush=True)

    stop.set()
    await probe_task
    lag_probe.stop()
    snap = exp.metrics.snapshot()["counters"]
    assert snap.get("updates_received", 0) == c * rounds
    assert snap.get("updates_received_edge_partial", 0) == n_edges * rounds
    final_sd = {k: np.asarray(v, np.float32)
                for k, v in params_to_state_dict(exp.params).items()}
    await session.close()
    for rn in runners:
        await rn.cleanup()

    hb = _timer_stats(bench, "heartbeat_s")
    lag = _timer_stats(bench, "loop_lag_s")
    esnap = edge_metrics.snapshot()["counters"] if n_edges else {}
    stats = {
        "n_edges": n_edges,
        "cohort": c,
        "full_blob_bytes": full_size,
        "root_bytes_down_per_round":
            sum(p["root_bytes_down"] for p in per_round) / len(per_round),
        "heartbeat_p50_s": hb["p50_s"],
        "heartbeat_p95_s": hb["p95_s"],
        "heartbeat_samples": hb["count"],
        "root_ingest_fold": _timer_stats(exp.metrics, "ingest_fold_s"),
        "root_ingest_decode": _timer_stats(exp.metrics, "ingest_decode_s"),
        "loop_lag_p95_s": lag["p95_s"],
        "loop_lag_max_s": lag["max_s"],
        "rounds": per_round,
    }
    if n_edges:
        stats["edge_ingest_fold"] = _timer_stats(
            edge_metrics, "ingest_fold_s")
        stats["edge_counters"] = {
            k: esnap.get(k, 0.0)
            for k in ("edge_blob_fetches", "edge_blob_hits",
                      "edge_updates_folded", "edge_partials_shipped",
                      "edge_registers_proxied", "edge_relay_notifies")
        }
    return stats, final_sd


async def _edge_section(c: int, dim: int, n_edges: int, rounds: int) -> dict:
    """Flat vs ``n_edges``-edge hierarchy at the same cohort size. The
    two runs are seeded identically (same model init, same per-worker
    noise streams), so the final root aggregates must agree within
    streaming-mean float tolerance — the associativity claim the edge
    tier rests on, checked here at benchmark scale too, not just in
    tests."""
    print(f"[edge] C={c}, flat (direct to root)...",
          file=sys.stderr, flush=True)
    flat, flat_sd = await _edge_topology_once(c, dim, 0, rounds)
    print(f"[edge] C={c}, {n_edges} edge aggregators...",
          file=sys.stderr, flush=True)
    edged, edge_sd = await _edge_topology_once(c, dim, n_edges, rounds)

    max_abs_diff = max(
        float(np.max(np.abs(flat_sd[k] - edge_sd[k]))) for k in flat_sd)
    agg_equal = all(
        np.allclose(flat_sd[k], edge_sd[k], rtol=1e-4, atol=1e-6)
        for k in flat_sd)
    reduction = flat["root_bytes_down_per_round"] / max(
        edged["root_bytes_down_per_round"], 1.0)
    assert agg_equal, \
        f"edge aggregate diverged from flat fold (max |d|={max_abs_diff})"
    assert reduction >= 3.0, \
        f"root downlink reduction {reduction:.1f}x < 3x"
    out = {
        "cohort": c,
        "model_dim": dim,
        "n_edges": n_edges,
        "rounds_per_config": rounds,
        "flat": flat,
        "edged": edged,
        "root_downlink_reduction_x": reduction,
        "aggregate_max_abs_diff": max_abs_diff,
        "aggregate_allclose": agg_equal,
    }
    print(f"[edge] root downlink {flat['root_bytes_down_per_round']:.0f}B "
          f"-> {edged['root_bytes_down_per_round']:.0f}B per round "
          f"({reduction:.1f}x), aggregate max |d|={max_abs_diff:.2e}",
          file=sys.stderr, flush=True)
    return out


async def _roots_once(c: int, n_roots: int, n_exps: int, waves: int) -> dict:
    """One root-replica configuration: ``n_exps`` experiments registered
    on every one of ``n_roots`` roots (each root announcing itself via
    ``ha_replica_id`` against the shared ``ha_replicas`` map), C clients
    split round-robin over the experiments. Each client registers at
    root-0, heartbeats once with redirects disabled, and — on a 307 —
    re-registers at the owner the response names, exactly the lazy
    topology-learning path a real worker takes. The heartbeat storm then
    runs against the learned owners. The ghost registrations the
    misrouted first contacts leave in root-0's registries are reported,
    not hidden — in production the TTL monitor expires them."""
    import aiohttp

    ports = [_free_port() for _ in range(n_roots)]
    urls = {f"root-{i}": f"http://127.0.0.1:{p}" for i, p in enumerate(ports)}
    exp_names = [f"shard{j}" for j in range(n_exps)]

    runners = []
    roots = []  # rid -> list of experiments
    for i, port in enumerate(ports):
        mapp = web.Application()
        mgr = Manager(mapp)
        exps = []
        for name in exp_names:
            kwargs = {}
            if n_roots > 1:
                kwargs = {"ha_replicas": urls,
                          "ha_replica_id": f"root-{i}"}
            exps.append(mgr.register_experiment(
                linear_regression_model(64, name=name), name=name,
                start_background_tasks=False, **kwargs,
            ))
        mrunner = web.AppRunner(mapp)
        await mrunner.setup()
        await web.TCPSite(mrunner, "127.0.0.1", port).start()
        runners.append(mrunner)
        roots.append(exps)

    # the same ring the managers built — predicts who owns what, and
    # therefore exactly how many first contacts must be redirected
    owner_of = {n: "root-0" for n in exp_names}
    if n_roots > 1:
        topo = replication.ExperimentTopology(sorted(urls))
        owner_of = {n: topo.assign(n) for n in exp_names}
    expected_redirects = sum(
        1 for k in range(c) if owner_of[exp_names[k % n_exps]] != "root-0")

    bench = Metrics()
    lag_probe = LoopLagProbe(bench, interval=0.05)
    lag_probe.start()
    conn = aiohttp.TCPConnector(limit=256)
    timeout = aiohttp.ClientTimeout(total=600.0)
    session = aiohttp.ClientSession(connector=conn, timeout=timeout)

    redirects = 0

    async def enroll(k: int) -> tuple:
        nonlocal redirects
        name = exp_names[k % n_exps]
        base = f"{urls['root-0']}/{name}"
        async with session.get(f"{base}/register",
                               json={"port": k + 1}) as r:
            cred = await r.json()
        async with session.get(
            f"{base}/heartbeat", json={"client_id": cred["client_id"],
                                       "key": cred["key"]},
            allow_redirects=False,
        ) as r:
            if r.status == 307:
                body = await r.json()
                redirects += 1
                base = body["url"].rstrip("/")
                async with session.get(f"{base}/register",
                                       json={"port": k + 1}) as r2:
                    cred = await r2.json()
            else:
                assert r.status == 200, await r.text()
        return name, base, cred

    t0 = time.perf_counter()
    clients = await asyncio.gather(*[enroll(k) for k in range(c)])
    enroll_wall = time.perf_counter() - t0
    assert redirects == expected_redirects, \
        f"{redirects} redirects followed, topology predicted " \
        f"{expected_redirects}"

    async def beat(name: str, base: str, cred: dict):
        with bench.timer("heartbeat_s"):
            async with session.get(
                f"{base}/heartbeat",
                json={"client_id": cred["client_id"], "key": cred["key"]},
                allow_redirects=False,
            ) as r:
                assert r.status == 200, f"{name}: {r.status}"

    t0 = time.perf_counter()
    for _ in range(waves):
        await asyncio.gather(*[beat(*cl) for cl in clients])
    storm_wall = time.perf_counter() - t0
    lag_probe.stop()
    await session.close()

    served = {}
    for name, base, _ in clients:
        served[owner_of[name]] = served.get(owner_of[name], 0) + waves
    per_root = []
    for i in range(n_roots):
        rid = f"root-{i}"
        registered = sum(len(e.registry) for e in roots[i])
        redirected = sum(
            e.metrics.snapshot()["counters"].get("heartbeats_redirected", 0.0)
            for e in roots[i])
        per_root.append({
            "replica": rid,
            "experiments_owned":
                sum(1 for n in exp_names if owner_of[n] == rid),
            "clients": sum(1 for n, _, _ in clients if owner_of[n] == rid),
            "registered_entries": registered,
            "heartbeats_served": served.get(rid, 0),
            "heartbeats_redirected": redirected,
        })
    for r in runners:
        await r.cleanup()

    hb = _timer_stats(bench, "heartbeat_s")
    lag = _timer_stats(bench, "loop_lag_s")
    return {
        "n_roots": n_roots,
        "cohort": c,
        "experiments": n_exps,
        "enroll_wall_s": enroll_wall,
        "redirects_followed": redirects,
        "storm_waves": waves,
        "heartbeats_total": c * waves,
        "storm_wall_s": storm_wall,
        "heartbeats_per_s": c * waves / storm_wall,
        "heartbeat_p50_s": hb["p50_s"],
        "heartbeat_p95_s": hb["p95_s"],
        "max_root_clients": max(p["clients"] for p in per_root),
        "ghost_registrations_at_root0": redirects,
        "loop_lag_p95_s": lag["p95_s"],
        "loop_lag_max_s": lag["max_s"],
        "per_root": per_root,
    }


async def _roots_section(c: int, n_roots: int, n_exps: int,
                         waves: int) -> dict:
    """1 root vs ``n_roots`` replicas at the same C. The division of
    per-root load (registry occupancy, heartbeats served) is the claim;
    latency columns carry the shared-event-loop caveat."""
    print(f"[roots] C={c}, {n_exps} experiments, 1 root (flat)...",
          file=sys.stderr, flush=True)
    flat = await _roots_once(c, 1, n_exps, waves)
    print(f"[roots] C={c}, {n_roots} root replicas (hash-ring sharded)...",
          file=sys.stderr, flush=True)
    sharded = await _roots_once(c, n_roots, n_exps, waves)

    for p in sharded["per_root"]:
        assert p["experiments_owned"] >= 1, \
            f"{p['replica']} owns no experiments — ring imbalanced"
    reduction = flat["max_root_clients"] / max(sharded["max_root_clients"], 1)
    assert reduction >= 2.0, \
        f"per-root load reduction {reduction:.1f}x < 2x with " \
        f"{n_roots} roots"
    out = {
        "cohort": c,
        "n_roots": n_roots,
        "experiments": n_exps,
        "flat": flat,
        "sharded": sharded,
        "per_root_load_reduction_x": reduction,
    }
    print(f"[roots] busiest root: {flat['max_root_clients']} -> "
          f"{sharded['max_root_clients']} clients ({reduction:.1f}x), "
          f"{sharded['redirects_followed']} one-time redirects, "
          f"storm {sharded['heartbeats_per_s']:.0f} hb/s",
          file=sys.stderr, flush=True)
    return out


async def _main(cohorts, dim, rounds, spec, sections, uplink_cohort,
                uplink_dim, resume_mb, chunk_mb, edge_cohort, edge_count,
                edge_rounds, roots_cohort, roots_count, roots_exps,
                roots_waves, prior) -> dict:
    out = {
        "benchmark": "dataplane_scale",
        "delta_spec": spec,
        "caveat": (
            "all C workers share one process and event loop; latency "
            "percentiles measure protocol + loopback scheduling, not a "
            "real network. Byte counts are exact."
        ),
        "results": prior.get("results", []),
        "uplink": prior.get("uplink"),
        "chunk_resume": prior.get("chunk_resume"),
        "edge_topology": prior.get("edge_topology"),
        "root_sharding": prior.get("root_sharding"),
    }
    if "downlink" in sections:
        out["results"] = []
        for c in cohorts:
            out["results"].append(await _one_cohort(c, dim, rounds, spec))
    if "uplink" in sections:
        out["uplink"] = await _uplink_section(uplink_cohort, uplink_dim)
    if "resume" in sections:
        out["chunk_resume"] = await _resume_section(resume_mb, chunk_mb)
    if "edge" in sections:
        out["edge_topology"] = await _edge_section(
            edge_cohort, dim, edge_count, edge_rounds)
    if "roots" in sections:
        out["root_sharding"] = await _roots_section(
            roots_cohort, roots_count, roots_exps, roots_waves)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cohorts", default="16,64,128")
    ap.add_argument("--dim", type=int, default=65536)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--delta-spec", default="topk:0.05:q8")
    ap.add_argument("--sections", default="downlink,uplink,resume",
                    help="comma list of downlink,uplink,resume,edge,roots; "
                         "skipped sections keep the previous JSON's "
                         "numbers")
    ap.add_argument("--uplink-cohort", type=int, default=64)
    ap.add_argument("--uplink-dim", type=int, default=1048576,
                    help="model dim for the uplink burst (~4MB/update)")
    ap.add_argument("--resume-mb", type=int, default=100)
    ap.add_argument("--chunk-mb", type=int, default=4)
    ap.add_argument("--edge-cohort", type=int, default=256)
    ap.add_argument("--edge-count", type=int, default=4)
    ap.add_argument("--edge-rounds", type=int, default=2)
    ap.add_argument("--roots-cohort", type=int, default=1024)
    ap.add_argument("--roots-count", type=int, default=4)
    ap.add_argument("--roots-experiments", type=int, default=16)
    ap.add_argument("--roots-waves", type=int, default=3)
    ap.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(__file__),
                             "dataplane_scale.json"),
    )
    args = ap.parse_args()
    cohorts = [int(x) for x in args.cohorts.split(",") if x]
    sections = {s.strip() for s in args.sections.split(",") if s.strip()}
    prior = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prior = json.load(f)
        except (OSError, ValueError):
            prior = {}
    result = asyncio.run(_main(
        cohorts, args.dim, args.rounds, args.delta_spec, sections,
        args.uplink_cohort, args.uplink_dim, args.resume_mb, args.chunk_mb,
        args.edge_cohort, args.edge_count, args.edge_rounds,
        args.roots_cohort, args.roots_count, args.roots_experiments,
        args.roots_waves, prior,
    ))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    for r in result["results"]:
        print(f"C={r['cohort']}: {r['downlink_reduction_x']:.1f}x downlink "
              f"reduction ({r['steady_bytes_down_per_round']:.0f}B vs "
              f"push {r['push_equiv_bytes_per_round']:.0f}B per round)")
    if result.get("uplink"):
        u = result["uplink"]
        print(f"uplink C={u['cohort']}: heartbeat p95 "
              f"{u['baseline_inline']['heartbeat_p95_s'] * 1e3:.1f}ms -> "
              f"{u['pipelined']['heartbeat_p95_s'] * 1e3:.1f}ms "
              f"({u['heartbeat_p95_speedup_x']:.1f}x), "
              f"{u['pipelined']['uplink_mb_per_s']:.0f} MB/s ingested")
    if result.get("chunk_resume"):
        cr = result["chunk_resume"]
        print(f"chunk resume: killed at "
              f"{100 * cr['killed_at_fraction']:.0f}%, retransferred "
              f"{100 * cr['retransfer_fraction']:.1f}% of "
              f"{cr['body_bytes'] / 1e6:.0f}MB")
    if result.get("edge_topology"):
        et = result["edge_topology"]
        print(f"edge C={et['cohort']}: root downlink "
              f"{et['flat']['root_bytes_down_per_round'] / 1e6:.1f}MB -> "
              f"{et['edged']['root_bytes_down_per_round'] / 1e6:.2f}MB "
              f"per round ({et['root_downlink_reduction_x']:.1f}x, "
              f"{et['n_edges']} edges), aggregate max "
              f"|d|={et['aggregate_max_abs_diff']:.2e}")
    if result.get("root_sharding"):
        rs = result["root_sharding"]
        print(f"roots C={rs['cohort']}: busiest root "
              f"{rs['flat']['max_root_clients']} -> "
              f"{rs['sharded']['max_root_clients']} clients "
              f"({rs['per_root_load_reduction_x']:.1f}x across "
              f"{rs['n_roots']} roots, "
              f"{rs['sharded']['redirects_followed']} one-time 307s)")
    print(f"wrote {args.out}")
