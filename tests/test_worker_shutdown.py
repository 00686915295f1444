"""A worker shuts down while no root answers its heartbeat.

``ExperimentWorker.heartbeat`` retries until a root answers; a tick
caught in that loop when the roots go away never returns, and a
``PeriodicTask.stop()`` that waits for it hung the worker's clean-up
(seen as ``tests/test_replication.py`` never ending, about one run in
four). Every wait here is bounded."""

import asyncio
import time

from aiohttp import web

from baton_tpu.core.training import make_local_trainer
from baton_tpu.data.synthetic import linear_client_data
from baton_tpu.models import linear_regression_model
from baton_tpu.server.http_manager import Manager
from baton_tpu.server.http_worker import ExperimentWorker
from baton_tpu.server.utils import PeriodicTask

import numpy as np
from test_http_protocol import free_port


async def _until(cond, seconds=10.0, dt=0.02):
    for _ in range(int(seconds / dt)):
        if cond():
            return True
        await asyncio.sleep(dt)
    return cond()


def test_periodic_task_cancel_ends_a_tick_that_never_returns():
    async def main():
        started = asyncio.Event()

        async def tick():
            started.set()
            await asyncio.Event().wait()  # retries for ever

        task = PeriodicTask(tick, 0.01).start()
        await asyncio.wait_for(started.wait(), 5)
        t0 = time.monotonic()
        await asyncio.wait_for(task.cancel(), 5)
        return time.monotonic() - t0, task.is_started

    elapsed, still_started = asyncio.run(main())
    assert elapsed < 1.0 and not still_started


def test_periodic_task_cancel_of_an_idle_schedule_is_a_stop():
    async def main():
        ticks = []

        async def tick():
            ticks.append(1)

        task = PeriodicTask(tick, 0.01).start()
        await _until(lambda: len(ticks) >= 2, 5)
        await asyncio.wait_for(task.cancel(), 1)
        n = len(ticks)
        await asyncio.sleep(0.05)
        return n >= 2 and len(ticks) == n and not task.is_started

    assert asyncio.run(main())


def test_worker_cleans_up_while_its_only_root_is_gone():
    async def main():
        name, mport, wport = "shutdown", free_port(), free_port()
        model = linear_regression_model(10)
        mapp = web.Application()
        exp = Manager(mapp).register_experiment(model, name=name)
        mrunner = web.AppRunner(mapp)
        await mrunner.setup()
        await web.TCPSite(mrunner, "127.0.0.1", mport).start()

        data = linear_client_data(np.random.default_rng(0), min_batches=1,
                                  max_batches=1)
        wapp = web.Application()
        worker = ExperimentWorker(
            wapp, model, f"127.0.0.1:{mport}", name=name, port=wport,
            heartbeat_time=0.05,
            trainer=make_local_trainer(model, batch_size=32),
            get_data=lambda: (data, data["x"].shape[0]))
        wrunner = web.AppRunner(wapp)
        await wrunner.setup()
        await web.TCPSite(wrunner, "127.0.0.1", wport).start()
        assert await _until(lambda: len(exp.registry) == 1)
        assert await _until(lambda: worker._heartbeat_task is not None)

        await asyncio.wait_for(mrunner.cleanup(), 10)  # the root dies
        # several heartbeat periods later a tick has found the root gone
        # and sits in heartbeat()'s retry loop (first backoff 1 s)
        await asyncio.sleep(0.3)
        # (a clean-up that hangs swallows wait_for's cancellation and
        # returns at the timeout: the clock tells the two apart)
        t0 = time.monotonic()
        await asyncio.wait_for(wrunner.cleanup(), 5)
        return time.monotonic() - t0

    assert asyncio.run(main()) < 2.0
