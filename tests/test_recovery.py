"""Crash-recovery chaos tests (ISSUE 1 acceptance):

* the manager is torn down MID-ROUND and rebuilt from its write-ahead
  journal — workers keep their auth keys, the in-flight round resumes
  (or aborts, per ``recovery_policy``) and completes, and no client is
  double-counted in the aggregate;
* a worker whose ``update`` POSTs are refused/dropped retries from its
  at-least-once outbox until the manager acks;
* retries of an update whose 200 was lost are deduplicated by
  ``update_id``.
"""

import asyncio

import numpy as np
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from baton_tpu.core.training import make_local_trainer
from baton_tpu.data.synthetic import linear_client_data
from baton_tpu.models.linear import linear_regression_model
from baton_tpu.server import wire
from baton_tpu.server.http_manager import Manager
from baton_tpu.server.http_worker import ExperimentWorker
from baton_tpu.server.state import params_to_state_dict
from baton_tpu.utils.faults import FaultInjector

from test_http_protocol import free_port


def run(coro):
    return asyncio.run(coro)


async def _wait(cond, n=600, dt=0.05):
    for _ in range(n):
        if cond():
            return True
        await asyncio.sleep(dt)
    return cond()


async def _start_manager(name, mport, inj=None, **exp_kwargs):
    """Manager app on a real socket; returns (experiment, runner)."""
    model = linear_regression_model(10)
    middlewares = [inj.middleware] if inj is not None else []
    mapp = web.Application(middlewares=middlewares)
    exp = Manager(mapp).register_experiment(model, name=name, **exp_kwargs)
    mrunner = web.AppRunner(mapp)
    await mrunner.setup()
    await web.TCPSite(mrunner, "127.0.0.1", mport).start()
    return exp, mrunner


async def _start_workers(name, mport, n_workers, trainer):
    model = linear_regression_model(10)
    nprng = np.random.default_rng(3)
    workers, runners = [], []
    for _ in range(n_workers):
        wport = free_port()
        data = linear_client_data(nprng, min_batches=2, max_batches=2)
        wapp = web.Application()
        w = ExperimentWorker(
            wapp, model, f"127.0.0.1:{mport}",
            name=name, port=wport, heartbeat_time=0.5,
            trainer=trainer,
            get_data=lambda d=data: (d, d["x"].shape[0]),
            outbox_backoff=(0.05, 0.4),
        )
        wrunner = web.AppRunner(wapp)
        await wrunner.setup()
        await web.TCPSite(wrunner, "127.0.0.1", wport).start()
        workers.append(w)
        runners.append(wrunner)
    return workers, runners


async def _start_round(mport, name, n_epoch=2):
    import aiohttp

    async with aiohttp.ClientSession() as session:
        async with session.get(
            f"http://127.0.0.1:{mport}/{name}/start_round?n_epoch={n_epoch}"
        ) as resp:
            assert resp.status == 200
            return await resp.json()


# ----------------------------------------------------------------------
# outbox: retry-until-delivery


def test_outbox_retries_503_until_delivered():
    """Every update POST is refused N times; the outbox keeps retrying
    (capped backoff) and the round still completes with full
    participation — the seed dropped the round's training on the first
    failure."""

    async def main():
        inj = FaultInjector()
        name, mport = "rty", free_port()
        exp, mrunner = await _start_manager(name, mport, inj=inj)
        trainer = make_local_trainer(linear_regression_model(10),
                                     batch_size=32, learning_rate=0.02)
        workers, wrunners = await _start_workers(name, mport, 1, trainer)
        assert await _wait(lambda: len(exp.registry) == 1)

        # warm-up: compile the trainer outside the faulted window
        # the manager closes the round on the POST; the worker counts the
        # delivery when the 200 reaches it, a moment later
        await _start_round(mport, name)
        assert await _wait(lambda: not exp.rounds.in_progress
                           and workers[0].n_updates == 1)

        rule = inj.error(f"/{name}/update", status=503, times=3)
        acks = await _start_round(mport, name)
        assert all(acks.values())
        assert await _wait(lambda: not exp.rounds.in_progress
                           and workers[0].n_updates == 2)
        # delivery happened on the attempt AFTER the injected refusals
        assert rule.hits == 3
        snap = workers[0].metrics.snapshot()
        assert snap["counters"]["update_retries"] >= 3
        assert snap["counters"]["updates_delivered"] == 2
        assert exp.metrics.snapshot()["counters"]["updates_received"] == 2

        for r in [mrunner] + wrunners:
            await r.cleanup()

    run(main())


def test_outbox_retries_dropped_connection_until_delivered():
    """Same as above but the POSTs die at the TCP level (connection
    reset, no HTTP response at all)."""

    async def main():
        inj = FaultInjector()
        name, mport = "rtd", free_port()
        exp, mrunner = await _start_manager(name, mport, inj=inj)
        trainer = make_local_trainer(linear_regression_model(10),
                                     batch_size=32, learning_rate=0.02)
        workers, wrunners = await _start_workers(name, mport, 1, trainer)
        assert await _wait(lambda: len(exp.registry) == 1)

        await _start_round(mport, name)
        assert await _wait(lambda: not exp.rounds.in_progress)

        rule = inj.drop(f"/{name}/update", times=2)
        await _start_round(mport, name)
        assert await _wait(lambda: not exp.rounds.in_progress)
        assert rule.hits == 2
        assert workers[0].n_updates == 2
        assert exp.metrics.snapshot()["counters"]["updates_received"] == 2

        for r in [mrunner] + wrunners:
            await r.cleanup()

    run(main())


# ----------------------------------------------------------------------
# dedup by update_id


def test_duplicate_update_id_acked_but_not_recounted():
    """A retry of an already-accepted upload (the 200 was lost in
    transit) is acked 200 again but folded into the round exactly once."""

    async def main():
        app = web.Application()
        exp = Manager(app).register_experiment(
            linear_regression_model(4), name="dd",
            start_background_tasks=False,
        )
        client = TestClient(TestServer(app))
        await client.start_server()

        creds = []
        for port in (1, 2):
            resp = await client.get("/dd/register", json={"port": port})
            creds.append(await resp.json())

        exp.rounds.start_round(n_epoch=1)
        # two participants so one report leaves the round OPEN — a
        # dedup that wrongly re-counted would end it early
        for c in creds:
            exp.rounds.client_start(c["client_id"])

        body = wire.encode(
            params_to_state_dict(exp.params),
            {"update_name": exp.rounds.round_name, "n_samples": 8,
             "loss_history": [0.1], "update_id": "uid-1"},
        )
        url = (f"/dd/update?client_id={creds[0]['client_id']}"
               f"&key={creds[0]['key']}")
        for _ in range(3):  # original + two retries of the same upload
            resp = await client.post(
                url, data=body,
                headers={"Content-Type": wire.CONTENT_TYPE},
            )
            assert resp.status == 200
        snap = exp.metrics.snapshot()
        assert snap["counters"]["updates_received"] == 1
        assert snap["counters"]["duplicate_updates_deduped"] == 2
        # round still waiting on the second participant — the retries
        # did not consume its slot
        assert exp.rounds.in_progress and exp.rounds.clients_left == 1
        # membership stats counted the upload once
        assert exp.registry[creds[0]["client_id"]].num_updates == 1

        # a NEW update from the same client (fresh update_id) is acked —
        # at-least-once delivery — but the FIRST accepted upload remains
        # final: under streaming aggregation the original contribution
        # is already folded into the running sum and cannot be retracted
        body2 = wire.encode(
            params_to_state_dict(exp.params),
            {"update_name": exp.rounds.round_name, "n_samples": 8,
             "loss_history": [0.05], "update_id": "uid-2"},
        )
        resp = await client.post(
            url, data=body2,
            headers={"Content-Type": wire.CONTENT_TYPE},
        )
        assert resp.status == 200
        assert len(exp.rounds.client_responses) == 1
        assert exp.rounds.update_ids[creds[0]["client_id"]] == "uid-1"
        snap = exp.metrics.snapshot()
        assert snap["counters"]["repeat_updates_ignored"] == 1
        assert snap["counters"]["updates_received"] == 1
        await client.close()

    run(main())


# ----------------------------------------------------------------------
# manager crash mid-round


async def _crashed_mid_round(name, journal_path, recovery_policy):
    """Common setup: manager A + 2 workers run one clean round (compile
    + journal compaction), then a round whose updates are all refused;
    manager A is torn down with the round open and the workers' outboxes
    still retrying. Returns everything the recovery half needs."""
    inj = FaultInjector()
    mport = free_port()
    exp_a, mrunner_a = await _start_manager(
        name, mport, inj=inj, journal_path=journal_path,
        recovery_policy=recovery_policy,
    )
    trainer = make_local_trainer(linear_regression_model(10),
                                 batch_size=32, learning_rate=0.02)
    workers, wrunners = await _start_workers(name, mport, 2, trainer)
    assert await _wait(lambda: len(exp_a.registry) == 2)

    await _start_round(mport, name)
    assert await _wait(lambda: not exp_a.rounds.in_progress)
    assert exp_a.rounds.n_rounds == 1

    # round 2: no update can land — the round is open at "crash" time
    inj.error(f"/{name}/update", status=503)
    acks = await _start_round(mport, name)
    assert sum(acks.values()) == 2
    crashed_round = exp_a.rounds.round_name
    # both workers finish training and park their update in the outbox
    assert await _wait(
        lambda: all(not w.round_in_progress for w in workers)
        and all(w._pending is not None for w in workers)
    )
    assert exp_a.rounds.in_progress  # died mid-round

    await mrunner_a.cleanup()  # the crash
    return mport, workers, wrunners, crashed_round


def test_manager_crash_recovery_resumes_round_from_journal():
    async def main():
        name = "rec"
        import tempfile, os

        with tempfile.TemporaryDirectory() as td:
            journal_path = os.path.join(td, "wal.jsonl")
            mport, workers, wrunners, crashed_round = (
                await _crashed_mid_round(name, journal_path, "resume")
            )
            ids_before = [w.client_id for w in workers]
            keys_before = [w.key for w in workers]

            # rebuild the manager on the same port from the journal
            exp_b, mrunner_b = await _start_manager(
                name, mport, journal_path=journal_path,
                recovery_policy="resume",
            )
            # registry recovered BEFORE the app even serves: same ids,
            # same auth keys
            assert set(exp_b.registry.clients) == set(ids_before)
            for cid, key in zip(ids_before, keys_before):
                assert exp_b.registry[cid].key == key
            assert exp_b.rounds.n_rounds == 1  # round 1 survived too

            # each client may be folded into the aggregate exactly once
            captured = {}
            orig_end = exp_b.rounds.end_round

            def end_wrapper():
                responses = orig_end()
                captured.update(responses)
                return responses

            exp_b.rounds.end_round = end_wrapper

            # the in-flight round resumes under its ORIGINAL name and
            # completes — via parked outboxes or re-announce retrain
            assert await _wait(
                lambda: exp_b.rounds.n_rounds == 2, n=900
            )
            snap = exp_b.metrics.snapshot()
            assert snap["counters"]["recovery_rounds_resumed"] == 1
            assert set(captured) == set(ids_before)  # both, exactly once
            assert all(
                r["n_samples"] > 0 for r in captured.values()
            )

            # workers never had to re-register: keys stayed valid
            assert [w.client_id for w in workers] == ids_before
            assert [w.key for w in workers] == keys_before

            # the journal recorded the resumed round as started+ended
            from baton_tpu.server.journal import Journal

            events = Journal(journal_path, fsync="never").load()[1]
            started = [e for e in events if e["event"] == "round_started"]
            # post-compaction the journal may be empty again (round 2's
            # end compacts); check via the recovered state instead
            st = exp_b.journal.recover()
            assert st.n_rounds == 2 and st.open_round is None
            assert started == [] or any(
                e.get("resumed") for e in started
            )

            # the federation is healthy: one more clean round
            await _start_round(mport, name)
            assert await _wait(lambda: exp_b.rounds.n_rounds == 3)

            for r in [mrunner_b] + wrunners:
                await r.cleanup()

    run(main())


def test_manager_crash_recovery_abort_policy():
    """recovery_policy="abort": the in-flight round is cleanly discarded
    on restart — the round counter stands, the workers' parked updates
    are 410'd into abandonment, and the next round runs clean."""

    async def main():
        name = "rab"
        import tempfile, os

        with tempfile.TemporaryDirectory() as td:
            journal_path = os.path.join(td, "wal.jsonl")
            mport, workers, wrunners, crashed_round = (
                await _crashed_mid_round(name, journal_path, "abort")
            )

            exp_b, mrunner_b = await _start_manager(
                name, mport, journal_path=journal_path,
                recovery_policy="abort",
            )
            assert not exp_b.rounds.in_progress
            assert exp_b.rounds.n_rounds == 1
            assert (
                exp_b.metrics.snapshot()["counters"]
                ["recovery_rounds_aborted"] == 1
            )

            # the parked updates hit the rebuilt manager, get 410
            # (round dead), and the outboxes abandon them
            assert await _wait(
                lambda: all(w._pending is None for w in workers)
            )
            assert all(
                w.metrics.snapshot()["counters"].get(
                    "updates_abandoned_round_gone", 0) >= 1
                for w in workers
            )
            assert exp_b.metrics.snapshot()["counters"].get(
                "updates_received", 0) == 0

            # auth keys still valid; a fresh round completes normally
            acks = await _start_round(mport, name)
            assert sum(acks.values()) == 2
            assert await _wait(lambda: exp_b.rounds.n_rounds == 2)

            for r in [mrunner_b] + wrunners:
                await r.cleanup()

    run(main())
