"""A steady round is settled by the round after it; a round that
compiled or loaded a program settles itself (ISSUE 37).

``FedSim.run_round`` of a round whose launches all hit the jit's fast
path returns once its waves and fold are queued; the next ``run_round``
on that ``FedSim`` waits for the round only after it has queued its own
programs, and a read of ``last_compute`` waits for it there
(``FedSim._settle``). A round in which a launch added an entry to its
program's cache, or whose shape the compute tracker had not seen, waits
for itself before it returns and leaves nothing pending. What has to
stay true: the same numbers to the bit, one compute record a round and
in order, ``progress_fn``'s sync a wave, no handle left behind by a
round that raised."""

import sys
import time

import jax
import numpy as np
import optax
import pytest

from baton_tpu.obs.compute import validate_record
from baton_tpu.parallel import engine
from baton_tpu.parallel.mesh import make_mesh, shard_client_arrays
from baton_tpu.server.http_manager import _clean_compute
# the cohort of six clients, its FedSim, and the stand-in for ``annotate``
# that records what was opened and what is open
from test_round_spans import _linear_cohort as _cohort
from test_round_spans import _linear_sim as _sim
from test_round_spans import recorder  # noqa: F401  (a fixture)


def _names(recorder):
    return [name for name, _ in recorder.opened]


@pytest.fixture
def waits(monkeypatch):
    """Every ``jax.block_until_ready`` the engine makes: what was waited
    for."""
    seen = []
    block = jax.block_until_ready

    def recorded(x):
        seen.append(x)
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", recorded)
    return seen


# ------------------------------------------------ (1) the same numbers
def _three_rounds(sim, params, state, data, n, wave_size, settle_between):
    out = []
    for i in range(3):
        res = sim.run_round(params, data, n, jax.random.key(10 + i),
                            wave_size=wave_size, server_opt_state=state)
        if settle_between:
            # what every round did before ISSUE 36: nothing is pending
            # when the next one starts
            jax.block_until_ready(res)
            assert sim.last_compute is not None and sim._pending is None
        params, state = res.params, res.server_opt_state
        out.append(res)
    return out


@pytest.mark.parametrize("frozen", [False, True],
                         ids=["whole", "frozen_partition"])
@pytest.mark.parametrize("wave_size", [None, 4],
                         ids=["one_wave", "two_waves"])
@pytest.mark.parametrize("mesh", [None, 2], ids=["no_mesh", "mesh2"])
@pytest.mark.parametrize("aggregator", ["mean", "median"])
def test_chained_rounds_are_bit_identical_to_rounds_settled_one_by_one(
        aggregator, mesh, wave_size, frozen):
    data, n = _cohort()
    kw = {"aggregator": aggregator,
          "server_optimizer": optax.sgd(0.5, momentum=0.9)}
    if mesh is not None:
        kw["mesh"] = make_mesh(mesh)
        data = shard_client_arrays(data, kw["mesh"])
    if frozen:
        kw["trainable"] = lambda path, leaf: path == "w"
    results = []
    for settle_between in (False, True):
        sim = _sim(**kw)
        params = sim.init(jax.random.key(0))
        results.append(_three_rounds(
            sim, params, sim.init_server_opt_state(params), data, n,
            wave_size, settle_between))
    for chained, settled in zip(*results):
        for name in ("params", "loss_history", "client_losses",
                     "n_samples_total", "server_opt_state"):
            a = jax.tree_util.tree_leaves(getattr(chained, name))
            b = jax.tree_util.tree_leaves(getattr(settled, name))
            assert len(a) == len(b) and a, name
            for x, y in zip(a, b):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------ (2) what a round waits for
@pytest.fixture
def at_each_wait(recorder, monkeypatch):
    """``(what was waited for, the spans open then, the spans opened
    since the fixture's list was last emptied)`` a wait of the engine's."""
    seen = []
    block = jax.block_until_ready

    def recorded(x):
        seen.append((x, list(recorder.open), _names(recorder)))
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", recorded)
    return seen


def _round(sim, params, data, n, i, **kw):
    return sim.run_round(params, data, n, jax.random.key(i),
                         **{"wave_size": 4, **kw})


def test_the_first_round_waits_for_itself_and_a_steady_one_for_the_round_before(
        recorder, at_each_wait):
    data, n = _cohort()
    sim = _sim()
    # the first round's launch compiles (its program's cache_entries
    # grow): it waits for its own loss sum, after its own fold, and
    # leaves nothing pending
    res = _round(sim, sim.init(jax.random.key(0)), data, n, 1)
    (own_sum, open_then, opened_then), = at_each_wait
    assert sim._pending is None and sim._rounds_queued == 1
    assert open_then == ["baton.round", "baton.round.sync"]
    assert opened_then.count("baton.round.dispatch.launch") == 2
    assert opened_then[-2:] == ["baton.round.fold", "baton.round.sync"]
    assert dict(recorder.opened)["baton.round.sync"] == {
        "settles": 1, "ready": int(own_sum.is_ready()), "own": 1}
    # the second hits the fast path: nothing is pending, it waits for
    # nothing, and is left pending itself
    del recorder.opened[:], at_each_wait[:]
    res = _round(sim, res.params, data, n, 2)
    assert at_each_wait == [] and sim._pending.index == 2
    # the third and the fourth wait once each, for the round before,
    # after both their waves and their fold are queued
    for i in (3, 4):
        handle = sim._pending.loss_sum
        del recorder.opened[:], at_each_wait[:]
        res = _round(sim, res.params, data, n, i)
        (waited, open_then, opened_then), = at_each_wait
        assert waited is handle
        assert open_then == ["baton.round", "baton.round.sync"]
        assert opened_then.count("baton.round.dispatch.launch") == 2
        assert opened_then[-2:] == ["baton.round.fold", "baton.round.sync"]
        sync = dict(recorder.opened)["baton.round.sync"]
        assert (sync["settles"], sync["own"]) == (i - 1, 0)
        assert sim._pending.index == i and sim._pending.loss_sum is not handle


def test_a_new_cohort_shape_mid_run_settles_the_round_before_and_then_itself(
        recorder, at_each_wait):
    data, n = _cohort()
    sim = _sim()
    res = _round(sim, sim.init(jax.random.key(0)), data, n, 1)
    res = _round(sim, res.params, data, n, 2)
    res = _round(sim, res.params, data, n, 3)
    before = sim._pending.loss_sum
    del recorder.opened[:], at_each_wait[:]
    # three clients: a shape the tracker has not seen, so what follows
    # compiles. The round before is settled ahead of this round's first
    # wave (its record's time ends before the compile starts), and this
    # round at its own end
    res = _round(sim, res.params, data, n, 4, client_indices=np.arange(3))
    assert sim._pending is None
    (first, open_first, opened_first), (second, _, opened_second) = (
        at_each_wait)
    assert first is before and second is not before
    assert open_first == ["baton.round", "baton.round.sync"]
    assert "baton.round.stage" not in opened_first
    assert opened_second[-2:] == ["baton.round.fold", "baton.round.sync"]
    syncs = [a for name, a in recorder.opened if name == "baton.round.sync"]
    assert [(a["settles"], a["own"]) for a in syncs] == [(3, 0), (4, 1)]
    assert sim.last_compute["steps"] == 3 * 2
    assert sim.last_compute["compile_s_source"] == "jax_monitoring"
    # the shape it had before is still on the fast path
    del at_each_wait[:]
    _round(sim, res.params, data, n, 5)
    assert at_each_wait == [] and sim._pending.index == 5


def test_a_launch_that_adds_a_cache_entry_settles_its_round_at_its_end(
        recorder, at_each_wait):
    """The tracker has seen the shape, the jit has not seen the call: on
    a mesh the second round's parameters come from the fold committed to
    the devices, where the first round's were not. The launch's
    ``cache_entries`` grow, and the round waits for itself."""
    data, n = _cohort()
    mesh = make_mesh(2)
    sim = _sim(mesh=mesh)
    data = shard_client_arrays(data, mesh)
    res = _round(sim, sim.init(jax.random.key(0)), data, n, 1)
    assert sim._pending is None
    del recorder.opened[:], at_each_wait[:]
    res = _round(sim, res.params, data, n, 2)
    entries = [a["cache_entries"] for name, a in recorder.opened
               if name == "baton.round.dispatch.launch"]
    assert entries[0] == 2  # the first round left 1
    (_, _, opened_then), = at_each_wait
    assert opened_then[-2:] == ["baton.round.fold", "baton.round.sync"]
    sync = dict(recorder.opened)["baton.round.sync"]
    assert (sync["settles"], sync["own"]) == (2, 1) and sim._pending is None
    # the tracker calls it a hit: nothing compiled for a shape of its own
    assert sim.last_compute["cache_hit"]
    del at_each_wait[:]
    _round(sim, res.params, data, n, 3)
    assert at_each_wait == [] and sim._pending.index == 3


def test_a_program_that_counts_no_entries_is_judged_by_the_trackers_shapes(
        recorder, at_each_wait, monkeypatch):
    data, n = _cohort()
    sim = _sim()
    wave_program = sim._wave_program

    def uncounted(n_epochs, robust):
        program, bind = wave_program(n_epochs, robust)
        return (lambda *args: program(*args)), bind

    monkeypatch.setattr(sim, "_wave_program", uncounted)
    res = _round(sim, sim.init(jax.random.key(0)), data, n, 1)
    assert len(at_each_wait) == 1 and sim._pending is None
    assert dict(recorder.opened)["baton.round.sync"]["own"] == 1
    del at_each_wait[:]
    _round(sim, res.params, data, n, 2)
    assert at_each_wait == [] and sim._pending.index == 2


# ------------------------------------------------ (3) a record a round
def test_n_rounds_leave_n_records_in_order_each_the_rounds_own(monkeypatch):
    data, n = _cohort()
    sim = _sim()
    params = sim.init(jax.random.key(0))
    asked, records = [], []
    record_round = sim.compute_probe.record_round

    def recorded(**kw):
        asked.append(kw)
        records.append(record_round(**kw))
        return records[-1]

    monkeypatch.setattr(sim.compute_probe, "record_round", recorded)
    # a shape's first round compiles and settles itself; one it has had
    # is left to the round after it, which may be of another shape
    cohorts = [np.arange(k) for k in (6, 6, 3, 3, 6, 5, 6)]
    own = [True, False, True, False, False, True, False]
    t0 = time.perf_counter()
    for i, chosen in enumerate(cohorts):
        params = sim.run_round(params, data, n, jax.random.key(i),
                               wave_size=4, client_indices=chosen).params
        # a round behind, never more; none behind after one that compiled
        assert len(records) == (i + 1 if own[i] else i)
        assert (sim._pending is None) == own[i]
    last = sim.last_compute
    wall = time.perf_counter() - t0
    assert len(records) == len(cohorts) and last is records[-1]
    # a steady round is settled by the round after it, once that round's
    # head has run: where that head builds programs (a cohort size the
    # process has not staged before), they fall into the steady round's
    # time
    next_builds = own[1:] + [False]
    for kw, record, chosen, settled_itself, head_after_builds in zip(
            asked, records, cohorts, own, next_builds):
        assert validate_record(record) == []
        assert kw["n_samples"] == float(n[chosen].sum())
        # two steps of 4 rows a client: the largest of each cohort has 8
        assert kw["steps"] == record["steps"] == len(chosen) * 2
        assert kw["signature"][0] == len(chosen)
        assert record["train_s_source"] in ("host_waited",
                                            "found_ready_upper_bound")
        assert _clean_compute(record)["train_s_source"] == (
            record["train_s_source"])
        # what JAX built while the round's time ran is in its record,
        # and is less than that time. A shape new to the tracker need
        # not build: three clients run the wave program of four
        assert record["cache_hit"] == (not settled_itself)
        assert record["compile_s_source"] == (
            "jax_monitoring" if record["compile_s"] > 0 else "cache_hit")
        if record is records[0]:  # this FedSim's own wave program
            assert record["compile_s"] > 0
        elif not (settled_itself or head_after_builds):
            assert record["compile_s"] == 0.0
        assert record["compile_cold_s"] <= record["compile_s"] < kw["train_s"]
        assert _clean_compute(record)["compile_cold_s"] == (
            record["compile_cold_s"])
    # a round's time starts where the round before it ended: the
    # intervals do not overlap, so they fit in the loop's wall time
    assert all(kw["train_s"] > 0 for kw in asked)
    assert sum(kw["train_s"] for kw in asked) <= wall


def test_last_compute_read_after_round_k_is_round_ks():
    data, n = _cohort()
    sim = _sim()
    params = sim.init(jax.random.key(0))
    assert sim.last_compute is None
    for k, chosen in enumerate([np.arange(6), np.arange(3), np.arange(6)]):
        params = sim.run_round(params, data, n, jax.random.key(k),
                               wave_size=4, client_indices=chosen).params
        record = sim.last_compute
        assert sim._pending is None
        assert record["steps"] == len(chosen) * 2
        assert record["cache_hit"] == (k == 2)
        assert sim.last_compute is record  # a second read settles nothing


def test_a_round_found_ready_says_its_time_is_an_upper_bound():
    data, n = _cohort()
    sim = _sim()
    res = sim.run_round(sim.init(jax.random.key(0)), data, n,
                        jax.random.key(1))
    res = sim.run_round(res.params, data, n, jax.random.key(2))
    jax.block_until_ready(res)
    time.sleep(0.05)
    record = sim.last_compute
    assert record["train_s_source"] == "found_ready_upper_bound"
    assert record["train_s"] >= 0.05


# --------------------------------------------------------- (4) errors
def _two_rounds(sim, data, n, **kw):
    """The first round, which settles itself, and the second, pending."""
    first = _round(sim, sim.init(jax.random.key(0)), data, n, 1, **kw)
    second = _round(sim, first.params, data, n, 2, **kw)
    assert sim._pending.index == 2
    return second


def test_a_round_that_raises_on_the_host_leaves_nothing_pending(
        recorder, monkeypatch):
    data, n = _cohort()
    sim = _sim()
    second = _two_rounds(sim, data, n)
    wave_program = sim._wave_program
    _, bind = wave_program(1, robust=False)

    def program(*args):
        raise FloatingPointError("the wave program failed")

    monkeypatch.setattr(sim, "_wave_program",
                        lambda n_epochs, robust: (program, bind))
    del recorder.opened[:]
    with pytest.raises(FloatingPointError):
        _round(sim, second.params, data, n, 3)
    assert recorder.open == [] and sim._pending is None
    # the round before it was settled on the way out, and has its record
    assert _names(recorder)[-3:] == ["baton.round.dispatch.launch",
                                     "baton.round.sync", "baton.round.record"]
    assert dict(recorder.opened)["baton.round.sync"]["settles"] == 2
    assert sim.last_compute["steps"] == 6 * 2
    assert sim._rounds_queued == 2
    monkeypatch.setattr(sim, "_wave_program", wave_program)
    del recorder.opened[:]
    fourth = _round(sim, second.params, data, n, 4)
    assert np.isfinite(np.asarray(fourth.loss_history)).all()
    # nothing was pending: the round after a failed one waits for none
    assert "baton.round.sync" not in _names(recorder)
    assert recorder.open == [] and sim._pending.index == 3


@pytest.mark.parametrize("settled_by", ["the_next_round", "a_read", "itself"])
def test_an_error_of_the_wait_is_raised_once_where_the_round_is_settled(
        monkeypatch, recorder, settled_by):
    data, n = _cohort()
    sim = _sim()
    params = sim.init(jax.random.key(0))
    if settled_by == "itself":
        # a FedSim's first round compiles, so its wait is its own: the
        # device's error is raised by the call that queued the round
        lost, handles = 1, []
    else:
        params = _two_rounds(sim, data, n, wave_size=None).params
        lost, handles = 2, [sim._pending.loss_sum]
    block = jax.block_until_ready

    def failing(x):
        if not handles or x is handles[0]:
            handles[:] = [x]
            raise RuntimeError(f"the device lost round {lost}")
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", failing)
    with pytest.raises(RuntimeError, match=f"lost round {lost}"):
        if settled_by == "a_read":
            sim.last_compute
        else:
            _round(sim, params, data, n, 3, wave_size=None)
    assert recorder.open == [] and sim._pending is None
    # the read does not raise again, and shows the last record written
    assert (sim.last_compute is None) == (settled_by == "itself")
    again = _round(sim, params, data, n, 3, wave_size=None)
    assert np.isfinite(np.asarray(again.loss_history)).all()
    assert sim.last_compute["steps"] == 6 * 2


# ------------------------------------------------------ (5) progress_fn
def test_progress_fn_is_called_once_a_wave_after_that_waves_loss_is_ready(
        waits):
    data, n = _cohort()
    sim = _sim()
    params = sim.init(jax.random.key(0))
    calls = []

    def progress(done, total):
        # the engine has just waited for this wave's loss sum
        calls.append((done, total, len(waits), waits[-1].is_ready()))

    res = _round(sim, params, data, n, 1, progress_fn=progress)
    assert calls == [(1, 2, 1, True), (2, 2, 2, True)]
    assert waits[0] is not waits[1]
    # the round compiled: a third wait, for the round's own sum, which is
    # the two waves' added
    assert len(waits) == 3 and waits[2] is not waits[1]
    assert sim._pending is None
    res = _round(sim, res.params, data, n, 2, progress_fn=progress)
    # a wait a wave and no other: nothing was pending
    assert [c[:3] for c in calls[2:]] == [(1, 2, 4), (2, 2, 5)]
    assert len(waits) == 5 and sim._pending.loss_sum is not waits[4]
    _round(sim, res.params, data, n, 3, progress_fn=progress)
    # a wait a wave, and one for the round before
    assert [c[:3] for c in calls[4:]] == [(1, 2, 6), (2, 2, 7)]
    assert len(waits) == 8 and waits[7] is not waits[6]


# ------------------------------------------- (6) the frame of run_round
@pytest.mark.skipif(sys.version_info[:2] != (3, 12),
                    reason="the frame layout counted is CPython 3.12's")
def test_run_rounds_frame_is_the_size_its_set_up_was_measured_at():
    """Where JAX's lowering falls on the interpreter's data stack, and
    with it whether it pays a page fault a call, depends on the size of
    every frame above the launch (engine.py, RUN_ROUND_FRAME_WORDS). A
    local or a deeper expression added to run_round moves it: count the
    faults again (scripts/lowering_faults/) before moving this number."""
    code = engine.FedSim.run_round.__code__
    cells = set(code.co_cellvars) - set(code.co_varnames)
    words = (code.co_nlocals + len(cells) + len(code.co_freevars)
             + code.co_stacksize)
    assert words == engine.RUN_ROUND_FRAME_WORDS
