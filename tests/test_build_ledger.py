"""A job's start, seen from inside the program (ISSUE 50): the build
ledger of ``baton_tpu/obs/compute.py`` hears every jaxpr trace, lowering
and backend build that JAX makes (``jax.monitoring``), keeps the
outermost ones by program, holds a ``baton.build.*`` span open across
each, and is what a round's ``compile_s`` and five set-up metrics of the
benchmark read."""

import importlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

import baton_tpu
from baton_tpu.obs import compute
from baton_tpu.obs.compute import Build, BuildLedger, builds
from test_round_spans import _linear_cohort, _linear_sim, _profiled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest  # noqa: E402

PHASES = ["trace", "lower", "backend"]
SET_UP_READERS = ["build_trace_s", "build_lower_s", "build_backend_s",
                  "build_cold_s", "program_import_s"]


@pytest.fixture
def ledger():
    """The process's ledger, emptied before and after."""
    builds().reset()
    yield builds()
    builds().reset()


def _fresh(scale):
    """A jitted function JAX has not traced: a new function object."""
    @jax.jit
    def fresh_program(x):
        return jnp.tanh(x) * scale + 1.0

    return fresh_program


# ------------------------------------------------------- (a) the ledger
def test_a_first_call_is_three_events_of_one_program_and_a_second_none(
        ledger):
    f, x = _fresh(0.5), jnp.ones(5)
    ledger.reset()  # the array's own program is built
    t0 = time.perf_counter()
    jax.block_until_ready(f(x))
    wall = time.perf_counter() - t0
    events = ledger.events()
    assert [e.phase for e in events] == PHASES
    # the trace names the function, the module after it ``jit(...)``
    assert {e.program for e in events} == {"fresh_program"}
    assert all(isinstance(e, Build) and e.seconds > 0 for e in events)
    assert [e.cache_hit for e in events[:2]] == [None, None]
    assert isinstance(events[2].cache_hit, bool)
    assert t0 < events[0].ended <= events[1].ended <= events[2].ended
    totals = ledger.totals()
    assert set(totals) == {"trace_s", "lower_s", "backend_s", "cold_s",
                           "builds", "cache_hits"}
    assert [totals[p + "_s"] for p in PHASES] == [e.seconds for e in events]
    assert totals["builds"] == 1
    assert ledger.by_program() == {"fresh_program": totals}
    assert ledger.since(t0) == events[::-1]  # newest first
    assert 0 < sum(e.seconds for e in ledger.since(t0)) <= wall
    # only what ended after the time asked for
    assert ledger.since(events[1].ended) == [events[2]]
    assert ledger.since(time.perf_counter()) == []
    jax.block_until_ready(f(x))
    assert ledger.events() == events


def test_a_jit_traced_inside_anothers_trace_is_counted_once(ledger):
    entered = []

    def scalar(event, value, **kw):
        if event.endswith("jaxpr_trace_duration"):
            entered.append(kw["fun_name"])

    inner = _fresh(2.0)

    @jax.jit
    def outer_program(x):
        return inner(x) + jnp.cos(inner(x * 2.0))

    x = jnp.ones(3)
    ledger.reset()
    jax.monitoring.register_scalar_listener(scalar)
    try:
        t0 = time.perf_counter()
        jax.block_until_ready(outer_program(x))
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_scalar_listener(scalar)
    # JAX entered the inner traces (and jnp's own jits) inside the outer
    assert entered[0] == "outer_program" and "fresh_program" in entered
    assert len(entered) > 2
    traces = [e for e in ledger.events() if e.phase == "trace"]
    assert [e.program for e in traces] == ["outer_program"]
    assert 0 < ledger.totals()["trace_s"] <= wall
    assert sum(ledger.totals()[p + "_s"] for p in PHASES) <= wall
    assert list(ledger.by_program()) == ["outer_program"]


def test_an_event_entered_before_the_ledger_listened_is_let_go():
    mine = BuildLedger()
    event = "/jax/core/compile/backend_compile_duration"
    mine._on_duration(event, 1.0, fun_name="jit(f)")  # no entry was heard
    assert mine.events() == [] and mine.totals()["builds"] == 0
    # and a pair that is heard is recorded, whatever else is said between
    mine._on_scalar(event, time.time(), fun_name="jit(f)")
    mine._on_scalar("/jax/something/else", 3)
    mine._on_event("/jax/compilation_cache/cache_hits")
    mine._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    assert mine.events() == []  # a duration of no build ends none
    mine._on_duration(event, 2.0, fun_name="jit(f)")
    only, = mine.events()
    assert only[:3] == ("f", "backend", 2.0)
    assert only.cache_hit is True
    assert mine.totals() == {"trace_s": 0.0, "lower_s": 0.0,
                             "backend_s": 2.0, "cold_s": 0.0, "builds": 1,
                             "cache_hits": 1}


def test_the_kept_events_are_bounded_and_the_totals_are_not():
    mine = BuildLedger()
    event = "/jax/core/compile/jaxpr_trace_duration"
    for i in range(compute._EVENTS_KEPT + 10):
        mine._on_scalar(event, 0.0, fun_name=f"f{i % 7}")
        mine._on_duration(event, 0.5, fun_name=f"f{i % 7}")
    assert len(mine.events()) == compute._EVENTS_KEPT
    assert mine.totals()["trace_s"] == 0.5 * (compute._EVENTS_KEPT + 10)
    assert len(mine.by_program()) == 7
    lines = mine.summary(top=3)
    assert len(lines) == 4 and lines[0].startswith("7 programs: trace ")


def test_a_program_the_persistent_cache_serves_is_a_hit_and_not_cold(
        ledger, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {name: getattr(jax.config, name) for name in names}
    f, x = _fresh(0.125), jnp.ones(6)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        ledger.reset()
        jax.block_until_ready(f(x))
        cold = ledger.totals()
        assert cold["builds"] == 1 and cold["cache_hits"] == 0
        assert cold["cold_s"] == cold["backend_s"] > 0
        assert ledger.events()[-1].cache_hit is False
        assert os.listdir(str(tmp_path))
        jax.clear_caches()  # the jit's own, in memory; the files stay
        jax.block_until_ready(f(x))
        warm = ledger.totals()
        assert warm["builds"] == 2 and warm["cache_hits"] == 1
        assert warm["cold_s"] == cold["cold_s"]
        assert warm["backend_s"] > cold["backend_s"]
        served = ledger.events()[-1]
        assert served.phase == "backend" and served.cache_hit is True
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_importing_the_module_again_adds_no_listener():
    counts = [len(jax_monitoring.get_event_duration_listeners()),
              len(jax_monitoring.get_scalar_listeners()),
              len(jax_monitoring.get_event_listeners())]
    again = importlib.import_module("baton_tpu.obs.compute")
    from baton_tpu.obs import compute as once_more
    assert again is compute and once_more is compute
    assert again.builds() is builds()
    assert counts == [len(jax_monitoring.get_event_duration_listeners()),
                      len(jax_monitoring.get_scalar_listeners()),
                      len(jax_monitoring.get_event_listeners())]
    mine = builds()
    assert sum(getattr(l, "__self__", None) is mine
               for l in jax_monitoring.get_event_duration_listeners()) == 1


def test_the_package_says_what_its_import_took():
    assert isinstance(baton_tpu.IMPORT_S, float) and baton_tpu.IMPORT_S > 0


# ----------------------------------------------- (b) the spans, a session
@pytest.fixture(scope="module")
def first_rounds(tmp_path_factory):
    """A ``FedSim``'s first three rounds under a CPU profiler session."""
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    builds().reset()

    def three_rounds():
        p = params
        for i in range(3):
            p = sim.run_round(p, data, n, jax.random.key(i),
                              wave_size=4).params
        return p

    _, spans, _ = _profiled(three_rounds,
                            str(tmp_path_factory.mktemp("builds")))
    return spans, builds().events()


def _inside(outer, spans):
    return [s for s in spans if outer[1] <= s[1] and s[2] <= outer[2]
            and s is not outer]


@pytest.mark.parametrize("phase", PHASES)
def test_session_a_first_launch_holds_the_wave_programs_build(
        first_rounds, phase):
    spans, events = first_rounds
    launch = [s for s in spans if s[0] == "baton.round.dispatch.launch"][0]
    built = [s for s in _inside(launch, spans)
             if s[0] == "baton.build." + phase]
    assert [s[3]["program"] for s in built] == ["_wave_sums_vmap"]
    # the span is the event the ledger kept, to the profiler's clock
    kept, = [e for e in events
             if (e.program, e.phase) == ("_wave_sums_vmap", phase)]
    assert (built[0][2] - built[0][1]) / 1e9 == pytest.approx(
        kept.seconds, abs=2e-3)
    assert ("cache_hit" in built[0][3]) == (phase == "backend")
    if phase == "backend":
        assert built[0][3]["cache_hit"] == int(kept.cache_hit)


def test_session_every_build_span_names_its_program_and_a_third_round_none(
        first_rounds):
    spans, events = first_rounds
    built = [s for s in spans if s[0].startswith("baton.build.")]
    assert len(built) == len(events) > 3
    assert {s[0] for s in built} == {"baton.build." + p for p in PHASES}
    assert all(s[3]["program"] for s in built)
    rounds = [s for s in spans if s[0] == "baton.round"]
    assert len(rounds) == 3
    assert [s for s in _inside(rounds[0], built)]
    assert _inside(rounds[2], built) == []


# ------------------------------------------------ (c) the compute record
def test_a_first_rounds_compile_s_is_what_jax_built_since_its_start(ledger):
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    asked = []
    record_round = sim.compute_probe.record_round

    def recorded(**kw):
        asked.append((time.perf_counter(), kw["train_s"]))
        return record_round(**kw)

    sim.compute_probe.record_round = recorded
    t0 = time.perf_counter()
    first = sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
    wall = time.perf_counter() - t0
    record = sim.last_compute
    (at, train_s), = asked
    assert record["compile_s_source"] == "jax_monitoring"
    assert record["compile_s"] == pytest.approx(
        sum(b.seconds for b in ledger.since(at - train_s)), abs=2e-6)
    assert 0 < record["compile_s"] < record["train_s"] < wall
    assert 0 <= record["compile_cold_s"] <= record["compile_s"]
    wave = ledger.by_program()["_wave_sums_vmap"]
    assert record["compile_s"] >= (wave["trace_s"] + wave["lower_s"]
                                   + wave["backend_s"])
    # steady rounds build nothing
    params = first.params
    for i in range(2, 4):
        params = sim.run_round(params, data, n, jax.random.key(i),
                               wave_size=4).params
    steady = sim.last_compute
    assert len(asked) == 3
    assert (steady["compile_s"], steady["compile_s_source"],
            steady["compile_cold_s"]) == (0.0, "cache_hit", 0.0)


# ------------------------------------------------------ (d) the readers
@pytest.mark.parametrize("name", SET_UP_READERS + ["host_late_share"])
def test_a_reader_reads_nothing_without_a_time_or_a_trace(name):
    reader = manifest.load_module(ROOT, "layer_metrics", name)
    assert reader.read(None, {}, {"name": "resnet18_c32_w1"}) is None
    # a rehearsal's counters: counts, and no ``init_s``
    assert reader.read(None, {"compiles_in_window": 0, "n_waves": 1},
                       {"name": "resnet18_c32_w1"}) is None


@pytest.mark.parametrize("name,key", [
    ("build_trace_s", "trace_s"), ("build_lower_s", "lower_s"),
    ("build_backend_s", "backend_s"), ("build_cold_s", "cold_s")])
def test_a_set_up_reader_reads_the_ledgers_total(name, key, ledger):
    jax.block_until_ready(_fresh(3.0)(jnp.ones(2)))
    reader = manifest.load_module(ROOT, "layer_metrics", name)
    value = reader.read(None, {"init_s": 1.0}, {})
    assert value == ledger.totals()[key]
    assert value > 0 or key == "cold_s"


def test_the_import_reader_reads_the_packages_own_stamp():
    reader = manifest.load_module(ROOT, "layer_metrics", "program_import_s")
    assert reader.read(None, {"init_s": 1.0}, {}) == baton_tpu.IMPORT_S


@pytest.mark.parametrize("ready,runs,share", [
    (0, 4, 0.0), (1, 4, 25.0), (4, 4, 100.0)])
def test_host_late_share_is_ready_syncs_over_syncs(ready, runs, share):
    reader = manifest.load_module(ROOT, "layer_metrics", "host_late_share")
    reduced = {"span_runs": {"baton.round.sync": runs, "baton.round": runs},
               "span_attrs": {"baton.round.sync": {
                   "settles": 10, "ready": ready, "own": 0}}}
    assert reader.read(reduced, {"init_s": 1.0}, {}) == share
    # a window without a sync, or a sync that says nothing of ``ready``
    assert reader.read({"span_runs": {}, "span_attrs": {}}, {}, {}) is None
    reduced["span_attrs"]["baton.round.sync"].pop("ready")
    assert reader.read(reduced, {}, {}) is None


def test_the_six_entries_are_the_last_of_the_list():
    """Appended together, in this order, after everything the benchmark
    had then; a later PR appends after them (by membership, at no fixed
    distance from the end)."""
    bench = manifest.load_manifest(ROOT)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(SET_UP_READERS[0])
    last = bench["per_layer"][at:at + 6]
    assert [m["name"] for m in last] == SET_UP_READERS + ["host_late_share"]
    assert "window_core_roofline" in names[:at]
    assert all("workloads" not in m for m in last)
    assert {m["layer"] for m in last[:5]} == {"set-up"}
    assert {m["moves"] for m in last[:5]} == {"setup_s"}
