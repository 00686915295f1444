"""The head of a round as layer metrics (ISSUE 35): ``dispatch_idle_ms``,
``launch_idle_ms``, ``prepare_idle_ms``, ``harness_idle_ms`` and
``host_busy_ms_per_round`` on spans and idle gaps written by hand, the
partition of ``idle_ms_per_round`` they make with the three idle metrics
the benchmark had, and the way from ``FedSim.run_round``'s spans to a
reader through a profiler session recorded here."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest, trace_reduce as tr  # noqa: E402

RULES = manifest.load_op_categories(ROOT)
NAMES = manifest.load_trace_names(ROOT)
BENCH = manifest.load_manifest(ROOT)
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
NEW_METRICS = ["dispatch_idle_ms", "launch_idle_ms", "prepare_idle_ms",
               "harness_idle_ms", "host_busy_ms_per_round"]
# the idle metrics that, with the new ones, partition a round's idle
HAD = ["stage_idle_ms", "sync_record_idle_ms", "fold_idle_ms"]
CHILDREN = ("baton.round.dispatch.launch", "baton.round.dispatch.accumulate",
            "baton.round.prepare.keys", "baton.round.prepare.select")
CELL = {"required": {"kernel": None}}
NS = 1e-6  # a nanosecond in the metrics' milliseconds


def _read(name, reduced):
    return manifest.load_module(ROOT, "layer_metrics", name).read(
        reduced, {}, CELL)


def _span(name, start, dur, **stats):
    return {"plane": tr.HOST_PLANE, "line": "python3", "name": name,
            "start_ns": float(start), "dur_ns": float(dur), "stats": stats}


def _busy(start, dur, plane, module="jit__wave_sums_vmap(7)"):
    return [{"plane": plane, "line": tr.MODULE_LINE, "name": module,
             "start_ns": float(start), "dur_ns": float(dur)},
            {"plane": plane, "line": tr.OP_LINE, "name": "fusion.1",
             "start_ns": float(start), "dur_ns": float(dur), "scope": "",
             "opcode": "fusion", "kind": "kLoop", "shape": "f32[8]"}]


def _round(t, select=False, late=0):
    """One round of 1,000 ns from ``t``. The chip runs the wave from
    ``t + 180 + late`` (40 ns into ``launch``) to ``t + 780`` and the
    fold from ``t + 785`` to ``t + 795``."""
    spans = [
        _span("fedbench.round", t, 1000),
        _span("baton.round", t + 10, 980, clients=4, waves=1, wave_size=4),
        _span("baton.round.prepare", t + 20, 80),
        _span("baton.round.prepare.keys", t + 40, 30),
        _span("baton.round.stage", t + 100, 30, wave=0, real=4,
              padded=0),
        _span("baton.round.dispatch", t + 130, 100, wave=0),
        _span("baton.round.dispatch.launch", t + 140, 60, wave=0, leaves=6,
              cache_entries=1),
        _span("baton.round.dispatch.accumulate", t + 205, 20, wave=0),
        _span("baton.round.fold", t + 230, 20, programs=1),
        _span("baton.round.sync", t + 250, 550),
        _span("baton.round.record", t + 800, 30),
        _span("baton.round.update", t + 830, 150),
    ]
    if select:
        spans.append(_span("baton.round.prepare.select", t + 24, 10))
    return spans


def _trace(planes=(DEV0,), select=False, late=None):
    """Two rounds 100 ns apart and the harness's last sync; ``late``
    maps a plane to how much later its waves start."""
    rows = []
    for t in (0, 1100):
        rows += _round(t, select)
        for plane in planes:
            wait = (late or {}).get(plane, 0)
            rows += _busy(t + 180 + wait, 600 - wait, plane)
            rows += _busy(t + 785, 10, plane, "jit_fold(3)")
    return rows + [_span("fedbench.sync", 2100, 100)]


def _reduced(**kw):
    return tr.reduce_rows(_trace(**kw), RULES, NAMES)


# a round's idle in the trace above, by where it fell (ns)
IDLE = {
    "dispatch_idle_ms": 10 + 40,      # 130-140 in dispatch, 140-180 in launch
    "launch_idle_ms": 40,
    "prepare_idle_ms": 20 + 30 + 30,  # 20-40, keys 40-70, 70-100
    "stage_idle_ms": 30,
    "sync_record_idle_ms": 5 + 5 + 30,  # 780-785, 795-800, record
    "fold_idle_ms": 0,
    # fedbench.round 0-10 and 990-1000; the 100 between the two rounds
    # and the 100 of the last sync, over two rounds
    "harness_idle_ms": 20 + 50 + 50,
}
ROUND_AND_UPDATE = 20 + 150           # 10-20 and 980-990; update 830-980


@pytest.mark.parametrize("name", sorted(IDLE))
def test_each_idle_metric_reads_its_spans(name):
    assert _read(name, _reduced()) == pytest.approx(IDLE[name] * NS)


def test_host_busy_is_the_rounds_own_time_without_the_wait():
    # own time: duration less children. baton.round 980 - 960, prepare
    # 80 - 30, keys 30, stage 30, dispatch 100 - 80, launch 60,
    # accumulate 20, fold 20, record 30, update 150; sync's 550 is a wait
    reduced = _reduced()
    assert _read("host_busy_ms_per_round", reduced) == pytest.approx(
        (20 + 50 + 30 + 30 + 20 + 60 + 20 + 20 + 30 + 150) * NS)
    assert reduced["host_self_s"]["baton.round.sync"] == pytest.approx(
        2 * 550e-9)
    # the harness's own spans are not the program's time
    harness = sum(v for k, v in reduced["host_self_s"].items()
                  if k.startswith("fedbench."))
    assert harness == pytest.approx((2 * 20 + 100) * 1e-9)


def test_a_childs_idle_is_not_lost_to_its_parent():
    reduced = _reduced(select=True)
    by_span = reduced["devices"][DEV0]["idle_by_span_s"]
    # the reduction gives a gap to the innermost span ...
    assert by_span["baton.round.dispatch"] == pytest.approx(2 * 10e-9)
    assert by_span["baton.round.dispatch.launch"] == pytest.approx(2 * 40e-9)
    assert by_span["baton.round.prepare.select"] == pytest.approx(2 * 10e-9)
    assert by_span["baton.round.prepare"] == pytest.approx(2 * 40e-9)
    # ... and the parent's metric takes its children's back
    assert _read("dispatch_idle_ms", reduced) == pytest.approx(50 * NS)
    assert _read("prepare_idle_ms", reduced) == pytest.approx(80 * NS)
    assert _read("launch_idle_ms", reduced) <= _read("dispatch_idle_ms",
                                                     reduced)


@pytest.mark.parametrize("select", [False, True])
def test_the_idle_metrics_partition_a_rounds_idle(select):
    reduced = _reduced(select=select)
    parts = [n for n in IDLE if n != "launch_idle_ms"]  # a part of dispatch
    total = _read("idle_ms_per_round", reduced)
    assert total == pytest.approx(
        (sum(IDLE[n] for n in parts) + ROUND_AND_UPDATE) * NS)
    by_span = reduced["devices"][DEV0]["idle_by_span_s"]
    left_over = 1e3 * (by_span["baton.round"]
                       + by_span["baton.round.update"]) / reduced["n_rounds"]
    assert sum(_read(n, reduced) for n in parts) == pytest.approx(
        total - left_over)


@pytest.mark.parametrize("name", HAD + ["idle_ms_per_round",
                                        "dispatch_idle_ms",
                                        "prepare_idle_ms",
                                        "harness_idle_ms"])
def test_the_accepted_metrics_read_the_same_without_the_new_spans(name):
    """A program that opens no child span (the parent commit) gives the
    metrics the benchmark had, and the two parents' totals, the same
    numbers: the children only say where inside the parent."""
    with_children = _reduced()
    without = tr.reduce_rows(
        [r for r in _trace() if r["name"] not in CHILDREN], RULES, NAMES)
    assert not set(CHILDREN) & set(without["span_runs"])
    assert _read(name, without) == pytest.approx(_read(name, with_children))


def test_a_program_without_the_launch_span_reads_nothing_there():
    without = tr.reduce_rows(
        [r for r in _trace() if r["name"] not in CHILDREN], RULES, NAMES)
    assert _read("launch_idle_ms", without) is None
    # no span of the program at all: only the harness's metric reads
    bare = tr.reduce_rows(
        [r for r in _trace() if not r["name"].startswith("baton.")],
        RULES, NAMES)
    assert _read("host_busy_ms_per_round", bare) is None
    assert _read("dispatch_idle_ms", bare) is None
    assert _read("prepare_idle_ms", bare) is None
    assert _read("harness_idle_ms", bare) == pytest.approx(
        _read("idle_ms_per_round", bare))


def test_idle_is_the_mean_over_the_cells_devices():
    reduced = _reduced(planes=(DEV0, DEV1), late={DEV1: 20})
    # the second chip's waves start 20 ns later, still inside launch
    assert _read("launch_idle_ms", reduced) == pytest.approx(50 * NS)
    assert _read("dispatch_idle_ms", reduced) == pytest.approx(60 * NS)
    assert _read("prepare_idle_ms", reduced) == pytest.approx(80 * NS)
    # host time is the host's: one figure whatever the chips
    assert _read("host_busy_ms_per_round", reduced) == pytest.approx(
        _read("host_busy_ms_per_round", _reduced()))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_without_a_trace_a_reader_reads_nothing(name):
    assert _read(name, None) is None


def test_the_five_metrics_are_entries_for_every_cell():
    """Membership, order among the five, and each entry's fields: where
    in ``per_layer`` they stand is the contract's to say, and the next
    PR's entries come after them."""
    entries = BENCH["per_layer"]
    names = [m["name"] for m in entries]
    assert [n for n in names if n in NEW_METRICS] == NEW_METRICS
    for m in entries:
        if m["name"] not in NEW_METRICS:
            continue
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "round loop",
                     "moves": "samples_per_s_per_chip"}
        reader = manifest.load_module(ROOT, "layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
    for cell in BENCH["workloads"]:
        assert set(NEW_METRICS) <= {
            m["name"] for m in manifest.metrics_for(entries, cell["name"])}


def test_the_earlier_metrics_are_as_the_benchmark_had_them():
    """What ``test_fedbench_sarvam.py::test_the_hybrids_metrics_stand_
    where_the_benchmark_had_them`` and ``test_fedbench_olmo_hybrid.py``
    guard beside position (each holds a PR's metrics to the END of
    ``per_layer``, where the contract has every PR append its own, and
    fails at that line from the next PR on): the nine are there, in
    their order, from the trace, each in its cells. No position is held
    here."""
    names = [m["name"] for m in BENCH["per_layer"]]
    theirs = ["linear_attn_ms", "delta_scan_ms", "lm_loss_ms",
              "delta_scan_roofline", "mla_ms", "moe_ms", "expert_matmul_ms",
              "expert_matmul_roofline", "mla_core_roofline"]
    assert [n for n in names if n in theirs] == theirs
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    hybrid, sarvam = "olmo_hybrid_c4_l1024", "sarvam_105b_c4_l2048"
    for i, name in enumerate(theirs):
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["workloads"] == (
            [hybrid, sarvam] if name == "lm_loss_ms"
            else [hybrid] if i < 4 else [sarvam])


# ------------------------------------ from the program's spans to a reader
def test_the_programs_spans_reach_the_reduction(tmp_path):
    """Two rounds of ``FedSim.run_round`` under a profiler session, read
    as ``fedbench/run.py`` reads a trace: the new spans are kept by
    ``read_events`` under the ``baton.`` prefix, counted in
    ``span_runs``, timed in ``host_self_s``, and their numeric
    attributes summed in ``span_attrs``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from baton_tpu.models import linear_regression_model
    from baton_tpu.parallel.engine import FedSim

    x = np.ones((6, 8, 4), np.float32)
    data = {"x": jnp.asarray(x), "y": jnp.asarray(x.sum(-1, keepdims=True))}
    n = np.asarray([8, 5, 8, 3, 8, 1], np.int32)
    sim = FedSim(linear_regression_model(4), batch_size=4, learning_rate=0.05)
    params = sim.init(jax.random.key(0))
    params = sim.run_round(params, data, n, jax.random.key(1),
                           wave_size=4).params
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(2):
            with jax.profiler.TraceAnnotation("fedbench.round"):
                params = sim.run_round(params, data, n, jax.random.key(2 + i),
                                       wave_size=4).params
        jax.block_until_ready(params)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    rows = tr.read_events(found[0], NAMES["span_prefixes"])
    host = tr.reduce_host(rows)
    assert {name: host["span_runs"].get(name) for name in CHILDREN} == {
        "baton.round.dispatch.launch": 4,
        "baton.round.dispatch.accumulate": 4,
        "baton.round.prepare.keys": 2, "baton.round.prepare.select": None}
    attrs = host["span_attrs"]
    assert attrs["baton.round.dispatch.launch"]["leaves"] == 4 * 6
    entries = attrs["baton.round.dispatch.launch"]["cache_entries"]
    assert entries >= 4 and entries % 4 == 0  # the same at every launch
    # what a span launched is the runtime's to say: no count rides on it
    assert "programs" not in attrs.get("baton.round.dispatch.accumulate", {})
    assert "programs" not in attrs["baton.round.stage"]
    # own times add up: the rounds' whole time less their wait
    reduced = {"host_self_s": host["host_self_s"], "n_rounds": 2}
    busy = _read("host_busy_ms_per_round", reduced)
    waited = host["host_self_s"]["baton.round.sync"]
    whole = sum(r["dur_ns"] for r in rows if r["name"] == "baton.round")
    assert busy == pytest.approx(1e3 * (whole / 1e9 - waited) / 2)
