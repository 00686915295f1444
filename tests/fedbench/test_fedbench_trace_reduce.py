"""The reduction from a profiler trace to numbers: interval arithmetic
on cases built by hand, the reader's walk over a trace recorded here on
the CPU, and the whole reduction of a trace recorded on the chip in
PR 22 (``fedbench/testdata/``) against numbers written here."""

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
DEV = "/device:TPU:0"


# ------------------------------------------------------- by hand: intervals
@pytest.mark.parametrize("intervals,merged,total", [
    ([], [], 0),
    ([(5, 5)], [], 0),                                    # empty interval
    ([(0, 10)], [[0, 10]], 10),
    ([(0, 10), (5, 15)], [[0, 15]], 15),                  # overlap
    ([(0, 10), (2, 4), (3, 9)], [[0, 10]], 10),           # nesting
    ([(20, 30), (0, 10)], [[0, 10], [20, 30]], 20),       # unsorted, apart
    ([(0, 10), (10, 20)], [[0, 20]], 20),                 # touching
])
def test_merge_and_its_total(intervals, merged, total):
    assert tr.merge(intervals) == merged
    assert sum(e - s for s, e in tr.merge(intervals)) == total


@pytest.mark.parametrize("merged,window,expected", [
    ([], (0, 10), [(0, 10)]),
    ([[0, 10]], (0, 10), []),
    ([[2, 4], [6, 8]], (0, 10), [(0, 2), (4, 6), (8, 10)]),
    ([[0, 3], [7, 12]], (1, 10), [(3, 7)]),
])
def test_gaps(merged, window, expected):
    assert tr.gaps(merged, *window) == expected


def _op(name, start, dur, opcode="fusion", kind="kLoop", plane=DEV):
    return {"plane": plane, "line": tr.OP_LINE, "name": name,
            "start_ns": float(start), "dur_ns": float(dur),
            "opcode": opcode, "kind": kind, "shape": "f32[8]"}


def _module(name, start, dur, plane=DEV):
    return {"plane": plane, "line": tr.MODULE_LINE, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def _span(name, start, dur):
    return {"plane": tr.HOST_PLANE, "line": "python", "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_self_time_takes_children_from_their_parent_once():
    ops = [_op("while", 0, 100), _op("conv.1", 10, 30), _op("fusion", 50, 40),
           _op("inner", 60, 10), _op("after", 120, 5)]
    got = {r["name"]: s for r, s, _ in tr.self_times(ops)}
    assert {r["name"] for r, _, leaf in tr.self_times(ops) if not leaf} == {
        "while", "fusion"}
    assert got == {"while": 30, "conv.1": 30, "fusion": 30, "inner": 10,
                   "after": 5}
    assert sum(got.values()) == sum(e - s for s, e in tr.merge(
        (o["start_ns"], o["start_ns"] + o["dur_ns"]) for o in ops))


def test_parse_op_reads_the_instruction_text():
    text = ("%fusion.890 = bf16[32,32,32,32,32,2]{3,2,5,4,1,0:T(8,128)(2,1)} "
            "fusion(f32[32,32]{1,0} %bitcast.1217, f32[32]{0} %copy.2490), "
            "kind=kLoop, calls=%fused_computation.14.clone.clone")
    assert tr.parse_op(text) == {
        "name": "fusion.890", "opcode": "fusion", "kind": "kLoop",
        "shape": "bf16[32,32,32,32,32,2]{3,2,5,4,1,0:T(8,128)(2,1)}"}
    done = ("%copy-done = u32[2]{0:T(128)S(1)} copy-done((u32[2]{0:T(128)S(1)}, "
            "u32[2]{0:T(128)}, u32[]{:S(2)}) %copy-start)")
    assert tr.parse_op(done)["opcode"] == "copy-done"
    tup = "%all-reduce.64 = (f32[10]{0}, f32[512,10]{0,1}) all-reduce(f32[10] %a)"
    assert tr.parse_op(tup)["opcode"] == "all-reduce"
    assert tr.parse_op(tup)["name"] == "all-reduce.64"
    assert tr.parse_op("not an instruction")["opcode"] == ""


def test_classify_reads_opcode_and_fusion_kind():
    assert tr.classify(_op("x", 0, 1, "fusion", "kOutput"), RULES) == "mxu"
    assert tr.classify(_op("x", 0, 1, "convolution", ""), RULES) == "mxu"
    assert tr.classify(_op("x", 0, 1, "all-reduce", ""), RULES) == "collective"
    assert tr.classify(_op("x", 0, 1, "fusion", "kLoop"), RULES) == "loop_fusion"
    assert tr.classify(_op("x", 0, 1, "copy", ""), RULES) == "copy"
    assert tr.classify(_op("x", 0, 1, "while", ""), RULES) == "other"
    assert tr.classify({"name": "x"}, RULES) == "other"


def _hand_rows():
    """Two rounds of 1000 ns on one device: a 600 ns wave program (a
    while of 500 holding a 300 ns conv and a 100 ns all-reduce, the
    latter with 40 ns of a fusion beside it), 100 ns of tail and a 50 ns
    fold program."""
    rows = []
    for k in (0, 1):
        t = 1000 * k
        rows += [
            _span("fedbench.round", t, 900), _span("fedbench.sync", t + 900, 100),
            _module("jit__wave_sums_vmap(11)", t + 100, 600),
            _op("while.1", t + 100, 500, "while", ""),
            _op("convolution.2", t + 150, 300, "fusion", "kOutput"),
            _op("all-reduce.3", t + 460, 100, "all-reduce", ""),
            _op("fusion.6", t + 500, 40),
            _op("tail.4", t + 600, 100),
            _module("jit_fold(12)", t + 800, 50),
            _op("add.5", t + 800, 50),
        ]
    return rows


def test_reduce_rows_on_a_trace_built_by_hand():
    reduced = tr.reduce_rows(_hand_rows(), RULES, NAMES)
    assert reduced["n_rounds"] == 2
    assert reduced["window_s"] == pytest.approx(2000e-9)
    d = reduced["devices"][DEV]
    assert d["busy_s"] == pytest.approx(2 * 650e-9)
    assert d["idle_s"] == pytest.approx(2 * 350e-9)
    assert d["module_s"] == pytest.approx(
        {"jit__wave_sums_vmap": 1200e-9, "jit_fold": 100e-9})
    assert d["module_runs"] == {"jit__wave_sums_vmap": 2, "jit_fold": 2}
    assert d["category_s"] == pytest.approx(
        {"mxu": 600e-9, "collective": 2 * 60e-9, "loop_fusion": 2 * 190e-9,
         "other": 2 * 100e-9})
    assert d["collective_exposed_s"] == pytest.approx(2 * 60e-9)
    # the gap from the fold of round 1 to the wave of round 2
    assert d["longest_gap_s"] == pytest.approx(250e-9)
    # the 250 ns gap from the fold of round 1 to the wave of round 2 is
    # cut at the span edges it crosses: 50 round, 100 sync, 100 round
    assert d["idle_by_span_s"] == pytest.approx({
        "fedbench.round": 500e-9, "fedbench.sync": 200e-9})
    # rows without scopes: the wave program's time is all 'other', unscoped
    assert d["wave"]["phase_s"] == pytest.approx({"other": 2 * 600e-9})
    assert d["wave"]["unscoped_s"] == pytest.approx(d["wave"]["self_s"])
    assert reduced["span_runs"] == {"fedbench.round": 2, "fedbench.sync": 2}
    assert reduced["span_attrs"] == {}
    assert tr.wave_module(d) == "jit__wave_sums_vmap"
    top = tr.breakdown(reduced)
    assert top["device_ops"][0] == ["convolution.2 fusion kOutput f32[8]",
                                    pytest.approx(600e-9)]
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10


def test_layer_metric_readers_on_the_hand_trace():
    reduced = tr.reduce_rows(_hand_rows(), RULES, NAMES)
    cell = {"required": {"kernel": "conv", "kernel_flops_per_round": 30.0,
                         "kernel_bytes_per_round": 1.0},
            "peaks": {"flops_per_s_bf16": 1e9, "hbm_bytes_per_s": 1e9}}

    def read(name):
        return manifest.load_module(ROOT, "layer_metrics", name).read(
            reduced, {}, cell)

    assert read("wave_ms") == pytest.approx(600e-6)
    assert read("nonwave_device_ms") == pytest.approx(50e-6)
    assert read("idle_ms_per_round") == pytest.approx(350e-6)
    assert read("device_idle_share") == pytest.approx(35.0)
    assert read("collective_ms") == pytest.approx(60e-6)
    # least time 30 ns a round (compute) over 300 ns of conv ops a round
    assert read("conv_roofline") == pytest.approx(10.0)
    assert read("matmul_roofline") is None


def test_a_trace_without_device_planes_reduces_to_nothing():
    spans = [r for r in _hand_rows() if r["plane"] == tr.HOST_PLANE]
    assert tr.reduce_rows(spans, RULES, NAMES) is None
    assert tr.reduce_rows([r for r in _hand_rows()
                           if r["plane"] != tr.HOST_PLANE], RULES,
                          NAMES) is None


# ----------------------------------------------- the reader, on a CPU trace
def test_reader_walks_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("fedbench.round"):
                y = f(x)
            with jax.profiler.TraceAnnotation("fedbench.sync"):
                y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    rows = tr.read_events(found[0], NAMES["span_prefixes"])
    names = [r["name"] for r in rows]
    assert names.count("fedbench.round") == 2
    assert names.count("fedbench.sync") == 2
    assert all(r["plane"] == tr.HOST_PLANE and r["dur_ns"] > 0 for r in rows)
    window = tr.traced_window(rows)
    assert window[1] > window[0]
    # the CPU has no device plane: nothing for a device metric to read
    assert tr.reduce_rows(rows, RULES, NAMES) is None
    path = str(tmp_path / "rows.json.gz")
    tr.write_rows(rows, path)
    assert tr.load_rows(path) == rows


# ------------------------------- the whole reduction, on a trace of the chip
CHIP_ROWS = os.path.join(ROOT, "fedbench", "testdata",
                         "resnet18_c32_w1.two_rounds.rows.json.gz")


@pytest.fixture(scope="module")
def chip_reduced():
    """Two rounds of ``resnet18_c32_w1`` on a TPU v5 lite (my chip run,
    PR 22): the rows ``read_events`` took from the ``.xplane.pb`` (6.9 MB
    for five rounds, so the event table is committed instead), cut to
    the first two rounds and rebased to the window's start."""
    return tr.reduce_rows(tr.load_rows(CHIP_ROWS), RULES, NAMES)


def test_chip_trace_busy_idle_and_window(chip_reduced):
    assert list(chip_reduced["devices"]) == [DEV]
    assert chip_reduced["n_rounds"] == 2
    d = chip_reduced["devices"][DEV]
    assert chip_reduced["window_s"] == pytest.approx(0.677793992, rel=1e-9)
    assert d["busy_s"] == pytest.approx(0.627583228, rel=1e-9)
    assert d["idle_s"] == pytest.approx(0.050210764, rel=1e-9)
    assert d["busy_s"] + d["idle_s"] == pytest.approx(d["window_s"], rel=1e-12)
    assert d["longest_gap_s"] == pytest.approx(0.004419947, rel=1e-9)
    # each gap cut at the span edges it crosses (until PR 26 a whole gap
    # went to the span its midpoint fell in: 48.393, 1.533, 0.284 ms)
    assert d["idle_by_span_s"] == pytest.approx({
        "fedbench.round": 0.047427998,
        tr.BETWEEN: 0.002443997,
        "fedbench.sync": 0.000338769}, rel=1e-7)
    assert sum(d["idle_by_span_s"].values()) == pytest.approx(d["idle_s"],
                                                              rel=1e-9)


def test_chip_trace_modules(chip_reduced):
    d = chip_reduced["devices"][DEV]
    assert tr.wave_module(d) == "jit__wave_sums_vmap"
    assert d["module_runs"] == {
        "jit__wave_sums_vmap": 2, "jit_true_divide": 126,
        "jit_convert_element_type": 8, "jit__threefry_split": 3,
        "jit__threefry_fold_in": 2, "jit_maximum": 2}
    assert d["module_s"]["jit__wave_sums_vmap"] == pytest.approx(
        0.627257645, rel=1e-9)
    # the eager fold: one tiny program a parameter leaf, 63 a round
    assert d["module_s"]["jit_true_divide"] == pytest.approx(
        0.000364658, rel=1e-6)


def test_chip_trace_categories_add_up_to_busy(chip_reduced):
    d = chip_reduced["devices"][DEV]
    assert d["category_s"] == pytest.approx({
        "loop_fusion": 0.263814008, "copy": 0.212793702,
        "mxu": 0.15044886, "custom_fusion": 0.000314709,
        "reduce_fusion": 0.000182839, "other": 0.00002911}, rel=1e-6)
    assert sum(d["category_s"].values()) == pytest.approx(d["busy_s"],
                                                          rel=1e-9)
    assert "collective" not in d["category_s"]
    assert d["collective_exposed_s"] == 0.0
    top = tr.breakdown(chip_reduced)["device_ops"]
    assert len(top) == 10
    assert top[0][0].startswith("fusion.890 fusion kLoop bf16[32,32,32,32,32,2]")
    assert top[0][1] == pytest.approx(0.014989838, rel=1e-9)


def test_chip_trace_layer_metrics(chip_reduced):
    bench = manifest.load_manifest(ROOT)
    config = manifest.load_config(ROOT, bench, "resnet18_cifar10")
    required = manifest.load_module(ROOT, "flops", "resnet18_cifar10").required(
        config, {"n_samples": [48] * 32, "batch": 32, "local_epochs": 1})
    cell = {"required": required,
            "peaks": manifest.load_peaks(ROOT, "TPU v5 lite")}

    def read(name):
        return manifest.load_module(ROOT, "layer_metrics", name).read(
            chip_reduced, {}, cell)

    assert read("wave_ms") == pytest.approx(313.6288225, rel=1e-9)
    assert read("idle_ms_per_round") == pytest.approx(25.105382, rel=1e-9)
    assert read("device_idle_share") == pytest.approx(
        100 * 0.050210764 / 0.677793992, rel=1e-9)
    # the five other modules' 0.390686 ms over two rounds
    assert read("nonwave_device_ms") == pytest.approx(0.195343, rel=1e-6)
    # 5.1187e12 conv FLOPs a round at 197e12 FLOP/s is 25.98 ms; the
    # matrix unit's ops took 75.22 ms a round
    assert read("conv_roofline") == pytest.approx(
        100 * (required["kernel_flops_per_round"] / 197e12)
        / (0.15044886 / 2), rel=1e-9)
    assert 34.0 < read("conv_roofline") < 35.0
    assert read("collective_ms") is None
    assert read("matmul_roofline") is None
