"""The program's spans and scopes in ``trace_reduce``: the scope rule,
idle time by host span and the wave program by scope on rows written by
hand, the reader on a small trace file built here with ``xplane_pb2``
(no profiler session), the layer metrics that read them; and what is
left of ``fedbench/scope_split.py``, the printer and the HLO text."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest, scope_split as ss, trace_reduce as tr  # noqa: E402

RULES = manifest.load_op_categories(ROOT)
NAMES = manifest.load_trace_names(ROOT)
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
TRAIN = ("jit(_wave_sums_vmap)/local_train/vmap(jit(train))/"
         "jit(train_with_opt_state)/while/body/closed_call/")
STEP = TRAIN + "while/body/closed_call/"


# ------------------------------------------------------------ the scope rule
@pytest.mark.parametrize("scope,phase,part,block", [
    (STEP + "grad/jvp(s0b1)/conv/conv_general_dilated",
     "forward", "conv", "s0b1"),
    (STEP + "grad/transpose(jvp(s0b1))/norm/convert_element_type",
     "backward", "norm", "s0b1"),
    (STEP + "grad/transpose(jvp(s1b0))/shortcut/conv/conv_general_dilated",
     "backward", "conv", "s1b0"),
    (STEP + "grad/jvp(s1b0)/shortcut/add", "forward", "shortcut", "s1b0"),
    (STEP + "grad/jvp(stem)/norm/mul", "forward", "norm", "stem"),
    (STEP + "grad/jvp(stem)/jit(relu)/max", "forward", "stem", "stem"),
    (STEP + "grad/jvp(head)/dot_general", "forward", "head", "head"),
    (STEP + "grad/transpose(jvp())/mul", "backward", "other", "(none)"),
    (STEP + "grad/jvp(block3)/attention/bhqd,bhkd->bhqk/dot_general",
     "forward", "attention", "block3"),
    (STEP + "grad/transpose(jvp(block11))/mlp/dot_general",
     "backward", "mlp", "block11"),
    (STEP + "grad/jvp(embed)/gather", "forward", "embed", "embed"),
    (STEP + "grad/transpose(jvp(block0))/grad/jvp(block0)/checkpoint/"
            "rematted_computation/attention/dot_general",
     "recompute", "attention", "block0"),
    (STEP + "grad/transpose(jvp(block0))/grad/jvp(block0)/checkpoint/mlp/"
            "dot_general", "backward", "mlp", "block0"),
    (STEP + "optimizer/mul", "optimizer", "other", "(none)"),
    (TRAIN + "shuffle/jit(_take)/gather", "shuffle", "other", "(none)"),
    ("jit(_wave_sums_vmap)/wave_sums/dot_general",
     "wave_sums", "other", "(none)"),
    ("jit(kernel)/shard_map/wave_psum/psum", "psum", "other", "(none)"),
    ("jit(_wave_sums_vmap)/local_train/vmap(jit(train))/broadcast_in_dim",
     "other", "other", "(none)"),
    # a primitive called transpose is no backward pass
    (STEP + "grad/jvp(block0)/attention/transpose",
     "forward", "attention", "block0"),
    ("", "other", "other", "(none)"),
])
def test_phase_part_and_block_of_a_scope(scope, phase, part, block):
    assert tr.phase_of(scope, NAMES) == phase
    assert tr.part_of(scope, NAMES) == part
    assert tr.block_of(scope, NAMES) == block


def test_a_configuration_s_own_scopes_are_laid_over_the_names():
    names = manifest.load_trace_names(
        ROOT, {"scopes": {"parts": ["router", "experts"],
                          "blocks": "layer\\d+"}})
    scope = STEP + "grad/jvp(layer7)/mlp/experts/dot_general"
    assert tr.part_of(scope, names) == "experts"
    assert tr.part_of(scope, NAMES) == "mlp"
    assert tr.block_of(scope, names) == "layer7"
    assert tr.block_of(scope, NAMES) == "(none)"
    assert tr.block_of(STEP + "grad/jvp(s0b1)/conv", names) == "s0b1"
    assert NAMES["parts"] == manifest.load_trace_names(ROOT)["parts"]


@pytest.mark.parametrize("scope,scoped", [
    (STEP + "grad/jvp(s0b0)/conv/conv_general_dilated", True),
    ("jit(_wave_sums_vmap)/wave_sums/mul", True),
    ("jit(kernel)/shard_map/wave_psum/psum", True),
    ("", False),
    ("params['fc']['w']", False),
    ("jit(true_divide)/div", False),
])
def test_is_scoped(scope, scoped):
    assert tr.is_scoped(scope, NAMES) is scoped


HLO = '''HloModule jit__wave_sums_vmap, entry_computation_layout={...}

%fused_computation.17 (p0: bf16[32,8]) -> bf16[32,8] {
  %p0 = bf16[32,8]{1,0} parameter(0)
  ROOT %multiply.3 = bf16[32,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(_wave_sums_vmap)/local_train/x/grad/transpose(jvp(s0b1))/norm/mul" stack_frame_id=7}
}

ENTRY %main.1 (a: bf16[32,8]) -> bf16[32,8] {
  %a = bf16[32,8]{1,0} parameter(0), metadata={op_name="params['stem']"}
  %copy.5 = bf16[32,8]{0,1} copy(%a)
  %fusion.887 = bf16[32,8]{1,0:T(8,128)(2,1)} fusion(%copy.5), kind=kLoop, calls=%fused_computation.17, metadata={op_name="jit(_wave_sums_vmap)/local_train/x/grad/transpose(jvp(s0b1))/norm/convert_element_type" stack_frame_id=130}, backend_config={"flag_configs":[]}
  ROOT %convolution_fusion.2 = bf16[32,8]{1,0} fusion(%fusion.887), kind=kOutput, calls=%fused_computation.18, metadata={op_name="jit(_wave_sums_vmap)/local_train/x/grad/jvp(s0b1)/conv/conv_general_dilated"}
}
'''


def test_scopes_from_hlo_keeps_each_instruction_s_own_op_name():
    scopes = ss.scopes_from_hlo(HLO)
    assert scopes["fusion.887"].endswith(
        "transpose(jvp(s0b1))/norm/convert_element_type")
    assert scopes["multiply.3"].endswith("/norm/mul")
    assert scopes["convolution_fusion.2"].endswith("conv_general_dilated")
    assert scopes["a"] == "params['stem']"
    assert "copy.5" not in scopes  # the compiler's own copy names nothing


# ---------------------------------------------------------- rows by hand
def _op(name, start, dur, scope="", opcode="fusion", kind="kLoop",
        plane=DEV0):
    return {"plane": plane, "line": tr.OP_LINE, "name": name,
            "start_ns": float(start), "dur_ns": float(dur), "scope": scope,
            "opcode": opcode, "kind": kind, "shape": "f32[8]"}


def _module(name, start, dur, plane=DEV0):
    return {"plane": plane, "line": tr.MODULE_LINE, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def _span(name, start, dur, **stats):
    return {"plane": tr.HOST_PLANE, "line": "python3", "name": name,
            "start_ns": float(start), "dur_ns": float(dur), "stats": stats}


FWD = STEP + "grad/jvp(s0b0)/conv/conv_general_dilated"
BWD_NORM = STEP + "grad/transpose(jvp(s0b0))/norm/mul"
OPT = STEP + "optimizer/sub"


def _round(t, plane=DEV0):
    """One round's rows from time ``t``: host spans over 1000 ns, the
    wave module from t+100 to t+700 (a ``while`` from +150 holding a
    forward conv, a backward norm fusion and an unscoped copy; a
    ``wave_sums`` op after it), one divide in the fold."""
    rows = [
        _module("jit__wave_sums_vmap(7)", t + 100, 600, plane),
        _op("while.1", t + 150, 450, TRAIN + "while", "while", "", plane),
        _op("convolution_fusion.2", t + 150, 100, FWD, "fusion", "kOutput",
            plane),
        _op("fusion.887", t + 250, 200, BWD_NORM, plane=plane),
        _op("copy.5", t + 450, 50, "", "copy", "", plane),
        _op("fusion.9", t + 500, 50, OPT, plane=plane),
        _op("fusion.10", t + 620, 80, "jit(_wave_sums_vmap)/wave_sums/mul",
            plane=plane),
        _module("jit_true_divide(3)", t + 800, 40, plane),
        _op("divide.1", t + 800, 40, "jit(true_divide)/div", "divide", "",
            plane),
    ]
    return rows


def _spans(t):
    return [
        _span("fedbench.round", t, 1000),
        _span("baton.round", t + 10, 980, clients=4, waves=1, wave_size=4),
        _span("baton.round.prepare", t + 20, 60),
        _span("baton.round.stage", t + 80, 10, wave=0, real=3, padded=1),
        _span("baton.round.dispatch", t + 90, 40, wave=0),
        _span("baton.round.sync", t + 130, 590),
        _span("baton.round.record", t + 720, 30),
        _span("baton.round.fold", t + 750, 200),
        _span("baton.round.update", t + 950, 30),
    ]


def _trace(planes=(DEV0,)):
    rows = []
    for t in (0, 1000):
        rows += _spans(t)
        for plane in planes:
            rows += _round(t, plane)
    return rows + [_span("fedbench.sync", 2000, 100)]


def test_innermost_span_rule():
    spans = _spans(0)
    assert tr.innermost(50, spans) == "baton.round.prepare"
    assert tr.innermost(135, spans) == "baton.round.sync"
    assert tr.innermost(15, spans) == "baton.round"
    assert tr.innermost(5, spans) == "fedbench.round"
    assert tr.innermost(5000, spans) == tr.BETWEEN


def test_window_and_rounds_are_the_harness_s():
    spans = [r for r in _trace() if r["plane"] == tr.HOST_PLANE]
    assert tr.traced_window(spans) == (0.0, 2100.0)
    # the program's spans move neither the window nor the round count
    assert tr.traced_window(spans) == tr.traced_window(
        [r for r in spans if r["name"].startswith("fedbench.")])
    assert tr.reduce_rows(_trace(), RULES, NAMES)["n_rounds"] == 2
    # without the harness's round span there is no window
    own = [r for r in spans if r["name"].startswith("baton.")]
    assert tr.traced_window(own) is None
    assert tr.traced_window([]) is None


def test_idle_by_phase_places_every_gap_and_adds_up():
    rows = _trace()
    out = tr.reduce_rows(rows, RULES, NAMES)
    idle = out["devices"][DEV0]
    # a round: busy 150-600, 620-700, 800-840; the window ends at 2100.
    # The gap 840-1150 crosses from one round's fold into the next
    # round's sync and is cut at every span edge on the way.
    ns = {k: v * 1e9 for k, v in idle["idle_by_span_s"].items()}
    want = {
        "baton.round.fold": 2 * (50 + 110),      # 750-800, 840-950
        "baton.round.sync": 2 * (20 + 20 + 20),  # 130-150, 600-620, 700-720
        "baton.round.prepare": 2 * 60,
        "baton.round.dispatch": 2 * 40,
        "baton.round.record": 2 * 30,
        "baton.round.update": 2 * 30,
        "baton.round.stage": 2 * 10,
        "baton.round": 2 * (10 + 10),            # 10-20, 980-990
        "fedbench.round": 2 * (10 + 10),         # 0-10, 990-1000
        "fedbench.sync": 100,
    }
    assert ns == pytest.approx(want)
    total = sum(want.values())
    assert total == 2100 - 2 * (450 + 80 + 40)
    assert idle["idle_s"] * 1e9 == pytest.approx(total)
    # the busy and idle arithmetic does not see the program's spans
    without = tr.reduce_rows(
        [r for r in rows if not r["name"].startswith("baton.")], RULES, NAMES)
    for key in ("busy_s", "idle_s", "window_s", "longest_gap_s"):
        assert without["devices"][DEV0][key] == idle[key]
    assert sum(without["devices"][DEV0]["idle_by_span_s"].values()) * 1e9 \
        == pytest.approx(total)
    # the readers: ms a round, sync and record together
    assert tr.idle_ms_in(out, "baton.round.fold") * 1e6 == pytest.approx(160)
    assert tr.idle_ms_in(out, "baton.round.sync", "baton.round.record") \
        * 1e6 == pytest.approx(60 + 30)
    assert tr.idle_ms_in(out, "baton.round.no_such_span") is None
    assert tr.idle_ms_in(None, "baton.round.fold") is None
    assert tr.breakdown(out)["idle_gaps"][0] == [
        "baton.round.fold", pytest.approx(320e-9)]


def test_a_gap_inside_one_span_is_placed_as_reduce_device_places_it():
    spans = _spans(0)
    edges = sorted({t for r in spans
                    for t in (r["start_ns"], r["start_ns"] + r["dur_ns"])})
    assert list(tr.place((760, 790), spans, edges)) == [
        ("baton.round.fold", 30)]
    assert list(tr.place((700, 800), spans, edges)) == [
        ("baton.round.sync", 20), ("baton.round.record", 30),
        ("baton.round.fold", 50)]


def test_host_ms_by_phase_is_self_time():
    out = tr.reduce_rows(_trace(), RULES, NAMES)["host_self_s"]
    ns = {k: v * 1e9 / 2 for k, v in out.items()}  # a round
    assert ns["baton.round.sync"] == pytest.approx(590)
    assert ns["baton.round.fold"] == pytest.approx(200)
    # baton.round: 980 less its seven children's 960
    assert ns["baton.round"] == pytest.approx(20)
    assert ns["fedbench.round"] == pytest.approx(20)
    assert ns["fedbench.sync"] == pytest.approx(50)  # once, over two rounds


def test_stage_attributes_are_reported():
    out = tr.reduce_rows(_trace(), RULES, NAMES)
    # summed by span name over the window's two rounds
    assert out["span_attrs"]["baton.round.stage"] == {
        "wave": 0, "real": 6, "padded": 2}
    assert out["span_attrs"]["baton.round"] == {
        "clients": 8, "waves": 2, "wave_size": 8}
    assert out["span_runs"]["baton.round.stage"] == 2
    assert "baton.round.fold" not in out["span_attrs"]
    assert out["n_rounds"] == 2
    # 3 real clients of 4 in a wave, 18 real samples in 3 x 8 slots
    reader = manifest.load_module(ROOT, "layer_metrics", "padded_slot_share")
    counters = {"real_samples": 18, "sample_slots": 24}
    assert reader.read(out, counters, {}) == pytest.approx(
        100 * (1 - 18 / (4 * 8)))
    assert reader.read(out, {}, {}) is None
    assert reader.read(None, counters, {}) is None


def test_wave_by_scope_self_time_under_a_while_and_fusion_by_root():
    reduced = tr.reduce_rows(_trace(), RULES, NAMES)
    wave = reduced["devices"][DEV0]["wave"]
    assert wave["module"] == "jit__wave_sums_vmap"
    assert wave["runs"] == 2
    # the while's 450 ns hold 400 ns of children: 50 ns are its own
    # (seconds over both executions -> ns an execution)
    ns = lambda table: {k: v * 1e9 / 2 for k, v in table.items()}  # noqa: E731
    assert ns(wave["phase_s"]) == pytest.approx({
        "other": 50 + 50,  # the while's own time and the unscoped copy
        "forward": 100, "backward": 200, "optimizer": 50, "wave_sums": 80})
    assert wave["self_s"] * 1e9 / 2 == pytest.approx(530)
    assert ns(wave["phase_part_s"]["backward"]) == pytest.approx(
        {"norm": 200})
    assert ns(wave["phase_part_s"]["forward"]) == pytest.approx(
        {"conv": 100})
    assert ns(wave["block_phase_s"]["s0b0"]) == pytest.approx(
        {"forward": 100, "backward": 200})
    assert ns(wave["category_part_s"]["mxu"]) == pytest.approx(
        {"conv": 100})
    assert ns(wave["category_part_s"]["loop_fusion"]) == pytest.approx(
        {"norm": 200, "other": 50 + 80})
    assert ns(wave["category_part_s"]["copy"]) == pytest.approx(
        {"other": 50})
    # only the copy names none of the program's scopes
    assert wave["unscoped_s"] / wave["self_s"] == pytest.approx(50 / 530)
    # the divide runs outside the wave module: 530 of the device's 570
    assert sum(wave["phase_s"].values()) == pytest.approx(wave["self_s"])
    assert reduced["devices"][DEV0]["busy_s"] * 1e9 / 2 == pytest.approx(570)
    # the readers: ms of one execution
    assert tr.wave_ms_under(reduced, phase="backward") * 1e6 == \
        pytest.approx(200)
    assert tr.wave_ms_under(reduced, part="norm") * 1e6 == pytest.approx(200)
    assert tr.wave_ms_under(reduced, "forward", "conv") * 1e6 == \
        pytest.approx(100)
    assert tr.wave_ms_under(reduced) * 1e6 == pytest.approx(530)
    assert tr.wave_ms_under(reduced, phase="recompute") is None
    assert tr.wave_ms_under(reduced, part="attention") is None
    assert tr.wave_ms_under(None, phase="forward") is None


def test_join_fallback_gives_scopeless_events_their_hlo_op_name():
    rows = _trace()
    for r in rows:
        if r["line"] == tr.OP_LINE:
            r["scope"] = ""  # as read with nothing but JAX
    none = tr.reduce_rows(rows, RULES, NAMES)["devices"][DEV0]["wave"]
    assert none["unscoped_s"] == pytest.approx(none["self_s"])
    assert set(none["phase_s"]) == {"other"}
    # fusion.887 and convolution_fusion.2 are in the text, by their own
    # (root's) op_name; while.1, copy.5, fusion.9, fusion.10 are not
    assert ss.join_scopes(rows, ss.scopes_from_hlo(HLO)) == 2 * 2
    joined = tr.reduce_rows(rows, RULES, NAMES)["devices"][DEV0]["wave"]
    ns = {k: v * 1e9 / 2 for k, v in joined["phase_s"].items()}
    assert ns == pytest.approx({"forward": 100, "backward": 200,
                                "other": 50 + 50 + 50 + 80})
    assert joined["unscoped_s"] / joined["self_s"] == pytest.approx(230 / 530)


def test_two_device_planes_are_split_apart():
    rows = _trace((DEV0, DEV1))
    for r in rows:  # the second chip's weighted sum ends 50 ns sooner
        if r["plane"] == DEV1 and r["name"] == "fusion.10":
            r["dur_ns"] = 30.0
    out = tr.reduce_rows(rows, RULES, NAMES)
    assert sorted(out["devices"]) == [DEV0, DEV1]
    d0, d1 = out["devices"][DEV0], out["devices"][DEV1]
    assert d0["wave"]["phase_s"]["wave_sums"] * 1e9 / 2 == pytest.approx(80)
    assert d1["wave"]["phase_s"]["wave_sums"] * 1e9 / 2 == pytest.approx(30)
    # the chip that finishes early idles inside the sync, not elsewhere
    assert (d1["idle_by_span_s"]["baton.round.sync"]
            - d0["idle_by_span_s"]["baton.round.sync"]) * 1e9 / 2 == \
        pytest.approx(50)
    assert d1["idle_by_span_s"]["baton.round.fold"] == pytest.approx(
        d0["idle_by_span_s"]["baton.round.fold"])
    # a reader takes the mean over the devices
    assert tr.wave_ms_under(out, phase="wave_sums") * 1e6 == \
        pytest.approx((80 + 30) / 2)
    assert tr.idle_ms_in(out, "baton.round.sync") * 1e6 == \
        pytest.approx(60 + 50 / 2)


@pytest.mark.parametrize("rows", [
    [],
    [r for r in _trace() if r["plane"] == tr.HOST_PLANE],   # a CPU trace
    [r for r in _trace() if r["plane"] != tr.HOST_PLANE],   # no spans
])
def test_a_trace_with_nothing_to_split_says_so(rows):
    assert tr.reduce_rows(rows, RULES, NAMES) is None


# ------------------------------------------------- the reader, on a file
@pytest.fixture(scope="module")
def xplane_file(tmp_path_factory):
    space = tr.load_xplane_pb2().XSpace()

    def plane_of(name):
        plane = space.planes.add(name=name)
        names = {}

        def stat(holder, key, value):
            sid = names.setdefault(key, len(names) + 1)
            plane.stat_metadata[sid].id = sid
            plane.stat_metadata[sid].name = key
            st = holder.stats.add(metadata_id=sid)
            if isinstance(value, str):
                st.str_value = value
            else:
                st.int64_value = value

        def event(line, mid, name, start_ps, dur_ps, md_stats=(), stats=()):
            md = plane.event_metadata[mid]
            if not md.name:
                md.id, md.name = mid, name
                for k, v in md_stats:
                    stat(md, k, v)
            ev = line.events.add(metadata_id=mid, offset_ps=start_ps,
                                 duration_ps=dur_ps)
            for k, v in stats:
                stat(ev, k, v)

        return plane, event

    dev, ev = plane_of(DEV0)
    modules = dev.lines.add(name=tr.MODULE_LINE, timestamp_ns=1000)
    ops = dev.lines.add(name=tr.OP_LINE, timestamp_ns=1000)
    skipped = dev.lines.add(name="Async XLA Ops", timestamp_ns=1000)
    ev(modules, 1, "jit__wave_sums_vmap(7)", 100_000, 600_000)
    fusion = ("%fusion.887 = bf16[32,8]{1,0:T(8,128)(2,1)} fusion(bf16[32,8] "
              "%copy.5), kind=kLoop, calls=%fused_computation.17")
    for start in (150_000, 400_000):  # one instruction, run twice
        ev(ops, 2, fusion, start, 200_000,
           md_stats=[("hlo_category", "loop fusion"),
                     ("tf_op", BWD_NORM + ":mul")])
    ev(ops, 3, "%copy.5 = bf16[32,8]{0,1} copy(bf16[32,8]{1,0} %a)",
       600_000, 50_000, md_stats=[("hlo_category", "copy")])
    ev(skipped, 4, "%copy-start.1 = ...", 0, 10_000)
    host, ev = plane_of(tr.HOST_PLANE)
    main = host.lines.add(name="python3", timestamp_ns=1000)
    ev(main, 1, "fedbench.round", 0, 1_000_000)
    ev(main, 2, "baton.round.stage", 80_000, 10_000,
       stats=[("wave", 0), ("real", 3), ("padded", 1)])
    ev(main, 3, "something.else", 0, 5_000)
    space.planes.add(name="/host:metadata")
    path = tmp_path_factory.mktemp("xplane") / "tiny.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_read_rows_takes_scope_from_the_event_metadata(xplane_file):
    rows = tr.read_events(xplane_file, NAMES["span_prefixes"])
    assert [r["name"] for r in rows if r["plane"] == tr.HOST_PLANE] == [
        "fedbench.round", "baton.round.stage"]
    stage = next(r for r in rows if r["name"] == "baton.round.stage")
    assert stage["stats"] == {"wave": 0, "real": 3, "padded": 1}
    assert (stage["start_ns"], stage["dur_ns"]) == (1080.0, 10.0)
    ops = [r for r in rows if r["line"] == tr.OP_LINE]
    assert [(r["name"], r["opcode"], r["kind"], r["scope"], r["start_ns"],
             r["dur_ns"]) for r in ops] == [
        ("fusion.887", "fusion", "kLoop", BWD_NORM, 1150.0, 200.0),
        ("fusion.887", "fusion", "kLoop", BWD_NORM, 1400.0, 200.0),
        ("copy.5", "copy", "", "", 1600.0, 50.0)]
    assert len([r for r in rows if r["line"] == tr.MODULE_LINE]) == 1
    assert not [r for r in rows if r["line"] == "Async XLA Ops"]


def test_read_events_agrees_with_jax_s_own_reader_less_the_scopes(
        xplane_file):
    """``jax.profiler.ProfileData`` shows the same events, names, starts
    and durations (and a span's attributes), but no metadata stat: the
    scopes are what ``xplane_pb2`` is read for. Loading it does not
    import tensorflow."""
    from jax.profiler import ProfileData

    rows = tr.read_events(xplane_file, NAMES["span_prefixes"])
    own = []
    for plane in ProfileData.from_file(xplane_file).planes:
        for line in plane.lines:
            if plane.name == tr.HOST_PLANE or line.name in (
                    tr.MODULE_LINE, tr.OP_LINE):
                own += [(plane.name, line.name, ev.name, float(ev.start_ns),
                         float(ev.duration_ns), dict(ev.stats))
                        for ev in line.events
                        if plane.name != tr.HOST_PLANE
                        or ev.name.startswith(tuple(NAMES["span_prefixes"]))]
    assert [(r["plane"], r["line"],
             r["name"] if "opcode" not in r else None, r["start_ns"],
             r["dur_ns"]) for r in rows] == [
        (p, l, n if l != tr.OP_LINE else None, s, d)
        for p, l, n, s, d, _ in own]
    assert [r["stats"] for r in rows if r["plane"] == tr.HOST_PLANE] == [
        st for p, _, _, _, _, st in own if p == tr.HOST_PLANE]
    assert tr.read_events(xplane_file, ["fedbench."])[-1]["name"] == \
        "fedbench.round"
    assert tr.load_xplane_pb2().__name__ == "fedbench_xplane_pb2"


def test_main_prints_one_json_object_last(xplane_file, capsys, tmp_path):
    hlo = tmp_path / "wave.txt"
    hlo.write_text(HLO)
    rc = ss.main(["--trace", xplane_file, "--hlo", str(hlo)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["n_rounds"] == 1
    wave = out["devices"][DEV0]["wave"]
    assert wave["phase_part_s"]["backward"]["norm"] * 1e9 == \
        pytest.approx(400)
    assert wave["unscoped_s"] / wave["self_s"] == pytest.approx(50 / 450)
    assert out["span_attrs"]["baton.round.stage"]["real"] == 3
    assert "op_s" not in out["devices"][DEV0]
    with pytest.raises(SystemExit):
        ss.main([])  # neither --trace nor --hlo-of


def test_hlo_of_builds_the_cell_s_wave_program_as_run_py_does():
    """Tiny sizes on the CPU: the text carries this cell's scopes and is
    the module ``run_round`` names ``jit__wave_sums_vmap``."""
    text = ss.hlo_of("bert_base_c10_l128", seed=3, rehearse_cpu=True)
    assert text.startswith("HloModule jit__wave_sums_vmap")
    scopes = set(ss.scopes_from_hlo(text).values())
    phases = {tr.phase_of(s, NAMES) for s in scopes}
    assert {"forward", "backward", "optimizer", "shuffle",
            "wave_sums"} <= phases
    assert {"attention", "mlp", "norm", "embed", "head"} <= {
        tr.part_of(s, NAMES) for s in scopes}
