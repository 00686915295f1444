"""``fedbench/scope_split.py``: the scope rule, the two splits on rows
and HLO snippets written by hand, and the reader on a small trace file
built here with ``xplane_pb2`` (no profiler session)."""

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
    assert ss.phase_of(scope) == phase
    assert ss.part_of(scope) == part
    assert ss.block_of(scope) == block


@pytest.mark.parametrize("scope,scoped", [
    (STEP + "grad/jvp(s0b0)/conv/conv_general_dilated", True),
    ("jit(_wave_sums_vmap)/wave_sums/mul", True),
    ("jit(kernel)/shard_map/wave_psum/psum", True),
    ("", False),
    ("params['fc']['w']", False),
    ("jit(true_divide)/div", False),
])
def test_is_scoped(scope, scoped):
    assert ss.is_scoped(scope) is scoped


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
    assert ss.innermost(50, spans) == "baton.round.prepare"
    assert ss.innermost(135, spans) == "baton.round.sync"
    assert ss.innermost(15, spans) == "baton.round"
    assert ss.innermost(5, spans) == "fedbench.round"
    assert ss.innermost(5000, spans) == ss.BETWEEN


def test_window_and_rounds_are_the_harness_s():
    spans = [r for r in _trace() if r["plane"] == tr.HOST_PLANE]
    assert ss.traced_window(spans) == ((0.0, 2100.0), 2)
    assert ss.traced_window(spans)[0] == tr.traced_window(
        [r for r in spans if r["name"].startswith("fedbench.")])
    # a trace of another harness: the program's own round spans
    own = [r for r in spans if r["name"].startswith("baton.")]
    assert ss.traced_window(own) == ((10.0, 1990.0), 2)
    assert ss.traced_window([]) == (None, 0)


def test_idle_by_phase_places_every_gap_and_adds_up():
    rows = _trace()
    out = ss.split(rows, RULES, {})
    idle = out["idle_by_phase"][DEV0]
    # a round: busy 150-600, 620-700, 800-840; the window ends at 2100.
    # The gap 840-1150 crosses from one round's fold into the next
    # round's sync and is cut at every span edge on the way.
    ns = {k: v * 1e6 * 2 for k, v in idle["ms_per_round"].items()}
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
    reduced = tr.reduce_rows(
        [r for r in rows if not r["name"].startswith("baton.")], RULES)
    assert idle["idle_ms_per_round"] * 2 / 1e3 == pytest.approx(
        reduced["devices"][DEV0]["idle_s"])
    assert idle["share_in_a_span_narrower_than_baton_round"] == \
        pytest.approx(1 - (40 + 40 + 100) / total)
    assert idle["share_of_baton_round_idle_in_a_narrower_span"] == \
        pytest.approx(1 - 40 / (total - 40 - 100))


def test_a_gap_inside_one_span_is_placed_as_reduce_device_places_it():
    spans = _spans(0)
    edges = sorted({t for r in spans
                    for t in (r["start_ns"], r["start_ns"] + r["dur_ns"])})
    assert list(ss.place((760, 790), spans, edges)) == [
        ("baton.round.fold", 30)]
    assert list(ss.place((700, 800), spans, edges)) == [
        ("baton.round.sync", 20), ("baton.round.record", 30),
        ("baton.round.fold", 50)]


def test_host_ms_by_phase_is_self_time():
    out = ss.split(_trace(), RULES, {})["host_ms_by_phase"]
    ns = {k: v * 1e6 for k, v in out.items()}  # a round
    assert ns["baton.round.sync"] == pytest.approx(590)
    assert ns["baton.round.fold"] == pytest.approx(200)
    # baton.round: 980 less its seven children's 960
    assert ns["baton.round"] == pytest.approx(20)
    assert ns["fedbench.round"] == pytest.approx(20)
    assert ns["fedbench.sync"] == pytest.approx(50)  # once, over two rounds


def test_stage_attributes_are_reported():
    out = ss.split(_trace(), RULES, {})
    assert out["waves"][0] == {"wave": 0, "real": 3, "padded": 1}
    assert out["n_rounds"] == 2


def test_wave_by_scope_self_time_under_a_while_and_fusion_by_root():
    wave = ss.split(_trace(), RULES, {})["wave_by_scope"][DEV0]
    assert wave["wave_module"] == "jit__wave_sums_vmap"
    assert wave["wave_runs_per_round"] == 1
    assert wave["module_ms_per_round"] * 1e6 == pytest.approx(600)
    # the while's 450 ns hold 400 ns of children: 50 ns are its own
    ns = lambda table: {k: v * 1e6 for k, v in table.items()}  # noqa: E731
    assert ns(wave["phase_ms"]) == pytest.approx({
        "other": 50 + 50,  # the while's own time and the unscoped copy
        "forward": 100, "backward": 200, "optimizer": 50, "wave_sums": 80})
    assert wave["ops_self_ms_per_round"] * 1e6 == pytest.approx(530)
    assert ns(wave["phase_x_part_ms"]["backward"]) == pytest.approx(
        {"norm": 200})
    assert ns(wave["phase_x_part_ms"]["forward"]) == pytest.approx(
        {"conv": 100})
    assert ns(wave["block_x_phase_ms"]["s0b0"]) == pytest.approx(
        {"forward": 100, "backward": 200})
    assert ns(wave["category_x_part_ms"]["mxu"]) == pytest.approx(
        {"conv": 100})
    assert ns(wave["category_x_part_ms"]["loop_fusion"]) == pytest.approx(
        {"norm": 200, "other": 50 + 80})
    assert ns(wave["category_x_part_ms"]["copy"]) == pytest.approx(
        {"other": 50})
    # only the copy names none of the program's scopes
    assert wave["unscoped_share"] == pytest.approx(50 / 530)
    # the divide runs outside the wave module and is not in its tables
    assert "jit(true_divide)/div" not in json.dumps(wave)
    total = sum(v for t in wave["phase_x_part_ms"].values()
                for v in t.values())
    assert total == pytest.approx(wave["ops_self_ms_per_round"])


def test_join_fallback_gives_scopeless_events_their_hlo_op_name():
    rows = _trace()
    for r in rows:
        if r["line"] == tr.OP_LINE:
            r["scope"] = ""  # as read with nothing but JAX
    none = ss.split(rows, RULES, {})["wave_by_scope"][DEV0]
    assert none["unscoped_share"] == pytest.approx(1.0)
    assert set(none["phase_ms"]) == {"other"}
    joined = ss.split(rows, RULES, ss.scopes_from_hlo(HLO))[
        "wave_by_scope"][DEV0]
    ns = {k: v * 1e6 for k, v in joined["phase_ms"].items()}
    # fusion.887 and convolution_fusion.2 are in the text, by their own
    # (root's) op_name; while.1, copy.5, fusion.9, fusion.10 are not
    assert ns == pytest.approx({"forward": 100, "backward": 200,
                                "other": 50 + 50 + 50 + 80})
    assert joined["scope_from_hlo_join_share"] == pytest.approx(300 / 530)
    assert joined["unscoped_share"] == pytest.approx(230 / 530)


def test_two_device_planes_are_split_apart():
    rows = _trace((DEV0, DEV1))
    for r in rows:  # the second chip's weighted sum ends 50 ns sooner
        if r["plane"] == DEV1 and r["name"] == "fusion.10":
            r["dur_ns"] = 30.0
    out = ss.split(rows, RULES, {})
    assert sorted(out["wave_by_scope"]) == [DEV0, DEV1]
    assert out["wave_by_scope"][DEV0]["phase_ms"]["wave_sums"] * 1e6 == \
        pytest.approx(80)
    assert out["wave_by_scope"][DEV1]["phase_ms"]["wave_sums"] * 1e6 == \
        pytest.approx(30)
    # the chip that finishes early idles inside the sync, not elsewhere
    d0 = out["idle_by_phase"][DEV0]["ms_per_round"]
    d1 = out["idle_by_phase"][DEV1]["ms_per_round"]
    assert (d1["baton.round.sync"] - d0["baton.round.sync"]) * 1e6 == \
        pytest.approx(50)
    assert d1["baton.round.fold"] == pytest.approx(d0["baton.round.fold"])


@pytest.mark.parametrize("rows", [
    [],
    [r for r in _trace() if r["plane"] == tr.HOST_PLANE],   # a CPU trace
    [r for r in _trace() if r["plane"] != tr.HOST_PLANE],   # no spans
])
def test_a_trace_with_nothing_to_split_says_so(rows):
    assert "error" in ss.split(rows, RULES, {})


# ------------------------------------------------- the reader, on a file
@pytest.fixture(scope="module")
def xplane_file(tmp_path_factory):
    pb2 = ss._load_xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane_pb2 imports here")
    space = pb2.XSpace()

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
    rows, source = ss.read_rows(xplane_file)
    assert source == "tf_op"
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


def test_read_rows_without_xplane_pb2_is_the_same_less_the_scopes(
        xplane_file, monkeypatch):
    with_scopes, _ = ss.read_rows(xplane_file)
    monkeypatch.setattr(ss, "_load_xplane_pb2", lambda: None)
    rows, source = ss.read_rows(xplane_file)
    assert source == "none"
    assert all(r["scope"] == "" for r in rows if r["line"] == tr.OP_LINE)
    strip = lambda rs: [{k: v for k, v in r.items() if k != "scope"}  # noqa: E731
                        for r in rs]
    assert strip(rows) == strip(with_scopes)


def test_main_prints_one_json_object_last(xplane_file, capsys, tmp_path):
    hlo = tmp_path / "wave.txt"
    hlo.write_text(HLO)
    rc = ss.main(["--trace", xplane_file, "--hlo", str(hlo)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["scope_source"] == "tf_op" and out["n_rounds"] == 1
    wave = out["wave_by_scope"][DEV0]
    assert wave["phase_x_part_ms"]["backward"]["norm"] * 1e6 == \
        pytest.approx(400)
    assert wave["unscoped_share"] == pytest.approx(50 / 450)
    with pytest.raises(SystemExit):
        ss.main([])  # neither --trace nor --hlo-of


def test_hlo_of_builds_the_cell_s_wave_program_as_run_py_does():
    """Tiny sizes on the CPU: the text carries this cell's scopes and is
    the module ``run_round`` names ``jit__wave_sums_vmap``."""
    text = ss.hlo_of("bert_base_c10_l128", seed=3, rehearse_cpu=True)
    assert text.startswith("HloModule jit__wave_sums_vmap")
    scopes = set(ss.scopes_from_hlo(text).values())
    phases = {ss.phase_of(s) for s in scopes}
    assert {"forward", "backward", "optimizer", "shuffle",
            "wave_sums"} <= phases
    assert {"attention", "mlp", "norm", "embed", "head"} <= {
        ss.part_of(s) for s in scopes}
