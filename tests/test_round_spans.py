"""The round's host spans and the wave program's scopes (ISSUE 24).

Three things: the compiled wave program (``FedSim.lower_wave``) carries
the scopes in its ``op_name``s, forward and backward apart; a CPU
profiler session shows the ``baton.round.*`` spans of
``FedSim.run_round`` nested and counted, with their attributes; and
every path through ``run_round`` closes every span it opens."""

import collections
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from baton_tpu.models import linear_regression_model
from baton_tpu.models.bert import BertConfig, bert_classifier_model
from baton_tpu.models.resnet import resnet_model
from baton_tpu.parallel import engine
from baton_tpu.parallel.engine import FedSim
from baton_tpu.parallel.mesh import make_mesh, shard_client_arrays



def _linear_cohort(n_clients=6, capacity=8, dim=4):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_clients, capacity, dim)).astype(np.float32)
    y = (x @ np.arange(1, dim + 1, dtype=np.float32))[..., None]
    n = np.asarray([8, 5, 8, 3, 8, 1][:n_clients], np.int32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}, n


def _linear_sim(**kw):
    return FedSim(linear_regression_model(4), batch_size=4,
                  learning_rate=0.05, **kw)


# ------------------------------------------------------------ (a) scopes
def _op_names(sim, data, n, wave_size=None):
    params = jax.jit(sim.init)(jax.random.key(0))
    text = sim.lower_wave(params, data, n, jax.random.key(1), 1,
                          wave_size).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _images(n_clients=2):
    return ({"x": jnp.ones((n_clients, 4, 4, 4, 3)),
             "y": jnp.zeros((n_clients, 4), jnp.int32)},
            np.asarray([4, 3][:n_clients], np.int32))


def _tokens(n_clients=2):
    return ({"x": jnp.ones((n_clients, 4, 8), jnp.int32),
             "y": jnp.zeros((n_clients, 4), jnp.int32)},
            np.asarray([4, 3][:n_clients], np.int32))


def _tiny_resnet():
    return resnet_model(blocks_per_stage=(1, 1), n_groups=2, name="tiny")


def _tiny_bert(**kw):
    return bert_classifier_model(BertConfig.tiny(max_len=8, n_layers=1), **kw)


@pytest.fixture(scope="module")
def resnet_names():
    return _op_names(FedSim(_tiny_resnet(), batch_size=4), *_images())


@pytest.fixture(scope="module")
def bert_names():
    return _op_names(FedSim(_tiny_bert(), batch_size=4), *_tokens())


def _with(names, *words):
    return [x for x in names if all(w in x for w in words)]


@pytest.mark.parametrize("scope", [
    "local_train", "shuffle", "grad", "optimizer", "wave_sums"])
@pytest.mark.parametrize("model", ["resnet", "bert"])
def test_wave_program_names_the_trainer_and_engine_scopes(
        model, scope, resnet_names, bert_names):
    names = resnet_names if model == "resnet" else bert_names
    assert _with(names, scope), f"no op_name holds {scope!r}"


@pytest.mark.parametrize("model,scope", [
    ("resnet", "stem"), ("resnet", "s0b0"), ("resnet", "s1b0"),
    ("resnet", "conv"), ("resnet", "norm"), ("resnet", "shortcut"),
    ("resnet", "head"),
    ("bert", "embed"), ("bert", "block0"), ("bert", "attention"),
    ("bert", "mlp"), ("bert", "norm"), ("bert", "head"),
])
def test_model_scopes_tell_forward_from_backward(
        model, scope, resnet_names, bert_names):
    names = _with(resnet_names if model == "resnet" else bert_names, scope)
    forward = [x for x in names if "jvp(" in x and "transpose(" not in x]
    backward = [x for x in names if "transpose(" in x]
    assert forward, f"{scope}: no forward op among {names[:5]}"
    if scope != "embed":  # integer inputs: the gather has no cotangent path
        assert backward, f"{scope}: no backward op among {names[:5]}"


def test_scopes_nest_block_then_part(resnet_names):
    assert any(re.search(r"jvp\(s1b0\)\)?/shortcut/conv/", x)
               for x in resnet_names)
    assert any(re.search(r"local_train/.*optimizer/", x) for x in resnet_names)


def test_remat_marks_the_recomputed_forward():
    names = _op_names(FedSim(_tiny_bert(remat=True), batch_size=4),
                      *_tokens())
    assert _with(names, "rematted_computation", "attention")


def test_mesh_wave_program_names_its_psums():
    mesh = make_mesh(4)
    data, n = _linear_cohort(n_clients=4)
    names = _op_names(_linear_sim(mesh=mesh),
                      shard_client_arrays(data, mesh), n[:4])
    assert _with(names, "wave_psum")
    assert _with(names, "local_train") and _with(names, "wave_sums")


def test_lower_wave_is_the_program_run_round_dispatches():
    """Same jitted callable, same staged shapes: after a round, lowering
    the wave again adds no entry to the jit's cache."""
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
    program, _ = sim._wave_program(1, robust=False)
    before = program._cache_size()
    lowered = sim.lower_wave(params, data, n, jax.random.key(1), 1, 4)
    assert "_wave_sums_vmap" in lowered.as_text()[:400]
    sim.run_round(params, data, n, jax.random.key(2), wave_size=4)
    assert program._cache_size() == before


@pytest.mark.parametrize("kwargs", [
    {"aggregator": "median"}, {"wave_size": "auto"}])
def test_lower_wave_refuses_what_it_does_not_lower(kwargs):
    data, n = _linear_cohort()
    sim = _linear_sim(**{k: v for k, v in kwargs.items()
                         if k == "aggregator"})
    with pytest.raises(NotImplementedError):
        sim.lower_wave(sim.init(jax.random.key(0)), data, n,
                       jax.random.key(1), 1, kwargs.get("wave_size"))


# ------------------------------------------- (b) one profiler session
#: the CPU runtime's host event for one execution of a compiled program
EXECUTE = "PjRtCpuExecutable::Execute"


def _profiled(work, tdir):
    """``(work(), spans, executions)``: ``work`` under a CPU profiler
    session, the ``baton.*`` spans it left, ``(name, start, end,
    attributes)`` in order of their start, and the runtime's ``EXECUTE``
    events in the same form."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=options)
    try:
        result = work()
        jax.block_until_ready(result)
    finally:
        jax.profiler.stop_trace()
    spans, executions = [], []
    for path in glob.glob(tdir + "/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name.startswith("baton."):
                        spans.append((ev.name, ev.start_ns, end,
                                      dict(ev.stats)))
                    elif ev.name == EXECUTE:
                        executions.append((ev.name, ev.start_ns, end, {}))
    return result, sorted(spans, key=lambda s: s[1]), executions


def _executed_inside(span, executions) -> int:
    """Executions of a compiled program that lie inside ``span``."""
    return sum(span[1] <= e[1] and e[2] <= span[2] for e in executions)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Two rounds of 6 clients in waves of 4 (the second wave has two
    phantom clients) and then a read of ``last_compute`` under a CPU
    profiler session, and the same round twice outside any session: the
    ``FedSim``'s first, which compiles and so settles itself, and its
    second, which hits the jit's fast path and is left pending. A steady
    round is settled by the round after it (ISSUE 37): the session's
    first round settles the one pending outside it, its second the
    first, and the read the second."""
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    outside = sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
    assert sim._pending is None
    jax.block_until_ready(
        sim.run_round(params, data, n, jax.random.key(1), wave_size=4).params)
    assert sim._pending.index == 2

    def two_rounds():
        first = sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
        second = sim.run_round(first.params, data, n, jax.random.key(2),
                               wave_size=4)
        return first, second.params, sim.last_compute

    (first, _, record), spans, executions = _profiled(
        two_rounds, str(tmp_path_factory.mktemp("trace")))
    return {"spans": spans, "executions": executions, "outside": outside,
            "inside": first, "record": record}


@pytest.mark.parametrize("name,count", [
    ("baton.round", 2), ("baton.round.prepare", 2), ("baton.round.stage", 4),
    ("baton.round.dispatch", 4), ("baton.round.sync", 3),
    ("baton.round.record", 3), ("baton.round.fold", 2),
    ("baton.round.update", 2), ("baton.round.dispatch.launch", 4),
    ("baton.round.dispatch.accumulate", 4), ("baton.round.prepare.keys", 2),
    ("baton.round.prepare.select", 0)])
def test_session_counts_each_span(session, name, count):
    assert sum(s[0] == name for s in session["spans"]) == count


@pytest.fixture(scope="module")
def chosen_session(tmp_path_factory):
    """A ``FedSim``'s first two rounds, of a chosen cohort, under a
    profiler session: the first compiles."""
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    chosen = np.asarray([0, 2, 3, 5, 1])

    def two_rounds():
        first = sim.run_round(params, data, n, jax.random.key(1),
                              wave_size=4, client_indices=chosen)
        return sim.run_round(first.params, data, n, jax.random.key(2),
                             wave_size=4, client_indices=chosen).params

    _, spans, executions = _profiled(
        two_rounds, str(tmp_path_factory.mktemp("trace_select")))
    return spans, executions


def test_session_opens_select_once_a_round_for_a_chosen_cohort(
        chosen_session):
    spans, executions = chosen_session
    prepares = [s for s in spans if s[0] == "baton.round.prepare"]
    selects = [s for s in spans if s[0] == "baton.round.prepare.select"]
    assert len(prepares) == len(selects) == 2
    for prepare, select in zip(prepares, selects):
        assert prepare[1] <= select[1] and select[2] <= prepare[2]
        # the three takes (x, y, n_samples) and the indices' own cast
        if executions:
            assert _executed_inside(select, executions) >= 3


def test_session_a_round_that_compiled_settles_itself_inside_its_round(
        chosen_session):
    spans, _ = chosen_session
    first, second = [s for s in spans if s[0] == "baton.round"]
    # the round's own parts, not what JAX built inside them
    # (``baton.build.*``: tests/test_build_ledger.py)
    inner = [s for s in spans if s[0].startswith("baton.round.")
             and s[0].count(".") == 2
             and first[1] <= s[1] and s[2] <= first[2]]
    assert [s[0][len("baton.round."):] for s in inner][-4:] == [
        "fold", "sync", "record", "update"]
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    sync, = [s for s in spans if s[0] == "baton.round.sync"]
    assert sync in inner
    assert sync[3] == {"settles": 1, "ready": sync[3]["ready"], "own": 1}
    # the second round hit the fast path: it waits for no one, and no one
    # has waited for it yet
    assert len([s for s in spans if s[0] == "baton.round.record"]) == 1
    assert not [s for s in spans if s[1] >= second[2]]


def test_session_spans_nest_in_their_round_in_order(session):
    rounds = [s for s in session["spans"] if s[0] == "baton.round"]
    # no partition: nothing is held once, and the linear model's five
    # float32 parameters are what a client holds
    assert [r[3] for r in rounds] == [
        {"clients": 6, "waves": 2, "wave_size": 4, "frozen_bytes": 0,
         "trainable_bytes": 20}] * 2
    for _, r0, r1, _ in rounds:
        inner = [s for s in session["spans"]
                 if s[0] != "baton.round" and r0 <= s[1] and s[2] <= r1]
        assert [s[0][len("baton.round."):] for s in inner] == [
            "prepare", "prepare.keys",
            "stage", "dispatch", "dispatch.launch", "dispatch.accumulate",
            "stage", "dispatch", "dispatch.launch", "dispatch.accumulate",
            "fold", "sync", "record", "update"]
        # a child lies inside the span it is named after, and of two
        # spans of one parent each ends before the next starts
        for i, b in enumerate(inner):
            parent = b[0].rsplit(".", 1)[0]
            if parent != "baton.round":
                a = [s for s in inner[:i] if s[0] == parent][-1]
                assert a[1] <= b[1] and b[2] <= a[2]
        for depth in (3, 4):
            level = [s for s in inner if s[0].count(".") + 1 == depth]
            assert all(a[2] <= b[1] for a, b in zip(level, level[1:]))
        # stage, sync, record and fold keep their idle: no child under them
        assert not [s for s in inner if s[0].count(".") > 2
                    and s[0].split(".")[2] not in ("prepare", "dispatch")]
    inside_a_round = sum(r0 <= s[1] and s[2] <= r1
                         for s in session["spans"] if s[0] != "baton.round"
                         for _, r0, r1, _ in rounds)
    # the read of last_compute after the last round opens the two spans
    # that are in no round
    assert inside_a_round == len(session["spans"]) - len(rounds) - 2


def test_session_sync_says_which_round_it_settled(session):
    """``settles`` counts this ``FedSim``'s rounds from 1, ``ready`` is
    whether the round was done when the host came to wait for it (the
    round outside the session had been waited for by the test itself),
    and ``own`` whether the round waited for is the one that waits."""
    syncs = [s[3] for s in session["spans"] if s[0] == "baton.round.sync"]
    assert [a["settles"] for a in syncs] == [2, 3, 4]
    assert all(set(a) == {"settles", "ready", "own"} and a["ready"] in (0, 1)
               for a in syncs)
    # none of the three launched a program the jit had not run before
    assert [a["own"] for a in syncs] == [0, 0, 0]
    assert syncs[0]["ready"] == 1
    records = [s[3] for s in session["spans"] if s[0] == "baton.round.record"]
    assert records == [{}] * 3


def test_session_a_read_of_last_compute_settles_outside_any_round(session):
    rounds = [s for s in session["spans"] if s[0] == "baton.round"]
    last = [s for s in session["spans"] if s[1] >= rounds[-1][2]]
    assert [s[0] for s in last] == ["baton.round.sync", "baton.round.record"]
    assert last[0][2] <= last[1][1]
    # the record the read returned is the second round's, whole
    record = session["record"]
    assert record["steps"] == 6 * 2 and record["cache_hit"]
    assert record["train_s_source"] in ("host_waited",
                                        "found_ready_upper_bound")


def test_session_stage_counts_the_phantom_clients(session):
    stages = [s[3] for s in session["spans"] if s[0] == "baton.round.stage"]
    # batch 4 divides the 8 rows the largest client fills: no row is cut
    assert stages == [
        {"wave": 0, "real": 4, "padded": 0, "rows": 8, "capacity": 8},
        {"wave": 1, "real": 2, "padded": 2, "rows": 8, "capacity": 8}] * 2
    dispatches = [s[3] for s in session["spans"]
                  if s[0] == "baton.round.dispatch"]
    assert dispatches == [{"wave": 0}, {"wave": 1}] * 2


def test_session_launch_says_what_it_handed_over(session):
    launches = [s[3] for s in session["spans"]
                if s[0] == "baton.round.dispatch.launch"]
    # w and b, x and y, n_samples, the keys
    assert [(a["wave"], a["leaves"]) for a in launches] == [(0, 6), (1, 6)] * 2
    # a call that hits the jit's fast path adds no entry: the second
    # round's count is the first's
    entries = [a["cache_entries"] for a in launches]
    assert entries[0] >= 1 and len(set(entries)) == 1


def test_session_launches_are_the_runtimes_own_by_span(session):
    """What a span launched is read from the runtime's own events inside
    it, not from an attribute: the spans carry no count of programs."""
    accumulates = [s for s in session["spans"]
                   if s[0] == "baton.round.dispatch.accumulate"]
    assert [s[3] for s in accumulates] == [{"wave": 0}, {"wave": 1}] * 2
    if not session["executions"]:
        pytest.skip(f"this runtime's trace has no {EXECUTE} event")
    executions = session["executions"]
    # wave 0 adds to nothing and its losses are whole; wave 1:
    # _acc_tree_add, the two adds, and the slice of the padded losses
    assert [_executed_inside(s, executions) for s in accumulates] == [
        0, 4] * 2
    stages = [s for s in session["spans"] if s[0] == "baton.round.stage"]
    # wave 0 is cut from the cohort (x, y, n_samples, the keys: several
    # programs on a typed key array); wave 1 is cut and padded too
    inside = [_executed_inside(s, executions) for s in stages]
    assert inside[0] >= 4 and inside[1] > inside[0]
    assert inside[2:] == inside[:2]
    for span in session["spans"]:
        if span[0] == "baton.round.dispatch.launch":
            # the jitted call and nothing else
            assert _executed_inside(span, executions) == 1


def test_session_stage_says_the_rows_it_staged(tmp_path_factory):
    """Clients of 48 real rows handed over in 64 at batch 32 (ISSUE 30):
    in a real session every ``stage`` span carries the 48 rows a client
    the wave computes beside the 64 it was handed."""
    x = np.ones((6, 64, 4), np.float32)
    data = {"x": jnp.asarray(x), "y": jnp.asarray(x.sum(-1, keepdims=True))}
    n = np.full((6,), 48, np.int32)
    sim = FedSim(linear_regression_model(4), batch_size=32,
                 learning_rate=0.05)
    params = sim.init(jax.random.key(0))
    _, spans, _ = _profiled(
        lambda: sim.run_round(params, data, n, jax.random.key(1),
                              wave_size=4).params,
        str(tmp_path_factory.mktemp("trace_rows")))
    stages = [s[3] for s in spans if s[0] == "baton.round.stage"]
    assert stages == [
        {"wave": 0, "real": 4, "padded": 0, "rows": 48, "capacity": 64},
        {"wave": 1, "real": 2, "padded": 2, "rows": 48, "capacity": 64}]


def test_session_fold_launches_one_program(session):
    folds = [s[3] for s in session["spans"] if s[0] == "baton.round.fold"]
    assert folds == [{"programs": 1}] * 2


def test_second_round_compiles_nothing_and_donates_nothing_of_the_callers():
    """The fold program is keyed by shapes alone: a second round of the
    same shapes builds no program (counted as ``fedbench/run.py`` counts
    ``compiles_in_window``), and what the caller handed in (parameters,
    server state) is still readable after it."""
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    data, n = _linear_cohort()
    sim = _linear_sim(server_optimizer=optax.sgd(0.5, momentum=0.9))
    params = sim.init(jax.random.key(0))
    state = sim.init_server_opt_state(params)
    first = sim.run_round(params, data, n, jax.random.key(1), wave_size=4,
                          server_opt_state=state)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        jax.jit(lambda x: x * 3 + 1)(jnp.zeros(3))  # a compile is heard
        heard = len(compiles)
        second = sim.run_round(first.params, data, n, jax.random.key(2),
                               wave_size=4,
                               server_opt_state=first.server_opt_state)
        jax.block_until_ready(second.params)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert heard >= 1 and len(compiles) == heard
    for kept in (params, state, first.params, first.server_opt_state):
        for leaf in jax.tree_util.tree_leaves(kept):
            assert np.isfinite(np.asarray(leaf)).all()  # not deleted


def test_round_is_bit_equal_inside_and_outside_a_session(session):
    a, b = session["outside"], session["inside"]
    for x, y in zip(jax.tree_util.tree_leaves((a.params, a.loss_history,
                                               a.client_losses)),
                    jax.tree_util.tree_leaves((b.params, b.loss_history,
                                               b.client_losses))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------ (c) every path closes its spans
class _Recorder:
    """Stands in for ``annotate``: what was opened, and what is open."""

    def __init__(self):
        self.opened, self.open = [], []

    def __call__(self, name, **attrs):
        recorder = self

        class Span(contextlib.AbstractContextManager):
            def __enter__(self):
                recorder.opened.append((name, attrs))
                recorder.open.append(name)
                return self

            def __exit__(self, *exc):
                assert recorder.open.pop() == name  # innermost first
                return False

            def set_metadata(self, **more):
                attrs.update(more)

        return Span()


@pytest.fixture
def recorder(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(engine, "annotate", rec)
    return rec


def _boom(done, total):
    raise RuntimeError("progress hook failed")


PATHS = {
    "plain": ({}, {}, 6, 2),
    "one_wave": ({}, {"wave_size": None}, 6, 1),
    "client_indices": ({}, {"client_indices": np.asarray([0, 2, 3, 5, 1])},
                       5, 2),
    "robust": ({"aggregator": "median"}, {}, 6, 2),
    "progress_fn": ({}, {"progress_fn": lambda done, total: None}, 6, 2),
    "server_optimizer": ({"server_optimizer": optax.sgd(1.0)}, {}, 6, 2),
    "mesh": ({"mesh": 2}, {}, 6, 2),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_path_opens_and_closes_its_spans(recorder, path):
    sim_kw, round_kw, clients, waves = PATHS[path]
    sim_kw = dict(sim_kw)
    data, n = _linear_cohort()
    if "mesh" in sim_kw:
        sim_kw["mesh"] = make_mesh(sim_kw["mesh"])
        data = shard_client_arrays(data, sim_kw["mesh"])
    sim = _linear_sim(**sim_kw)
    kwargs = {"wave_size": 4, **round_kw}
    res = sim.run_round(sim.init(jax.random.key(0)), data, n,
                        jax.random.key(1), **kwargs)
    assert np.isfinite(np.asarray(res.loss_history)).all()
    assert recorder.open == []
    counts = collections.Counter(name for name, _ in recorder.opened)
    # a FedSim's first round compiles: it waits for itself and writes its
    # own record, between its fold and its update
    steady = {"baton.round": 1, "baton.round.prepare": 1,
              "baton.round.prepare.keys": 1,
              "baton.round.stage": waves,
              "baton.round.dispatch": waves,
              "baton.round.dispatch.launch": waves,
              "baton.round.dispatch.accumulate": waves,
              "baton.round.fold": 1,
              "baton.round.update": 1}
    if "client_indices" in round_kw:
        steady["baton.round.prepare.select"] = 1
    settling = {**steady, "baton.round.sync": 1, "baton.round.record": 1}
    assert counts == settling
    first_round = list(recorder.opened)

    def settled():
        names = [name for name, _ in recorder.opened]
        assert names[-4:] == ["baton.round.fold", "baton.round.sync",
                              "baton.round.record", "baton.round.update"]
        return dict(recorder.opened)["baton.round.sync"]

    assert settled() == {"settles": 1, "ready": settled()["ready"], "own": 1}
    assert sim._pending is None
    # the second hits the jit's fast path, waits for nothing and is left
    # pending; on a mesh its parameters come committed to the devices by
    # the fold, the launch adds an entry, and it settles itself as well
    del recorder.opened[:]
    res = sim.run_round(res.params, data, n, jax.random.key(2), **kwargs)
    assert recorder.open == []
    counts = collections.Counter(name for name, _ in recorder.opened)
    if path == "mesh":
        assert counts == settling and settled()["own"] == 1
        assert sim._pending is None
    else:
        assert counts == steady and sim._pending.index == 2
    # the third settles the second where that is pending, after its own
    # fold, and is itself left pending
    del recorder.opened[:]
    sim.run_round(res.params, data, n, jax.random.key(3), **kwargs)
    assert recorder.open == [] and sim._pending.index == 3
    counts = collections.Counter(name for name, _ in recorder.opened)
    if path == "mesh":
        assert counts == steady
    else:
        assert counts == settling
        sync = settled()
        assert (sync["settles"], sync["own"]) == (2, 0)
        assert sync["ready"] in (0, 1)
    # and a read of last_compute settles the last one, in no round; once
    del recorder.opened[:]
    assert sim.last_compute["steps"] == clients * 2
    assert [name for name, _ in recorder.opened] == [
        "baton.round.sync", "baton.round.record"]
    assert recorder.opened[0][1]["settles"] == 3
    assert recorder.opened[0][1]["own"] == 0
    assert sim.last_compute["steps"] == clients * 2
    assert len(recorder.opened) == 2 and recorder.open == []
    recorder.opened[:] = first_round
    for nm, a in recorder.opened:  # every launch and accumulate is whole
        if nm == "baton.round.dispatch.launch":
            assert a["leaves"] == 6 and a["cache_entries"] >= 1
        if nm == "baton.round.dispatch.accumulate":
            assert set(a) == {"wave"}
        if nm == "baton.round.stage":
            assert set(a) == {"wave", "real", "padded", "rows", "capacity"}
    name, attrs = recorder.opened[0]
    assert name == "baton.round" and attrs["clients"] == clients
    assert attrs["waves"] == waves
    assert sum(a["real"] for nm, a in recorder.opened
               if nm == "baton.round.stage") == clients


# the eager programs inside ``accumulate`` by path, a wave, as the CPU
# runtime counts them on one device. 6 clients in waves of 4
LAUNCHED = {
    # wave 0 adds to nothing and its losses are whole; wave 1's sums are
    # added to wave 0's (_acc_tree_add, the loss, the weight) and its
    # padded losses cut
    "mean": ({}, {}, [0, 4]),
    "mean_without_client_losses": (
        {}, {"collect_client_losses": False}, [0, 3]),
    # the whole cohort: nothing is added and nothing cut
    "one_wave": ({}, {"wave_size": None}, [0]),
    # a cast, a product and a sum a wave; wave 1 adds the loss and the
    # weight and cuts w, b, n_samples and the losses (twice: the
    # product's and the clients')
    "robust": ({"aggregator": "median"}, {}, [3, 10]),
    # 5 chosen clients: three phantoms in wave 1
    "phantom_clients": (
        {}, {"client_indices": np.asarray([0, 2, 3, 5, 1])}, [0, 4]),
}


@pytest.mark.parametrize("path", sorted(LAUNCHED))
def test_launch_and_accumulate_bound_what_the_runtime_executes(
        tmp_path, path):
    """``launch`` holds the jitted call and nothing else, and
    ``accumulate`` the eager programs after it, on every path: counted
    from the runtime's own events inside each span."""
    sim_kw, round_kw, waves = LAUNCHED[path]
    data, n = _linear_cohort()
    sim = _linear_sim(**sim_kw)
    params = sim.init(jax.random.key(0))
    kwargs = {"wave_size": 4, **round_kw}
    sim.run_round(params, data, n, jax.random.key(1), **kwargs)
    _, spans, executions = _profiled(
        lambda: sim.run_round(params, data, n, jax.random.key(2),
                              **kwargs).params, str(tmp_path))
    if not executions:
        pytest.skip(f"this runtime's trace has no {EXECUTE} event")
    inside = {name: [_executed_inside(s, executions)
                     for s in spans if s[0] == name]
              for name in ("baton.round.dispatch.launch",
                           "baton.round.dispatch.accumulate")}
    assert inside == {"baton.round.dispatch.launch": [1] * len(waves),
                      "baton.round.dispatch.accumulate": waves}


@pytest.mark.parametrize("partition", [False, True])
def test_outside_a_session_no_parameter_tree_is_walked_after_round_one(
        monkeypatch, partition):
    """The spans' byte and leaf counts are shapes, and shapes do not
    change: a ``FedSim`` walks its parameter trees in its first round
    and keeps what it found."""
    walked = []
    tree_bytes = engine._tree_bytes
    monkeypatch.setattr(engine, "_tree_bytes",
                        lambda tree: walked.append(1) or tree_bytes(tree))
    data, n = _linear_cohort()
    sim = _linear_sim(trainable=(lambda path, leaf: path == "w")
                      if partition else None)
    params = sim.init(jax.random.key(0))
    first = sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
    assert len(walked) == 2  # the trainable tree and the frozen one
    second = sim.run_round(first.params, data, n, jax.random.key(2),
                           wave_size=4)
    sim.run_round(second.params, data, n, jax.random.key(3), wave_size=None)
    assert len(walked) == 2


def test_the_cached_shapes_are_the_trees_own(recorder):
    data, n = _linear_cohort()
    sim = _linear_sim(trainable=lambda path, leaf: path == "w")
    params = sim.init(jax.random.key(0))
    for key in (1, 2):
        params = sim.run_round(params, data, n, jax.random.key(key),
                               wave_size=4).params
    rounds = [a for nm, a in recorder.opened if nm == "baton.round"]
    # w is four float32 a client, b one float32 held once
    assert [(a["trainable_bytes"], a["frozen_bytes"]) for a in rounds] == [
        (16, 4)] * 2
    assert {a["leaves"] for nm, a in recorder.opened
            if nm == "baton.round.dispatch.launch"} == {6}


def test_trees_of_another_structure_are_walked_anew(monkeypatch):
    """The kept shapes are of one structure of the two trees: a tree
    with other leaves is walked again, and is never read as the last."""
    walked = []
    tree_bytes = engine._tree_bytes
    monkeypatch.setattr(engine, "_tree_bytes",
                        lambda tree: walked.append(1) or tree_bytes(tree))
    sim = _linear_sim()
    base = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}
    adapted = {**base, "a": jnp.zeros((4, 2), jnp.bfloat16)}
    assert sim._param_shapes(base, {}) == (2, 20, 0)
    assert sim._param_shapes(base, {}) == (2, 20, 0)
    assert len(walked) == 2
    assert sim._param_shapes(adapted, {}) == (3, 36, 0)
    assert sim._param_shapes([base["w"]], [base["b"]]) == (2, 16, 4)
    assert len(walked) == 6


def test_a_wave_program_that_is_no_jit_object_runs_the_round(recorder,
                                                             monkeypatch):
    """``cache_entries`` is the jitted program's own count where it has
    one: a wave program without it runs the round all the same."""
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    jitted, bind = sim._wave_program(1, robust=False)
    monkeypatch.setattr(
        sim, "_wave_program",
        lambda n_epochs, robust: (lambda *args: jitted(*args), bind))
    res = sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
    assert np.isfinite(np.asarray(res.loss_history)).all()
    launches = [a for nm, a in recorder.opened
                if nm == "baton.round.dispatch.launch"]
    assert [set(a) for a in launches] == [{"wave", "leaves"}] * 2


@pytest.mark.parametrize("last_opened,sim_kw,round_kw,error", [
    ("prepare.select", {}, {"client_indices": np.asarray([99])}, IndexError),
    ("prepare.keys", {"aggregator": "median"}, {"wave_size": "auto"},
     NotImplementedError),
    ("dispatch.accumulate", {}, {"progress_fn": _boom}, RuntimeError),
])
def test_an_exception_leaves_no_span_open(recorder, last_opened, sim_kw,
                                          round_kw, error):
    data, n = _linear_cohort()
    sim = _linear_sim(**sim_kw)
    params = sim.init(jax.random.key(0))
    kwargs = {"wave_size": 4, **round_kw}
    # an index past the cohort is refused on the host since ISSUE 30 (it
    # had clamped): the round's rows are read from n_samples there
    with pytest.raises(error):
        sim.run_round(params, data, n, jax.random.key(1), **kwargs)
    assert recorder.opened[-1][0] == f"baton.round.{last_opened}"
    assert recorder.open == []


def test_a_wave_program_that_raises_leaves_no_span_open(recorder,
                                                        monkeypatch):
    data, n = _linear_cohort()
    sim = _linear_sim()
    params = sim.init(jax.random.key(0))
    _, bind = sim._wave_program(1, robust=False)

    def program(*args):
        raise FloatingPointError("the wave program failed")

    monkeypatch.setattr(sim, "_wave_program",
                        lambda n_epochs, robust: (program, bind))
    with pytest.raises(FloatingPointError):
        sim.run_round(params, data, n, jax.random.key(1), wave_size=4)
    assert [name for name, _ in recorder.opened][-2:] == [
        "baton.round.dispatch", "baton.round.dispatch.launch"]
    assert "cache_entries" not in recorder.opened[-1][1]
    assert recorder.open == []


def test_annotate_takes_attributes_and_works_outside_a_session():
    from baton_tpu.utils.profiling import annotate

    with annotate("baton.test", wave=1) as span:
        span.set_metadata(clients=3)  # no session: nothing is recorded
    assert isinstance(span, jax.profiler.TraceAnnotation)
