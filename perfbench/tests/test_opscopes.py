"""``perfbench/opscopes.py`` on files and traces made by hand, whose answers
can be worked out on paper: an ``XSpace`` encoded here field by field (two
planes, a ``ref_value`` and a ``str_value`` ``tf_op``, an operation without
one), the class of a name stack, the time by class of a trace whose classes
overlap and whose ``while`` must not count, the guard that keeps a reader
off another run's file, and the seven readers with their entries in
BENCHMARK.json."""

import json
import os

import pytest

from perfbench import opscopes, worker, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000
SEVEN = ("scope_mixer_ms", "scope_experts_ms", "scope_mlp_ms",
         "scope_norm_ms", "scope_vocab_ms", "scope_optimizer_ms",
         "scope_unnamed_pct")

DOT = "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,64]{1,0} %p.0), kind=kOutput"
NORM = "%fusion.2 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop"
ADAM = "%fusion.3 = f32[64,128]{1,0} fusion(f32[64,128]{1,0} %p.2), kind=kLoop"
STRAY = "%fusion.4 = s32[8]{0} fusion(s32[8]{0} %p.3), kind=kLoop"
COPY = "%copy.5 = bf16[8,128]{0,1} copy(bf16[8,128]{1,0} %p.4)"
RELAID = "%copy.7 = f32[64,128]{0,1} copy(f32[64,128]{1,0} %p.5)"
WHILE = ("%while.6 = (s32[], bf16[8,128]{1,0}) while((s32[], "
         "bf16[8,128]{1,0}) %tuple.1), condition=%cond.1, body=%body.1")
PATHS = {
    DOT: "jit(step)/jvp(M)/layers_0/rt.mixer/attn/q_proj/dot_general",
    NORM: "jit(step)/transpose(jvp(M))/layers_0/rt.mixer/rt.norm/input_norm/mul",
    ADAM: "jit(step)/rt.optimizer/add",
    STRAY: "jit(step)/jvp(M)/iota",
    WHILE: "jit(step)/jvp(M)/rt.vocab/while",
    COPY: None,
}


# ----------------------------------------------------------------------
# an XSpace by hand: varints and length-delimited fields
# ----------------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(number: int, value: int) -> bytes:
    return _varint(number << 3) + _varint(value)


def _bytes(number: int, value) -> bytes:
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _double(number: int) -> bytes:
    return _varint(number << 3 | 1) + b"\0" * 8


def _entry(key: int, message: bytes) -> bytes:
    return _int(1, key) + _bytes(2, message)


def _plane(name: str, events, paths, timestamp_ns=1000, line=xplane.OPS_LINE):
    """An ``XPlane``: ``events`` [(instruction text, offset ps, duration ps)]
    on one line; stat 1 is ``tf_op``, stat 2 ``flops`` (a double: passed
    over), stats from 100 on are names a ``ref_value`` points at. Texts at
    even places carry ``tf_op`` as a ``ref_value``, at odd ones as a
    ``str_value``."""
    texts = list(dict.fromkeys(text for text, _, _ in events))
    stat_meta = {1: "tf_op", 2: "flops"}
    event_meta = []
    for i, text in enumerate(texts):
        stats = _bytes(5, _int(1, 2) + _double(2))
        if paths.get(text) and i % 2 == 0:
            stat_meta[100 + i] = paths[text]
            stats += _bytes(5, _int(1, 1) + _int(7, 100 + i))
        elif paths.get(text):
            stats += _bytes(5, _int(1, 1) + _bytes(5, paths[text]))
        event_meta.append(_entry(i + 1, _int(1, i + 1) + _bytes(2, text)
                                 + stats))
    xline = _int(1, 7) + _bytes(2, line) + _int(3, timestamp_ns) + b"".join(
        _bytes(4, _int(1, texts.index(text) + 1) + _int(2, offset)
               + _int(3, duration))
        for text, offset, duration in events)
    return (_int(1, 1) + _bytes(2, name) + _bytes(3, xline)
            + b"".join(_bytes(4, e) for e in event_meta)
            + b"".join(_bytes(5, _entry(k, _int(1, k) + _bytes(2, v)))
                       for k, v in stat_meta.items()))


def _space(*planes) -> bytes:
    return b"".join(_bytes(1, p) for p in planes)


def _file(tmp_path, events, name="t.xplane.pb", **kw):
    host = _plane("/host:CPU", [("bench/step", 0, 5)], {}, line="python")
    path = tmp_path / name
    path.write_bytes(_space(host, _plane(opscopes.PLANE, events, PATHS, **kw)))
    return str(path)


def _steps(n=3, vocab_ms=(4, 2, 1)):
    """Steps of 20 ms on a clock that starts at 1000 ns: a mixer's dot of 3
    ms, its norm overlapping the dot's last ms and running 2 more, a
    ``while`` of 9 ms under ``rt.vocab`` that holds the optimizer's update
    of ``vocab_ms[i]``, a stray operation of 1 ms and a compiler's copy of
    half a ms. -> (events for ``_plane``, the ``Trace`` of them)."""
    events, ops, spans, modules = [], [], [], []
    for i in range(n):
        t = i * 22 * MS
        for text, start, ms in ((DOT, 0, 3), (NORM, 2, 3),
                                (WHILE, 6, 9), (ADAM, 6, vocab_ms[i]),
                                (STRAY, 16, 1), (COPY, 17, 0.5)):
            s, e = t + int(start * MS), t + int((start + ms) * MS)
            events.append((text, s * 1000, (e - s) * 1000))
            ops.append((text, 1000 + s, 1000 + e))
        spans.append(("bench/step", 1000 + t - MS // 2, 1000 + t + 21 * MS))
        modules.append(("jit_step(1)", 1000 + t, 1000 + t + 20 * MS))
    return events, xplane.Trace(ops={0: sorted(ops, key=lambda o: o[1])},
                                modules={0: modules}, spans=spans)


# ----------------------------------------------------------------------
# names
# ----------------------------------------------------------------------

def test_names_reads_chip_0s_metadata_both_kinds_of_string(tmp_path):
    events, _ = _steps(1)
    found = opscopes.names(_file(tmp_path, events))
    assert found == PATHS
    assert found[COPY] is None                    # an event without tf_op
    # the host's plane and its event are not chip 0's
    assert "bench/step" not in found


def test_a_file_without_the_plane_has_no_names(tmp_path):
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(_space(_plane("/host:CPU", [("x", 0, 1)], {})))
    assert opscopes.names(str(path)) == {}
    other = tmp_path / "chip1.xplane.pb"
    other.write_bytes(_space(_plane("/device:TPU:1", [(DOT, 0, 1)], PATHS)))
    assert opscopes.names(str(other)) == {}


def test_a_file_is_parsed_once_a_process(tmp_path, monkeypatch):
    events, _ = _steps(1)
    path = _file(tmp_path, events, name="once.xplane.pb")
    first = opscopes.names(path)
    monkeypatch.setattr(opscopes, "_device_plane", lambda p: 1 / 0)
    assert opscopes.names(path) is first


# ----------------------------------------------------------------------
# class_of
# ----------------------------------------------------------------------

@pytest.mark.parametrize("path, kind", [
    ("jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/"
     "layers_3/rt.mixer/attn/rt.norm/q_norm/mul", "norm"),
    ("jit(step)/jvp(M)/layers_3/rt.mixer/attn/q_proj/dot_general", "mixer"),
    ("jit(step)/jvp()/rt.vocab/while/body/closed_call/dot_general", "vocab"),
    ("jit(step)/rt.optimizer/add", "optimizer"),
    # a scope entered right under a transform is printed inside it
    ("jit(step)/jvp(rt.vocab)/while/body/closed_call/dot_general", "vocab"),
    ("jit(step)/transpose(jvp(rt.vocab))/mul", "vocab"),
    ("jit(step)/jvp(rt.vocab)/rt.norm/mul", "norm"),
    ("jit(step)/jvp(M)/rt.mixer", "mixer"),
    ("jit(step)/jvp(M)/rt.mixerish/mul", None),
    ("jit(step)/jvp(M)/layers_0/part.norm/mul", None),
    ("jit(step)/jvp(M)/layers_3/attn/q_proj/dot_general", None),
    ("jit(step)/jvp(M)/rt.bogus/mul", None),
    ("jit(step)/jvp(M)/rt.mixer/rt.bogus/mul", None),
    ("jit(step)/jvp(M)/art.mixer/mul", None),
    ("", None), (None, None),
])
def test_the_innermost_segment_is_the_class(path, kind):
    assert opscopes.class_of(path) == kind


def test_the_six_words_are_the_programs():
    from ray_tpu._private import steptrace

    assert opscopes.KINDS == steptrace.DEVICE_SCOPES
    assert opscopes.PREFIX == "rt."
    with steptrace.device_scope("mixer"):
        pass
    with pytest.raises(ValueError):
        steptrace.device_scope("bogus")


# ----------------------------------------------------------------------
# by_class
# ----------------------------------------------------------------------

def test_time_by_class_is_a_union_a_step_and_a_median_over_the_steps():
    _, trace = _steps(3, vocab_ms=(4, 2, 1))
    classes = opscopes.by_class(trace, PATHS)
    # the dot [0, 3]; the norm [2, 5] is its own class though it overlaps
    assert classes["mixer"] == pytest.approx(3.0)
    assert classes["norm"] == pytest.approx(3.0)
    # the while's 9 ms count nowhere: the update inside it is an event of
    # its own, 4, 2 and 1 ms -> median 2
    assert classes["vocab"] == 0.0
    assert classes["optimizer"] == pytest.approx(2.0)
    assert classes["experts"] == classes["mlp"] == 0.0
    assert classes[opscopes.UNNAMED] == pytest.approx(1.0)
    assert classes[opscopes.NO_PATH] == pytest.approx(0.5)
    assert set(classes) == {*opscopes.KINDS, "unnamed", "no_path"}


def test_a_path_that_is_not_the_programs_is_no_path():
    """The compiler's copy of an argument is named after the argument, and
    what it expands an operation into after the expansion: neither begins
    ``jit(``, and no scope of the program's could have held them."""
    _, trace = _steps(1)
    trace.ops[0].append((RELAID, 1000 + 18 * MS, 1000 + 19 * MS))
    classes = opscopes.by_class(trace, dict(PATHS, **{
        RELAID: "params['layers_0']['attn']['q_proj']['kernel']"}))
    assert classes[opscopes.NO_PATH] == pytest.approx(1.5)
    assert classes[opscopes.UNNAMED] == pytest.approx(1.0)
    expanded = dict(PATHS, **{STRAY: "while/body/gather"})
    assert opscopes.by_class(trace, expanded)[opscopes.UNNAMED] == 0.0


def test_two_operations_of_one_class_that_overlap_count_once():
    _, trace = _steps(1)
    trace.ops[0].append((DOT, 1000 + 2 * MS, 1000 + 4 * MS))
    trace.ops[0].sort(key=lambda o: o[1])
    # [0, 3] + [2, 4] -> [0, 4]
    assert opscopes.by_class(trace, PATHS)["mixer"] == pytest.approx(4.0)


def test_an_operation_the_names_do_not_hold_has_no_path():
    _, trace = _steps(1)
    classes = opscopes.by_class(trace, {})
    # [0, 5] + [6, 10] + [16, 17.5]: everything but the while
    assert classes[opscopes.NO_PATH] == pytest.approx(10.5)
    assert all(classes[kind] == 0.0 for kind in opscopes.KINDS)


def test_a_trace_without_steps_reads_nothing():
    assert opscopes.by_class(xplane.Trace(), PATHS) is None
    assert opscopes.read_classes(None) is None
    assert opscopes.read_classes(xplane.Trace()) is None


# ----------------------------------------------------------------------
# the guard, and the readers
# ----------------------------------------------------------------------

def test_the_names_are_the_traces_own_files_or_none(tmp_path):
    events, trace = _steps(2)
    assert opscopes.names_of(trace, _file(tmp_path, events)) == PATHS
    # another run's file: one event fewer; another first start
    fewer = _file(tmp_path, events[:-1], name="fewer.xplane.pb")
    assert opscopes.names_of(trace, fewer) is None
    later = _file(tmp_path, events, name="later.xplane.pb", timestamp_ns=5000)
    assert opscopes.names_of(trace, later) is None
    assert opscopes.names_of(trace, str(tmp_path / "fewer.xplane.pb")) is None
    assert opscopes.names_of(xplane.Trace(), _file(
        tmp_path, events, name="again.xplane.pb")) is None


def test_the_file_as_jax_reads_it_passes_its_own_guard(tmp_path):
    """The hand-made file through ``xplane.load`` (``ProfileData``): its
    events' names are the metadata's names, their count and first start
    what this file's reader finds. The join is exact."""
    events, made = _steps(2)
    path = _file(tmp_path, events)
    trace = xplane.load(path)
    assert trace.ops[0] == made.ops[0]
    assert opscopes.names_of(trace, path) == PATHS
    assert {name for name, _, _ in trace.ops[0]} == set(PATHS)


def test_the_newest_trace_file_is_a_run_of_run_pys(tmp_path, monkeypatch):
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert opscopes.newest_trace_file() is None
    for age, run in ((200, "perfbench_old"), (100, "perfbench_new"),
                     (0, "another_tool")):
        session = tmp_path / run / "trace" / "plugins" / "profile" / "s"
        session.mkdir(parents=True)
        (session / "h.xplane.pb").write_bytes(b"")
        stamp = os.path.getmtime(session / "h.xplane.pb") - age
        os.utime(session / "h.xplane.pb", (stamp, stamp))
    assert opscopes.newest_trace_file() == str(
        tmp_path / "perfbench_new" / "trace" / "plugins" / "profile" / "s"
        / "h.xplane.pb")


def _read(name, trace):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=None,
                              chips=1, flops_per_token=1.0, model={},
                              traffic={})
    return worker._load_reader(ROOT, "metrics", name).read(reading)


def test_the_seven_readers_over_a_trace_and_its_file(tmp_path, monkeypatch):
    events, trace = _steps(3, vocab_ms=(4, 2, 1))
    path = _file(tmp_path, events)
    monkeypatch.setattr(opscopes, "newest_trace_file", lambda: path)
    got = {name: _read(name, trace) for name in SEVEN}
    assert got["scope_mixer_ms"] == pytest.approx(3.0)
    assert got["scope_norm_ms"] == pytest.approx(3.0)
    assert got["scope_optimizer_ms"] == pytest.approx(2.0)
    # a class the step does not hold is absent, not 0
    assert got["scope_experts_ms"] is None and got["scope_mlp_ms"] is None
    assert got["scope_vocab_ms"] is None
    # busy: [0, 5] + the while's [6, 15] + [16, 17.5] = 15.5 ms a step
    assert xplane.device_step_ms(trace) == pytest.approx(15.5)
    assert got["scope_unnamed_pct"] == pytest.approx(100 * 1.5 / 15.5)


def test_with_the_guard_failing_the_seven_are_absent_not_wrong(
        tmp_path, monkeypatch):
    events, trace = _steps(3)
    other = _file(tmp_path, events[:-2], name="other_run.xplane.pb")
    monkeypatch.setattr(opscopes, "newest_trace_file", lambda: other)
    assert all(_read(name, trace) is None for name in SEVEN)
    monkeypatch.setattr(opscopes, "newest_trace_file", lambda: None)
    _, again = _steps(3)
    assert all(_read(name, again) is None for name in SEVEN)


def test_a_program_without_the_scopes_reports_none_of_the_seven(
        tmp_path, monkeypatch):
    """The parent's trace under this PR's benchmark files: every operation
    has a path and none a class."""
    events, trace = _steps(2)
    bare = {text: path and path.replace("rt.", "")
            for text, path in PATHS.items()}
    monkeypatch.setitem(globals(), "PATHS", bare)
    path = _file(tmp_path, events, name="parent.xplane.pb")
    monkeypatch.setattr(opscopes, "newest_trace_file", lambda: path)
    assert all(_read(name, trace) is None for name in SEVEN)


def test_the_benchmark_file_lists_the_seven_where_they_read():
    with open(os.path.join(ROOT, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    steps = next(m for m in bench["end_to_end"]
                 if m["name"] == "tokens_per_s_per_chip")["workloads"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    dense, routed = set(), set()
    for cell in bench["workloads"]:
        if cell["name"] not in steps:
            continue
        config = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
        with open(os.path.join(ROOT, "..", config["file"])) as f:
            model = json.load(f)
        has_experts = any(k in model for k in (
            "n_routed_experts", "num_experts", "num_local_experts"))
        if has_experts:
            routed.add(cell["name"])
        if not has_experts or model.get("first_k_dense_replace") or model.get(
                "num_dense_layers"):
            dense.add(cell["name"])
    for name in SEVEN:
        entry = per_layer[name]
        assert entry["layer"] == "jitted step"
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "tokens_per_s_per_chip"
        assert entry["better"] == "lower"
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert os.path.isfile(os.path.join(ROOT, "metrics", name + ".py"))
    assert len(steps) == 12 and "gpt2-124m.job" not in steps
    for name in ("scope_mixer_ms", "scope_norm_ms", "scope_vocab_ms",
                 "scope_optimizer_ms", "scope_unnamed_pct"):
        assert per_layer[name]["workloads"] == steps
    assert set(per_layer["scope_experts_ms"]["workloads"]) == routed
    assert len(routed) == 8
    assert set(per_layer["scope_mlp_ms"]["workloads"]) == dense
    assert len(dense) == 7
