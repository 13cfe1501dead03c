"""Set-up's account from the runtime's ring (perfbench/setupspans.py): rings
made by hand whose answers can be worked out on paper, then the whole
command at rehearsal size with the five metrics that read it
(perfbench/tests/rehearsal_setup.json: rehearsal_spans.json and their
entries), held against the phases ``run.py`` times from outside.
"""

import re

import pytest

from perfbench import setupspans, worker
from perfbench.tests.test_progspans import EPOCH, _trace_and_ring
from perfbench.tests.test_rehearsal import ROOT, _assert_line, _run
from ray_tpu._private import steptrace

SETUP = "perfbench/tests/rehearsal_setup.json"
READERS = ("compile_s", "step_trace_lower_s", "step_backend_s",
           "step_cache_hit_pct", "first_save_s")
T0 = EPOCH - 100.0  # the train session's start, on the ring's clock


def _compile(name, part, start, end, cache=None):
    """A ring record ``start`` .. ``end`` seconds after the session's start."""
    return {"kind": "compile", "idx": 0, "name": name, "first": False,
            "rank": 0, "step": 0, "part": part, "cache": cache,
            "retrieval_s": None, "start": T0 + start, "end": T0 + end}


def _span(name, start, end):
    return {"kind": "phase", "idx": 0, "step": 0, "phase": name, "rank": 0,
            "start": T0 + start, "end": T0 + end, "n": None}


def _read(monkeypatch, setup, dropped=0):
    """Every reader's answer on test_progspans' trace, with ``setup`` laid
    before that ring's spans (which begin a second before the window; its
    own record of step 0 gives way to ``setup``'s)."""
    trace, ring = _trace_and_ring()
    records = sorted(setup + [r for r in ring if r["kind"] != "step"],
                     key=lambda r: r["start"])
    monkeypatch.setattr(steptrace, "process_snapshot", lambda: {
        "records": records, "dropped": dropped})
    reading = worker._Reading(trace=trace)
    return {name: worker._load_reader(ROOT, "perfbench/metrics", name).read(
        reading) for name in READERS}


STEP = setupspans.step_name()
ONE_COMPILATION = [
    {"kind": "step", "idx": 0, "step": 0, "rank": 0, "start": T0,
     "end": T0 + 60.0},
    _compile("make_state", "trace", 1.0, 2.0),
    _compile("make_state", "lower", 2.0, 2.5),
    _compile("make_state", "backend", 2.5, 6.5, "miss"),
    _compile(STEP, "trace", 10.0, 18.0),
    _compile("pass_of_a_layer", "trace", 11.0, 13.0),  # inside the step's
    _compile(STEP, "lower", 18.0, 20.0),
    _compile(STEP, "backend", 20.5, 24.5, "hit"),
]


def test_the_step_has_the_name_the_program_exports():
    assert STEP == "step"


def test_overlapping_records_count_once_in_compile_s(monkeypatch):
    got = _read(monkeypatch, ONE_COMPILATION)
    # 5.5 s of make_state, 10 s of the step's trace and lowering (the 2 s
    # of the pass lie inside them), 4 s of its load: a sum would say 21.5
    assert got["compile_s"] == pytest.approx(19.5)
    assert got["step_trace_lower_s"] == pytest.approx(10.0)
    assert got["step_backend_s"] == pytest.approx(4.0)
    assert got["step_cache_hit_pct"] == 100.0
    assert got["first_save_s"] is None  # no save before the window


def test_two_compilations_of_the_step_one_missed_read_50(monkeypatch):
    again = [_compile(STEP, "trace", 30.0, 33.0),
             _compile(STEP, "lower", 33.0, 34.0),
             _compile(STEP, "backend", 34.0, 44.0, "miss")]
    got = _read(monkeypatch, ONE_COMPILATION + again)
    assert got["step_cache_hit_pct"] == 50.0
    assert got["step_trace_lower_s"] == pytest.approx(14.0)
    assert got["step_backend_s"] == pytest.approx(14.0)
    assert got["compile_s"] == pytest.approx(33.5)


def test_a_compilation_inside_the_window_is_not_set_up(monkeypatch):
    """test_progspans' window begins 5 ms after EPOCH, on the ring's clock."""
    late = [_compile(STEP, "backend", 100.01, 100.02, "miss")]
    got = _read(monkeypatch, ONE_COMPILATION + late)
    assert got["step_cache_hit_pct"] == 100.0
    assert got["compile_s"] == pytest.approx(19.5)


def test_a_ring_that_dropped_this_sessions_records_reads_none(monkeypatch):
    # the record of step 0 is gone, or is older than the oldest kept
    got = _read(monkeypatch, ONE_COMPILATION[1:], dropped=3)
    assert got == dict.fromkeys(READERS)
    # what was dropped is older than the session: nothing of it is missing
    before = [_span("train/report", -50.0, -49.9)]
    got = _read(monkeypatch, before + ONE_COMPILATION, dropped=3)
    assert got["compile_s"] == pytest.approx(19.5)


def test_the_parents_ring_reads_none(monkeypatch):
    """Before the hook recorded parts: a record named by the event's last
    segment, no function, no part."""
    parents = [{"kind": "compile", "idx": 0, "name": "backend_compile_duration",
                "first": False, "rank": 0, "start": T0 + 5.0, "end": T0 + 9.0},
               _span("ckpt/setup", 40.0, 52.0)]
    got = _read(monkeypatch, ONE_COMPILATION[:1] + parents)
    assert got == dict.fromkeys(READERS)


def test_first_save_s_is_the_first_save_and_not_a_later_one(monkeypatch):
    saves = [_span("ckpt/setup", 40.0, 52.0),  # the orbax import
             _span("ckpt/commit", 52.0, 52.0),  # nothing to wait for yet
             _span("ckpt/snapshot", 52.0, 57.0),
             _span("save/commit", 57.0, 59.5),  # behind the steps: not the loop's
             _span("ckpt/setup", 70.0, 70.1),
             _span("ckpt/commit", 70.1, 70.2),
             _span("ckpt/snapshot", 70.2, 70.6)]
    got = _read(monkeypatch, ONE_COMPILATION + saves)
    # the traced window's own save (3 + 8 + 18 ms) is a measured one
    assert got["first_save_s"] == pytest.approx(17.0)


def test_without_a_trace_nothing_is_read():
    assert setupspans.before_window(None, ONE_COMPILATION) is None


# ----------------------------------------------------------------------
# the whole command, on the CPU
# ----------------------------------------------------------------------

def _phases(proc):
    found = re.search(r"set-up phases \(s\): (\{.*\})", proc.stdout).group(1)
    return dict((k, float(v)) for k, v in re.findall(r"'([^']+)': ([\d.]+)",
                                                     found))


def _held_against_the_phases(last, phases):
    value = {k: m["value"] for k, m in last["metrics"].items()}
    step = value["step_trace_lower_s"] + value["step_backend_s"]
    seen = phases["state->compile_or_cache_load"]
    assert abs(step - seen) <= max(0.3, 0.02 * seen), (step, seen)
    assert value["step_trace_lower_s"] > 0 and value["step_backend_s"] > 0
    assert step <= value["compile_s"] < sum(phases.values())
    return value


def test_traced_step_run_misses_an_empty_cache_and_hits_it_next(
        tmp_path, monkeypatch):
    # a toy step compiles in under the second jax asks of an entry it keeps
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    hits = []
    for _ in range(2):
        proc, last = _run("tiny.step", 1, tmp_path, bench_file=SETUP)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        _assert_line({**last, "metrics": {
            k: v for k, v in last["metrics"].items()
            if k != "step_cache_hit_pct"}}, {
            "gang_start_s", "hbm_plan_gib", "report_ms", "compile_s",
            "step_trace_lower_s", "step_backend_s"})
        assert last["metrics"]["step_cache_hit_pct"]["unit"] == "%"
        value = _held_against_the_phases(last, _phases(proc))
        hits.append(value["step_cache_hit_pct"])
    assert hits == [0.0, 100.0]


def test_traced_job_run_reads_its_first_save(tmp_path):
    proc, last = _run("tiny.job", 1, tmp_path, bench_file=SETUP)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert set(READERS) <= set(last["metrics"])
    phases = _phases(proc)
    value = _held_against_the_phases(last, phases)
    # the loop's share of the warm-up save, which run.py times from outside
    # with the checksum and the report round it
    assert 0 < value["first_save_s"] <= phases["warm_up_steps->first_save"]
