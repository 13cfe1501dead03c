"""Laying the program's ring on the trace's clock (perfbench/progspans.py):
a hand-made trace and ring whose answers can be worked out on paper, then
the whole command at rehearsal size with the seven metrics that read it
(perfbench/tests/rehearsal_spans.json: rehearsal.json and their entries).
"""

import copy

import pytest

from perfbench import progspans, worker, xplane
from perfbench.tests.test_rehearsal import _assert_line, _run

MS = 1_000_000
EPOCH = 1_790_000_000.0  # the ring's clock: time.time()
OFFSET_NS = -round(EPOCH * 1e9) + 5 * MS  # the trace began 5 ms before


def _phase(name, start_ns, end_ns, n=None, idx=0):
    """A ring record whose times, moved by OFFSET_NS, are start/end_ns."""
    return {"kind": "phase", "idx": idx, "step": 0, "phase": name, "rank": 0,
            "start": (start_ns - OFFSET_NS) / 1e9,
            "end": (end_ns - OFFSET_NS) / 1e9, "n": n}


def _trace_and_ring():
    """Three steps of 10 ms, each after 2 ms of bench/data, then a 30 ms
    save. The iterator takes 1 ms of each bench/data, the second one 1.5 ms
    of which 1 ms is a fetch; each report takes 0.1 ms and ends with its
    bench span but for up to 2 us (what really lies between the two exits);
    the save is 3 + 8 + 18 ms and a 1 ms report. Before the window, records
    of the warm-up."""
    spans, ring = [], []
    for i in range(3):
        t = i * 12 * MS
        spans += [("bench/data", t, t + 2 * MS),
                  ("bench/step", t + 2 * MS, t + 12 * MS)]
        took = (3 * MS // 2) if i == 1 else MS
        ring.append(_phase("data/next", t + 1000, t + 1000 + took, 4))
        if i == 1:
            ring.append(_phase("data/fetch", t + 2000, t + 2000 + MS, 8))
        ring.append(_phase("train/report", t + 12 * MS - MS // 10 - 1000 * i,
                           t + 12 * MS - 1000 * i))
    t = 36 * MS
    spans.append(("bench/ckpt", t, t + 30 * MS))
    ring += [_phase("ckpt/setup", t, t + 3 * MS),
             _phase("ckpt/snapshot", t + 3 * MS, t + 11 * MS, 1000),
             _phase("ckpt/commit", t + 11 * MS, t + 29 * MS, 1000),
             _phase("train/report", t + 29 * MS, t + 30 * MS - 2000)]
    warm_up = [_phase("train/report", -900 * MS, -899 * MS),
               _phase("data/next", -800 * MS, -799 * MS, 4),
               {"kind": "step", "idx": 0, "step": 0, "rank": 0,
                "start": EPOCH - 1.0, "end": EPOCH - 0.9}]
    ring = warm_up + ring
    for i, r in enumerate(ring):
        r["idx"] = i
    return xplane.Trace(spans=sorted(spans, key=lambda s: s[1])), ring


def test_alignment_recovers_a_known_offset():
    trace, ring = _trace_and_ring()
    offset, deviations = progspans.pair_deviations(trace, ring)
    # the pairs are 0, 1, 2 and 2 us apart: the median of the four
    assert offset == pytest.approx(OFFSET_NS + 1500, abs=300)
    assert max(abs(d) for d in deviations) < 2 * 1000
    spans = progspans.align(trace, ring)
    assert {k: len(v) for k, v in spans.items()} == {
        "data/next": 3, "data/fetch": 1, "train/report": 4, "ckpt/setup": 1,
        "ckpt/snapshot": 1, "ckpt/commit": 1}  # the warm-up's are outside
    start, end, n = spans["ckpt/snapshot"][0]
    # float64 keeps about 0.25 us of an epoch time
    assert start == pytest.approx(39 * MS, abs=2500)
    assert end - start == pytest.approx(8 * MS, abs=600) and n == 1000

    reading = worker._Reading(trace=trace, _program_spans=spans)
    assert progspans.median_ms(reading, "train/report") == pytest.approx(
        0.1, abs=1e-3)
    # (1 + 1.5 + 1) ms over three steps; one fetch of 1 ms over three steps
    assert progspans.total_ms_per(reading, "data/next", "bench/step") \
        == pytest.approx(3.5 / 3, abs=1e-3)
    assert progspans.total_ms_per(reading, "data/fetch", "bench/step") \
        == pytest.approx(1 / 3, abs=1e-3)
    assert progspans.total_ms_per(reading, "ckpt/commit", "bench/ckpt") \
        == pytest.approx(18.0, abs=1e-3)
    assert progspans.median_ms(reading, "no/such") is None


def _reports(ring):
    return [r for r in ring if r.get("phase") == "train/report"]


@pytest.mark.parametrize("why", [
    "a report missing from the ring", "a report more than the trace has",
    "a pair 1 ms off", "a fetch outside bench/data",
    "a save's span outside bench/ckpt", "records dropped inside the window",
    "a program without spans"])
def test_alignment_refuses(why):
    trace, ring = _trace_and_ring()
    ring, dropped = copy.deepcopy(ring), 0
    assert progspans.align(trace, ring) is not None
    if why == "a report missing from the ring":
        # the warm-up's report takes its place in the pairs, 900 ms off
        ring.remove(_reports(ring)[2])
    elif why == "a report more than the trace has":
        ring.append(_phase("train/report", 30 * MS, 30 * MS + 1000))
    elif why == "a pair 1 ms off":
        _reports(ring)[2]["end"] += 1e-3
    elif why == "a fetch outside bench/data":
        ring.append(_phase("data/fetch", 5 * MS, 6 * MS, 8))  # in a step
    elif why == "a save's span outside bench/ckpt":
        ring.append(_phase("ckpt/setup", 30 * MS, 31 * MS))
    elif why == "records dropped inside the window":
        ring, dropped = ring[4:], 4  # the first step's data/next is gone
    elif why == "a program without spans":
        ring = [r for r in ring if r["kind"] != "phase"]
    assert progspans.align(trace, ring, dropped) is None


def test_records_dropped_before_the_window_do_no_harm():
    trace, ring = _trace_and_ring()
    assert progspans.align(trace, ring[2:], dropped=2) == progspans.align(
        trace, ring)


def test_program_spans_reads_this_process_ring_once(monkeypatch):
    from ray_tpu._private import steptrace

    trace, ring = _trace_and_ring()
    calls = []
    monkeypatch.setattr(
        steptrace, "process_snapshot",
        lambda: calls.append(1) or {"records": ring, "dropped": 0})
    reading = worker._Reading(trace=trace)
    assert progspans.program_spans(reading) == progspans.align(trace, ring)
    assert progspans.program_spans(reading) is reading._program_spans
    assert calls == [1]
    assert progspans.program_spans(worker._Reading(trace=None)) is None


SPAN_METRICS = {"report_ms.job", "data_next_ms", "data_fetch_ms",
                "ckpt_setup_ms", "ckpt_snapshot_ms", "ckpt_commit_ms"}


def test_traced_job_rehearsal_prints_the_program_span_metrics(tmp_path):
    proc, last = _run("tiny.job", 1, tmp_path,
                      bench_file="perfbench/tests/rehearsal_spans.json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    _assert_line(last, {"gang_start_s", "data_wait_pct", "ckpt_stall_ms",
                        "hbm_plan_gib.job"} | SPAN_METRICS)
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert all(last["metrics"][k]["unit"] == "ms" for k in SPAN_METRICS)
    # the parts lie inside what times them from outside
    assert m["data_fetch_ms"] <= m["data_next_ms"]
    assert (m["ckpt_setup_ms"] + m["ckpt_snapshot_ms"] + m["ckpt_commit_ms"]
            <= m["ckpt_stall_ms"])


def test_traced_step_rehearsal_prints_report_ms(tmp_path):
    proc, last = _run("tiny.step", 1, tmp_path,
                      bench_file="perfbench/tests/rehearsal_spans.json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    _assert_line(last, {"gang_start_s", "hbm_plan_gib", "report_ms"})
