"""The program's own spans, laid on the profiler trace's clock.

The runtime records spans where the work happens (``steptrace.span``:
``train/report``, ``data/next`` around ``data/fetch``, ``ckpt/setup``,
``ckpt/snapshot``, ``ckpt/commit``) into a ring in the worker process,
stamped with ``time.time()``. A reader runs in that process, so the ring
is at hand; the trace it is given (``xplane.Trace``) holds the benchmark
loop's ``bench/*`` spans on the profiler's clock, which counts from an
instant inside ``start_trace`` that Python cannot read. The two clocks
are joined by the events both record: every ``bench/step`` and
``bench/ckpt`` span ends with ``train.report`` returning, so the k-th
last ``train/report`` record and the k-th last such span end at the same
instant. The offset is the median difference over all pairs.

Refused, and then every metric that reads this is absent from the line
(an absent metric is seen, a wrong one is not): a pair more than 0.2 ms
off the median, counts that do not match, records the ring dropped
inside the window, a program span outside the ``bench/*`` span its call
is made in. A program that records no such spans (the parent of the PR
that added them) is refused by the count.
"""

from __future__ import annotations

import statistics

from perfbench import xplane

REPORT = "train/report"
REPORT_ENDS = ("bench/step", "bench/ckpt")  # spans that end with a report
PARENTS = {"train/": REPORT_ENDS, "data/": ("bench/data",),
           "ckpt/": ("bench/ckpt",)}
TOLERANCE_NS = 200_000


def _ns(seconds: float) -> int:
    return round(seconds * 1e9)


def pair_deviations(trace, records):
    """-> (offset_ns, [each pair's distance from it in ns]) between the
    ring's clock and the trace's, or None where the ring holds fewer
    ``train/report`` records than the trace has spans that end with one."""
    ends = sorted(e for n, _, e in trace.spans if n in REPORT_ENDS)
    reports = sorted(_ns(r["end"]) for r in records
                     if r["kind"] == "phase" and r["phase"] == REPORT)
    if not ends or len(reports) < len(ends):
        return None
    diffs = [e - r for e, r in zip(ends, reports[-len(ends):])]
    offset = round(statistics.median(diffs))
    return offset, [d - offset for d in diffs]


def align(trace, records, dropped: int = 0):
    """-> {name: [(start_ns, end_ns, n)]} of the ring's spans inside the
    traced window, on the trace's clock and sorted by start; or None (see
    the module's docstring). ``records`` is ``steptrace.snapshot()``,
    ``dropped`` how many older records the ring has overwritten."""
    window = xplane.window(trace)
    paired = window and pair_deviations(trace, records)
    if not paired:
        return None
    offset, deviations = paired
    if max(abs(d) for d in deviations) > TOLERANCE_NS:
        return None
    lo, hi = window
    if dropped and _ns(records[0]["end"]) + offset > lo:
        return None  # the oldest record kept is not from before the window
    parents = {name: xplane.union(xplane.spans_named(trace, name))
               for names in PARENTS.values() for name in names}
    spans = {}
    for r in records:
        if r["kind"] != "phase":
            continue
        start, end = _ns(r["start"]) + offset, _ns(r["end"]) + offset
        if not lo <= (start + end) // 2 <= hi:
            continue  # the warm-up's, by midpoint as xplane matches steps
        for prefix, names in PARENTS.items():
            if r["phase"].startswith(prefix) and not any(
                    s - TOLERANCE_NS <= start and end <= e + TOLERANCE_NS
                    for name in names for s, e in parents[name]):
                return None
        spans.setdefault(r["phase"], []).append((start, end, r.get("n")))
    if len(spans.get(REPORT, ())) != len(deviations):
        return None  # a report inside the window that ends no bench/* span
    return {name: sorted(found) for name, found in spans.items()}


def program_spans(reading):
    """``align`` of the reading's trace with this process's ring; worked
    out once per reading, whichever reader asks first."""
    if not hasattr(reading, "_program_spans"):
        from ray_tpu._private import steptrace

        snap = steptrace.process_snapshot()
        reading._program_spans = (
            align(reading.trace, snap["records"], snap["dropped"])
            if reading.trace else None)
    return reading._program_spans


# ----------------------------------------------------------------------
# what the readers (perfbench/metrics/<name>.py) compute from them
# ----------------------------------------------------------------------

def median_ms(reading, name: str):
    found = (program_spans(reading) or {}).get(name)
    if not found:
        return None
    return statistics.median(e - s for s, e, _ in found) / 1e6


def total_ms_per(reading, name: str, per: str):
    """Time inside the spans ``name`` over the number of ``per`` spans of
    the benchmark's loop (``bench/step``: a mean per step; ``bench/ckpt``:
    a mean per save)."""
    found = (program_spans(reading) or {}).get(name)
    count = found and len(xplane.spans_named(reading.trace, per))
    if not count:
        return None
    return sum(e - s for s, e, _ in found) / count / 1e6
