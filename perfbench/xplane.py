"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals
to the numbers the per-layer metrics read.

The trace is what ``jax.profiler.start_trace`` writes. Each TPU chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO operation; the host is the plane ``/host:CPU``, where a
``jax.profiler.TraceAnnotation("bench/...")`` made by the train loop is an
event of that name. Both are on one clock (nanoseconds), so a gap on the
device can be laid against what the host was doing. Read with nothing but
``jax.profiler.ProfileData``; only the worker, which holds jax, calls this.

All times inside are integer nanoseconds; the public numbers are seconds
or milliseconds as their names say.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_CONTROL_FLOW = ("while", "conditional", "call")
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])?")
SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"%?(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all|async-collective)"
    r"(-start|-done)?\b")


@dataclass
class Trace:
    """ops[chip] = [(name, start, end)] of the chip's HLO operations and
    modules[chip] = the same of its executed programs, sorted by start;
    spans = [(name, start, end)] of the host's bench/* annotations."""
    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)


def short_name(event_name: str) -> str:
    """An operation's event carries its whole HLO text: keep the
    instruction's name and the first shape of its result."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return event_name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        chip = DEVICE_PLANE.fullmatch(plane.name)
        for line in plane.lines:
            if chip and line.name in (OPS_LINE, MODULES_LINE):
                into = trace.ops if line.name == OPS_LINE else trace.modules
                into[int(chip.group(1))] = sorted(
                    ((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                     for e in line.events), key=lambda o: o[1])
            elif plane.name.startswith("/host:"):
                trace.spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    trace.spans.sort(key=lambda s: s[1])
    return trace


# ----------------------------------------------------------------------
# interval arithmetic on sorted [(start, end)] lists
# ----------------------------------------------------------------------

def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a, b) -> int:
    """Total length of the intersection of two unions."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def subtract(a, b) -> list:
    """The part of union ``a`` outside union ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ----------------------------------------------------------------------
# what the metrics read
# ----------------------------------------------------------------------

def spans_named(trace: Trace, name: str) -> list:
    return [(s, e) for n, s, e in trace.spans if n == name]


def window(trace: Trace):
    """The traced window: first bench/* span's start to the last one's
    end. None without spans."""
    if not trace.spans:
        return None
    return (min(s for _, s, _ in trace.spans),
            max(e for _, _, e in trace.spans))


def busy(trace: Trace, chip: int, lo: int, hi: int) -> list:
    return union(clip([(s, e) for _, s, e in trace.ops[chip]], lo, hi))


def busy_and_window_s(trace: Trace):
    """-> (seconds an operation ran on the device, averaged over the chips;
    seconds of the traced window), or None where no device was traced."""
    w = window(trace)
    if w is None or not trace.ops:
        return None
    per_chip = [length(busy(trace, c, *w)) for c in trace.ops]
    return sum(per_chip) / len(per_chip) / 1e9, (w[1] - w[0]) / 1e9


def is_collective(name: str) -> bool:
    return COLLECTIVE.match(name) is not None


def collective_intervals(ops) -> list:
    """Union of the time collectives are in flight on one chip: a
    synchronous collective's own event, and for an asynchronous one the
    stretch from its ``-start`` to the matching ``-done`` (same kind, first
    in first out), during which compute may run beside it."""
    out, open_starts = [], defaultdict(list)
    for name, s, e in ops:
        m = COLLECTIVE.match(name)
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_starts[kind].append(s)
            out.append((s, e))
        elif phase == "-done" and open_starts[kind]:
            out.append((open_starts[kind].pop(0), e))
        else:
            out.append((s, e))
    return union(out)


def step_device_work(trace: Trace, chip: int) -> list:
    """For each bench/step span, the device's work for it:
    [(start, end, busy ns, ops)]. The work is the programs (events of the
    chip's ``XLA Modules`` line) whose midpoint lies inside the span: the
    span ends with the loss read back, so a step's program runs inside it,
    and the midpoint forgives the fraction of a millisecond by which the
    device's clock and the host's differ. ``busy`` is the union of the
    operations inside that stretch."""
    ops, modules = trace.ops.get(chip, []), trace.modules.get(chip, [])
    starts = [s for _, s, _ in ops]
    out = []
    for lo, hi in spans_named(trace, SPAN_PREFIX + "step"):
        mine = [(s, e) for _, s, e in modules if lo <= (s + e) // 2 <= hi]
        if not mine:
            continue
        start, end = mine[0][0], max(e for _, e in mine)
        inside = ops[bisect.bisect_left(starts, start):
                     bisect.bisect_left(starts, end)]
        busy_ns = length(clip(union((s, e) for _, s, e in inside),
                              start, end))
        out.append((start, end, busy_ns, inside))
    return out


def device_step_ms(trace: Trace, chip: int = 0):
    steps = step_device_work(trace, chip)
    if not steps:
        return None
    return statistics.median(b for _, _, b, _ in steps) / 1e6


def host_gap_ms(trace: Trace, chip: int = 0):
    """Median over consecutive steps of the device's gap between them that
    no bench/data or bench/ckpt span covers."""
    steps = step_device_work(trace, chip)
    if len(steps) < 2:
        return None
    covered = union(spans_named(trace, SPAN_PREFIX + "data")
                    + spans_named(trace, SPAN_PREFIX + "ckpt"))
    gaps = []
    for (_, end, _, _), (start, _, _, _) in zip(steps, steps[1:]):
        gap = [(end, start)] if start > end else []
        gaps.append(length(gap) - overlap(gap, covered))
    return statistics.median(gaps) / 1e6


def collective_ms_and_exposed_pct(trace: Trace, chip: int = 0):
    """-> (ms of collectives in flight per step, % of that during which no
    other operation ran on the chip), or None without steps or
    collectives."""
    steps = step_device_work(trace, chip)
    if not steps:
        return None
    in_flight, exposed = 0, 0
    for _, _, _, ops in steps:
        coll = collective_intervals(ops)
        compute = union((s, e) for n, s, e in ops if not is_collective(n))
        in_flight += length(coll)
        exposed += length(subtract(coll, compute))
    if not in_flight:
        return None
    return in_flight / len(steps) / 1e6, 100.0 * exposed / in_flight


def breakdown(trace: Trace, chip: int = 0, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by the bench/* span that covers most of each, then each
    span's share of all idle time (``sum:<span>``)."""
    w = window(trace)
    if w is None or chip not in trace.ops:
        return {}
    by_name = defaultdict(int)
    for name, s, e in trace.ops[chip]:
        short = short_name(name)
        # a while or conditional is listed through the operations inside it
        if e > w[0] and s < w[1] and not short.startswith(_CONTROL_FLOW):
            by_name[short] += e - s
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    busy_now = busy(trace, chip, *w)
    gaps = sorted(subtract([w], busy_now), key=lambda g: g[0] - g[1])
    span_unions = {n: clip(union(spans_named(trace, n)), *w)
                   for n in sorted({n for n, _, _ in trace.spans})}
    named = []
    for gap in gaps[:top]:
        cover = {n: overlap([gap], u) for n, u in span_unions.items()}
        best = max(cover, key=cover.get, default=None)
        named.append((best if best and cover[best] * 2 >= length([gap])
                      else "none", length([gap])))
    # a span's idle time is its length less the device's busy time inside it
    sums = {n: length(u) - overlap(u, busy_now)
            for n, u in span_unions.items()}
    sums["none"] = max(0, length(gaps) - sum(sums.values()))
    totals = sorted(((f"sum:{n}", v) for n, v in sums.items() if v),
                    key=lambda kv: -kv[1])
    keep = max(0, top - len(totals))
    return {
        "device_ops": [[n, v / 1e9] for n, v in device_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in named[:keep] + totals[:top]],
    }

