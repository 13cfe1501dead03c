"""The start-up path's own account, gathered from every process it crosses.

``progspans`` and ``setupspans`` read the ring of the worker they run in.
What the runtime does before the loop's first line happens in three
processes: the driver (``ray_tpu.init`` with ``init/gcs``, ``init/raylet``,
``init/connect``; the gang's ``gang/placement``, ``gang/workers``,
``gang/backend``, ``gang/datasets``, ``gang/launch``; later each save's
``ckpt/persist``), the train worker (``worker/boot``, ``gang/session``,
``gang/chip_wait``, ``gang/loop``; ``save/commit`` from the thread that
writes a save) and the processes between them. Every ring records on
``time.time()``, one clock on one host, and the GCS gathers them all
(``ray_tpu.util.state.steptrace_summary()``: each worker's through its
raylet, each driver's through its own connection, which answers while the
driver sits in ``fit()``). A reader runs in the train worker after the
traced run and asks once per reading; the driver's records carry a
``node_id`` that begins ``driver:``, the worker's this process's pid.

"Before the traced window" is ``setupspans.before_window``'s instant: the
ring's clock laid on the trace's by ``progspans.pair_deviations``.

Absent, never wrong. Every metric that reads this is ``None`` where: the
scrape fails or could not reach a process (``errors``), twice in a row; the
reply names no rings (a program that does not say what each ring dropped);
a driver's ring or this worker's dropped records; this worker's ring cannot
be laid on the trace; no driver holds an ``init`` record (the parent of the
PR that added them).
"""

from __future__ import annotations

import os
import statistics

from perfbench import progspans, xplane

DRIVER = "driver:"
GANG_PREFIXES = ("gang/", "worker/")


def scrape():
    """-> the merged view of every ring the GCS could reach, or None. A
    process that did not answer may answer the second time; a metric that
    is absent from a line reads as a metric done away with."""
    from ray_tpu.util import state

    for _ in range(2):
        try:
            merged = state.steptrace_summary()
        except Exception:  # the benchmark's line goes on without it
            continue
        if not merged.get("errors"):
            return merged
    return None


def window_on_the_rings_clock(trace, records):
    """-> (begins, ends) of the traced window in epoch seconds, or None
    where ``records`` (this process's ring) cannot be laid on the trace."""
    window = trace and xplane.window(trace)
    paired = window and progspans.pair_deviations(trace, records)
    if not paired:
        return None
    return tuple((t - paired[0]) / 1e9 for t in window)


def account(merged, window, pid: int):
    """-> {"driver": spans, "worker": spans, "boots": [(start, end)],
    "window": window}, ``spans`` being {name: [(start, end, n)]} sorted by
    start, of the records that end by the window's end; or None (see the
    module's docstring). ``merged`` is ``scrape()``'s, ``window`` the
    traced window in epoch seconds, ``pid`` the train worker's. ``boots``
    are the ``worker/boot`` records of the processes that hold a
    ``gang/session``: the gang's workers."""
    if not merged or not window or "rings" not in merged:
        return None
    mine = lambda r: r.get("pid") == pid and not _is_driver(r)
    if any(ring.get("dropped") for ring in merged["rings"]
           if _is_driver(ring) or mine(ring)):
        return None
    driver, worker, by_pid = {}, {}, {}
    for r in merged.get("phases", ()):
        if r["end"] > window[1]:
            continue
        span = (r["start"], r["end"], r.get("n"))
        if _is_driver(r):
            driver.setdefault(r["phase"], []).append(span)
        else:
            by_pid.setdefault(r.get("pid"), {}).setdefault(
                r["phase"], []).append(span)
            if mine(r):
                worker.setdefault(r["phase"], []).append(span)
    if not driver.get("init"):
        return None
    for spans in (driver, worker):
        for found in spans.values():
            found.sort()
    boots = [(s, e) for spans in by_pid.values() if "gang/session" in spans
             for s, e, _ in spans.get("worker/boot", ())]
    return {"driver": driver, "worker": worker, "boots": boots,
            "window": window}


def _is_driver(rec) -> bool:
    return str(rec.get("node_id") or "").startswith(DRIVER)


def cluster_account(reading):
    """``account`` of one scrape against the reading's traced window;
    worked out once per reading, whichever reader asks first."""
    if not hasattr(reading, "_cluster_account"):
        from ray_tpu._private import steptrace

        snap = steptrace.process_snapshot()
        window = window_on_the_rings_clock(reading.trace, snap["records"])
        reading._cluster_account = (
            account(scrape(), window, os.getpid()) if window else None)
    return reading._cluster_account


# ----------------------------------------------------------------------
# what the readers (perfbench/metrics/<name>.py) compute from it
# ----------------------------------------------------------------------

def _last_before_window(found, process: str, name: str):
    """The last span ``name`` of ``process`` that ends before the traced
    window begins: the start that led to this window."""
    spans = [s for s in found[process].get(name, ())
             if s[1] <= found["window"][0]]
    return spans[-1] if spans else None


def span_s(reading, process: str, name: str):
    """Seconds of the span ``name`` in the driver's or the train worker's
    ring (``process``: "driver" / "worker")."""
    found = cluster_account(reading)
    span = found and _last_before_window(found, process, name)
    return span and span[1] - span[0]


def worker_boot_s(reading):
    """The whole length of the train worker's ``worker/boot``, the longest
    where the gang has several workers, whether the raylet started the
    process ahead of the gang or for it."""
    found = cluster_account(reading)
    if not found or not found["boots"]:
        return None
    return max(e - s for s, e in found["boots"])


def gang_unspanned_s(reading):
    """``gang_start_s`` (the benchmark's, from outside) less what the
    account covers of it: the union of every ``gang/*`` and ``worker/*``
    span of driver and workers between ``gang/placement``'s start and
    ``gang/loop``'s end."""
    found = cluster_account(reading)
    outside = reading.host.get("gang_start_s")
    first = found and _last_before_window(found, "driver", "gang/placement")
    last = found and _last_before_window(found, "worker", "gang/loop")
    if outside is None or not first or not last:
        return None
    lo, hi = first[0], last[1]
    covered = [(s, e) for spans in (found["driver"], found["worker"])
               for name, got in spans.items()
               if name.startswith(GANG_PREFIXES) for s, e, _ in got]
    covered += found["boots"]
    return outside - xplane.length(xplane.union(xplane.clip(covered, lo, hi)))


def save_commit_s(reading):
    """Median length of the ``save/commit`` records (the thread that writes
    a save's files behind the next steps) that ended by the window's end:
    whole records, the warm-up save's among them, whose write ends inside
    the window."""
    found = cluster_account(reading)
    commits = found and found["worker"].get("save/commit")
    return commits and statistics.median(e - s for s, e, _ in commits)


def ckpt_persist_ms(reading):
    """The driver's ``ckpt/persist`` (the copy of a save into the trial
    directory, while the worker trains on), a mean per save over those that
    ended by the window's end, in ms."""
    found = cluster_account(reading)
    copies = found and found["driver"].get("ckpt/persist")
    return copies and 1e3 * sum(e - s for s, e, _ in copies) / len(copies)
