"""Set-up's account, read from the runtime's ring.

``setup_s`` runs from the start of ``run.py`` to the first measured step
boundary. What the program does in that time it records itself, in the
step-telemetry ring of the worker process (``ray_tpu/_private/steptrace.py``):

- one record of kind ``compile`` for each part jax times of every function
  it compiles in the process, whoever jitted it (the program's step, the
  state's making, the benchmark's own jits, the float32 reference's):
  ``part`` is ``trace``, ``lower`` or ``backend`` (XLA's compile, or on a
  hit of the persistent cache the entry's load), ``name`` the function,
  ``start`` / ``end`` jax's own on ``time.time()``, and on ``backend``
  ``cache`` is the cache's verdict: ``hit``, ``miss`` or ``uncached``;
- the spans of every ``save_pytree``, ``ckpt/setup|snapshot|commit``, the
  warm-up save's among them.

A reader runs in that process after the traced run and takes the ring as
``progspans`` does. Set-up is what ends before the traced window begins:
the ring's clock is laid on the trace's by ``progspans.pair_deviations``,
and a record belongs to set-up if it ends before the window's first span
starts. Refused, and then every metric that reads this is absent from the
line (an absent metric is seen, a wrong one is not): a ring that cannot be
laid on the trace; a ring that has dropped records of this train session
(``dropped`` > 0 and the oldest record kept is younger than the session's
start, which the record of step 0 carries); a ring that holds no
``compile`` record with a part, which is the program before it recorded
them.
"""

from __future__ import annotations

from perfbench import progspans, xplane

STEP_PARTS = ("trace", "lower")  # Python's share of a compilation
SAVE_SPANS = ("ckpt/setup", "ckpt/snapshot", "ckpt/commit")


def step_name():
    """The name the program jits its train step under, or None where the
    program does not say (the parent of the PR that exported it)."""
    from ray_tpu.parallel import train_step

    return getattr(train_step, "STEP_NAME", None)


def before_window(trace, records, dropped: int = 0):
    """-> the ring's records that end before the traced window begins,
    oldest first; or None (see the module's docstring)."""
    window = trace and xplane.window(trace)
    paired = window and progspans.pair_deviations(trace, records)
    if not paired:
        return None
    if dropped:
        began = [r["start"] for r in records
                 if r["kind"] == "step" and r["step"] == 0]
        if not began or records[0]["start"] > began[-1]:
            return None
    begins = (window[0] - paired[0]) / 1e9  # on the ring's clock
    return [r for r in records if r["end"] <= begins]


def account(records):
    """-> {"compiles": the ``compile`` records with a part, "spans": {name:
    [(start, end)]} of the ``phase`` records}, or None where no compile
    record has a part."""
    compiles = [r for r in records
                if r["kind"] == "compile" and r.get("part")]
    if not compiles:
        return None
    spans = {}
    for r in records:
        if r["kind"] == "phase":
            spans.setdefault(r["phase"], []).append((r["start"], r["end"]))
    return {"compiles": compiles, "spans": spans}


def setup_account(reading):
    """``account`` of this process's ring before the reading's traced
    window; worked out once per reading, whichever reader asks first."""
    if not hasattr(reading, "_setup_account"):
        from ray_tpu._private import steptrace

        snap = steptrace.process_snapshot()
        records = before_window(reading.trace, snap["records"],
                                snap["dropped"])
        reading._setup_account = account(records) if records else None
    return reading._setup_account


# ----------------------------------------------------------------------
# what the readers (perfbench/metrics/<name>.py) compute from it
# ----------------------------------------------------------------------

def compile_s(reading):
    """Seconds inside jax's compilation path, for every function: the
    length of the union of the records' intervals. A function's ``trace``
    holds its inner functions' traces, so a sum would count them twice."""
    found = setup_account(reading)
    if not found:
        return None
    return xplane.length(xplane.union(
        (r["start"], r["end"]) for r in found["compiles"]))


def _step_records(reading, parts):
    found, name = setup_account(reading), step_name()
    if not found or name is None:
        return None
    return [r for r in found["compiles"]
            if r["name"] == name and r["part"] in parts] or None


def step_seconds(reading, parts):
    """Seconds inside ``parts`` of the train step's compilations, all of
    them (a step whose state comes back in other shardings is compiled
    twice)."""
    mine = _step_records(reading, parts)
    return mine and sum(r["end"] - r["start"] for r in mine)


def step_cache_hit_pct(reading):
    mine = _step_records(reading, ("backend",))
    return mine and 100.0 * sum(r["cache"] == "hit" for r in mine) / len(mine)


def first_save_s(reading):
    """What the loop was blocked for by the process's first
    ``save_pytree``: its ``ckpt/*`` spans, from the first ``ckpt/setup``
    up to the next one."""
    found = setup_account(reading)
    setups = found and sorted(found["spans"].get("ckpt/setup", ()))
    if not setups:
        return None
    ends = setups[1][0] if len(setups) > 1 else float("inf")
    return sum(e - s for name in SAVE_SPANS
               for s, e in found["spans"].get(name, ())
               if setups[0][0] <= s < ends)
