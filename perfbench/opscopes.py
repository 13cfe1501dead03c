"""The device's time by the module that wrote it.

The program puts every operation of its step under one of six classes
(``ray_tpu/_private/steptrace.py:device_scope``: a ``jax.named_scope`` whose
name is ``rt.<kind>``), and the compiler carries an operation's name stack
into the trace: an ``XLA Ops`` event's METADATA on ``/device:TPU:0`` has a
stat ``tf_op`` that holds it (``jit(step)/transpose(jvp(Keye))/layers_1/
rt.mixer/attn/q_proj/dot_general``; a scope entered right under a transform
is printed inside it, ``jit(step)/jvp(rt.vocab)/while/body/...``).
``jax.profiler.ProfileData`` hands out an event's name, start and duration
and none of its metadata's stats, so ``perfbench/xplane.py`` (which reads
with nothing but ``ProfileData``) stays the reader of the events and their
clock, and this file adds only the names: a reader of the ``.xplane.pb``'s
wire format with the standard library alone. The join is exact: a
``ProfileData`` event's ``name`` IS its metadata's ``name``.

A fusion is one event, classed by the name stack the compiler gave the
fusion (its root instruction's). An operation without ``tf_op`` (a copy the
compiler made) belongs to nobody: ``no_path``; so does one whose ``tf_op``
is no name stack of the program's, which all begin ``jit(``: the compiler
names a copy of an argument after the argument
(``params['layers_8']['mixer']['experts_wi']``) and what it expands an
operation into after the expansion (``while/body/gather``). One with the
program's path and no ``rt.`` segment is ``unnamed``: the program wrote it
outside every scope.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import tempfile

from perfbench import xplane

PLANE = "/device:TPU:0"
PREFIX = "rt."
PROGRAM = "jit("   # how a name stack of the program's begins
# this file's own copy of ``steptrace.DEVICE_SCOPES``: the benchmark imports
# nothing of the program (a test holds the two equal)
KINDS = ("mixer", "experts", "mlp", "norm", "vocab", "optimizer")
UNNAMED, NO_PATH = "unnamed", "no_path"


# ----------------------------------------------------------------------
# the wire format: varints and length-delimited fields
# ----------------------------------------------------------------------

def _varint(buf, at: int):
    value, shift = 0, 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """-> (field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are passed
    over."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire == 1:
            at += 8
        elif wire == 5:
            at += 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")


def _map_values(entries):
    """The values (field 2) of a map's entries."""
    for entry in entries:
        for number, value in _fields(entry):
            if number == 2 and not isinstance(value, int):
                yield value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


class _Plane:
    """One ``XPlane``, its repeated fields gathered and not yet read."""

    def __init__(self, buf):
        self.name, self.lines = "", []
        self.event_metadata, self.stat_metadata = [], []
        for number, value in _fields(buf):
            if number == 2:
                self.name = _text(value)
            elif number == 3:
                self.lines.append(value)
            elif number == 4:
                self.event_metadata.append(value)
            elif number == 5:
                self.stat_metadata.append(value)


def _device_plane(path: str):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, value in _fields(space):  # XSpace.planes = 1
        if number == 1 and not isinstance(value, int):
            plane = _Plane(value)   # its fields gathered; none is read yet
            if plane.name == PLANE:
                return plane
    return None


def _names(plane: _Plane) -> dict:
    stat_names = {}
    for meta in _map_values(plane.stat_metadata):  # XStatMetadata
        ident, name = 0, ""
        for number, value in _fields(meta):
            if number == 1:
                ident = value
            elif number == 2:
                name = _text(value)
        stat_names[ident] = name
    out = {}
    for meta in _map_values(plane.event_metadata):  # XEventMetadata
        name, path = "", None
        for number, value in _fields(meta):
            if number == 2:
                name = _text(value)
            elif number == 5:  # XStat
                stat = dict(_fields(value))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:
                    path = _text(stat[5])
                elif 7 in stat:  # a stat_metadata id whose name is the string
                    path = stat_names.get(stat[7])
        out[name] = path or None
    return out


def _ops_line(plane: _Plane):
    """-> (events on the plane's ``XLA Ops`` line, the first one's start in
    ns as ``ProfileData`` gives it), or None without the line."""
    for line in plane.lines:
        name, timestamp_ns, offsets = "", 0, []
        for number, value in _fields(line):
            if number == 2:
                name = _text(value)
                if name != xplane.OPS_LINE:
                    break
            elif number == 3:
                timestamp_ns = value
            elif number == 4:  # XEvent: offset_ps = 2
                offsets.append(next(
                    (v for n, v in _fields(value) if n == 2), 0))
        if name == xplane.OPS_LINE:
            first = min(offsets) if offsets else 0
            return len(offsets), int(timestamp_ns + first / 1000)
    return None


_parsed = {}  # path -> (names, events on chip 0, first start ns) or None


def _parse(path: str):
    if path not in _parsed:
        plane = _device_plane(path)
        line = _ops_line(plane) if plane else None
        _parsed[path] = (_names(plane), *line) if line else None
    return _parsed[path]


def names(path: str) -> dict:
    """{instruction text: tf_op, None where the operation has none} of the
    operations of chip 0's plane in the ``.xplane.pb`` at ``path``; {}
    where the file has no such plane. Parsed once a process."""
    parsed = _parse(path)
    return parsed[0] if parsed else {}


# ----------------------------------------------------------------------
# which file a loaded trace came from
# ----------------------------------------------------------------------

def newest_trace_file():
    """The newest ``.xplane.pb`` a run of ``perfbench/run.py`` wrote: the
    worker traces into ``<storage>/trace`` with ``storage =
    tempfile.mkdtemp(prefix="perfbench_")``. jax 0.9.0 does not answer for
    the session's directory after ``stop_trace``
    (``jax._src.profiler._profile_state.reset()`` clears ``log_dir``)."""
    found = glob.glob(os.path.join(tempfile.gettempdir(), "perfbench_*",
                                   "trace", "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def names_of(trace, path=None):
    """``names`` of the file ``trace`` was loaded from, or None where that
    cannot be proved: ``path`` (the newest trace file where None) must hold
    as many ``XLA Ops`` events on chip 0 as ``trace`` and the same first
    start. A reader never reads another run's file."""
    ops = trace.ops.get(0) if trace else None
    path = path or (newest_trace_file() if ops else None)
    if not ops or not path:
        return None
    parsed = _parse(path)
    if parsed is None:
        return None
    found, events, first_ns = parsed
    if events != len(ops) or abs(first_ns - ops[0][1]) > 1:
        return None
    return found


# ----------------------------------------------------------------------
# from names to classes
# ----------------------------------------------------------------------

# a segment ``rt.<word>``, bare or as jax prints a scope entered right under a
# transform, wrapped in it: ``jvp(rt.vocab)``, ``transpose(jvp(rt.vocab))``
_SEGMENT = re.compile(r"(?:^|[/(])" + re.escape(PREFIX) + r"(\w+)(?=[/):]|$)")


def class_of(tf_op):
    """The class of an operation's name stack: its LAST ``rt.`` segment (a
    norm inside a mixer is the norm's), None where it has none or the word
    is not one of the six."""
    words = _SEGMENT.findall(tf_op or "")
    return words[-1] if words and words[-1] in KINDS else None


def by_class(trace, found: dict):
    """{kind: ms a step on chip 0}, the six classes, ``unnamed`` (the
    program's path, no class) and ``no_path`` (no ``tf_op``, or one that is
    not the program's: this file's header): per step the length of the union
    of the intervals of each class's operations, ``while`` / ``conditional``
    / ``call`` left out (their time is that of the operations inside them,
    events of their own), median over the traced steps. None without a
    traced step."""
    steps = xplane.step_device_work(trace, 0)
    if not steps:
        return None
    kinds = {}  # an instruction's text -> its class, looked up once
    per_step = []
    for _, _, _, ops in steps:
        mine = {kind: [] for kind in (*KINDS, UNNAMED, NO_PATH)}
        for name, start, end in ops:
            kind = kinds.get(name)
            if kind is None:
                if xplane.short_name(name).startswith(xplane._CONTROL_FLOW):
                    kind = "-"
                else:
                    path = found.get(name) or ""
                    kind = (class_of(path) or UNNAMED) if path.startswith(
                        PROGRAM) else NO_PATH
                kinds[name] = kind
            if kind != "-":
                mine[kind].append((start, end))
        per_step.append({kind: xplane.length(xplane.union(intervals))
                         for kind, intervals in mine.items()})
    return {kind: statistics.median(s[kind] for s in per_step) / 1e6
            for kind in per_step[0]}


_read = []  # [(trace, its by_class)]: the seven readers share one reading


def read_classes(trace):
    """``by_class`` of a reader's ``r.trace`` over the names of the file it
    came from; None where the file cannot be proved to be the trace's, and
    where no operation carries a class (a program without the scopes: the
    vocabulary is not there to read)."""
    if not (trace and trace.ops):
        return None
    if not (_read and _read[0][0] is trace):
        found = names_of(trace)
        classes = by_class(trace, found) if found is not None else None
        if classes and not any(classes[kind] for kind in KINDS):
            classes = None
        _read[:] = [(trace, classes)]
    return _read[0][1]


def read_class(r, kind: str):
    """What ``scope_<kind>_ms`` reports: the class's ms, None where it has
    none in this step."""
    classes = read_classes(r.trace)
    return (classes[kind] or None) if classes else None
