"""The start-up path's account, gathered from every ring
(perfbench/clusterspans.py): merged records made by hand whose answers can
be worked out on paper, each reader's absent cases, then the whole command
at rehearsal size with the ten metrics that read it
(perfbench/tests/rehearsal_start.json: rehearsal_setup.json and their
entries), held against what ``run.py`` times from outside.
"""

import json
import os
import re

import pytest

from perfbench import clusterspans, worker
from perfbench.tests.test_progspans import EPOCH, _trace_and_ring
from perfbench.tests.test_rehearsal import ROOT, _run

START = "perfbench/tests/rehearsal_start.json"
STARTUP = ("init_s", "init_gcs_s", "init_raylet_s", "gang_placement_s",
           "gang_workers_s", "worker_boot_s", "chip_wait_s",
           "gang_unspanned_s")
SAVE = ("save_commit_s", "ckpt_persist_ms")
DRIVER = {"node_id": "driver:c0ffee", "pid": 100}
WORKER = {"node_id": "node-a", "pid": 200}
IDLE = {"node_id": "node-a", "pid": 201}  # started ahead, never a gang's
T0 = EPOCH - 100.0  # ``ray_tpu.init`` entered, on the rings' clock
WINDOW = (EPOCH - 0.005, EPOCH + 0.061)  # test_progspans' trace, about


def _span(process, name, start, end, n=None, thread=None):
    """A merged record ``start`` .. ``end`` seconds after ``init`` began."""
    rec = {"kind": "phase", "idx": 0, "step": 0, "phase": name, "rank": 0,
           "start": T0 + start, "end": T0 + end, "n": n, **process}
    if thread:
        rec["thread"] = thread
    return rec


def _merged(**changes):
    """A run on paper. ``init`` takes 6.0 s: the GCS 0.5, the raylet 4.5,
    the connection 0.2. ``fit()`` is called at 7.0 s; the gang's spans
    cover 7.1 .. 11.0 but for 0.1 s between ``gang/workers`` and
    ``gang/backend``; the loop's first line runs at 11.2. The train worker
    booted inside ``init`` (3.0 .. 4.0: the raylet started it ahead), the
    idle one took 2.5 s. Two saves: commits of 2.0 and 3.0 s, the second
    ending inside the window; the driver's copies 40 and 60 ms; a third
    commit still running at the window's end."""
    w = WINDOW[0] - T0  # the window begins ``w`` seconds after ``init``
    phases = [
        _span(DRIVER, "init", 0.0, 6.0),
        _span(DRIVER, "init/gcs", 0.1, 0.6),
        _span(DRIVER, "init/raylet", 0.6, 5.1),
        _span(DRIVER, "init/connect", 5.1, 5.3),
        _span(IDLE, "worker/boot", 2.0, 4.5),
        _span(WORKER, "worker/boot", 3.0, 4.0),
        _span(DRIVER, "gang/placement", 7.1, 7.3, n=1),
        _span(DRIVER, "gang/workers", 7.3, 9.8, n=1),
        _span(WORKER, "gang/session", 8.0, 9.7, thread="actor-exec_0"),
        _span(DRIVER, "gang/backend", 9.9, 10.4),
        _span(WORKER, "gang/chip_wait", 9.95, 10.35, n=5),
        _span(DRIVER, "gang/datasets", 10.4, 10.6, n=1),
        _span(DRIVER, "gang/launch", 10.6, 11.0),
        _span(WORKER, "gang/loop", 10.7, 10.95),
        _span(WORKER, "save/commit", 60.0, 62.0, n=10**9,
              thread="save-commit"),
        _span(DRIVER, "ckpt/persist", 62.5, 62.54),
        _span(WORKER, "save/commit", w + 0.05 - 3.0, w + 0.05, n=10**9,
              thread="save-commit"),  # ends 50 ms into the window
        _span(DRIVER, "ckpt/persist", w + 0.0555 - 0.06, w + 0.0555),
        _span(WORKER, "save/commit", w + 0.06, w + 5.0, n=10**9,
              thread="save-commit"),
    ]
    merged = {"phases": sorted(phases, key=lambda r: r["start"]),
              "errors": [],
              "rings": [dict(DRIVER, dropped=0), dict(WORKER, dropped=0),
                        dict(IDLE, dropped=0)]}
    merged.update(changes)
    return merged


def _read(merged, names=STARTUP + SAVE, gang_start_s=4.2):
    reading = worker._Reading(
        trace=None, host={"gang_start_s": gang_start_s},
        _cluster_account=clusterspans.account(merged, WINDOW, WORKER["pid"]))
    return {name: worker._load_reader(ROOT, "perfbench/metrics", name).read(
        reading) for name in names}


def test_the_ten_readers_on_a_run_worked_out_on_paper():
    value = _read(_merged())
    assert value["init_s"] == pytest.approx(6.0)
    assert value["init_gcs_s"] == pytest.approx(0.5)
    assert value["init_raylet_s"] == pytest.approx(4.5)
    assert value["gang_placement_s"] == pytest.approx(0.2)
    assert value["gang_workers_s"] == pytest.approx(2.5)
    # the train worker's, whole, though it began before the gang's start;
    # not the idle worker's 2.5 s
    assert value["worker_boot_s"] == pytest.approx(1.0)
    assert value["chip_wait_s"] == pytest.approx(0.4)
    # 4.2 s from outside; of 7.1 (``gang/placement`` begins) .. 10.95
    # (``gang/loop`` ends) all is covered but 9.8 .. 9.9
    assert value["gang_unspanned_s"] == pytest.approx(4.2 - 3.75)
    # whole records that ended by the window's end: 2.0 and 3.0 s
    assert value["save_commit_s"] == pytest.approx(2.5)
    assert value["ckpt_persist_ms"] == pytest.approx(50.0)


def test_a_restarted_gang_reads_the_start_that_led_to_the_window():
    merged = _merged()
    again = [dict(r, start=r["start"] + 20.0, end=r["end"] + 20.0)
             for r in merged["phases"] if r["phase"].startswith("gang/")]
    next(r for r in again if r["phase"] == "gang/workers")["end"] += 0.5
    merged["phases"] = sorted(merged["phases"] + again,
                              key=lambda r: r["start"])
    value = _read(merged, ("gang_workers_s", "init_s"))
    assert value == {"gang_workers_s": pytest.approx(3.0),
                     "init_s": pytest.approx(6.0)}


@pytest.mark.parametrize("why,changes", [
    ("a process the scrape could not reach",
     {"errors": [{"node_id": "node-a", "pid": 201, "error": "Timeout"}]}),
    ("a driver's ring that dropped records",
     {"rings": [dict(DRIVER, dropped=3), dict(WORKER, dropped=0)]}),
    ("the train worker's ring dropped records",
     {"rings": [dict(DRIVER, dropped=0), dict(WORKER, dropped=1)]}),
    ("a program that names no rings", {"rings": None}),
    ("the parent's records: no init, no gang spans",
     {"phases": [r for r in _merged()["phases"]
                 if r["phase"] in ("save/commit", "ckpt/persist")]}),
])
def test_absent_never_wrong(why, changes, monkeypatch):
    merged = _merged(**changes)
    if merged["rings"] is None:
        del merged["rings"]
    if merged["errors"]:  # the gatherer's business: it asks twice
        asked = []
        monkeypatch.setattr(
            "ray_tpu.util.state.steptrace_summary",
            lambda: asked.append(1) or merged)
        assert clusterspans.scrape() is None and len(asked) == 2
        merged = None
    assert set(_read(merged).values()) == {None}, why


def test_a_process_that_answers_the_second_time_is_read(monkeypatch):
    replies = [_merged(errors=[{"client_id": "x", "error": "Timeout"}]),
               _merged()]
    monkeypatch.setattr("ray_tpu.util.state.steptrace_summary",
                        lambda: replies.pop(0))
    assert clusterspans.scrape()["errors"] == [] and not replies


def test_an_idle_workers_dropped_ring_refuses_nothing():
    merged = _merged(rings=[dict(DRIVER, dropped=0), dict(WORKER, dropped=0),
                            dict(IDLE, dropped=9)])
    assert _read(merged, ("init_s",))["init_s"] == pytest.approx(6.0)


def test_a_run_without_saves_or_a_wait_leaves_those_readers_absent():
    merged = _merged()
    merged["phases"] = [r for r in merged["phases"] if r["phase"] not in (
        "save/commit", "ckpt/persist", "gang/chip_wait")]
    value = _read(merged)
    assert [n for n, v in value.items() if v is None] == [
        "chip_wait_s", "save_commit_s", "ckpt_persist_ms"]
    # and what covered the wait is covered by ``gang/backend`` still
    assert value["gang_unspanned_s"] == pytest.approx(0.45)


def test_the_window_is_laid_on_the_rings_clock_as_set_up_lays_it():
    trace, ring = _trace_and_ring()
    begins, ends = clusterspans.window_on_the_rings_clock(trace, ring)
    # the trace began 5 ms before EPOCH and holds 66 ms of spans
    assert begins == pytest.approx(EPOCH - 0.005, abs=1e-5)
    assert ends - begins == pytest.approx(0.066, abs=1e-5)
    assert clusterspans.window_on_the_rings_clock(None, ring) is None
    assert clusterspans.window_on_the_rings_clock(trace, []) is None


def test_the_benchmark_file_gives_every_cell_the_eight_and_the_job_two():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in STARTUP + SAVE}
    assert [m["name"] for m in bench["per_layer"][-10:]] == list(
        STARTUP + SAVE)
    for name in STARTUP:
        assert "workloads" not in mine[name] and mine[name]["unit"] == "s"
        assert mine[name]["moves"] == "setup_s"
        assert mine[name]["source"] == "program_span"
    assert {mine[n]["layer"] for n in STARTUP[:3]} == {"cluster start"}
    assert {mine[n]["layer"] for n in STARTUP[3:]} == {"gang"}
    for name in SAVE:
        assert mine[name]["workloads"] == ["gpt2-124m.job"]
        assert mine[name]["layer"] == "checkpoint"
        assert mine[name]["moves"] == "job_tokens_per_s_per_chip"
    for name in STARTUP + SAVE:
        assert os.path.isfile(os.path.join(
            ROOT, "perfbench", "metrics", name + ".py"))
    for cell in bench["workloads"]:
        names = {m["name"] for m in bench["per_layer"]
                 if "workloads" not in m or cell["name"] in m["workloads"]}
        assert set(STARTUP) <= names
        assert (set(SAVE) <= names) == (cell["name"] == "gpt2-124m.job")


# ----------------------------------------------------------------------
# the whole command, on the CPU
# ----------------------------------------------------------------------

def _phases(proc):
    found = re.search(r"set-up phases \(s\): (\{.*\})", proc.stdout).group(1)
    return dict((k, float(v)) for k, v in re.findall(r"'([^']+)': ([\d.]+)",
                                                     found))


def test_traced_job_run_reads_the_start_and_the_saves(tmp_path):
    proc, last = _run("tiny.job", 1, tmp_path, bench_file=START)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    value = {k: m["value"] for k, m in last["metrics"].items()}
    assert set(STARTUP + SAVE) <= set(value)
    # every metric the cell printed before this account is printed still
    assert {"gang_start_s", "report_ms.job", "data_next_ms", "data_fetch_ms",
            "ckpt_setup_ms", "ckpt_snapshot_ms", "ckpt_commit_ms",
            "compile_s", "first_save_s"} <= set(value)
    phases = _phases(proc)
    # ``init`` lies inside what run.py times round it with its own imports
    assert 0 < value["init_gcs_s"] + value["init_raylet_s"] < value["init_s"]
    assert value["init_s"] <= phases["process_start->ray_tpu_init"]
    # the gang's start from inside, against the same from outside
    assert value["gang_start_s"] == pytest.approx(
        phases["fit_called->loop_entered"], abs=1e-3)
    assert value["gang_placement_s"] + value["gang_workers_s"] \
        < value["gang_start_s"]
    assert 0 < value["worker_boot_s"] and 0 < value["chip_wait_s"] < 1
    assert abs(value["gang_unspanned_s"]) < 0.3
    assert value["save_commit_s"] > 0 and value["ckpt_persist_ms"] > 0
