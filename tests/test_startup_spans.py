"""The start-up path records itself (``steptrace.span`` /
``record_phase``) in the three processes it crosses: ``ray_tpu.init`` in
the driver with its three children, the gang's start in the driver
(``gang/*``) and in the train worker (``worker/boot``, ``gang/*``), all
gathered by ``util.state.steptrace_summary()``; and how the train timeline
draws them: the driver in a row of its own, a span that ran off its
process's main thread on a lane of its own.

No assertion on a duration but the account's own check: what the spans
cover of the wall time from ``fit()`` to the loop's first line.
"""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import steptrace

pytestmark = pytest.mark.steptrace

DRIVER_GANG = ["gang/placement", "gang/workers", "gang/backend",
               "gang/datasets", "gang/launch"]
WORKER_START = ["worker/boot", "gang/session", "gang/chip_wait", "gang/loop"]


@pytest.fixture(scope="module")
def cluster():
    """A cluster of this module's own whose raylet starts no worker ahead
    of need, so the gang's worker boots inside the gang's start."""
    steptrace.set_enabled(True)
    steptrace.reset()
    before = os.environ.get("RAY_TPU_worker_prestart")
    os.environ["RAY_TPU_worker_prestart"] = "0"
    try:
        ray_tpu.init(num_cpus=4, num_tpus=1)
        yield
    finally:
        ray_tpu.shutdown()
        if before is None:
            del os.environ["RAY_TPU_worker_prestart"]
        else:
            os.environ["RAY_TPU_worker_prestart"] = before
        steptrace.reset()


def _is_driver(rec):
    return str(rec.get("node_id")).startswith("driver:")


def test_init_leaves_init_with_its_three_children_inside_it_in_order(cluster):
    phases = [r for r in steptrace.snapshot() if r["kind"] == "phase"]
    # a span is written when it ends: the children, then the whole
    assert [r["phase"] for r in phases] == [
        "init/gcs", "init/raylet", "init/connect", "init"]
    gcs, raylet, connect, init = phases
    assert init["start"] <= gcs["start"] <= gcs["end"] <= raylet["start"] \
        <= raylet["end"] <= connect["start"] <= connect["end"] <= init["end"]
    assert not any("thread" in r for r in phases)  # the main thread's


def test_the_gcs_records_its_own_start_inside_the_drivers_init_gcs(cluster):
    from ray_tpu.util import state

    merged = state.steptrace_summary()
    by_name = {r["phase"]: r for r in merged["phases"]
               if r["phase"].startswith(("init/gcs", "gcs/"))}
    assert sorted(by_name) == ["gcs/boot", "gcs/server", "init/gcs"]
    whole, boot, server = (by_name[n] for n in
                           ("init/gcs", "gcs/boot", "gcs/server"))
    assert boot["node_id"] == server["node_id"] == "gcs"
    assert boot["pid"] == server["pid"] != os.getpid()
    # process start is read to a clock tick, the spawn to a microsecond
    assert whole["start"] - 0.02 <= boot["start"] <= boot["end"] \
        <= server["start"] <= server["end"] <= whole["end"]
    assert {"node_id": "gcs", "pid": boot["pid"], "dropped": 0} \
        in merged["rings"]


def _loop(config):
    entered = time.time()  # the loop's first line
    from ray_tpu import train

    train.report({"entered": entered, "pid": os.getpid()})


@pytest.mark.parametrize("with_datasets", [False, True],
                         ids=["no-datasets", "datasets"])
def test_a_one_worker_fit_leaves_the_gangs_start_in_both_rings(
        cluster, with_datasets, tmp_path):
    from ray_tpu import data
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.util import state

    datasets = ({"train": data.from_items([{"x": i} for i in range(8)])}
                if with_datasets else None)
    fit_called = time.time()
    result = JaxTrainer(
        _loop, scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                            chips_per_worker=1),
        run_config=RunConfig(name="start", storage_path=str(tmp_path)),
        datasets=datasets).fit()
    assert result.error is None, result.error
    entered, pid = result.metrics["entered"], result.metrics["pid"]

    # the gang is gone (its worker may be named under ``errors``, dying):
    # its records are in the GCS's log, drained at the gang's end
    merged = state.steptrace_summary()
    rings = {(str(r["node_id"]).startswith("driver:"), r["pid"]): r["dropped"]
             for r in merged["rings"]}
    assert rings[(True, os.getpid())] == 0
    mine = [r for r in merged["phases"] if r["start"] >= fit_called - 1e-3]
    driver = [r for r in mine if _is_driver(r)]
    assert all(r["pid"] == os.getpid() for r in driver)
    assert [r["phase"] for r in driver] == [
        n for n in DRIVER_GANG if with_datasets or n != "gang/datasets"]
    by_name = {r["phase"]: r for r in driver}
    assert by_name["gang/placement"]["n"] == 1
    assert by_name["gang/workers"]["n"] == 1
    if with_datasets:
        assert by_name["gang/datasets"]["n"] == 1

    worker = [r for r in mine if r["pid"] == pid and not _is_driver(r)
              and r["phase"].startswith(("worker/", "gang/"))]
    assert [r["phase"] for r in worker] == WORKER_START
    boot, session, chip_wait, loop = worker
    assert boot["end"] <= session["start"]
    # no process was started ahead: the boot lies inside ``gang/workers``
    assert by_name["gang/workers"]["start"] <= boot["start"] \
        and session["end"] <= by_name["gang/workers"]["end"]
    assert chip_wait["n"] == 1  # one probe: no chip node here, none held
    # actor calls run on the executor's threads, the driver on its main one
    assert session["thread"] and "thread" not in by_name["gang/workers"]
    assert loop["end"] <= entered

    # the account's own check: what no span covers of fit() -> first line
    covered, at = 0.0, fit_called
    for r in sorted(driver + worker, key=lambda r: r["start"]):
        start, end = max(r["start"], at), min(r["end"], entered)
        if end > start:
            covered, at = covered + end - start, end
    assert 0 <= (entered - fit_called) - covered < 0.3

    # and the timeline an operator opens
    trace = steptrace.chrome_trace(merged)
    rows = {e["pid"]: e["args"]["name"] for e in trace if e["ph"] == "M"}
    drawn = {(rows[e["pid"]], e["name"]): e for e in trace
             if e.get("cat") == "phase"}
    for name in ["init", "init/gcs"] + [r["phase"] for r in driver]:
        assert drawn[("driver", name)]["tid"] == "phases"
    assert drawn[("rank 0", "worker/boot")]["tid"] == "phases"
    assert drawn[("rank 0", "gang/session")]["tid"].startswith("phases:")
    first_step = min(e["ts"] for e in trace if e.get("cat") == "step"
                     and e["pid"] == 0 and e["ts"] >= fit_called * 1e6)
    assert boot["end"] * 1e6 <= first_step


def test_a_span_off_the_main_thread_records_the_threads_name():
    steptrace.reset()
    try:
        with steptrace.span("on/main"):
            pass

        def write():
            with steptrace.span("save/commit", 7):
                pass

        thread = threading.Thread(target=write, name="save-commit")
        thread.start()
        thread.join(10)
        assert not thread.is_alive()
        on_main, off_main = [r for r in steptrace.snapshot()
                             if r["kind"] == "phase"]
        assert "thread" not in on_main
        assert off_main["thread"] == "save-commit" and off_main["n"] == 7
        # what progspans.align reads is where it was
        assert {"phase", "start", "end", "n"} <= set(off_main)
    finally:
        steptrace.reset()


def _phase(name, start, end, rank=0, **ident):
    return {"kind": "phase", "idx": 0, "step": 0, "phase": name,
            "rank": rank, "start": start, "end": end, "n": None, **ident}


def test_the_timeline_gives_the_driver_a_row_and_a_thread_a_lane():
    driver = {"node_id": "driver:abc", "pid": 10}
    worker = {"node_id": "node-a", "pid": 20}
    idle = {"node_id": "node-a", "pid": 21}
    merged = steptrace.merge_records([
        _phase("init", 1.0, 3.0, **driver),
        _phase("gcs/server", 1.5, 1.6, node_id="gcs", pid=11),
        _phase("gang/workers", 4.0, 7.0, **driver),
        _phase("worker/boot", 1.5, 2.5, **idle),
        _phase("worker/boot", 4.2, 5.0, **worker),  # rank 0 by default
        _phase("gang/session", 5.5, 6.5, rank=1, thread="actor-exec_0",
               **worker),
        _phase("train/report", 8.0, 8.1, rank=1, thread="train-loop",
               **worker),
        _phase("save/commit", 8.0, 9.0, rank=1, thread="save-commit",
               **worker),
        _phase("compute", 8.2, 8.4, rank=1),  # a record with no process
        {"kind": "restart", "idx": 0, "cause": "actor_died",
         "generation": 1, "start": 10.0, "end": 12.5},
    ])
    trace = steptrace.chrome_trace(merged)
    rows = {e["pid"]: e["args"]["name"] for e in trace if e["ph"] == "M"}
    assert sorted(rows.values()) == ["driver", "gcs", "rank 1", "worker 21"]
    assert len(set(rows)) == 4 and rows[1] == "rank 1"
    where = {e["name"]: (rows[e["pid"]], e["tid"]) for e in trace
             if e["ph"] == "X" and e["pid"] != rows.get("worker 21")
             and rows[e["pid"]] != "worker 21"}
    assert where == {
        "init": ("driver", "phases"),
        "gcs/server": ("gcs", "phases"),
        "gang/workers": ("driver", "phases"),
        "restart[actor_died] -> gen 1": ("driver", "recovery"),
        # in the row of the rank its process then took
        "worker/boot": ("rank 1", "phases"),
        "gang/session": ("rank 1", "phases:actor-exec_0"),
        "train/report": ("rank 1", "phases:train-loop"),
        "save/commit": ("rank 1", "phases:save-commit"),
        "compute": ("rank 1", "phases"),
    }
    idle_row = [e for e in trace if e["ph"] == "X"
                and rows[e["pid"]] == "worker 21"]
    assert [e["name"] for e in idle_row] == ["worker/boot"]
