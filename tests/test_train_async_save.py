"""A save inside a train session commits behind the steps that follow it
(``air.checkpoint.save_pytree``), and its checkpoint reaches the driver
only once its files are whole (``train.report``, the flush before the
loop's ``done``).

orbax's writer is slowed, stopped or failed where it does its work: in
``_background_wait_for_commit_futures``, which its own thread runs before
it renames the temporary directory. No assertion on a duration: on what is
on the queue and on disk, in which order, and which thread recorded what.
"""

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu._private import steptrace

pytest.importorskip("jax")
pytest.importorskip("orbax.checkpoint")

pytestmark = pytest.mark.steptrace

_CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
}
WAIT_S = 30.0


@pytest.fixture(autouse=True)
def _fresh_ring_and_no_commit_left():
    from ray_tpu.air import checkpoint

    steptrace.set_enabled(True)
    steptrace.reset()
    steptrace.clear_train_context()
    yield
    try:
        checkpoint.finish_commit()
    except Exception:
        pass
    steptrace.reset()
    steptrace.clear_train_context()


@pytest.fixture
def session():
    from ray_tpu.train import session as sess

    s = sess.init_session(sess.TrainContext(rank=0, world_size=1), None)
    yield s
    sess.shutdown_session()


class _Writer:
    """Stands before orbax's writer: each commit first takes the next gate
    and waits for it, then writes, or raises what the gate was made to
    fail with."""

    def __init__(self, monkeypatch):
        from orbax.checkpoint._src.checkpointers import async_checkpointer

        self.gates = queue.Queue()
        real = async_checkpointer._background_wait_for_commit_futures

        def gated(*args, **kwargs):
            gate, error = self.gates.get(timeout=WAIT_S)
            assert gate.wait(WAIT_S)
            if error is not None:
                raise error
            return real(*args, **kwargs)

        monkeypatch.setattr(async_checkpointer,
                            "_background_wait_for_commit_futures", gated)

    def gate(self, opened=False, error=None):
        gate = threading.Event()
        if opened:
            gate.set()
        self.gates.put((gate, error))
        return gate


def _tree(k):
    import jax.numpy as jnp

    return {"w": jnp.full((3, 5), float(k), jnp.float32),
            "b": jnp.arange(7, dtype=jnp.bfloat16) + k}


NBYTES = 3 * 5 * 4 + 7 * 2


def _whole(directory):
    return os.path.isdir(os.path.join(directory, "state_orbax"))


def _load(directory, k=0):
    from ray_tpu.air.checkpoint import load_pytree

    return load_pytree(str(directory), _tree(k), name="state")


def _same(a, b):
    return all(np.array_equal(np.asarray(a[n]), np.asarray(b[n])) for n in b)


def _save_and_report(directory, k):
    from ray_tpu.air.checkpoint import Checkpoint, save_pytree
    from ray_tpu.train import session as sess

    save_pytree(_tree(k), str(directory), name="state")
    sess.report({"k": k}, checkpoint=Checkpoint.from_directory(str(directory)))


def _spans(prefix=""):
    return [r for r in steptrace.snapshot()
            if r["kind"] == "phase" and r["phase"].startswith(prefix)]


# ---------------------------------------------------------------------------
# (a) the metrics go at once, the checkpoint when its files are whole
# ---------------------------------------------------------------------------

def test_checkpoint_follows_its_commit_in_save_order(tmp_path, monkeypatch,
                                                     session):
    from ray_tpu.train import session as sess

    writer = _Writer(monkeypatch)
    for k in range(3):
        gate = writer.gate()
        _save_and_report(tmp_path / f"save_{k}", k)
        # the loop is free, the files are not whole, nothing is handed over
        assert not _whole(tmp_path / f"save_{k}")
        sess.report({"k": k, "later": True})
        for later in (False, True):
            msg = session.queue.get_nowait()
            assert msg == {"type": "report", "metrics": (
                {"k": k, "later": True} if later else {"k": k})}
        assert session.queue.empty()
        gate.set()
        # save k+1 cannot start before this: the order is the saves'
        msg = session.queue.get(timeout=WAIT_S)
        assert msg == {"type": "checkpoint", "metrics": {"k": k},
                       "checkpoint_data": None,
                       "checkpoint_path": str(tmp_path / f"save_{k}")}
        assert _whole(tmp_path / f"save_{k}")
    for k in range(3):
        assert _same(_load(tmp_path / f"save_{k}"), _tree(k))


def test_other_checkpoints_take_the_path_they_took(tmp_path, monkeypatch,
                                                   session):
    """A dictionary, a directory nobody is writing, and a directory whose
    commit has ended ride with their report; a directory that holds the
    one being written is held back with it."""
    from ray_tpu.air.checkpoint import (Checkpoint, finish_commit,
                                        save_pytree)
    from ray_tpu.train import session as sess

    writer = _Writer(monkeypatch)
    gate = writer.gate()
    save_pytree(_tree(0), str(tmp_path / "run" / "save_0"), name="state")
    other = tmp_path / "other"
    other.mkdir()
    sess.report({"k": "dict"}, checkpoint=Checkpoint.from_dict({"step": 1}))
    sess.report({"k": "dir"}, checkpoint=Checkpoint.from_directory(str(other)))
    sess.report({"k": "parent"},
                checkpoint=Checkpoint.from_directory(str(tmp_path / "run")))
    msgs = [session.queue.get_nowait() for _ in range(3)]
    assert session.queue.empty()
    assert msgs[0]["checkpoint_data"] == {"step": 1}
    assert msgs[1]["checkpoint_path"] == str(other)
    assert "checkpoint_path" not in msgs[2]
    gate.set()
    late = session.queue.get(timeout=WAIT_S)
    assert (late["type"], late["checkpoint_path"]) == (
        "checkpoint", str(tmp_path / "run"))
    finish_commit()
    sess.report({"k": "ended"}, checkpoint=Checkpoint.from_directory(
        str(tmp_path / "run" / "save_0")))
    assert session.queue.get_nowait()["checkpoint_path"] == str(
        tmp_path / "run" / "save_0")


# ---------------------------------------------------------------------------
# (b) the session flushes: before done, before a drain report, on a stop
# ---------------------------------------------------------------------------

def _train_worker(loop):
    """The worker's own code, without the actor around it."""
    from ray_tpu.train.worker_group import TrainWorker

    worker = TrainWorker._cls()
    worker.setup_session(0, 1, 0, 0, "exp", "trial", "", None)
    worker.start_training(loop, {})
    return worker


def _messages_until_the_end(worker):
    out = []
    while not out or out[-1]["type"] not in ("done", "error"):
        out.append(worker._session.queue.get(timeout=WAIT_S))
    worker._thread.join(WAIT_S)
    assert not worker._thread.is_alive()
    return out


@pytest.mark.parametrize("how", ["return", "stop", "drain"])
def test_loop_ends_with_a_commit_in_flight(tmp_path, monkeypatch, how):
    from ray_tpu.train import session as sess

    writer = _Writer(monkeypatch)
    gate = writer.gate()
    at_exit = {}

    def loop():
        s = sess.get_session()
        if how == "stop":
            s.stop_requested.set()
        if how == "drain":
            threading.Timer(0.2, gate.set).start()
            sess.request_drain()
        try:
            _save_and_report(tmp_path / "save_0", 0)
        finally:
            at_exit["whole"] = _whole(tmp_path / "save_0")
            if how != "drain":
                threading.Timer(0.2, gate.set).start()

    try:
        msgs = _messages_until_the_end(_train_worker(loop))
    finally:
        sess.shutdown_session()
    assert all("error" not in m for m in msgs), msgs
    kinds = [m["type"] for m in msgs]
    if how == "drain":
        # the drain report waits for the files and carries the checkpoint:
        # the executor restores from it
        assert kinds == ["report", "done"] and at_exit["whole"]
        assert msgs[0]["drain"] is True
    else:
        assert kinds == ["report", "checkpoint", "done"]
        assert not at_exit["whole"] and "checkpoint_path" not in msgs[0]
    assert msgs[-2]["checkpoint_path"] == str(tmp_path / "save_0")
    assert msgs[-2]["metrics"] == {"k": 0}
    assert _same(_load(tmp_path / "save_0"), _tree(0))


def test_drain_report_comes_after_the_checkpoint_held_back(
        tmp_path, monkeypatch, session):
    from ray_tpu.train import session as sess

    writer = _Writer(monkeypatch)
    gate = writer.gate()
    _save_and_report(tmp_path / "save_0", 0)
    threading.Timer(0.2, gate.set).start()
    sess.request_drain()
    with pytest.raises(SystemExit):
        sess.report({"k": 1})
    kinds = []
    while not session.queue.empty():
        msg = session.queue.get_nowait()
        kinds.append((msg["type"], bool(msg.get("drain"))))
    assert kinds == [("report", False), ("checkpoint", False),
                     ("report", True)]


# ---------------------------------------------------------------------------
# (c) one commit in flight: the next save waits for it, and says so
# ---------------------------------------------------------------------------

def test_second_save_waits_for_the_first_and_records_who_did_what(
        tmp_path, monkeypatch, session):
    from ray_tpu.air.checkpoint import save_pytree

    by_thread = []
    record = steptrace.record_phase
    monkeypatch.setattr(
        steptrace, "record_phase",
        lambda name, *a, **kw: by_thread.append(
            (threading.current_thread().name, name)) or record(name, *a, **kw))
    writer = _Writer(monkeypatch)
    gate = writer.gate()
    writer.gate(opened=True)
    save_pytree(_tree(0), str(tmp_path / "save_0"), name="state")
    threading.Timer(0.3, gate.set).start()
    save_pytree(_tree(1), str(tmp_path / "save_1"), name="state")
    assert _whole(tmp_path / "save_0")

    first, second = _spans("ckpt/")[:3], _spans("ckpt/")[3:]
    for spans in (first, second):
        assert [r["phase"] for r in spans] == [
            "ckpt/setup", "ckpt/commit", "ckpt/snapshot"]
        assert spans[2]["n"] == NBYTES
    # the first save waited for nothing, the second for the first's bytes
    assert first[1]["n"] is None and second[1]["n"] == NBYTES
    written = _spans("save/commit")
    assert [r["n"] for r in written] == [NBYTES]
    # the second save's wait is the end of the first save's write
    assert second[1]["start"] <= written[0]["end"] <= second[1]["end"]
    assert written[0]["start"] >= first[2]["end"]

    from ray_tpu.air import checkpoint

    checkpoint.finish_commit()
    assert [r["n"] for r in _spans("save/commit")] == [NBYTES, NBYTES]
    main = threading.current_thread().name
    assert {(t, n) for t, n in by_thread if t != main} == {
        ("save-commit", "save/commit")}
    assert not [n for t, n in by_thread
                if t == main and not n.startswith("ckpt/")]


# ---------------------------------------------------------------------------
# (d) a commit that fails is the loop's error and nobody's checkpoint
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["save_pytree", "report", "flush"])
def test_failed_commit_raises_in_the_loop_once(tmp_path, monkeypatch, where):
    from ray_tpu.air import checkpoint
    from ray_tpu.train import session as sess

    writer = _Writer(monkeypatch)
    gate = writer.gate(error=OSError(28, "No space left on device"))
    writer.gate(opened=True)

    def loop():
        _save_and_report(tmp_path / "save_0", 0)
        gate.set()
        checkpoint._in_flight._thread.join(WAIT_S)
        if where == "save_pytree":
            checkpoint.save_pytree(_tree(1), str(tmp_path / "save_1"),
                                   name="state")
        elif where == "report":
            sess.report({"k": 1})

    try:
        msgs = _messages_until_the_end(_train_worker(loop))
    finally:
        sess.shutdown_session()
    assert [m["type"] for m in msgs] == ["report", "error"]
    assert "checkpoint_path" not in msgs[0]
    assert "No space left on device" in msgs[1]["error"]
    frame = {"save_pytree": "save_pytree", "report": "sess.report",
             "flush": "finish_commit()"}[where]
    assert frame in msgs[1]["traceback"]
    # no second attempt behind the loop's back, and the error is spent
    assert not os.path.exists(tmp_path / "save_0" / "state.msgpack")
    assert not _whole(tmp_path / "save_0")
    assert checkpoint._in_flight is None
    checkpoint.finish_commit()


# ---------------------------------------------------------------------------
# (e) outside a session nothing changed
# ---------------------------------------------------------------------------

def test_outside_a_session_the_files_are_whole_at_return(tmp_path,
                                                         monkeypatch):
    from ray_tpu.air import checkpoint

    writer = _Writer(monkeypatch)
    gate = writer.gate()
    threading.Timer(0.2, gate.set).start()
    checkpoint.save_pytree(_tree(4), str(tmp_path), name="state")
    assert _whole(tmp_path) and checkpoint._in_flight is None
    spans = _spans()
    assert [(r["phase"], r["n"]) for r in spans] == [
        ("ckpt/setup", None), ("ckpt/snapshot", NBYTES),
        ("ckpt/commit", NBYTES)]
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["start"]
    assert _same(_load(tmp_path), _tree(4))


def test_msgpack_fallback_stays_synchronous_inside_a_session(
        tmp_path, monkeypatch, session):
    from ray_tpu.air import checkpoint

    monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)
    checkpoint.save_pytree(_tree(2), str(tmp_path), name="state")
    assert (tmp_path / "state.msgpack").exists()
    assert checkpoint._in_flight is None
    assert [r["phase"] for r in _spans()] == [
        "ckpt/setup", "ckpt/snapshot", "ckpt/commit"]
    monkeypatch.undo()
    assert _same(_load(tmp_path), _tree(2))


# ---------------------------------------------------------------------------
# (f) end to end through JaxTrainer.fit()
# ---------------------------------------------------------------------------

def _slow_writer():
    """-> a function for a loop to call in its worker (nested, so that it
    travels by value): every commit there sleeps, or raises, before it
    writes."""

    def slow_writer(delay_s=0.0, error=None):
        import time

        from orbax.checkpoint._src.checkpointers import async_checkpointer

        real = async_checkpointer._background_wait_for_commit_futures

        def slowed(*args, **kwargs):
            if error is not None:
                raise error
            time.sleep(delay_s)
            return real(*args, **kwargs)

        async_checkpointer._background_wait_for_commit_futures = slowed

    return slow_writer


def test_fit_acknowledges_each_save_whole_and_as_of_its_own_step(
        ray_start_regular, tmp_path):
    from ray_tpu import train
    from ray_tpu.air.checkpoint import Checkpoint, load_pytree, save_pytree

    slow_writer = _slow_writer()
    saves_dir = str(tmp_path / "worker_saves")

    def loop(config):
        import jax
        import jax.numpy as jnp

        slow_writer(delay_s=0.4)
        step = jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
                       donate_argnums=0)
        state = {"w": jnp.zeros((64, 64), jnp.float32),
                 "count": jnp.zeros((), jnp.int32)}
        for i in range(6):
            state = step(state)
            train.report({"step": i})
            if i % 2 == 1:
                target = os.path.join(saves_dir, f"save_{i // 2}")
                save_pytree(state, target, name="state")
                whole = os.path.isdir(os.path.join(target, "state_orbax"))
                train.report({"step": i, "saved": i // 2,
                              "whole_at_return": whole},
                             checkpoint=Checkpoint.from_directory(target))
                # the buffers just saved are donated and overwritten while
                # the files are written
                state = step(step(state))
                state = jax.tree.map(lambda x: x - 2, state)
                jax.block_until_ready(state)
        train.report({"step": 6, "last": True})

    seen = []
    result = train.JaxTrainer(
        loop, jax_config=train.JaxConfig(env_vars=_CPU_ENV),
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_async_save",
                                   storage_path=str(tmp_path / "results")),
    )._fit_impl(result_callback=lambda m, ck: seen.append(m))
    assert result.error is None, result.error
    # the last report's metrics, not a late checkpoint's
    assert result.metrics == {"step": 6, "last": True}
    saved = [m for m in seen if "saved" in m]
    assert [m["saved"] for m in saved] == [0, 1, 2]
    assert not any(m["whole_at_return"] for m in saved)
    # a late checkpoint is nobody's result: one callback per report
    assert len(seen) == 6 + 3 + 1
    assert os.path.basename(result.checkpoint.path) == "checkpoint_000002"
    assert sorted(p for p in os.listdir(result.path)
                  if p.startswith("checkpoint_")) == [
        "checkpoint_000000", "checkpoint_000001", "checkpoint_000002"]
    target = {"w": np.zeros((64, 64), np.float32),
              "count": np.zeros((), np.int32)}
    for k in range(3):
        back = load_pytree(os.path.join(result.path, f"checkpoint_{k:06d}"),
                           target, name="state")
        assert int(back["count"]) == 2 * k + 2
        assert np.array_equal(np.asarray(back["w"]),
                              np.full((64, 64), 2 * k + 2, np.float32))


def test_fit_fails_with_the_error_of_a_commit_that_failed(ray_start_regular,
                                                          tmp_path):
    from ray_tpu import train
    from ray_tpu.air.checkpoint import Checkpoint, save_pytree

    slow_writer = _slow_writer()
    saves_dir = str(tmp_path / "worker_saves")

    def loop(config):
        import jax.numpy as jnp

        slow_writer(error=OSError(28, "No space left on device"))
        target = os.path.join(saves_dir, "save_0")
        save_pytree({"w": jnp.ones(8)}, target, name="state")
        train.report({"step": 0},
                     checkpoint=Checkpoint.from_directory(target))

    result = train.JaxTrainer(
        loop, jax_config=train.JaxConfig(env_vars=_CPU_ENV),
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_async_save_fails",
                                   storage_path=str(tmp_path / "results")),
    )._fit_impl()
    assert result.error is not None
    assert "No space left on device" in str(result.error)
    assert result.checkpoint is None
    assert not [p for p in os.listdir(result.path)
                if p.startswith("checkpoint_")]


# ---------------------------------------------------------------------------
# kill -9 between snapshot and commit
# ---------------------------------------------------------------------------

_KILLED_WORKER = """
import json, os, sys, threading
import jax.numpy as jnp
from orbax.checkpoint._src.checkpointers import async_checkpointer
from ray_tpu.air.checkpoint import Checkpoint, finish_commit, save_pytree
from ray_tpu.train import session as sess

root = sys.argv[1]
s = sess.init_session(sess.TrainContext(rank=0, world_size=1), None)

def save(k):
    target = os.path.join(root, f"save_{k}")
    save_pytree({"w": jnp.full((3, 5), float(k), jnp.float32)}, target,
                name="state")
    sess.report({"k": k}, checkpoint=Checkpoint.from_directory(target))

save(0)
finish_commit()
# from here on the writer never gets to write
async_checkpointer._background_wait_for_commit_futures = (
    lambda *a, **kw: threading.Event().wait())
save(1)
while not s.queue.empty():
    print(json.dumps(s.queue.get()), flush=True)
print("SNAPSHOT_TAKEN", flush=True)
threading.Event().wait()
"""


def test_kill_during_a_commit_leaves_the_save_before_it_whole(tmp_path):
    from ray_tpu.air.checkpoint import load_pytree
    from ray_tpu.air.config import CheckpointConfig
    from ray_tpu.train.backend_executor import _CheckpointBook

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILLED_WORKER, str(tmp_path / "saves")],
        env=env, stdout=subprocess.PIPE, text=True)
    # the driver's part, played here: persist what the queue hands over
    book = _CheckpointBook(str(tmp_path / "trial"), CheckpointConfig())
    os.makedirs(book.trial_dir)
    try:
        msgs = []
        for line in proc.stdout:
            if line.strip() == "SNAPSHOT_TAKEN":
                break
            msgs.append(json.loads(line))
        else:
            pytest.fail(f"the worker ended by itself: {proc.wait()}")
        os.kill(proc.pid, signal.SIGKILL)
        assert proc.wait(WAIT_S) == -signal.SIGKILL
    finally:
        proc.kill()
    for msg in msgs:
        if msg.get("checkpoint_path"):
            book.persist(None, msg["checkpoint_path"], msg["metrics"])
    assert [(m["type"], m["metrics"]["k"], "checkpoint_path" in m)
            for m in msgs] == [("report", 0, False), ("checkpoint", 0, True),
                               ("report", 1, False)]
    target = {"w": np.zeros((3, 5), np.float32)}
    latest = book.latest()
    assert os.path.basename(latest.path) == "checkpoint_000000"
    for whole in (latest.path, str(tmp_path / "saves" / "save_0")):
        back = load_pytree(whole, target, name="state")
        assert np.array_equal(np.asarray(back["w"]), np.zeros((3, 5)))
    # the killed save: nothing under the name a whole one has
    killed = tmp_path / "saves" / "save_1"
    assert not _whole(killed)
    assert all("tmp" in name for name in os.listdir(killed))
    with pytest.raises(FileNotFoundError):
        load_pytree(str(killed), target, name="state")
