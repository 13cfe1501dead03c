"""A worker's end: signalled in one place, gone only when reaped.

``ray_tpu.shutdown()`` returns with every process of the session reaped,
a killed actor's resources come back when its process is gone and not when
the signal is sent, and a TPU gang's worker waits for the host's chips
before it opens them (on a v5e 2x2 host the kernel takes up to 16 s to
release a dead worker's chips; PERF.md section 7).
"""

import errno
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.node import ProcessEnd
from ray_tpu.train import backend
from tests.conftest import wait_for_condition

# every test here ends its cluster itself
RAY_REUSE_CLUSTER = False

LINGER_S = 3.0


def _exists(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    return True


@ray_tpu.remote(num_cpus=0)
class Lingerer:
    """An actor whose process is slow to die, as a worker that held chips
    is: on SIGTERM it loses its connection to the raylet at once and its
    pid ``linger`` seconds later."""

    def linger_on_sigterm(self, linger: float, closed_marker: str = "") -> int:
        import __main__ as worker_main  # the worker's entry module

        from ray_tpu._private.worker import global_worker

        cw = global_worker.core_worker

        def flush(_cw):
            cw.raylet.on_close = None
            cw.io.run(cw.raylet.close())
            if closed_marker:
                open(closed_marker, "w").close()
            time.sleep(linger)

        worker_main._flush_observability = flush
        return os.getpid()


@pytest.fixture
def session():
    ray_tpu.init(num_cpus=2, resources={"X": 1})
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# (a) shutdown returns with the process reaped
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("killed_before", [True, False],
                         ids=["killed_long_before", "live_at_shutdown"])
def test_shutdown_returns_with_slow_worker_reaped(session, tmp_path,
                                                  killed_before):
    closed = str(tmp_path / "connection_closed")
    a = Lingerer.remote()
    pid = ray_tpu.get(a.linger_on_sigterm.remote(LINGER_S, closed), timeout=30)
    if killed_before:
        # the forgotten-connection path: the raylet has lost the worker's
        # connection, and with it (at the parent) the pid, before shutdown
        ray_tpu.kill(a)
        wait_for_condition(lambda: os.path.exists(closed), timeout=20)
        time.sleep(0.5)  # the raylet reads the end of the stream
        assert _exists(pid)
    start = time.monotonic()
    ray_tpu.shutdown()
    took = time.monotonic() - start
    assert not _exists(pid), f"shutdown returned after {took:.2f} s, pid alive"
    assert took < LINGER_S + 10


def _report_pid(config):
    from ray_tpu import train

    train.report({"pid": os.getpid()})


def test_gang_worker_exits_when_fit_returns(session, tmp_path):
    """A gang killed after its loop ended has no step boundary to drain to:
    it exits at the signal, not train_drain_grace_s (30 s) later."""
    from ray_tpu import train

    result = train.DataParallelTrainer(
        _report_pid, scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_end", storage_path=str(tmp_path)),
    ).fit()
    assert result.error is None
    pid = result.metrics["pid"]
    wait_for_condition(lambda: not _exists(pid), timeout=10)


# ---------------------------------------------------------------------------
# (b) a dead actor's resources come back when its process is reaped
# ---------------------------------------------------------------------------


def _in_bundle(cls):
    from ray_tpu.util.placement_group import placement_group
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy)

    pg = placement_group([{"X": 1}])
    assert pg.wait(30)
    return pg, cls.options(
        resources={"X": 1},
        scheduling_strategy=PlacementGroupSchedulingStrategy(
            pg, placement_group_bundle_index=0))


@pytest.mark.parametrize("how", ["killed", "killed_in_bundle", "crashed"])
def test_actor_resources_return_when_reaped(session, how):
    from ray_tpu.util.placement_group import remove_placement_group

    pg = None
    if how == "killed_in_bundle":
        pg, cls = _in_bundle(Lingerer)
    else:
        cls = Lingerer.options(resources={"X": 1})
    a = cls.remote()
    pid = ray_tpu.get(a.linger_on_sigterm.remote(LINGER_S), timeout=30)
    if how == "crashed":
        os.kill(pid, signal.SIGKILL)
    else:
        ray_tpu.kill(a)
    if pg is not None:
        remove_placement_group(pg)
        _, cls = _in_bundle(Lingerer)
    # the node has one X: the successor is placed only once X came back
    b = cls.remote()
    pid_b = ray_tpu.get(b.linger_on_sigterm.remote(0), timeout=30)
    assert pid_b != pid
    assert not _exists(pid), "X was handed on while its holder still lived"


# ---------------------------------------------------------------------------
# the primitive under both: SIGTERM, SIGKILL after the grace, gone when reaped
# ---------------------------------------------------------------------------

_DEAF = ("import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN);"
         " print('ready', flush=True); time.sleep(120)")


def _deaf_child() -> subprocess.Popen:
    proc = subprocess.Popen([sys.executable, "-c", _DEAF],
                            stdout=subprocess.PIPE)
    assert proc.stdout.readline().strip() == b"ready"
    return proc


@pytest.mark.parametrize("case", ["escalates", "forced", "overdue"])
def test_process_end(case):
    proc = _deaf_child()
    try:
        if case == "escalates":
            end = ProcessEnd(proc, grace=0.5)
            assert not end.gone()  # SIGTERM is ignored, the grace not over
            assert end.wait()
            assert 0.5 <= end.age < 10
            assert proc.returncode == -signal.SIGKILL
        elif case == "forced":
            end = ProcessEnd(proc, force=True)
            assert end.wait()
            assert end.age < 10
            assert proc.returncode == -signal.SIGKILL
        else:
            end = ProcessEnd(proc, grace=0.2, kill=lambda: None)
            assert not end.wait()  # never reaped: the caller logs and goes on
            assert end.overdue and proc.poll() is None
    finally:
        proc.kill()
        proc.wait(timeout=10)
    assert not _exists(proc.pid)


# ---------------------------------------------------------------------------
# (c) a TPU gang waits for its chips before it opens them
# ---------------------------------------------------------------------------


@pytest.fixture
def fast_wait(monkeypatch):
    monkeypatch.setattr(backend, "_CHIPS_POLL_S", 0.01)
    monkeypatch.setattr(backend, "CHIPS_FREE_DEADLINE_S", 0.3)


@pytest.mark.parametrize("busy_polls", [0, 3])
def test_gang_waits_until_chips_are_free(fast_wait, monkeypatch, capsys,
                                         busy_polls):
    polls = []

    def probe():
        polls.append(1)
        return "/dev/vfio/3" if len(polls) <= busy_polls else None

    monkeypatch.setattr(backend, "_busy_chip_node", probe)
    waited = backend._wait_for_chips()
    assert len(polls) == busy_polls + 1
    assert (waited >= 0.01 * busy_polls) and waited < 0.3
    assert f"waited {waited:.1f} s for this host's chips" in capsys.readouterr().out


def test_gang_gives_up_with_the_nodes_name(fast_wait, monkeypatch):
    monkeypatch.setattr(backend, "_busy_chip_node", lambda: "/dev/vfio/1")
    monkeypatch.setattr(backend, "_chip_holders", lambda node: [4242])
    start = time.monotonic()
    with pytest.raises(RuntimeError, match=r"/dev/vfio/1 .* by pid 4242"):
        backend._wait_for_chips()
    assert 0.3 <= time.monotonic() - start < 5


@pytest.mark.parametrize("use_tpu", [False, True])
def test_only_a_tpu_gang_waits(monkeypatch, use_tpu):
    calls = []
    monkeypatch.setattr(backend, "_wait_for_chips", lambda: calls.append(1))
    assert backend._jax_worker_setup(None, 1, 0, {}, use_tpu)
    assert len(calls) == int(use_tpu)


@pytest.mark.parametrize("held", [None, "b"])
def test_probe_opens_each_node_and_names_the_busy_one(monkeypatch, tmp_path,
                                                      held):
    from ray_tpu._private import resource_spec

    nodes = [str(tmp_path / n) for n in "abc"]
    for n in nodes:
        open(n, "w").close()
    monkeypatch.setattr(resource_spec, "tpu_device_nodes", lambda: nodes)
    real_open = os.open
    opened = []

    def fake_open(path, flags, *a):
        opened.append(path)
        if held and path.endswith(held):
            raise OSError(errno.EBUSY, "Device or resource busy", path)
        return real_open(path, flags, *a)

    monkeypatch.setattr(os, "open", fake_open)
    busy = backend._busy_chip_node()
    if held:
        assert busy == nodes[1] and opened == nodes[:2]
    else:
        assert busy is None and opened == nodes


def test_chip_holders_reads_proc_and_opens_nothing(tmp_path):
    node = str(tmp_path / "node")
    open(node, "w").close()
    assert backend._chip_holders(node) == []
    with open(node):
        assert backend._chip_holders(node) == [os.getpid()]
