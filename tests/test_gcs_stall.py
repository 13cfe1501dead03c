"""A stall of the whole host is not a node's death, and a process that
holds its interpreter for a while is not a dead peer.

A worker that opens the four chips of a v5e host stalls every process of
the machine for 7-14 s (PERF.md section 7): the raylet sends no heartbeat
and the GCS records none, and at the default ``node_death_timeout_s`` of
10 s the GCS used to wake, find the heartbeat 12 s old and declare its one
node dead, which kills the trainer's actors. The GCS now credits the time
it did not run itself to every node (``GcsServer._credit_own_stall``).

A trainer that writes GPT-2 XL's compiled step to the compile cache holds
its GIL for 23 s inside XLA's ``executable.serialize()`` and answers no
keepalive ping meanwhile; at ``rpc_keepalive_timeout_s`` 20 its raylet, the
GCS and the trainer itself each declared the other dead and the job was lost
(my chip run, PR 26). The default is now 120 s."""

import os
import signal
import time

import ray_tpu
from ray_tpu._private import gcs as gcs_mod
from ray_tpu._private.common import NodeInfo
from ray_tpu._private.config import GLOBAL_CONFIG as cfg

RAY_REUSE_CLUSTER = False  # this module stops the cluster's processes


def _gcs_with_node(age_s: float):
    server = gcs_mod.GcsServer.__new__(gcs_mod.GcsServer)
    node = NodeInfo.__new__(NodeInfo)
    node.last_heartbeat = time.monotonic() - age_s
    server.nodes = {"n": node}
    return server, node


def test_time_the_gcs_did_not_run_counts_against_no_node():
    server, node = _gcs_with_node(age_s=12.0)
    before = node.last_heartbeat
    server._credit_own_stall(11.5)
    assert node.last_heartbeat == before + 11.5


def test_an_ordinary_late_tick_credits_nothing():
    """Jitter of the loop's own sleep, up to one heartbeat interval, is not
    a stall: a node that is silent while the GCS runs must still time
    out."""
    server, node = _gcs_with_node(age_s=12.0)
    before = node.last_heartbeat
    server._credit_own_stall(cfg.heartbeat_interval_s)
    server._credit_own_stall(-0.01)
    assert node.last_heartbeat == before


def test_a_host_stall_past_the_death_timeout_loses_no_actor():
    """GCS and raylet stopped together for longer than
    ``node_death_timeout_s`` at default settings, as at device open on the
    four-chip host: afterwards the actor that was alive before still
    answers with its state, and new work runs."""
    assert cfg.node_death_timeout_s == 10.0  # the default is what is tested
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        counter = Counter.remote()
        assert ray_tpu.get(counter.bump.remote(), timeout=30) == 1
        node = ray_tpu._private.worker.global_worker.node
        pids = [node.gcs_proc.pid, node.raylet_proc.pid]
        for pid in pids:
            os.kill(pid, signal.SIGSTOP)
        try:
            time.sleep(cfg.node_death_timeout_s + 1.5)
        finally:
            for pid in pids:
                os.kill(pid, signal.SIGCONT)
        time.sleep(3 * cfg.heartbeat_interval_s)  # the health loop has run
        assert ray_tpu.get(counter.bump.remote(), timeout=30) == 2
        assert all(n["alive"] for n in ray_tpu.nodes())
        with open(os.path.join(node.logs, "gcs.out"), errors="replace") as f:
            log = f.read()
        assert "marked dead" not in log
        assert "credited to every node's heartbeat" in log
    finally:
        ray_tpu.shutdown()


def test_a_worker_silent_past_the_old_keepalive_timeout_is_not_dead():
    """The actor's process stopped for 22 s (XLA's serialize held the
    trainer's GIL for 23 on the chip's host), at default settings:
    afterwards it answers with its state."""
    assert cfg.rpc_keepalive_timeout_s >= 60.0
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n, os.getpid()

        counter = Counter.remote()
        _, pid = ray_tpu.get(counter.bump.remote(), timeout=30)
        os.kill(pid, signal.SIGSTOP)
        try:
            time.sleep(22.0)
        finally:
            os.kill(pid, signal.SIGCONT)
        assert ray_tpu.get(counter.bump.remote(), timeout=30) == (2, pid)
    finally:
        ray_tpu.shutdown()
