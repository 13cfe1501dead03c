"""Object-plane robustness: spilling, restore, pull admission, OOM defense.

Reference analogs: ray python/ray/tests/test_object_spilling.py,
test_out_of_memory_killer — spill under store pressure instead of erroring
(local_object_manager.h:40), restore on access, kill workers under host
memory pressure (memory_monitor.h:52).
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import LocalObjectStore


def _mk_store(tmp_path, capacity):
    store_dir = str(tmp_path / "store")
    spill_dir = str(tmp_path / "spill")
    return LocalObjectStore(store_dir, capacity, spill_dir)


def test_store_spills_pinned_objects_past_capacity(tmp_path):
    """Filling the store to 2x capacity with PINNED objects spills instead
    of raising; spilled objects remain addressable and restore on get."""
    store = _mk_store(tmp_path, capacity=1 << 20)
    payload = b"x" * (300 * 1024)
    oids = []
    for _ in range(8):  # ~2.4MB total vs 1MB capacity
        oid = ObjectID.from_random()
        store.put(oid, b"", [payload], len(payload))
        store.pin(oid)
        oids.append(oid)
    assert store.used_bytes() <= (1 << 20)
    stats = store.spilled_stats()
    assert stats["spilled_bytes_total"] > 0
    # every object is still addressable; get() restores spilled ones
    for oid in oids:
        assert store.contains(oid)
        buf = store.get(oid)
        assert buf is not None
        assert bytes(buf.data) == payload
        buf.release()


def test_store_delete_removes_spilled_file(tmp_path):
    store = _mk_store(tmp_path, capacity=256 * 1024)
    payload = b"y" * (200 * 1024)
    a, b = ObjectID.from_random(), ObjectID.from_random()
    store.put(a, b"", [payload], len(payload))
    store.pin(a)
    store.put(b, b"", [payload], len(payload))  # spills a
    assert store.contains(a)
    store.delete(a)
    assert not store.contains(a)
    spill_files = os.listdir(str(tmp_path / "spill"))
    assert spill_files == []


def test_pull_gate_priority_order():
    """Get-priority pulls are admitted before task-arg pulls when slots
    free up (ray: pull_manager.h:31 BundlePriority)."""
    import asyncio

    from ray_tpu._private.raylet import (
        PULL_PRIO_GET,
        PULL_PRIO_TASK_ARGS,
        _PullGate,
    )

    async def run():
        gate = _PullGate(max_concurrent=1, byte_budget=1 << 20)
        order = []
        await gate.acquire(PULL_PRIO_GET)  # occupy the only slot

        async def worker(tag, prio):
            await gate.acquire(prio)
            order.append(tag)
            gate.release_slot()

        # Queue a low-priority waiter first, then a high-priority one.
        t1 = asyncio.create_task(worker("args", PULL_PRIO_TASK_ARGS))
        await asyncio.sleep(0.05)
        t2 = asyncio.create_task(worker("get", PULL_PRIO_GET))
        await asyncio.sleep(0.05)
        gate.release_slot()
        await asyncio.gather(t1, t2)
        return order

    order = asyncio.run(run())
    assert order == ["get", "args"]


def test_big_object_roundtrip_through_cluster(ray_start_cluster):
    """A large object transfers between nodes in chunks and survives store
    pressure on the receiving side."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    cluster.add_node(num_cpus=1, resources={"there": 1.0})
    cluster.wait_for_nodes()
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote(resources={"there": 0.5})
    def far_sum(arr):
        return float(arr.sum())

    arr = np.ones(6_000_000, dtype=np.float32)  # ~24MB: multiple 8MB chunks
    ref = ray_tpu.put(arr)
    assert ray_tpu.get(far_sum.remote(ref), timeout=120) == 6_000_000.0


def test_memory_monitor_kills_worker(ray_start_cluster, tmp_path, monkeypatch):
    """Driving the (test-injected) memory usage over threshold kills the
    busiest retriable worker; the task errors with an OOM message after
    retries exhaust."""
    gauge = tmp_path / "memusage"
    gauge.write_text("0.0")
    monkeypatch.setenv("RAY_TPU_memory_monitor_test_path", str(gauge))
    monkeypatch.setenv("RAY_TPU_memory_monitor_refresh_ms", "100")
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)

    @ray_tpu.remote(max_retries=0)
    def hog():
        import time as _t

        _t.sleep(30)
        return 1

    ref = hog.remote()
    time.sleep(1.0)  # let it dispatch
    gauge.write_text("0.99")
    with pytest.raises(Exception, match="memory"):
        ray_tpu.get(ref, timeout=60)
