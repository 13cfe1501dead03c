"""The native (C++) one-file object writer and reader, and the log store:
round-trips and Python interop, exercised through the ctypes boundary;
and the node's store (object_store.LocalObjectStore) held to what the
native store's tests held: pinning against eviction, an arena-resident put.
"""

import os

import numpy as np
import pytest

from ray_tpu._private import native_store, object_store
from ray_tpu._private.ids import ObjectID

pytestmark = pytest.mark.skipif(
    not native_store.available(), reason="native store not built"
)


def _oid(i: int) -> ObjectID:
    return ObjectID(bytes([i]) * ObjectID.SIZE)


def test_native_write_python_read(tmp_path):
    d = str(tmp_path)
    payload = np.arange(1000, dtype=np.int64)
    native_store.write_object(
        d, _oid(1).hex(), b"meta", [payload.tobytes()], payload.nbytes
    )
    buf = object_store.read_object(d, _oid(1))
    assert buf is not None
    assert buf.metadata == b"meta"
    assert np.frombuffer(buf.data, np.int64).tolist() == payload.tolist()
    buf.release()


def test_python_write_native_read(tmp_path):
    d = str(tmp_path)
    object_store.write_object(d, _oid(2), b"m2", [b"hello", b"world"], 10)
    out = native_store.open_object(d, _oid(2).hex())
    assert out is not None
    handle, metadata, data = out
    assert metadata == b"m2"
    assert bytes(data) == b"helloworld"
    del data
    native_store.release(handle)
    assert native_store.object_exists(d, _oid(2).hex())


def test_store_eviction_and_pinning(tmp_path):
    """No spill target: a pinned object survives eviction; all pinned and
    full raises ObjectStoreFullError."""
    capacity = 1 << 20
    store = object_store.LocalObjectStore(str(tmp_path), capacity)
    blob = b"x" * (300 * 1024)
    for i in range(3):
        store.put(_oid(i + 1), b"", [blob], len(blob))
    assert store.used_bytes() <= capacity
    store.pin(_oid(3))
    # two more puts force eviction of the oldest unpinned objects
    store.put(_oid(4), b"", [blob], len(blob))
    store.put(_oid(5), b"", [blob], len(blob))
    assert store.contains(_oid(3))  # pinned survived
    assert not store.contains(_oid(1)) and not store.contains(_oid(2))
    assert store.used_bytes() <= capacity
    buf = store.get(_oid(3))
    assert bytes(buf.data) == blob
    buf.release()
    ids = {o.hex() for o in store.object_ids()}
    assert _oid(3).hex() in ids

    # everything pinned and full -> ObjectStoreFullError
    for oid in store.object_ids():
        store.pin(oid)
    with pytest.raises(object_store.ObjectStoreFullError):
        store.put(_oid(9), b"", [b"y" * (900 * 1024)], 900 * 1024)


def test_native_store_zero_copy_writable_buffer(tmp_path):
    d = str(tmp_path)
    arr = np.arange(256, dtype=np.uint8)
    native_store.write_object(d, _oid(7).hex(), b"", [memoryview(arr)],
                              arr.nbytes)
    buf = object_store.read_object(d, _oid(7))
    assert bytes(buf.data) == arr.tobytes()
    buf.release()


def test_cluster_put_is_arena_resident(tmp_path):
    """End-to-end: a driver's put past the inline threshold lands in the
    slab arena (no one-file object), and get reads it back from there."""
    import ray_tpu
    from ray_tpu._private import slab_arena
    from ray_tpu._private.worker import global_worker

    ray_tpu.init(num_cpus=2)
    try:
        big = np.random.default_rng(0).standard_normal(100_000)
        ref = ray_tpu.put(big)
        store_dir = global_worker.core_worker.store_dir
        assert slab_arena.exists(store_dir, ref.binary())
        assert not os.path.exists(
            object_store._obj_path(store_dir, ObjectID(ref.binary())))
        out = ray_tpu.get(ref, timeout=30)
        np.testing.assert_array_equal(out, big)
    finally:
        ray_tpu.shutdown()


def test_native_log_store_roundtrip(tmp_path):
    """C++ append-log KV store: put/tombstone/replay/compaction across
    reopen (the GCS persistence backend; src/log_store.cpp)."""
    import pytest

    from ray_tpu._private import native_store
    from ray_tpu._private.gcs_store import NativeLogStore

    if not native_store.available():
        pytest.skip("native library unavailable")
    path = str(tmp_path / "gcs.log")
    s = NativeLogStore(path)
    for i in range(100):
        s.put("kv", ("ns", f"k{i}".encode()), f"v{i}".encode())
    for i in range(0, 100, 2):
        s.put("kv", ("ns", f"k{i}".encode()), None)  # delete evens
    s.put("actor", b"aid", {"state": "ALIVE"})
    s.close()

    s2 = NativeLogStore(path)
    tables = s2.load()
    assert len(tables["kv"]) == 50
    assert tables["kv"][("ns", b"k1")] == b"v1"
    assert ("ns", b"k0") not in tables["kv"]
    assert tables["actor"][b"aid"]["state"] == "ALIVE"
    s2.close()

    # torn tail: truncate mid-record; replay keeps the intact prefix
    import os

    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 3)
    s3 = NativeLogStore(path)
    tables = s3.load()
    assert len(tables.get("kv", {})) in (49, 50)
    s3.close()
