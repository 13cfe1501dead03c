"""Wire-frame codec: out-of-band buffer table round-trips, the CRC over
the head, size enforcement (both directions), truncation rejection, the
handshake (one preamble, one ack, no downgrade), and the zero-copy send
guarantee (payload buffers reach the transport by reference, never
through the pickle stream).

Pure rpcio/serialization unit tests — no cluster.
"""

import asyncio
import hashlib

import numpy as np
import pytest

from ray_tpu._private import rpcio, serialization
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.rpcio import (
    KIND_NOTIFY,
    KIND_REQ,
    Connection,
    Finalized,
    RpcError,
    RpcServer,
    _decode_frame,
    connect,
)


class FakeWriter:
    """Captures every part handed to the transport, by reference."""

    def __init__(self):
        self.writes = []
        self.closed = False

    def write(self, data):
        self.writes.append(data)

    async def drain(self):
        pass

    def close(self):
        self.closed = True


def _conn():
    return Connection(None, FakeWriter(), name="test")


def _roundtrip(payload):
    """Encode one frame, then decode it the way the recv loop would."""
    conn = _conn()
    parts = conn._encode_frame(7, KIND_REQ, "m", payload)
    wire = b"".join(bytes(p) for p in parts)
    total = int.from_bytes(wire[:4], "little")
    body = wire[4: 4 + total]
    assert len(body) == total, "frame length header must cover the body"
    return _decode_frame(body)


# ---------------------------------------------------------------- codec --


def test_roundtrip_no_buffers():
    msg_id, kind, method, payload = _roundtrip({"a": 1, "b": "x"})
    assert (msg_id, kind, method) == (7, KIND_REQ, "m")
    assert payload == {"a": 1, "b": "x"}


@pytest.mark.parametrize("nbufs", [1, 2, 7, 32])
def test_roundtrip_buffer_counts(nbufs):
    arrs = [np.arange(i + 1, dtype=np.int64).repeat(200) for i in range(nbufs)]
    _, _, _, payload = _roundtrip({"arrs": arrs})
    assert len(payload["arrs"]) == nbufs
    for got, want in zip(payload["arrs"], arrs):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("size", [0, 1, 511, 512, 513, 1 << 20])
def test_roundtrip_buffer_sizes(size):
    arr = np.full(size, 7, dtype=np.uint8)
    _, _, _, payload = _roundtrip({"arr": arr, "tag": "t"})
    assert payload["tag"] == "t"
    assert np.array_equal(payload["arr"], arr)


def test_roundtrip_fuzz_mixed():
    rng = np.random.RandomState(0)
    for trial in range(25):
        n = int(rng.randint(0, 6))
        sizes = [int(rng.randint(0, 5000)) for _ in range(n)]
        value = {
            "bufs": [np.arange(s, dtype=np.uint8) for s in sizes],
            "blob": bytes(rng.bytes(int(rng.randint(0, 2000)))),
            "n": trial,
        }
        _, _, _, got = _roundtrip(value)
        assert got["n"] == trial
        assert got["blob"] == value["blob"]
        assert len(got["bufs"]) == n
        for g, w in zip(got["bufs"], value["bufs"]):
            assert np.array_equal(g, w)


@pytest.mark.parametrize("nbufs", [0, 1, 3, 32])
def test_crc_roundtrip(nbufs):
    arrs = [np.arange(1000 * (i + 1), dtype=np.int32) for i in range(nbufs)]
    msg_id, kind, method, payload = _roundtrip({"arrs": arrs, "tag": "t"})
    assert (msg_id, kind, method) == (7, KIND_REQ, "m")
    assert payload["tag"] == "t"
    for got, want in zip(payload["arrs"], arrs):
        assert np.array_equal(got, want)


def test_crc_detects_head_corruption():
    """Any flipped byte in the CRC-covered head (count byte, table,
    envelope) must raise the typed corruption error."""
    parts = _conn()._encode_frame(1, KIND_NOTIFY, "m",
                                  {"arr": np.zeros(4096, dtype=np.uint8)})
    wire = b"".join(bytes(p) for p in parts)
    body = bytearray(wire[4:])
    head_len = len(bytes(parts[0])) - 4  # head part minus the 4B length
    for off in (0, 5, head_len - 5, head_len - 1):
        mutated = bytearray(body)
        mutated[off] ^= 0x01
        with pytest.raises(rpcio.FrameCorruptError):
            _decode_frame(bytes(mutated))
    # untouched body still decodes
    _, _, _, payload = _decode_frame(bytes(body))
    assert payload["arr"].nbytes == 4096


def test_frame_exactly_at_max_message_passes():
    GLOBAL_CONFIG.update({"rpc_max_message_bytes": 1 << 20})
    try:
        conn = _conn()
        # binary-search a buffer size whose frame lands exactly on the cap
        lo, hi = 0, 1 << 20
        while lo < hi:
            mid = (lo + hi + 1) // 2
            try:
                conn._encode_frame(1, KIND_REQ, "m",
                                   {"a": np.zeros(mid, dtype=np.uint8)})
                lo = mid
            except RpcError:
                hi = mid - 1
        parts = conn._encode_frame(1, KIND_REQ, "m",
                                   {"a": np.zeros(lo, dtype=np.uint8)})
        wire = b"".join(bytes(p) for p in parts)
        assert int.from_bytes(wire[:4], "little") == (1 << 20)
        _, _, _, payload = _decode_frame(wire[4:])
        assert payload["a"].nbytes == lo
    finally:
        GLOBAL_CONFIG.reset()


# ----------------------------------------------------- size enforcement --


def test_send_side_oversize_raises_with_method_and_size():
    GLOBAL_CONFIG.update({"rpc_max_message_bytes": 10_000})
    try:
        conn = _conn()
        with pytest.raises(RpcError) as ei:
            conn._encode_frame(1, KIND_REQ, "push_chunks",
                               {"data": np.zeros(50_000, dtype=np.uint8)})
        msg = str(ei.value)
        assert "push_chunks" in msg and "10000" in msg
        assert not conn.writer.writes, "nothing may reach the wire"
    finally:
        GLOBAL_CONFIG.reset()


def test_request_nowait_oversize_leaves_no_pending_entry():
    async def main():
        GLOBAL_CONFIG.update({"rpc_max_message_bytes": 10_000})
        try:
            conn = _conn()
            with pytest.raises(RpcError):
                conn.request_nowait(
                    "m", {"data": np.zeros(50_000, dtype=np.uint8)})
            assert not conn._pending
            assert not conn.writer.writes
        finally:
            GLOBAL_CONFIG.reset()

    asyncio.run(main())


# ---------------------------------------------------------- truncation --


def _body(payload):
    parts = _conn()._encode_frame(1, KIND_NOTIFY, "m", payload)
    return b"".join(bytes(p) for p in parts)[4:]


def test_truncated_buffer_table_rejected():
    body = _body({"arr": np.zeros(4096, dtype=np.uint8)})
    # claim 200 table entries in a 5-byte body
    with pytest.raises(RpcError):
        _decode_frame(bytes([200]) + body[1:5])


def test_buffers_exceeding_frame_rejected():
    body = bytearray(_body({"arr": np.zeros(4096, dtype=np.uint8)}))
    assert body[0] == 1
    # inflate the recorded buffer length past the frame end
    body[1:5] = (1 << 30).to_bytes(4, "little")
    with pytest.raises(RpcError):
        _decode_frame(bytes(body))


def test_empty_body_rejected():
    with pytest.raises(RpcError):
        _decode_frame(b"")


# ------------------------------------------------------------ handshake --


class EchoHandler:
    def rpc_echo(self, conn, p):
        return p

    def rpc_finalized(self, conn, p):
        self.released = False

        def _rel():
            self.released = True

        return Finalized({"ok": True}, _rel)


def test_handshake_and_echo():
    async def main():
        handler = EchoHandler()
        srv = RpcServer(handler)
        port = await srv.start()
        conn = await connect("127.0.0.1", port, name="c", retries=3)
        try:
            # the ack is read before connect() returns: the server has
            # accepted this connection by now
            (sconn,) = srv.connections
            assert not sconn.closed
            arr = np.arange(65536, dtype=np.uint8)
            reply = await conn.request("echo", {"arr": arr})
            assert np.array_equal(reply["arr"], arr)
            reply = await conn.request("finalized", {})
            assert reply == {"ok": True}
            # release ran after the response frame was handed off
            for _ in range(10):
                if getattr(handler, "released", False):
                    break
                await asyncio.sleep(0.01)
            assert handler.released
        finally:
            await conn.close()
            await srv.stop()

    asyncio.run(main())


class _CountingServer(RpcServer):
    """The real server, counting the dials it was offered."""

    dials = 0

    async def _accept(self, reader, writer):
        self.dials += 1
        await super()._accept(reader, writer)


@pytest.mark.parametrize("magic", [b"RTPU1", b"RTPU2"])
def test_stale_preamble_is_refused(magic):
    """A process of an older checkout opens with an older magic (and this
    cluster's token): the server closes at the preamble, every time,
    without an ack byte and without a connection."""

    async def main():
        srv = _CountingServer(EchoHandler())
        port = await srv.start()
        digest = hashlib.sha256(
            rpcio.cluster_token().encode()).hexdigest().encode()
        assert len(magic + digest) == rpcio._AUTH_LEN
        try:
            for _ in range(3):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                writer.write(magic + digest)
                await writer.drain()
                got = await asyncio.wait_for(reader.read(), 10)
                assert got == b"", f"server answered a stale magic: {got!r}"
                writer.close()
            assert srv.dials == 3 and not srv.connections
            # the server is unharmed: today's preamble is accepted
            conn = await connect("127.0.0.1", port, name="c", retries=3)
            assert await conn.request("echo", {"x": 1}) == {"x": 1}
            await conn.close()
        finally:
            await srv.stop()

    asyncio.run(main())


def test_wrong_token_is_refused_at_every_dial():
    async def main():
        srv = _CountingServer(EchoHandler())
        port = await srv.start()
        try:
            with pytest.raises(rpcio.ConnectionLost) as ei:
                await connect("127.0.0.1", port, name="c", retries=3,
                              retry_delay=0.01, token="not-this-cluster")
            assert "handshake refused" in str(ei.value)
            assert "wrong cluster token" in str(ei.value)
            assert srv.dials == 3 and not srv.connections
        finally:
            await srv.stop()

    asyncio.run(main())


def test_refused_handshake_does_not_downgrade():
    """A server that closes at the preamble (another checkout's, or another
    token's): the client sends the one preamble at every dial and raises,
    after its retries, an error that names the handshake."""

    async def main():
        seen = []

        async def refuse(reader, writer):
            seen.append(await reader.readexactly(rpcio._AUTH_LEN))
            writer.close()

        server = await asyncio.start_server(refuse, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        try:
            with pytest.raises(rpcio.ConnectionLost) as ei:
                await connect("127.0.0.1", port, name="c", retries=4,
                              retry_delay=0.01)
            assert "handshake refused" in str(ei.value)
            assert "older ray_tpu" in str(ei.value)
            assert seen == [rpcio._auth_preamble(rpcio.cluster_token())] * 4
            assert seen[0].startswith(b"RTPU3")  # the bytes every process opens with
        finally:
            server.close()
            await server.wait_closed()

    asyncio.run(main())


# ------------------------------------------------------------ zero-copy --


def test_1mb_numpy_send_is_zero_copy():
    """The acceptance check: a 1MB array payload crosses _send with a tiny
    pickle envelope and the array's memory handed to the transport BY
    REFERENCE (a memoryview aliasing the array), never copied."""

    async def main():
        conn = _conn()
        arr = np.arange(1 << 20, dtype=np.uint8)
        await conn._send(1, KIND_NOTIFY, "m", {"arr": arr})
        writes = conn.writer.writes
        assert writes, "flush must have run"
        head = bytes(writes[0])
        total = int.from_bytes(head[:4], "little")
        nbufs = head[4]
        assert nbufs == 1
        buf_len = int.from_bytes(head[5:9], "little")
        assert buf_len == arr.nbytes
        # envelope = head minus 4B total, 1B nbufs, 4B table entry
        envelope_len = len(head) - 9
        assert envelope_len < 1024, (
            f"envelope carries payload bytes: {envelope_len}"
        )
        assert total == 1 + 4 + envelope_len + arr.nbytes
        views = [w for w in writes[1:] if isinstance(w, memoryview)]
        assert views, "buffer must be written as its own part"
        assert any(
            v.nbytes == arr.nbytes
            and np.shares_memory(np.frombuffer(v, dtype=np.uint8), arr)
            for v in views
        ), "buffer must alias the array's memory (zero-copy)"

    asyncio.run(main())


def test_serialized_value_slot_is_zero_copy_on_send():
    """The worker inline-arg shape: ('v', metadata, sv.to_wire()) must ship
    the value's array buffer by reference through a connection."""

    async def main():
        arr = np.arange(1 << 20, dtype=np.uint8)
        sv = serialization.serialize({"weights": arr})
        slot = ("v", sv.metadata, sv.to_wire())
        conn = _conn()
        await conn._send(2, KIND_NOTIFY, "execute", {"args": [slot]})
        writes = conn.writer.writes
        views = [w for w in writes if isinstance(w, memoryview)]
        assert any(
            v.nbytes == arr.nbytes
            and np.shares_memory(np.frombuffer(v, dtype=np.uint8), arr)
            for v in views
        ), "inline arg buffer must alias the caller's array"
        head = bytes(writes[0])
        envelope_len = len(head) - 5 - 4 * head[4]
        assert envelope_len < 4096

    asyncio.run(main())


def test_bufferlist_roundtrip():
    arr = np.arange(100_000, dtype=np.float32)
    sv = serialization.serialize({"x": arr, "y": "small"})
    _, _, _, payload = _roundtrip({"slot": ("v", sv.metadata, sv.to_wire())})
    kind, meta, data = payload["slot"]
    assert kind == "v"
    assert isinstance(data, serialization.BufferList)
    value = serialization.deserialize(meta, data)
    assert value["y"] == "small"
    assert np.array_equal(value["x"], arr)


def test_bufferlist_concat_matches_to_bytes():
    arr = np.arange(5000, dtype=np.uint8)
    sv = serialization.serialize([arr, b"tail"])
    assert sv.to_wire().concat() == sv.to_bytes()
    # raw-bytes fast path: to_bytes returns the buffer itself, no copy
    raw = b"z" * 4096
    sv2 = serialization.serialize(raw)
    assert sv2.to_bytes() is raw
