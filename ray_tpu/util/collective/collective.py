"""Collective communication library.

API parity with the reference's ray.util.collective
(ray: python/ray/util/collective/collective.py:120-655 — init_collective_group,
create_collective_group, allreduce, allgather, reducescatter, broadcast,
send, recv, barrier), with the NCCL/Gloo backends replaced by:

- backend="xla" (DEFAULT, the fast path): every rank is a process in ONE
  JAX distributed system (`jax.distributed.initialize`, which Train's
  JaxConfig performs for worker gangs); the group owns a
  one-device-per-rank Mesh and each op runs a compiled `shard_map` program
  (`lax.psum`/`all_gather`/`psum_scatter`), so on TPU pods the transfer
  rides ICI. Collectives still belong INSIDE the compiled step for the
  inner loop; this API is the out-of-graph parity surface.
- backend="store": a GCS-KV rendezvous fallback that works between any
  actors on any nodes with no JAX coupling, the analog of the reference's
  Gloo CPU backend. send/recv p2p always uses this path (XLA has no
  one-sided p2p outside a compiled program).

Out-of-graph ops here are for control-plane-sized data (weight broadcast,
metric reduction); inner-loop gradient reduction should use the in-graph
path (ray_tpu.parallel / trainers), exactly as NCCL-allreduce lives inside
torch DDP in the reference.

The store-path allreduce is not a naive payload swap: three composable,
independently flag-gated levers rebuild the hot path (each A/B-able
against the steptrace (group, seq) skew series PR 11 shipped):

1. **Chunked pipeline transport** (``collective_chunk_bytes``, default
   1MB; 0 = off): tensors above the threshold are reduce-scattered and
   allgathered in fixed-size chunks — each rank OWNS 1/world of the
   tensor, peers publish their contribution chunks, the owner
   accumulates and republishes the reduced chunk as soon as its last
   contribution lands, and bounded in-flight windows
   (``collective_pipeline_depth``, one window per fetch kind) keep
   reduction of chunk N overlapping the RPC round trips of chunk N+1.
   Chunk payloads ride
   rpcio's v2 out-of-band buffer table (``BufferList``): tensor bytes
   are never copied into a pickle envelope.
2. **Block-wise int8 quantization** (EQuARX-style, arxiv 2506.17615):
   ``quant="int8"`` per group (or ``RAY_TPU_collective_quant``) puts a
   per-chunk symmetric scale + int8 payload on the wire for SUM/MEAN
   float allreduces, dequantize-accumulate-requantize at the owner,
   fp32 restore at the end. All ranks — including the owner — decode
   the SAME requantized wire form, so results stay bit-identical
   across ranks. Non-SUM/MEAN ops and non-float dtypes fall back to
   exact full-precision transport.
3. **Straggler-tolerant chunk scheduling** (arxiv 2505.23523): each
   rank tracks the longest time it spent blocked on a peer's
   contribution chunks, relative to the fastest peer (receiver-clock
   only — no cross-host timestamp comparison, which NTP-grade clock
   offset would poison), folds it into an EWMA, and a peer whose lag
   exceeds
   ``collective_straggler_threshold`` has its chunks fetched LAST so
   the pipeline windows stay busy on ranks that have already
   published (0, the default = FIFO rank order).

Telemetry: every op (allreduce/allgather/reducescatter/broadcast/barrier)
consumes one per-group monotonic sequence number and records a steptrace
event (rank-local start/end/bytes keyed by (group, seq) — see
_private/steptrace.py) so a GCS-side merge can attribute per-collective
arrival skew to the rank that showed up last. Op records carry
``bytes`` (tensor size), ``wire`` (bytes this rank actually moved over
the transport, post-encoding) and ``logical`` (what the same movements
would have cost at full precision) — logical/wire is the
effective-bandwidth series the quantized path is judged by. Chunked ops
additionally record per-chunk spans (their own timeline lane; the
(group, seq) skew join still sees ONE collective row per op). With
RAY_TPU_TRACING=1 each op additionally emits a tracing span,
interleaving with task spans in ``state.timeline()``.

CPU portability: when the runtime cannot execute multiprocess XLA
computations (CPU backend raises "Multiprocess computations aren't
implemented"), the xla backend transparently falls back to the native
``_phase`` KV-rendezvous ring path — the API surface (and its steptrace
records) works everywhere; only the transport differs.
"""

from __future__ import annotations

import asyncio
import pickle
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ray_tpu._private import steptrace
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.serialization import BufferList
from ray_tpu.util import tracing

_KV_NS = b"collective"

# sentinel suffix: presence of <keybase>:__abort__ tells every rank blocked
# in a rendezvous wait that this generation of the group is dead
_ABORT_SUFFIX = b":__abort__"


class CollectiveWorldChangedError(RuntimeError):
    """The group's membership changed (a rank died or the gang was re-formed)
    while this rank was inside a collective. In-flight rendezvous waits raise
    this instead of running out the full collective timeout, so supervisors
    can tear down and re-form the group in seconds.
    """


class ReduceOp:
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"
    MEAN = "mean"


_REDUCERS = {
    ReduceOp.SUM: lambda xs: np.sum(xs, axis=0),
    ReduceOp.PRODUCT: lambda xs: np.prod(xs, axis=0),
    ReduceOp.MIN: lambda xs: np.min(xs, axis=0),
    ReduceOp.MAX: lambda xs: np.max(xs, axis=0),
    ReduceOp.MEAN: lambda xs: np.mean(xs, axis=0),
}

# pairwise accumulation ufuncs for the chunked path (MEAN = add + divide)
_ACC_UFUNC = {
    ReduceOp.SUM: np.add,
    ReduceOp.MEAN: np.add,
    ReduceOp.PRODUCT: np.multiply,
    ReduceOp.MIN: np.minimum,
    ReduceOp.MAX: np.maximum,
}

_metrics_cached = None


def _metrics():
    """Collective transport counters on the process registry (they ride
    the /metrics cluster scrape; run_chaos.sh triage greps them)."""
    global _metrics_cached
    if _metrics_cached is None:
        from ray_tpu._private import metrics_core

        reg = metrics_core.registry()
        _metrics_cached = (
            reg.counter("collective_wire_bytes_total",
                        "bytes this process moved over the collective "
                        "transport (post chunk/quant encoding)"),
            reg.counter("collective_logical_bytes_total",
                        "full-precision-equivalent bytes of the same "
                        "collective transport movements"),
            reg.counter("collective_chunk_retries_total",
                        "extra rendezvous polls while waiting on "
                        "collective chunks (peer not yet published)"),
            reg.counter("collective_chunks_total",
                        "chunks moved by the chunked collective path"),
        )
    return _metrics_cached


# ---------------------------------------------------------------------------
# wire codec: header + raw tensor bytes as out-of-band BufferList buffers
# ---------------------------------------------------------------------------
#
# A tensor payload is BufferList([header, body]): the pickled header
# (dtype/shape/quant-scale, ~100B, stays in the pickle envelope) and
# the raw tensor bytes, which rpcio's v2 framing sends
# out-of-band by reference — no pickle.dumps copy of the tensor on the
# send side, and a zero-copy memoryview over the read buffer on the
# receive side. Object-dtype tensors (and b"" markers) stay plain bytes.

_QS_EPS = 0.0  # symmetric int8: scale = max|x| / 127, zero-safe below


def _quant_encode(arr: np.ndarray):
    """Symmetric per-block int8 quantization: returns (int8 array, scale).
    The scale is computed in float64 and stored as a python float so
    every rank dequantizes from the identical value."""
    amax = float(np.max(np.abs(arr), initial=0.0))
    scale = amax / 127.0
    if scale <= 0.0:
        return np.zeros(arr.shape, np.int8), 0.0
    q = np.clip(np.rint(arr.astype(np.float32) / np.float32(scale)),
                -127, 127).astype(np.int8)
    return q, scale


def _quant_decode(q: np.ndarray, scale: float) -> np.ndarray:
    """Dequantize — deterministic fp32 arithmetic, identical on every
    rank that holds the same wire bytes."""
    if scale <= 0.0:
        return np.zeros(q.shape, np.float32)
    return q.astype(np.float32) * np.float32(scale)


def _wrap_body(hd_fields: dict, body_arr: np.ndarray) -> BufferList:
    hd = pickle.dumps(hd_fields, protocol=5)
    # 1-D view keeps the memoryview cast-safe for 0-d/N-d inputs alike
    return BufferList([hd, memoryview(body_arr.reshape(-1)).cast("B")])


def _enc_quant(q: np.ndarray, scale: float, dtype_str: str,
               shape) -> BufferList:
    """Wire form of an ALREADY-quantized block — the owner publishes the
    exact int8+scale it will locally dequantize, which is what makes the
    reduced result bit-identical across ranks."""
    return _wrap_body({"d": dtype_str, "s": shape, "q": "int8",
                       "sc": scale}, q)


def _enc_tensor(arr: np.ndarray, quant: str = "") -> "BufferList | bytes":
    """Encode a tensor (or chunk view) for the rendezvous wire."""
    if arr.dtype == object:
        return pickle.dumps(arr, protocol=5)  # structured payloads: legacy
    shape = arr.shape  # before ascontiguousarray, which promotes 0-d to 1-d
    arr = np.ascontiguousarray(arr)
    if quant == "int8":
        q, scale = _quant_encode(arr)
        return _enc_quant(q, scale, str(arr.dtype), shape)
    return _wrap_body({"d": str(arr.dtype), "s": shape, "q": "",
                       "sc": None}, arr)


def _dec_tensor(value) -> "tuple[np.ndarray, Optional[dict]]":
    """Decode a wire payload -> (tensor, header). Quantized payloads come
    back dequantized to fp32 (all ranks run the identical arithmetic on
    the identical wire bytes). The returned array may be a read-only
    view over the receive buffer — reducers copy, callers that need
    ownership copy."""
    if isinstance(value, BufferList):
        bufs = value.buffers
        hd0 = bufs[0]
        hd = pickle.loads(hd0 if isinstance(hd0, bytes) else bytes(hd0))
        body = bufs[1] if len(bufs) > 1 else b""
        shape = hd["s"]
        if hd["q"] == "int8":
            q = np.frombuffer(body, dtype=np.int8).reshape(shape)
            return _quant_decode(q, hd["sc"] or 0.0), hd
        return np.frombuffer(body, dtype=np.dtype(hd["d"])).reshape(shape), hd
    return pickle.loads(value), None


def _vsize(value) -> int:
    """Encoded size of a wire payload (what actually crossed the wire)."""
    if isinstance(value, BufferList):
        return value.nbytes
    return len(value) if value is not None else 0


@dataclass
class _Group:
    name: str
    world_size: int
    rank: int
    backend: str
    # generation epoch: bumped each time a gang re-forms a group under the
    # same name (after a rank death). Threaded into every rendezvous key so
    # a new generation cannot mis-join stale KV state from the dead one.
    epoch: int = 0
    seq: int = 0  # per-group monotonic op counter (the steptrace join key)
    # sticky: the xla transport proved unavailable (CPU multiprocess);
    # ops route through the _phase ring path from then on
    xla_fallback: bool = False
    # "" (full precision) or "int8": block-wise quantized wire for
    # SUM/MEAN float allreduces on the store path (group-level opt-in;
    # the RAY_TPU_collective_quant flag is the process-wide default)
    quant: str = ""
    # rank -> EWMA arrival lag (s) behind the op's fastest peer,
    # learned from receiver-local chunk wait times; drives
    # straggler-last chunk fetch ordering
    peer_lag: Dict[int, float] = field(default_factory=dict)
    # rank -> seconds into the previous chunked op's fetch loop when
    # that peer's LAST contribution chunk retired. Diagnostic for the
    # straggler-scheduling A/B: op completion is always bound by the
    # slowest contributor, but deferral retires fast peers' chunks
    # UNDER the straggler's delay instead of serialized after it, and
    # this is where that shows
    peer_cc_done: Dict[int, float] = field(default_factory=dict)
    p2p_send: Dict[int, int] = None  # per-destination send counters
    p2p_recv: Dict[int, int] = None  # per-source recv counters
    mesh: object = None  # xla backend: 1-device-per-rank Mesh over axis "ranks"
    _compiled: Dict = None  # xla backend: (op, shape, dtype, extra) -> jitted fn

    def __post_init__(self):
        self.p2p_send = {}
        self.p2p_recv = {}
        self._compiled = {}

    def alloc_seq(self) -> int:
        """Consume the next per-group sequence number (wraps at
        steptrace.SEQ_MOD; all ranks wrap at the same count, so the
        (group, seq) join key stays aligned)."""
        seq = self.seq
        self.seq = (self.seq + 1) % steptrace.SEQ_MOD
        return seq

    @property
    def keybase(self) -> str:
        """Rendezvous key prefix: generation-qualified group name."""
        return _keybase(self.name, self.epoch)

    @property
    def trace_name(self) -> str:
        """Group name as it appears in steptrace (group, seq) records.
        Epoch 0 keeps the bare name so existing timelines/joins are
        unchanged; re-formed generations are visibly distinct."""
        return self.name if self.epoch == 0 else f"{self.name}@{self.epoch}"


def _keybase(name: str, epoch: int) -> str:
    return f"{name}@{epoch}"


_groups: Dict[str, _Group] = {}
_lock = threading.Lock()


def _cw():
    from ray_tpu._private.worker import global_worker

    global_worker.check_connected()
    return global_worker.core_worker


def _kv_put(key: bytes, value, volatile: bool = False):
    """Put into the collective KV namespace. ``volatile=True`` marks
    rendezvous-lifetime data (tensor payloads a re-formed gang would
    republish anyway) that skips the GCS persist log; group membership,
    abort markers, and anything a GCS restart must replay stay
    persistent (the default)."""
    cw = _cw()
    cw.io.run(cw.gcs.request("kv_put", {"ns": _KV_NS, "key": key,
                                        "value": value,
                                        "volatile": volatile}))


def _kv_get(key: bytes):
    cw = _cw()
    return cw.io.run(cw.gcs.request("kv_get", {"ns": _KV_NS, "key": key}))


# async twins, scheduled on the core worker's io loop so the chunked
# transport can keep a pipelined window of puts/waits in flight while
# the calling thread reduces already-arrived chunks. The numpy work
# stays OFF the io loop — these coroutines only do RPC round trips.

async def _akv_put(cw, key: bytes, value):
    await cw.gcs.request("kv_put", {"ns": _KV_NS, "key": key,
                                    "value": value, "volatile": True})


async def _akv_wait(cw, key: bytes, timeout: float,
                    abort_key: Optional[bytes] = None):
    """Async poll for ``key`` (chunk rendezvous): same backoff + abort
    semantics as the sync ``_kv_wait``. Extra polls (the peer had not
    published yet) feed the chunk-retry counter chaos triage greps."""
    deadline = time.monotonic() + timeout
    delay = 0.002
    polls = 0
    while time.monotonic() < deadline:
        v = await cw.gcs.request("kv_get", {"ns": _KV_NS, "key": key})
        if v is not None:
            if polls:
                _metrics()[2].inc(polls)
            return v
        polls += 1
        if abort_key is not None and polls % 5 == 0:
            a = await cw.gcs.request("kv_get", {"ns": _KV_NS,
                                                "key": abort_key})
            if a is not None:
                raise CollectiveWorldChangedError(
                    f"collective group aborted while waiting on {key!r}: "
                    "membership changed (rank death or gang re-formation)"
                )
        await asyncio.sleep(delay)
        delay = min(delay * 1.5, 0.05)
    raise TimeoutError(f"collective rendezvous timed out on {key!r}")


def _kv_del_prefix(prefix: bytes):
    cw = _cw()
    cw.io.run(cw.gcs.request("kv_del", {"ns": _KV_NS, "key": prefix, "prefix": True}))


def _kv_wait(key: bytes, timeout: float, abort_key: bytes | None = None):
    """Poll ``key`` until it appears. When ``abort_key`` is given, every few
    polls also check for the group's abort marker — a supervisor killing a
    dead generation plants it so blocked survivors fail over in ~a poll
    interval with a typed error instead of running out ``timeout``."""
    deadline = time.monotonic() + timeout
    delay = 0.002
    polls = 0
    while time.monotonic() < deadline:
        v = _kv_get(key)
        if v is not None:
            return v
        polls += 1
        if abort_key is not None and polls % 5 == 0:
            if _kv_get(abort_key) is not None:
                raise CollectiveWorldChangedError(
                    f"collective group aborted while waiting on {key!r}: "
                    "membership changed (rank death or gang re-formation)"
                )
        time.sleep(delay)
        delay = min(delay * 1.5, 0.05)
    raise TimeoutError(f"collective rendezvous timed out on {key!r}")


def _build_xla_group(world_size: int, rank: int, group_name: str) -> _Group:
    """Validate + build an XLA-backed group.

    The xla backend is real SPMD: every rank must be a process in one JAX
    distributed system (``jax.distributed.initialize`` — the train backend's
    JaxConfig does this for worker gangs). The group owns a one-device-per-
    process Mesh over axis "ranks"; every op compiles a `shard_map` program
    whose body is `lax.psum`/`all_gather`/`psum_scatter`, so on TPU pods the
    transfer rides ICI (reference analog: the NCCL communicator in
    ray: util/collective/collective_group/nccl_collective_group.py).
    """
    import jax
    from jax.sharding import Mesh

    nproc = jax.process_count()
    if nproc != world_size:
        raise RuntimeError(
            f"backend='xla' requires one JAX process per rank: "
            f"world_size={world_size} but jax.process_count()={nproc}. "
            "Bootstrap the gang with jax.distributed.initialize (Train's "
            "JaxConfig(distributed='force') does this), or use "
            "backend='store'."
        )
    if nproc > 1 and jax.process_index() != rank:
        raise RuntimeError(
            f"rank {rank} does not match jax.process_index()="
            f"{jax.process_index()}; xla groups must be rank-aligned with "
            "the JAX distributed system"
        )
    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, d)
    devs = np.array([by_proc[p] for p in sorted(by_proc)])
    mesh = Mesh(devs, ("ranks",))
    return _Group(group_name, world_size, rank, "xla", mesh=mesh)


def init_collective_group(
    world_size: int,
    rank: int,
    backend: str = "xla",
    group_name: str = "default",
    epoch: int = 0,
    quant: str = "",
):
    """Declare this process's membership in a collective group
    (ray parity: collective.py init_collective_group). ``epoch`` is the
    gang generation: a re-formed group at the same name must pass the new
    generation so its rendezvous keys cannot collide with the dead one's.
    ``quant="int8"`` opts this group's float SUM/MEAN allreduces into the
    block-wise quantized wire (must be passed identically on every
    rank)."""
    if world_size <= 0 or not (0 <= rank < world_size):
        raise ValueError(f"invalid world_size={world_size} rank={rank}")
    if backend not in ("xla", "store"):
        raise ValueError(f"unsupported backend {backend!r} (xla|store)")
    if quant not in ("", "int8"):
        raise ValueError(f"unsupported quant {quant!r} (''|'int8')")
    if backend == "xla":
        g = _build_xla_group(world_size, rank, group_name)
        g.epoch = epoch
    else:
        g = _Group(group_name, world_size, rank, backend, epoch=epoch)
    g.quant = quant
    with _lock:
        _groups[group_name] = g
    _kv_put(f"{g.keybase}:member:{rank}".encode(), b"1")


def create_collective_group(
    actors: List,
    world_size: int,
    ranks: List[int],
    backend: str = "xla",
    group_name: str = "default",
    epoch: int = 0,
    quant: str = "",
):
    """Declare a group over actor handles from the driver
    (ray parity: collective.py create_collective_group): each actor must call
    ``init_collective_group`` (we invoke it via a well-known method or
    remote call on ``_rt_init_collective``). ``epoch``/``quant`` are only
    forwarded when set: the hook is a public parity surface and existing
    actors define it without the parameters — only re-formed gangs
    (epoch > 0, e.g. Train's recovery path) or quant-opted groups, whose
    workers accept them, need the extras threaded through."""
    import ray_tpu

    if quant not in ("", "int8"):
        raise ValueError(f"unsupported quant {quant!r} (''|'int8')")
    refs = []
    for actor, rank in zip(actors, ranks):
        extra = ()
        if quant:
            extra = (epoch, quant)
        elif epoch:
            extra = (epoch,)
        refs.append(
            actor._rt_init_collective.remote(
                world_size, rank, backend, group_name, *extra
            )
        )
    ray_tpu.get(refs, timeout=60)


def is_group_initialized(group_name: str = "default") -> bool:
    return group_name in _groups


def destroy_collective_group(group_name: str = "default"):
    with _lock:
        _groups.pop(group_name, None)
    # epoch-qualified keys ("name@<epoch>:...") plus the legacy bare prefix
    _kv_del_prefix(f"{group_name}@".encode())
    _kv_del_prefix(f"{group_name}:".encode())


def abort_group(group_name: str = "default", epoch: int | None = None):
    """Plant the abort marker for a group generation. Every rank of that
    generation blocked in a rendezvous wait raises
    ``CollectiveWorldChangedError`` within a poll interval. Callable from
    any connected process (the driver-side gang supervisor does NOT hold
    the group locally, so it passes the generation explicitly)."""
    if epoch is None:
        g = _groups.get(group_name)
        epoch = g.epoch if g else 0
    _kv_put(_keybase(group_name, epoch).encode() + _ABORT_SUFFIX, b"1")


def get_rank(group_name: str = "default") -> int:
    g = _groups.get(group_name)
    return g.rank if g else -1


def get_collective_group_size(group_name: str = "default") -> int:
    g = _groups.get(group_name)
    return g.world_size if g else -1


def _group(group_name: str) -> _Group:
    g = _groups.get(group_name)
    if g is None:
        raise RuntimeError(
            f"collective group '{group_name}' not initialized; call "
            f"init_collective_group first"
        )
    return g


def _to_numpy(tensor) -> np.ndarray:
    if isinstance(tensor, np.ndarray):
        return tensor
    try:
        import jax

        if isinstance(tensor, jax.Array):
            return np.asarray(tensor)
    except ImportError:
        pass
    return np.asarray(tensor)


def _phase(g: _Group, op: str, timeout: float, payload,
           seq: Optional[int] = None, tel: Optional[dict] = None) -> List:
    """All ranks contribute payload; returns all contributions rank-ordered.

    KV-barrier rendezvous keyed by (group, seq, op). The GCS KV plays the
    role of the reference's rendezvous store (ray: util/collective/
    collective_group/nccl_util.py store-based unique-id exchange).
    ``seq`` is the op's already-allocated group sequence number (every
    public op allocates one up front so steptrace records and rendezvous
    keys agree); direct callers may omit it. ``payload`` is bytes or an
    encoded-tensor ``BufferList`` (the out-of-band form); ``tel``, when
    given, accumulates wire/logical transport bytes.
    """
    if seq is None:
        seq = g.alloc_seq()
    base = f"{g.keybase}:{seq}:{op}".encode()
    abort_key = g.keybase.encode() + _ABORT_SUFFIX
    _kv_put(base + f":{g.rank}".encode(), payload, volatile=True)
    outs = []
    for r in range(g.world_size):
        outs.append(_kv_wait(base + f":{r}".encode(), timeout,
                             abort_key=abort_key))
    if tel is not None:
        # monolithic transport is full precision: wire == logical
        moved = _vsize(payload) + sum(_vsize(o) for o in outs)
        tel["wire"] += moved
        tel["logical"] += moved
    # rank 0 garbage-collects the previous phase's keys
    if g.rank == 0 and seq > 0:
        _kv_del_prefix(f"{g.keybase}:{seq - 1}:".encode())
    return outs


def _op(g: _Group, op: str, nbytes: int, call):
    """Run one collective op under telemetry: allocate the per-group seq,
    time the rank-local interval into the steptrace ring, and (with
    tracing enabled) wrap it in a span so it interleaves with task spans
    in state.timeline(). ``call(seq, tel)`` performs the actual
    transport, accumulating actual/full-precision transport bytes into
    ``tel["wire"]``/``tel["logical"]`` (left 0 = transport didn't
    measure, e.g. the in-graph XLA path; the record then defaults both
    to ``nbytes``).

    The record lands in a ``finally``: a rank that RAISES (rendezvous
    timeout because a peer never arrived — the straggler failure this
    plane exists to diagnose) still records its arrival time and how
    long it waited, so the GCS merge shows the (group, seq) row with the
    wedged rank in ``missing`` instead of showing nothing at all."""
    seq = g.alloc_seq()
    tel = {"wire": 0, "logical": 0}
    start = time.time()
    try:
        if tracing.is_enabled():
            with tracing.span(f"collective.{op}", group=g.trace_name,
                              seq=seq, rank=g.rank, world=g.world_size,
                              bytes=nbytes):
                return call(seq, tel)
        return call(seq, tel)
    finally:
        wire = tel["wire"] or None
        logical = tel["logical"] or None
        if wire is not None:
            m = _metrics()
            m[0].inc(wire)
            m[1].inc(logical or wire)
        steptrace.record_collective(g.trace_name, seq, op, g.rank,
                                    g.world_size, start, time.time(),
                                    nbytes, wire=wire, logical=logical)


# ---------------------------------------------------------------------------
# chunked pipeline transport (store path): reduce-scatter + allgather over
# fixed-size chunks, pipelined on the core worker's io loop
# ---------------------------------------------------------------------------


def _chunk_layout(n: int, world: int, chunk_elems: int) -> List[List[tuple]]:
    """Owner-sharded chunk plan over a flat n-element tensor: shard o
    (owned by rank o) is elements [o*n//world, (o+1)*n//world); each
    shard splits into chunk_elems-sized pieces (chunk_elems <= 0 keeps
    one chunk per shard — the quant-without-chunking configuration).
    Every shard gets at least one (possibly empty) chunk so the
    rendezvous key schedule is uniform across ranks."""
    plan = []
    for o in range(world):
        lo, hi = o * n // world, (o + 1) * n // world
        if chunk_elems <= 0 or hi - lo <= chunk_elems:
            plan.append([(lo, hi)])
            continue
        cuts = list(range(lo, hi, chunk_elems)) + [hi]
        plan.append([(a, b) for a, b in zip(cuts, cuts[1:]) if a < b])
    return plan


def _fetch_order(g: _Group, peers: List[int]) -> "tuple[List[int], List[int]]":
    """Chunk-fetch peer scheduling: returns ``(pipelined, deferred)``.
    FIFO rank order normally; a peer whose EWMA arrival lag exceeds
    ``collective_straggler_threshold`` is deferred — ALL its chunks are
    fetched after every other peer's, so the known straggler's
    not-yet-published keys never occupy the bounded pipeline windows
    while fast peers' chunks are ready to flow (arxiv 2505.23523). By
    the time a window reaches a deferred peer its chunks have usually
    landed, so the tail waits drain at poll speed. Threshold <= 0 (the
    default-off flag) keeps pure FIFO."""
    peers = sorted(peers)
    thr = GLOBAL_CONFIG.collective_straggler_threshold
    if thr <= 0 or not g.peer_lag:
        return peers, []
    laggy = [p for p in peers if g.peer_lag.get(p, 0.0) > thr]
    if not laggy:
        return peers, []
    laggy.sort(key=lambda p: (g.peer_lag.get(p, 0.0), p))
    return [p for p in peers if p not in set(laggy)], laggy


def _chunked_allreduce(g: _Group, arr: np.ndarray, op: str, timeout: float,
                       seq: int, tel: dict, quant: str = "") -> np.ndarray:
    """Allreduce ``arr`` over the store transport in owner-sharded chunks.

    Rank o owns shard o. Every rank publishes its contribution chunks
    for peer-owned shards; each owner accumulates a chunk as soon as all
    contributions land and immediately republishes the reduced chunk,
    while per-kind bounded windows of chunk waits keep the next chunks'
    RPC round trips in flight under the numpy work (reduce of chunk N
    overlaps transport of chunk N+1). With ``quant="int8"`` the wire
    carries per-chunk scale + int8; the owner dequantize-accumulates in
    fp32, requantizes the reduced chunk, and uses the requantized wire
    form for its OWN output too, so all ranks hold bit-identical
    results. All rendezvous keys live under the op's seq prefix
    (``<keybase>:<seq>:c[cr]:...``), so the existing rank-0 GC of the
    previous seq and the PR 17 abort/epoch machinery cover chunked ops
    unchanged."""
    import concurrent.futures as cf

    cw = _cw()
    W, rank = g.world_size, g.rank
    flat = np.ascontiguousarray(arr).reshape(-1)
    n, itemsize = flat.size, flat.dtype.itemsize
    chunk_bytes = GLOBAL_CONFIG.collective_chunk_bytes
    chunk_elems = max(1, chunk_bytes // itemsize) if chunk_bytes > 0 else 0
    plan = _chunk_layout(n, W, chunk_elems)
    gbase = [0] * W  # owner -> global chunk index of its chunk 0
    for o in range(1, W):
        gbase[o] = gbase[o - 1] + len(plan[o - 1])
    prefix = f"{g.keybase}:{seq}"
    abort_key = g.keybase.encode() + _ABORT_SUFFIX
    depth = max(1, GLOBAL_CONFIG.collective_pipeline_depth)
    ufunc = _ACC_UFUNC[op]
    mean = op == ReduceOp.MEAN
    deadline = time.monotonic() + timeout

    if quant:
        res_dtype = np.dtype(np.float32)
    elif mean and flat.dtype.kind in "biu":
        res_dtype = np.dtype(np.float64)  # np.mean-like int promotion
    else:
        res_dtype = flat.dtype
    out = np.empty(n, dtype=res_dtype)

    def fp_size(elems: int) -> int:
        return elems * itemsize

    put_futs: List = []

    def aput(key: str, value, elems: int):
        tel["wire"] += _vsize(value)
        tel["logical"] += (_vsize(value) if not quant
                           else _vsize(value) - elems + fp_size(elems))
        put_futs.append(cw.io.submit(_akv_put(cw, key.encode(), value)))

    # -- publish contributions for every peer-owned shard, chunk-major so
    # each owner's chunk 0 is on the wire before anyone's chunk 1
    rounds = max(len(pl) for pl in plan)
    for ci in range(rounds):
        for o in range(W):
            if o == rank or ci >= len(plan[o]):
                continue
            lo, hi = plan[o][ci]
            aput(f"{prefix}:cc:{o}:{ci}:{rank}",
                 _enc_tensor(flat[lo:hi], quant), hi - lo)

    # -- seed own-shard accumulators with this rank's own contribution
    # (quantize-roundtripped when quant is on: the analytic error bound
    # assumes every rank's contribution was quantized, owner included)
    my_chunks = plan[rank]
    acc: Dict[int, np.ndarray] = {}
    remaining: Dict[int, int] = {}
    chunk_t0: Dict[tuple, float] = {}
    for ci, (lo, hi) in enumerate(my_chunks):
        own = flat[lo:hi]
        if quant:
            q, sc = _quant_encode(own)
            acc[ci] = _quant_decode(q, sc)
        else:
            acc[ci] = own.astype(res_dtype, copy=True)
        remaining[ci] = W - 1

    def finalize_chunk(ci: int):
        lo, hi = my_chunks[ci]
        value = acc[ci]
        if mean:
            value = value / W if quant else (value / W).astype(res_dtype)
        if quant:
            q, sc = _quant_encode(value)
            enc = _enc_quant(q, sc, "float32", value.shape)
            # peers decode the requantized wire form; so do we, for
            # bit-identical results on every rank
            out[lo:hi] = _quant_decode(q, sc)
        else:
            enc = _enc_tensor(value)
            out[lo:hi] = value
        aput(f"{prefix}:cr:{rank}:{ci}", enc, hi - lo)
        now = time.time()
        steptrace.record_chunk(g.trace_name, seq, gbase[rank] + ci, op,
                               rank, chunk_t0.get(("cc", ci), now), now,
                               fp_size(hi - lo))
        _metrics()[3].inc()

    # -- pipelined fetch loop: contributions to my shard + reduced chunks
    # of peer shards. The two kinds draw from SEPARATE depth-bounded
    # windows: a cr wait only completes after its owner finalized, i.e.
    # after that owner fetched all W-1 contributions of its own — so cr
    # waits parked in a shared in-order window ahead of not-yet-submitted
    # cc items would starve every rank's contribution fetches as soon as
    # W-1 > depth, and the mutually-waiting ranks would deadlock until
    # the rendezvous timeout. Per-kind windows keep contribution fetches
    # flowing regardless of how many reduced-chunk waits are pending,
    # while the streams still interleave for transport/reduce overlap.
    # Within each kind the schedule is chunk-major FIFO (matches the
    # chunk-major publish order); a deferred (straggler) peer's chunks
    # go globally last within its kind.
    order, deferred = _fetch_order(g, [p for p in range(W) if p != rank])

    def _sched(kind: str) -> List[tuple]:
        out_items = []
        for batch in (order, deferred):
            for ci in range(rounds):
                for p in batch:
                    if kind == "cc" and ci < len(my_chunks):
                        out_items.append((kind, p, ci))
                    elif kind == "cr" and ci < len(plan[p]):
                        out_items.append((kind, p, ci))
        return out_items

    iters = {kind: iter(_sched(kind)) for kind in ("cc", "cr")}
    inflight = {"cc": 0, "cr": 0}
    window: Dict = {}
    peer_ccw: Dict[int, float] = {}  # peer -> max cc wait observed (s)
    peer_cc_done: Dict[int, float] = {}  # peer -> last cc retire offset (s)
    loop_t0 = time.monotonic()

    def submit_next(kind: str) -> bool:
        item = next(iters[kind], None)
        if item is None:
            return False
        _, p, ci = item
        if kind == "cc":
            key = f"{prefix}:cc:{rank}:{ci}:{p}"
            chunk_t0.setdefault((kind, ci), time.time())
        else:
            key = f"{prefix}:cr:{p}:{ci}"
            chunk_t0.setdefault((kind, p, ci), time.time())
        budget = max(0.01, deadline - time.monotonic())
        fut = cw.io.submit(_akv_wait(cw, key.encode(), budget, abort_key))
        window[fut] = (kind, p, ci, time.monotonic())
        inflight[kind] += 1
        return True

    def fill_windows():
        for kind in ("cc", "cr"):
            while inflight[kind] < depth and submit_next(kind):
                pass

    try:
        fill_windows()
        while window:
            done, _ = cf.wait(list(window),
                              return_when=cf.FIRST_COMPLETED)
            for fut in done:
                kind, p, ci, t_sub = window.pop(fut)
                inflight[kind] -= 1
                value = fut.result()  # raises: abort/timeout unwedge
                dec, _hd = _dec_tensor(value)
                now_m = time.monotonic()
                if kind == "cc":
                    peer_ccw[p] = max(peer_ccw.get(p, 0.0), now_m - t_sub)
                    peer_cc_done[p] = now_m - loop_t0
                elems = dec.size
                tel["wire"] += _vsize(value)
                tel["logical"] += (_vsize(value) if not quant
                                   else _vsize(value) - elems
                                   + fp_size(elems))
                if kind == "cc":
                    ufunc(acc[ci], dec, out=acc[ci],
                          casting="same_kind")
                    remaining[ci] -= 1
                    if remaining[ci] == 0:
                        finalize_chunk(ci)
                else:
                    lo, hi = plan[p][ci]
                    out[lo:hi] = dec
                    now = time.time()
                    steptrace.record_chunk(
                        g.trace_name, seq, gbase[p] + ci, op, rank,
                        chunk_t0.get(("cr", p, ci), now), now,
                        fp_size(hi - lo))
                    _metrics()[3].inc()
            fill_windows()
        for fut in put_futs:
            fut.result(max(0.01, deadline - time.monotonic()))
    except BaseException:
        for fut in window:
            fut.cancel()
        for fut in put_futs:
            fut.cancel()
        raise

    # -- fold this op's per-peer cc waits into the straggler EWMA.
    # Lag is measured entirely on the RECEIVER's clock: the longest
    # time this rank spent blocked on one of a peer's CONTRIBUTION
    # chunks, relative to the fastest peer's floor (which subtracts the
    # shared RPC/poll round trip; with a single peer there is no
    # reference and the raw wait stands in). Contributions are
    # published at the peer's op entry, so the max cc wait tracks
    # arrival lateness even when a late peer then publishes everything
    # in a burst (its LATER chunks complete instantly — a min- or
    # mean-style statistic would wash the signal out). Reduced-chunk
    # waits are excluded: an owner's cr publish is gated on OTHER
    # ranks' inputs, so counting it would charge fast owners with a
    # straggler's delay. Producer-side header timestamps are never
    # compared — ordinary NTP-grade cross-host clock offset exceeds
    # any useful threshold and would fabricate (or mask) stragglers. A
    # deferred peer's chunks are fetched last and usually land
    # pre-published, so its measured lag shrinks and a rehabilitated
    # peer drifts back under the threshold within a few ops.
    if peer_ccw:
        base = min(peer_ccw.values()) if len(peer_ccw) > 1 else 0.0
        for p, w in peer_ccw.items():
            lag = max(0.0, w - base)
            old = g.peer_lag.get(p)
            g.peer_lag[p] = lag if old is None else 0.7 * old + 0.3 * lag
    g.peer_cc_done = peer_cc_done

    # rank 0 garbage-collects the previous op's keys (chunk sub-keys
    # live under the seq prefix, so the one delete covers both paths)
    if rank == 0 and seq > 0:
        _kv_del_prefix(f"{g.keybase}:{seq - 1}:".encode())
    return out.reshape(arr.shape)


# ---------------------------------------------------------------------------
# XLA backend: compiled shard_map collectives over the group mesh
# ---------------------------------------------------------------------------

_XLA_REDUCE = {
    ReduceOp.SUM: "psum",
    ReduceOp.MEAN: "pmean",
    ReduceOp.MAX: "pmax",
    ReduceOp.MIN: "pmin",
}


def _xla_compiled(g: _Group, op: str, arr: "np.ndarray", extra=()):
    """Build (and cache per shape/dtype) the jitted SPMD program for ``op``.

    Every rank's contribution is one shard of a (world, *shape) global array
    over the "ranks" mesh axis; the body runs the XLA collective so the
    partitioner lowers it onto ICI rings. Returns ``(fn, fresh)`` —
    ``fresh`` means this (op, shape, dtype) was not cached, so the first
    execution will pay trace+compile (recorded as a steptrace compile
    event by the caller; a shape/dtype churn storm shows up per op).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    key = (op, arr.shape, str(arr.dtype), tuple(extra))
    fn = g._compiled.get(key)
    if fn is not None:
        return fn, False
    mesh = g.mesh
    in_spec = P("ranks")

    if op in ("psum", "pmean", "pmax", "pmin"):
        red = {"psum": jax.lax.psum, "pmean": jax.lax.pmean,
               "pmax": jax.lax.pmax, "pmin": jax.lax.pmin}[op]

        def body(x):  # x: (1, *shape) local shard
            return red(x[0], "ranks")

        out_spec = P()
    elif op == "allgather":
        def body(x):
            return jax.lax.all_gather(x[0], "ranks", axis=0, tiled=False)

        out_spec = P()
    elif op == "reducescatter":
        def body(x):
            return jax.lax.psum_scatter(
                x[0], "ranks", scatter_dimension=0, tiled=True
            )

        out_spec = P("ranks")
    elif op == "broadcast":
        (src,) = extra

        def body(x):
            return jax.lax.all_gather(x[0], "ranks", axis=0, tiled=False)[src]

        out_spec = P()
    else:  # pragma: no cover
        raise ValueError(op)

    # all_gather's replicated output can't be statically inferred; disable
    # the varying-axes check
    smapped = shard_map(body, mesh=mesh, in_specs=(in_spec,),
                        out_specs=out_spec, check_vma=False)
    fn = jax.jit(
        smapped,
        in_shardings=NamedSharding(mesh, in_spec),
        out_shardings=NamedSharding(mesh, out_spec),
    )
    g._compiled[key] = fn
    return fn, True


def _xla_global_input(g: _Group, arr: "np.ndarray"):
    """Stack this rank's tensor into the (world, *shape) global array, one
    shard per rank on the group mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(g.mesh, P("ranks"))
    shape = (g.world_size,) + arr.shape
    local = jax.device_put(
        arr[None, ...], g.mesh.local_mesh.devices.flat[0]
    )
    return jax.make_array_from_single_device_arrays(shape, sharding, [local])


def _xla_local_out(out) -> "np.ndarray":
    """Materialize this process's view of the op result."""
    shard = out.addressable_shards[0]
    return np.asarray(shard.data)


def _xla_unavailable(e: BaseException) -> bool:
    """The one failure we transparently degrade on: the backend cannot
    RUN multiprocess computations at all (CPU: "Multiprocess computations
    aren't implemented"). Anything else propagates — a real compile or
    shape error must not silently change transport."""
    return "multiprocess computation" in str(e).lower()


def _store_xla_equivalent(g: _Group, op: str, arr: "np.ndarray",
                          timeout: float, seq: Optional[int], extra=()):
    """Run the xla op's semantics over the native ``_phase`` ring path,
    returning exactly the shape the xla program would have produced for
    this rank (psum* -> reduced full array; allgather -> (world, *shape);
    reducescatter -> this rank's shard; broadcast -> src's array)."""
    if op == "broadcast":
        # only src's payload is ever read: non-src ranks contribute an
        # empty marker (same cheap form as the native broadcast path) —
        # world x full-tensor KV traffic for a one-way op is waste
        (src,) = extra
        payload = _enc_tensor(arr) if g.rank == src else b""
        outs = _phase(g, "x" + op, timeout, payload, seq=seq)
        return np.array(_dec_tensor(outs[src])[0])
    outs = _phase(g, "x" + op, timeout, _enc_tensor(arr), seq=seq)
    stacked = np.stack([_dec_tensor(o)[0] for o in outs])
    if op == "psum":
        return stacked.sum(axis=0)
    if op == "pmean":
        return stacked.mean(axis=0)
    if op == "pmax":
        return stacked.max(axis=0)
    if op == "pmin":
        return stacked.min(axis=0)
    if op == "allgather":
        return stacked
    if op == "reducescatter":
        return np.split(stacked.sum(axis=0), g.world_size, axis=0)[g.rank]
    raise ValueError(op)  # pragma: no cover


def _xla_collective(g: _Group, op: str, arr: "np.ndarray", extra=(),
                    timeout: float = 120.0, seq: Optional[int] = None):
    if not g.xla_fallback:
        try:
            # "first call" = this group's first program at all; a fresh
            # (op, shape, dtype) on a warm group is a RECOMPILE — shape
            # churn must render as the storm it is, not as benign firsts
            had_programs = bool(g._compiled)
            fn, fresh = _xla_compiled(g, op, arr, extra)
            t0 = time.time()
            out = _xla_local_out(fn(_xla_global_input(g, arr)))
            if fresh:
                # jit compiles lazily: a cache-miss call's wall time IS
                # trace+compile(+run) — attribute it per collective op
                steptrace.record_compile(f"collective.{op}", t0,
                                         time.time(),
                                         first=not had_programs)
            return out
        except Exception as e:
            if not _xla_unavailable(e):
                raise
            # Sticky per group: every rank hits the identical backend
            # limitation on its first op, so all ranks degrade at the
            # same seq and the _phase rendezvous keys line up.
            g.xla_fallback = True
    return _store_xla_equivalent(g, op, arr, timeout, seq, extra)


def allreduce(tensor, group_name: str = "default", op: str = ReduceOp.SUM,
              timeout: float = 120.0):
    """Allreduce across the group; returns the reduced tensor (jax arrays are
    immutable so the result is returned rather than written in place; numpy
    inputs are also updated in place for drop-in parity).

    Store-transport routing (also taken by xla groups once they degrade
    to the KV ring on CPU): tensors above ``collective_chunk_bytes`` —
    or any float SUM/MEAN when the group opted into quantization — take
    the chunked reduce-scatter+allgather pipeline; everything else takes
    the monolithic single-payload exchange (flags off == today's
    behavior, pinned byte-identical by test)."""
    g = _group(group_name)
    arr = _to_numpy(tensor)

    def _go(seq, tel):
        store_path = g.backend == "store" or g.xla_fallback
        if store_path and g.world_size > 1 and arr.dtype != object \
                and arr.size > 0:
            quant = ""
            if op in (ReduceOp.SUM, ReduceOp.MEAN) and arr.dtype.kind == "f":
                quant = g.quant or GLOBAL_CONFIG.collective_quant
            chunk_bytes = GLOBAL_CONFIG.collective_chunk_bytes
            if quant or (chunk_bytes > 0 and arr.nbytes > chunk_bytes):
                return _chunked_allreduce(g, arr, op, timeout, seq, tel,
                                          quant)
        if g.backend == "xla":
            if op == ReduceOp.PRODUCT:  # no pprod primitive: gather + prod
                gathered = _xla_collective(g, "allgather", arr,
                                           timeout=timeout, seq=seq)
                return np.prod(gathered, axis=0)
            return _xla_collective(g, _XLA_REDUCE[op], arr,
                                   timeout=timeout, seq=seq)
        outs = _phase(g, "ar", timeout, _enc_tensor(arr), seq=seq, tel=tel)
        return _REDUCERS[op](np.stack([_dec_tensor(o)[0] for o in outs]))

    result = _op(g, "allreduce", arr.nbytes, _go)
    if isinstance(tensor, np.ndarray) and tensor.flags.writeable:
        np.copyto(tensor, result.astype(tensor.dtype, copy=False))
        return tensor
    return result.astype(arr.dtype, copy=False)


def allreduce_multigpu(tensor_list, group_name: str = "default", op=ReduceOp.SUM):
    return [allreduce(t, group_name, op) for t in tensor_list]


def allgather(tensor, group_name: str = "default", timeout: float = 120.0):
    g = _group(group_name)
    arr = _to_numpy(tensor)

    def _go(seq, tel):
        if g.backend == "xla":
            gathered = _xla_collective(g, "allgather", arr, timeout=timeout,
                                       seq=seq)
            return [gathered[r] for r in range(g.world_size)]
        outs = _phase(g, "ag", timeout, _enc_tensor(arr), seq=seq, tel=tel)
        # gathered tensors escape to the caller: copy out of the rpc
        # receive buffers (the frames would pin them otherwise)
        return [np.array(_dec_tensor(o)[0]) for o in outs]

    return _op(g, "allgather", arr.nbytes, _go)


def reducescatter(tensor, group_name: str = "default", op: str = ReduceOp.SUM,
                  timeout: float = 120.0):
    """Reduce across ranks, then scatter: rank r receives shard r of the
    reduction (input's leading dim must divide by world_size)."""
    g = _group(group_name)
    arr = _to_numpy(tensor)
    if arr.shape[0] % g.world_size != 0:
        raise ValueError(
            f"leading dim {arr.shape[0]} not divisible by world size {g.world_size}"
        )

    def _go(seq, tel):
        if g.backend == "xla":
            if op == ReduceOp.SUM:
                return _xla_collective(g, "reducescatter", arr,
                                       timeout=timeout, seq=seq)
            gathered = _xla_collective(g, "allgather", arr, timeout=timeout,
                                       seq=seq)
            reduced = _REDUCERS[op](gathered)
            return np.split(reduced, g.world_size, axis=0)[g.rank]
        outs = _phase(g, "rs", timeout, _enc_tensor(arr), seq=seq, tel=tel)
        reduced = _REDUCERS[op](np.stack([_dec_tensor(o)[0] for o in outs]))
        return np.split(reduced, g.world_size, axis=0)[g.rank]

    return _op(g, "reducescatter", arr.nbytes, _go)


def broadcast(tensor, src_rank: int = 0, group_name: str = "default",
              timeout: float = 120.0):
    g = _group(group_name)
    # Non-src store-backend ranks never touch their local tensor (its
    # contents are about to be overwritten): materializing it here only
    # to count bytes would force a device-to-host copy per broadcast.
    # They contribute 0 payload bytes to the telemetry, which is honest.
    if g.backend == "xla" or g.rank == src_rank:
        arr = _to_numpy(tensor)
        nbytes = arr.nbytes
    else:
        arr, nbytes = None, 0

    def _go(seq, tel):
        if g.backend == "xla":
            return _xla_collective(g, "broadcast", arr, extra=(src_rank,),
                                   timeout=timeout, seq=seq)
        payload = _enc_tensor(arr) if g.rank == src_rank else b""
        outs = _phase(g, "bc", timeout, payload, seq=seq, tel=tel)
        # copy out of the rpc receive buffer: the decode is a read-only
        # view that would otherwise pin the frame (and surprise callers
        # who got owned writable arrays from the old pickle path)
        return np.array(_dec_tensor(outs[src_rank])[0])

    result = _op(g, "broadcast", nbytes, _go)
    if g.rank == src_rank:
        return tensor
    if isinstance(tensor, np.ndarray) and tensor.flags.writeable:
        np.copyto(tensor, result.astype(tensor.dtype, copy=False))
        return tensor
    return result


def barrier(group_name: str = "default", timeout: float = 120.0):
    g = _group(group_name)

    def _go(seq, tel):
        if g.backend == "xla":
            _xla_collective(g, "psum", np.zeros((1,), np.float32),
                            timeout=timeout, seq=seq)
            return None
        _phase(g, "barrier", timeout, b"1", seq=seq, tel=tel)
        return None

    _op(g, "barrier", 0, _go)


def send(tensor, dst_rank: int, group_name: str = "default"):
    """Point-to-point send (ray parity: collective.py send). Messages between
    each (src, dst) pair are ordered by a dedicated channel counter, so
    asymmetric patterns (rank0 sending to many peers) stay matched."""
    g = _group(group_name)
    seq = g.p2p_send.get(dst_rank, 0)
    g.p2p_send[dst_rank] = seq + 1
    key = f"{g.keybase}:p2p:{seq}:{g.rank}->{dst_rank}".encode()
    _kv_put(key, _enc_tensor(_to_numpy(tensor)), volatile=True)


def recv(tensor, src_rank: int, group_name: str = "default",
         timeout: float = 120.0):
    g = _group(group_name)
    seq = g.p2p_recv.get(src_rank, 0)
    g.p2p_recv[src_rank] = seq + 1
    key = f"{g.keybase}:p2p:{seq}:{src_rank}->{g.rank}".encode()
    data, _ = _dec_tensor(
        _kv_wait(key, timeout, abort_key=g.keybase.encode() + _ABORT_SUFFIX)
    )
    if isinstance(tensor, np.ndarray) and tensor.flags.writeable:
        np.copyto(tensor, data.astype(tensor.dtype, copy=False))
        return tensor
    # escaping result: own it — the decode may be a read-only view over
    # the rpc receive frame
    return np.array(data)
