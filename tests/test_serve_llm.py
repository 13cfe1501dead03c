"""LLM serving engine (serve/llm/): continuous batching, arena-paged KV
cache, prefix-affinity routing.

Fast deterministic units (tier-1 under the ``llm`` marker): prefix chain
hash nesting + longest-match semantics, the KV pool's page lifecycle in
heap AND arena mode (the arena path driven against a real
LocalObjectStore — zero-copy ``np.shares_memory`` proof, dead-range
reclaim on free, KVPG deletion instead of adoption on client death),
prefix-cache insert/match/LRU, the sequence scheduler's step-boundary
admission / copy-on-extend / drain baseline / shed behavior, the
affinity router's pick math directly on ``_RouterState``, and the
ingraph-psum parity satellite. E2E (own serve cluster): HTTP token
streaming with prefix reuse, 503 load shedding, kill -9 mid-decode with
zero leaked pages, and the flags-off byte-identity pin for plain
deployments.
"""

import asyncio
import json
import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import memview, slab_arena
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.object_store import LocalObjectStore
from ray_tpu.serve._common import (SERVE_CONTROLLER_NAME, SERVE_NAMESPACE,
                                   OverloadedError)
from ray_tpu.serve.llm import prefix
from ray_tpu.serve.llm.engine import LLMServer, SequenceScheduler
from ray_tpu.serve.llm.kv_cache import (KV_PAGE_OID_PREFIX, KVPool,
                                        PrefixCache, mint_page_oid)
from ray_tpu.serve.llm.model import SyntheticLLM

pytestmark = pytest.mark.llm

KV_HEX = KV_PAGE_OID_PREFIX.hex()


# ---------------------------------------------------------------------------
# prefix identity
# ---------------------------------------------------------------------------

def test_chain_hashes_nest():
    """A chain value commits to its WHOLE prefix: two prompts sharing
    block 1's tokens but not block 0's must not share block 1's chain."""
    a = prefix.chain_hashes([1, 2, 3, 4, 5, 6, 7], 2)
    assert len(a) == 3  # partial tail block has no identity
    assert a == prefix.chain_hashes([1, 2, 3, 4, 5, 6], 2)
    b = prefix.chain_hashes([9, 9, 3, 4, 5, 6], 2)
    assert a[0] != b[0] and a[1] != b[1]  # same block-1 tokens, new chain
    assert prefix.chain_hashes([1], 2) == []
    assert prefix.chain_hashes([1, 2, 3], 0) == []


def test_longest_match_depth_stops_at_first_miss():
    c = ["h0", "h1", "h2"]
    assert prefix.longest_match_depth(c, set()) == 0
    assert prefix.longest_match_depth(c, {"h0", "h1", "h2"}) == 3
    # a stray deeper hit after a miss is a collision, not a prefix
    assert prefix.longest_match_depth(c, {"h0", "h2"}) == 1


def test_tokenize_stable_across_processes():
    """Builtin hash() is interpreter-salted; the blake2b tokenizer must
    pin exact values or router/replica chains would never agree."""
    toks = prefix.tokenize("the quick fox the")
    assert toks == prefix.tokenize("the quick fox the")
    assert toks[0] == toks[3]  # same word, same id
    assert all(0 <= t < 50_000 for t in toks)


def test_extract_tokens_shapes():
    assert prefix.extract_tokens((), {"tokens": [1, 2]}) == [1, 2]
    assert prefix.extract_tokens(({"tokens": [3]},), {}) == [3]
    p = prefix.extract_tokens((), {"prompt": "a b"})
    assert p == prefix.tokenize("a b")
    assert prefix.extract_tokens((), {}) == []
    assert prefix.extract_tokens((42,), {}) == []  # non-LLM call shape

    class Env:  # serve Request envelope
        body = json.dumps({"prompt": "a b"}).encode()

    assert prefix.extract_tokens((Env(),), {}) == p


# ---------------------------------------------------------------------------
# KV pool: heap mode lifecycle + budget
# ---------------------------------------------------------------------------

def test_kv_pool_heap_budget_and_free():
    pool = KVPool(page_tokens=4, kv_dim=8, max_pages=3, use_arena=False)
    assert not pool.arena_backed
    pages = [pool.alloc() for _ in range(3)]
    assert all(p is not None for p in pages)
    assert pool.alloc() is None  # budget, not an exception
    assert pool.counts() == {"active": 3, "cached": 0, "free": 0}
    pool.incref(pages[0])
    pool.decref(pages[0])  # still one ref
    assert pool.available() == 0
    for p in pages:
        pool.decref(p)
    assert pool.counts() == {"active": 0, "cached": 0, "free": 3}


def test_prefix_cache_match_and_lru_eviction():
    pool = KVPool(page_tokens=4, kv_dim=8, max_pages=8, use_arena=False)
    cache = PrefixCache(pool, max_pages=2)
    p0, p1, p2 = (pool.alloc() for _ in range(3))
    cache.insert("c0", p0)
    cache.insert("c1", p1)
    got = cache.match(["c0", "c1", "c-miss", "c1"])
    assert got == [p0, p1]  # stops at first miss
    for p in got:
        pool.decref(p)
    # the match touched c0 then c1, so c0 is now LRU-oldest: inserting
    # c2 over the 2-page cap evicts c0
    cache.insert("c2", p2)
    assert set(cache.chains()) == {"c1", "c2"}
    # owner drops its refs; cached pages stay alive via the cache's ref
    for p in (p0, p1, p2):
        pool.decref(p)
    assert pool.counts()["cached"] == 2
    cache.note_lookup(10, 4)
    assert cache.hit_rate() == pytest.approx(0.4)
    cache.clear()
    assert pool.counts() == {"active": 0, "cached": 0, "free": 8}


# ---------------------------------------------------------------------------
# KV pool: arena mode against a real LocalObjectStore
# ---------------------------------------------------------------------------

class _FakeCoreWorker:
    """The thin slice of core-worker surface KVPool uses, wired straight
    to a LocalObjectStore: lease_slab request, free_objects notify, and
    the batched slab report."""

    def __init__(self, store: LocalObjectStore, client_id: str = "kv"):
        self.store = store
        self.client_id = client_id
        self.io = self
        self.raylet = self
        self.reports = []

    # io facade: the pool hands us the raylet "coroutine" (here: the
    # already-computed reply) to run/schedule
    def run(self, x, timeout=None):
        return x

    def call_soon(self, x):
        return x

    def request(self, op, payload):
        assert op == "lease_slab"
        return self.store.lease_slab(self.client_id, payload["bytes"],
                                     payload.get("seals"))

    def notify(self, op, payload):
        assert op == "free_objects"
        for b in payload["object_ids"]:
            self.store.delete(ObjectID(b))

    def _queue_slab_report(self, ent):
        self.reports.append(ent)
        self.store.record_slab_objects([ent])


def _arena_pool(tmp_path, **kw):
    store = LocalObjectStore(str(tmp_path / "shm"), 1 << 22)
    pool = KVPool(use_arena=False, **kw)
    pool._worker = _FakeCoreWorker(store)
    pool._writer = slab_arena.SlabWriter(str(tmp_path / "shm"))
    return store, pool


def test_kv_page_arena_zero_copy_and_ledger(tmp_path):
    memview.set_enabled(True)
    memview.reset()
    store, pool = _arena_pool(tmp_path, page_tokens=4, kv_dim=8,
                              max_pages=16)
    page = pool.alloc()
    assert page.oid is not None and page.oid.startswith(KV_PAGE_OID_PREFIX)
    # writes land in the segment mapping itself: an independent view of
    # the same store region sees them with zero copies anywhere
    page.data[0] = np.arange(8, dtype=np.float32)
    rb = pool.readback(page)
    assert np.shares_memory(page.data, rb)
    assert np.array_equal(rb[0], np.arange(8, dtype=np.float32))
    # accounting rode the slab report: the store ledger has the row with
    # the allocating callsite, and the page pins as referenced
    assert store.contains(ObjectID(page.oid))
    rows = {r["object_id"]: r for r in store.memview_objects()}
    row = rows[page.oid.hex()]
    assert row["state"] == "arena"
    assert "test_serve_llm.py" in (
        pool._worker.reports[0].get("c") or "")
    assert page.oid.hex() in {o.hex() for o in memview.external_pins()}
    # free: one notify, the entry goes dead (dead ranges grow), unpinned
    dead0 = store.arena_introspect()["dead_bytes"]
    pool.decref(page)
    assert not store.contains(ObjectID(page.oid))
    assert store.arena_introspect()["dead_bytes"] > dead0
    assert page.oid.hex() not in {o.hex() for o in memview.external_pins()}
    assert pool.counts() == {"active": 0, "cached": 0, "free": 16}
    memview.reset()


def test_kv_pages_die_with_client_not_adopted(tmp_path):
    """kill -9 semantics at the store layer: reclaim_client_slabs must
    DELETE a dead client's KV pages (cache dies with its replica) while
    still adopting ordinary sealed entries in the same segment."""
    store, pool = _arena_pool(tmp_path, page_tokens=4, kv_dim=8,
                              max_pages=16)
    kv_pages = [pool.alloc() for _ in range(3)]
    assert all(p.oid for p in kv_pages)
    # an ordinary unreported put in the same client's OTHER segment —
    # the adoption path the KV carve-out must not break
    r = store.lease_slab("kv", 1 << 20)
    w = slab_arena.SlabWriter(store.store_dir)
    w.attach(r["seg_id"], r["size"])
    data_oid = ObjectID.from_random()
    payload = b"d" * 4096
    assert w.try_put(data_oid.binary(), b"", [payload], len(payload))
    # the client dies without reporting/freeing anything
    new = store.reclaim_client_slabs("kv")
    assert data_oid.binary() in new, "real data must be adopted"
    assert store.contains(data_oid)
    for p in kv_pages:
        assert p.oid not in new, "KV pages must not be adopted"
        assert not store.contains(ObjectID(p.oid))


def test_kv_pool_releases_lease_on_close(tmp_path):
    store, pool = _arena_pool(tmp_path, page_tokens=4, kv_dim=8,
                              max_pages=16)
    page = pool.alloc()
    pool.decref(page)
    pool.close()  # graceful: seals + retires the lease via lease_slab
    assert store.reclaim_client_slabs("kv") == []


# ---------------------------------------------------------------------------
# sequence scheduler
# ---------------------------------------------------------------------------

def _sched(**kw):
    kw.setdefault("max_running", 4)
    kw.setdefault("max_queued", 8)
    pool = KVPool(page_tokens=kw.pop("page_tokens", 4),
                  kv_dim=8, max_pages=kw.pop("max_pages", 32),
                  use_arena=False)
    return SequenceScheduler(SyntheticLLM(kv_dim=8), pool, **kw)


async def _run_one(s, tokens, n):
    seq = await s.submit(tokens, n)
    out = [t async for t in s.stream(seq)]
    return seq, out


def test_scheduler_deterministic_and_prefix_reuse():
    async def main():
        s = _sched(prefix_cache_pages=16)
        seq1, out1 = await _run_one(s, list(range(10)), 6)
        seq2, out2 = await _run_one(s, list(range(10)), 6)
        assert len(out1) == 6 and out1 == out2, \
            "same prompt through cached pages must decode identically"
        assert seq1.cached_tokens == 0
        assert seq2.cached_tokens == 8  # 2 full pages of 4 reused
        assert s.cache.hit_rate() > 0
        s.stop()
        assert s.pool.counts()["active"] == 0, "stop leaked pages"
        assert s.pool.counts()["cached"] == 0
    asyncio.run(main())


def test_scheduler_copy_on_extend_protects_cached_tail():
    """Appending through a shared page must copy first: the cached
    page's bytes are other sequences' prefix."""
    async def main():
        s = _sched(prefix_cache_pages=16)
        await _run_one(s, list(range(8)), 4)   # caches 2-3 full pages
        chains = s.cache.chains()
        assert chains
        snap = {c: s.cache._pages[c].data.copy() for c in chains}
        # a second sequence reuses them then generates right through
        await _run_one(s, list(range(8)), 8)
        for c in chains:
            assert np.array_equal(s.cache._pages[c].data, snap[c]), \
                "cached page mutated by a borrowing sequence"
        s.stop()
    asyncio.run(main())


def test_scheduler_continuous_admits_mid_batch_drain_does_not():
    """Step boundaries driven by hand (no background task): the
    admission semantics without timing races."""
    async def main():
        cont = _sched(batching="continuous")
        cont.ensure_running = lambda: None
        a = await cont.submit(list(range(4)), 8)
        cont._admit()
        cont._decode_step()
        assert a.generated == 1
        b = await cont.submit(list(range(4)), 8)
        cont._admit()  # next step boundary: b joins the RUNNING batch
        assert a in cont.running and b in cont.running
        cont._decode_step()
        assert (a.generated, b.generated) == (2, 1)
        cont.stop()

        drain = _sched(batching="drain")
        drain.ensure_running = lambda: None
        a = await drain.submit(list(range(4)), 8)
        drain._admit()
        drain._decode_step()
        b = await drain.submit(list(range(4)), 8)
        drain._admit()
        assert b not in drain.running, \
            "drain: b admitted into a non-empty batch"
        while a in drain.running:
            drain._decode_step()
        assert b.generated == 0
        drain._admit()  # batch drained: NOW b enters
        assert b in drain.running
        drain.stop()
    asyncio.run(main())


def test_scheduler_sheds_on_queue_and_impossible_kv():
    async def main():
        s = _sched(max_queued=1, max_pages=4, page_tokens=4)
        # worst case 5 pages > 4-page pool: doomed, shed immediately
        with pytest.raises(OverloadedError):
            await s.submit(list(range(4)), 16)
        # fill the queue without running the loop (never start it)
        await s.submit(list(range(4)), 4)
        with pytest.raises(OverloadedError) as ei:
            await s.submit(list(range(4)), 4)
        assert "SERVE_OVERLOADED" in str(ei.value)
        assert s.shed_total == 2
        assert s.queue_depth() == 1
        s.stop()
    asyncio.run(main())


def test_scheduler_kv_budget_holds_admission_until_frees():
    """A queued sequence that does not fit waits at the head and gets
    admitted once the running one frees its pages."""
    async def main():
        s = _sched(max_pages=4, page_tokens=4, max_running=4)
        a = await s.submit(list(range(8)), 4)   # 3 pages worst case
        b = await s.submit(list(range(8)), 4)   # needs 3 > 1 free: waits
        out_a = [t async for t in s.stream(a)]
        out_b = [t async for t in s.stream(b)]
        assert len(out_a) == 4 and len(out_b) == 4
        assert s.steps >= 8, "b cannot have run concurrently with a"
        s.stop()
    asyncio.run(main())


# ---------------------------------------------------------------------------
# affinity router units (directly on _RouterState)
# ---------------------------------------------------------------------------

def _router(replicas, reported, index, block_tokens=2, fresh=True):
    from ray_tpu.serve.handle import _RouterState

    st = _RouterState("app", "dep")
    st.replicas = [(n, None) for n in replicas]
    st.inflight = {n: 0 for n in replicas}
    st.reported = dict(reported)
    st.reported_age0 = 0.0
    st.reported_at = time.monotonic() if fresh else None
    st.report_max_age_s = 5.0
    st.prefix_index = {n: frozenset(v) for n, v in index.items()}
    st.prefix_block_tokens = block_tokens
    return st


def test_router_longest_prefix_wins():
    chains = ["c0", "c1", "c2"]
    st = _router(["r1", "r2"], {"r1": 0, "r2": 0},
                 {"r1": ["c0"], "r2": ["c0", "c1"]})
    assert st.pick(chains)[0] == "r2"
    # equal depth: lower score breaks the tie
    st = _router(["r1", "r2"], {"r1": 3, "r2": 1},
                 {"r1": ["c0", "c1"], "r2": ["c0", "c1"]})
    assert st.pick(chains)[0] == "r2"


def test_router_affinity_yields_to_load():
    """Cache warmth must not defeat load balancing: a drowning winner is
    skipped (p2c takes over)."""
    chains = ["c0", "c1"]
    st = _router(["r1", "r2"], {"r1": 0.0, "r2": 10.0},
                 {"r2": ["c0", "c1"]})
    assert st.affinity_pick(chains) is None
    assert st.pick(chains)[0] in ("r1", "r2")  # legacy p2c path


def test_router_stale_report_disables_affinity():
    chains = ["c0"]
    st = _router(["r1", "r2"], {}, {"r2": ["c0"]}, fresh=False)
    assert st.reported_stale()
    assert st.affinity_pick(chains) is None
    picked = {st.pick(chains)[0] for _ in range(40)}
    assert picked == {"r1", "r2"}, "stale digests must fall back to p2c"


def test_router_plain_deployment_untouched():
    """No digests reported => request_chains is [] and pick() is exactly
    the legacy p2c — the flags-off byte-identity of the router."""
    st = _router(["r1", "r2"], {"r1": 0, "r2": 5}, {}, block_tokens=0)
    assert st.request_chains((), {"prompt": "a b c"}) == []
    assert st.pick([])[0] in ("r1", "r2")


def test_router_request_chains_from_llm_call_shapes():
    st = _router(["r1"], {"r1": 0}, {"r1": ["x"]}, block_tokens=2)
    toks = prefix.tokenize("w0 w1 w2 w3")
    want = prefix.chain_hashes(toks, 2)
    assert st.request_chains((), {"prompt": "w0 w1 w2 w3"}) == want
    assert st.request_chains((), {"tokens": toks}) == want
    assert st.request_chains((7,), {}) == []  # not an LLM request


# ---------------------------------------------------------------------------
# e2e: serve cluster
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llm_cluster():
    ray_tpu.init(num_cpus=4)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _url(path):
    return f"http://127.0.0.1:{serve.http_port()}{path}"


def _stream_tokens(body, path="/llm", timeout=60):
    import requests

    toks = []
    with requests.post(_url(path), json=body, stream=True,
                       timeout=timeout) as r:
        assert r.status_code == 200, r.text
        for line in r.iter_lines():
            if line:
                toks.append(json.loads(line)["token"])
    return toks


def test_llm_http_stream_prefix_reuse_and_metrics(llm_cluster):
    dep = serve.deployment(LLMServer, name="llm").options(num_replicas=1)
    h = serve.run(dep.bind(page_tokens=4, max_pages=64,
                           prefix_cache_pages=16),
                  name="llm", route_prefix="/llm")
    body = {"prompt": "sess1 w1 w2 w3 w4 w5 w6 w7", "max_tokens": 6}
    out1 = _stream_tokens(body)
    out2 = _stream_tokens(body)
    assert len(out1) == 6 and out1 == out2, \
        "cached-prefix decode must be byte-identical"
    info = ray_tpu.get(h.options(method_name="debug_info").remote().ref)
    assert info["arena_backed"] is True, \
        "in-cluster KV pages must be slab-arena entries, not heap"
    assert info["hit_rate"] > 0, "second request must hit the prefix cache"
    assert info["counts"]["cached"] > 0
    assert info["tokens_decode"] >= 12
    assert {"kv_cache_hit_rate", "kv_cache_pages", "serve_llm_batch_size",
            "serve_llm_shed_total", "serve_llm_tokens_total"} \
        <= set(info["metric_names"])
    proof = ray_tpu.get(
        h.options(method_name="debug_zero_copy").remote().ref)
    assert proof == {"oid_prefix_ok": True, "shares_memory": True,
                     "roundtrip_ok": True}
    # controller load report carries the llm block + prefix digest the
    # affinity router indexes
    controller = ray_tpu.get_actor(SERVE_CONTROLLER_NAME,
                                   namespace=SERVE_NAMESPACE)
    deadline = time.time() + 15
    llm_state = {}
    while time.time() < deadline:
        st = ray_tpu.get(controller.get_replica_state.remote("llm", "llm"))
        llm_state = st.get("llm") or {}
        if any(r.get("prefix_digest") for r in llm_state.values()):
            break
        time.sleep(0.3)
    assert llm_state, "controller never picked up the llm load report"
    rep = next(iter(llm_state.values()))
    assert rep["block_tokens"] == 4 and rep["prefix_digest"]
    serve.delete("llm")


def test_llm_http_shed_returns_503(llm_cluster):
    dep = serve.deployment(LLMServer, name="tiny").options(num_replicas=1)
    serve.run(dep.bind(page_tokens=4, max_pages=4, max_queued=2),
              name="tiny", route_prefix="/tiny")
    import requests

    # worst-case pages exceed the whole pool: shed at submit, BEFORE any
    # stream bytes — the proxy must answer a real 503, not a 200 + error
    r = requests.post(_url("/tiny"),
                      json={"prompt": "a b c", "max_tokens": 500},
                      timeout=30)
    assert r.status_code == 503
    assert r.headers.get("Retry-After") == "1"
    serve.delete("tiny")


def test_llm_kill9_mid_decode_leaves_no_pages(llm_cluster):
    """kill -9 a replica while it streams: the raylet's death reclaim
    must erase every KVPG page (dead ranges, not adoption) — the store
    holds no KV rows and memview issues no leak verdicts for them."""
    import requests

    from ray_tpu.util import state

    dep = serve.deployment(LLMServer, name="victim").options(
        num_replicas=1)
    h = serve.run(dep.bind(page_tokens=4, max_pages=64,
                           step_delay_s=0.05),
                  name="victim", route_prefix="/victim")
    info = ray_tpu.get(h.options(method_name="debug_info").remote().ref)
    assert info["arena_backed"] is True
    r = requests.post(_url("/victim"),
                      json={"prompt": "k1 k2 k3 k4 k5", "max_tokens": 200},
                      stream=True, timeout=30)
    it = r.iter_lines()
    next(it)  # decode underway: live KV pages in the arena
    next(it)
    os.kill(info["pid"], signal.SIGKILL)
    r.close()
    deadline = time.time() + 20
    kv_rows = None
    while time.time() < deadline:
        merged = state.object_summary()
        kv_rows = [row for row in merged["objects"]
                   if row["object_id"].startswith(KV_HEX)]
        if not kv_rows:
            break
        time.sleep(0.5)
    assert kv_rows == [], f"KV pages survived replica death: {kv_rows}"
    assert not [v for v in merged["verdicts"]
                if v["kind"] == "leak"
                and v.get("object_id", "").startswith(KV_HEX)]
    serve.delete("victim")


def test_flags_off_plain_deployment_byte_identical(llm_cluster):
    """The pin: a non-LLM deployment's replica metrics, controller state
    and queue-depth source are exactly the legacy shapes — nothing in
    the LLM plumbing leaks into plain serve."""

    @serve.deployment
    class Plain:
        def __call__(self, request):
            return "ok"

    h = serve.run(Plain.bind(), name="plain", route_prefix="/plain")
    assert ray_tpu.get(h.remote(None).ref) == "ok"
    controller = ray_tpu.get_actor(SERVE_CONTROLLER_NAME,
                                   namespace=SERVE_NAMESPACE)
    st = ray_tpu.get(controller.get_replica_state.remote("plain", "Plain"))
    assert "llm" not in st, "plain deployments must not report llm state"
    assert st["names"]
    rep = ray_tpu.get_actor(st["names"][0], namespace=SERVE_NAMESPACE)
    m = ray_tpu.get(rep.get_metrics.remote())
    assert set(m) == {"ongoing", "total"}, \
        f"legacy get_metrics payload changed: {sorted(m)}"
    # router state for a plain deployment: no prefix index, pick == p2c
    state_obj = h._state
    state_obj.refresh(force=True)
    assert state_obj.prefix_index == {}
    assert state_obj.prefix_block_tokens == 0
    serve.delete("plain")
