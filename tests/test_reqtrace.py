"""Request observatory (reqtrace.py): per-request serve tracing.

Unit: ring bounds/drops, zero-cost-off, merge/join with missing-side
records, skew-verdict math, chrome-trace structure, aggregator dedup,
router staleness fallback. E2E (real serve cluster): request-id
propagation proxy→replica, batch-span attribution, streaming TTFT,
slow-replica skew verdict on a 2-replica deployment, dashboard + agent
endpoints, and the blind-spot gauges (queue depth, handle inflight,
batch histograms) on the cluster scrape.
"""

import json
import os
import time
import urllib.request

import pytest
import requests

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import reqtrace

pytestmark = pytest.mark.reqtrace


# ---------------------------------------------------------------------------
# unit: ring + merge + verdict math (no cluster)
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_ring():
    reqtrace.set_enabled(True)
    reqtrace.reset()
    yield
    reqtrace.set_enabled(True)
    reqtrace.reset()


def test_ring_bounds_and_drop_accounting(fresh_ring):
    from ray_tpu._private.config import GLOBAL_CONFIG

    old = GLOBAL_CONFIG.reqtrace_ring_size
    GLOBAL_CONFIG.reqtrace_ring_size = 32
    try:
        for i in range(100):
            reqtrace.record_span(f"rid{i:04d}", "execute", 0.0, 1.0)
        snap = reqtrace.process_snapshot()
        assert len(snap["records"]) == 32
        assert snap["dropped"] == 100 - 32
        assert snap["record_calls"] == 100
        # oldest-first: the surviving records are the newest 32
        assert snap["records"][0]["rid"] == "rid0068"
        assert snap["records"][-1]["rid"] == "rid0099"
    finally:
        GLOBAL_CONFIG.reqtrace_ring_size = old


def test_zero_cost_when_disabled(fresh_ring):
    reqtrace.set_enabled(False)
    before = reqtrace.record_calls()
    reqtrace.record_span("rid1", "execute", 0.0, 1.0)
    reqtrace.record_mark("rid1", "first_byte", 0.5)
    assert reqtrace.record_calls() == before
    assert reqtrace.snapshot() == []
    reqtrace.set_enabled(True)
    reqtrace.record_span("rid1", "execute", 0.0, 1.0)
    assert reqtrace.record_calls() == before + 1


def _span(rid, phase, start, end, replica="", detail=None, **kw):
    return {"kind": "span", "idx": 0, "rid": rid, "phase": phase,
            "app": kw.get("app", "a"),
            "deployment": kw.get("deployment", "d"),
            "replica": replica, "start": start, "end": end,
            "detail": detail}


def test_merge_joins_by_rid_and_flags_missing_side(fresh_ring):
    records = [
        # complete request: proxy + replica sides join into one row
        _span("r1", "ingress", 0.0, 0.001),
        _span("r1", "route", 0.001, 0.002, detail={"replica": "rep0"}),
        _span("r1", "queue", 0.002, 0.010, replica="rep0"),
        _span("r1", "execute", 0.010, 0.050, replica="rep0"),
        _span("r1", "serialize", 0.051, 0.052),
        # routed but the replica side never arrived (died / overwritten)
        _span("r2", "ingress", 1.0, 1.001),
        _span("r2", "route", 1.001, 1.002, detail={"replica": "rep1"}),
        # mark with a first_byte for ttft
        {"kind": "mark", "idx": 0, "rid": "r1", "name": "first_byte",
         "app": "a", "deployment": "d", "replica": "rep0", "ts": 0.030},
    ]
    rows = reqtrace.merge_requests(records)
    assert len(rows) == 2
    r1 = next(r for r in rows if r["rid"] == "r1")
    assert r1["replica"] == "rep0"
    assert r1["missing"] is None
    assert {p["phase"] for p in r1["phases"]} == {
        "ingress", "route", "queue", "execute", "serialize"}
    assert r1["ttft"] == pytest.approx(0.030)
    assert r1["total"] == pytest.approx(0.052)
    r2 = next(r for r in rows if r["rid"] == "r2")
    assert r2["missing"] == "replica"
    assert r2["replica"] == "rep1"  # from the route decision


def test_skew_verdict_names_dominant_phase(fresh_ring):
    records = []
    # rep0: fast, 6 requests (1ms queue + 10ms execute)
    for i in range(6):
        t = float(i)
        records += [
            _span(f"f{i}", "queue", t, t + 0.001, replica="rep0"),
            _span(f"f{i}", "execute", t + 0.001, t + 0.011,
                  replica="rep0"),
        ]
    # rep1: slow, 6 requests — and it's QUEUE wait, not execute
    for i in range(6):
        t = 100.0 + i
        records += [
            _span(f"s{i}", "queue", t, t + 0.200, replica="rep1"),
            _span(f"s{i}", "execute", t + 0.200, t + 0.210,
                  replica="rep1"),
        ]
    merged = reqtrace.merge_records(records)
    verdicts = merged["verdicts"]
    assert len(verdicts) == 1
    v = verdicts[0]
    assert v["replica"] == "rep1"
    assert v["dominant_phase"] == "queue"
    assert v["ratio"] > 10
    assert "queue" in v["detail"]


def test_chrome_trace_structure(fresh_ring):
    records = [
        _span("r1", "ingress", 0.0, 0.001),
        _span("r1", "queue", 0.002, 0.01, replica="rep0"),
        _span("r1", "execute", 0.01, 0.05, replica="rep0"),
        {"kind": "mark", "idx": 0, "rid": "r1", "name": "first_byte",
         "app": "a", "deployment": "d", "replica": "rep0", "ts": 0.03},
    ]
    trace = reqtrace.chrome_trace(reqtrace.merge_records(records))
    metas = [ev for ev in trace if ev["ph"] == "M"]
    slices = [ev for ev in trace if ev["ph"] == "X"]
    names = {ev["args"]["name"] for ev in metas}
    assert any(n.startswith("replica rep0") for n in names)
    assert any(n.startswith("proxy") for n in names)
    assert all(ev["args"]["rid"] == "r1" for ev in slices)
    assert {ev["name"] for ev in slices} == {"ingress", "queue", "execute"}
    json.dumps(trace)  # must be serializable as-is


def test_aggregator_dedup_and_metrics(fresh_ring):
    from ray_tpu._private import metrics_core

    agg = reqtrace.RequestAggregator(registry=metrics_core.Registry())
    snap = {"node_id": "n1", "pid": 1, "records": [
        dict(_span("r1", "execute", 0.0, 0.5, replica="rep0"), idx=0),
        dict(_span("r1", "queue", 0.0, 0.1, replica="rep0"), idx=1),
    ]}
    assert agg.fold([snap]) == 2
    # identical re-scrape: high-water mark folds nothing twice
    assert agg.fold([snap]) == 0
    assert len(agg.records()) == 2
    # a NEW process that recycled the pid (lower top idx) starts fresh
    snap2 = {"node_id": "n1", "pid": 1, "records": [
        dict(_span("r2", "execute", 1.0, 1.5, replica="rep0"), idx=0),
    ]}
    assert agg.fold([snap2]) == 1
    merged = agg.fold_and_merge([], limit=0)
    assert len(merged["requests"]) == 2


def test_router_staleness_fallback():
    """Stale replica-reported queue lengths must stop steering p2c:
    score() drops the reported component past the age threshold."""
    from ray_tpu.serve.handle import _RouterState

    st = _RouterState("app", "dep")
    st.reported = {"rep0": 100.0, "rep1": 0.0}
    st.inflight = {"rep0": 0, "rep1": 3}
    st.report_max_age_s = 5.0
    # fresh report: reported dominates
    st.reported_age0 = 0.0
    st.reported_at = time.monotonic()
    assert not st.reported_stale()
    assert st.score("rep0") == 100.0
    assert st.score("rep1") == 3.0
    # controller snapshot was already old at reply time: ignore it
    st.reported_age0 = 60.0
    assert st.reported_stale()
    assert st.score("rep0") == 0.0
    assert st.score("rep1") == 3.0
    # no age ever reported (controller never collected): local only
    st.reported_at = None
    assert st.reported_stale()


# ---------------------------------------------------------------------------
# e2e: real serve cluster
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_cluster():
    ray_tpu.init(num_cpus=4)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _url(path):
    return f"http://127.0.0.1:{serve.http_port()}{path}"


def _summary(retries=10, want=lambda m: True):
    """serve_summary with a few retries for scrape/ring propagation."""
    from ray_tpu.util import state

    merged = {}
    for _ in range(retries):
        merged = state.serve_summary()
        if want(merged):
            return merged
        time.sleep(0.3)
    return merged


def test_request_id_propagates_proxy_to_replica(serve_cluster):
    @serve.deployment
    class Echo:
        def __call__(self, request):
            return {"ok": True}

    serve.run(Echo.bind(), name="rt_echo", route_prefix="/rt_echo")
    r = requests.get(_url("/rt_echo"), timeout=30)
    assert r.status_code == 200
    rid = r.headers.get("x-request-id")
    assert rid and len(rid) == 16

    def has_row(m):
        return any(x["rid"] == rid for x in m.get("requests") or ())

    merged = _summary(want=has_row)
    row = next(x for x in merged["requests"] if x["rid"] == rid)
    phases = {p["phase"] for p in row["phases"]}
    # proxy-side AND replica-side spans joined under the minted id
    assert {"ingress", "route", "queue", "execute", "serialize"} <= phases
    assert row["missing"] is None
    assert row["app"] == "rt_echo" and row["deployment"] == "Echo"
    assert row["replica"].startswith("SERVE_REPLICA::")
    # the route span carries the router's inflight snapshot
    route = next(p for p in row["phases"] if p["phase"] == "route")
    assert "inflight" in (route["detail"] or {})
    serve.delete("rt_echo")


def test_batch_span_attribution(serve_cluster):
    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        async def __call__(self, items):
            return [i * 10 for i in items]

    handle = serve.run(Batched.bind(), name="rt_batch",
                       route_prefix="/rt_batch")
    futs = [handle.remote(i) for i in range(4)]
    assert sorted(f.result(timeout_s=30) for f in futs) == [0, 10, 20, 30]

    def has_batch(m):
        return any(p["phase"] == "batch_wait"
                   for x in m.get("requests") or ()
                   for p in x["phases"])

    merged = _summary(want=has_batch)
    batch_spans = [p for x in merged["requests"] for p in x["phases"]
                   if p["phase"] == "batch_wait"
                   and x["deployment"] == "Batched"]
    assert batch_spans
    # the flush stamped batch key + size into the span detail
    assert any((p["detail"] or {}).get("size", 0) > 1
               for p in batch_spans)
    assert all("key" in (p["detail"] or {}) for p in batch_spans)
    serve.delete("rt_batch")


def test_streaming_ttft_marks(serve_cluster):
    @serve.deployment
    class Gen:
        def __call__(self, request):
            for i in range(3):
                time.sleep(0.02)
                yield f"tok{i} "

    serve.run(Gen.bind(), name="rt_gen", route_prefix="/rt_gen")
    r = requests.get(_url("/rt_gen"), timeout=30)
    assert r.text == "tok0 tok1 tok2 "
    rid = r.headers.get("x-request-id")
    assert rid

    def has_ttft(m):
        return any(x["rid"] == rid and x["ttft"] is not None
                   for x in m.get("requests") or ())

    merged = _summary(want=has_ttft)
    row = next(x for x in merged["requests"] if x["rid"] == rid)
    assert row["ttft"] is not None and row["ttft"] > 0
    assert "first_byte" in row["marks"] and "last_byte" in row["marks"]
    assert row["marks"]["last_byte"] >= row["marks"]["first_byte"]
    # TTFT < total: the first token left before the stream finished
    assert row["ttft"] < row["total"] + 1e-9
    dep = next(d for d in merged["deployments"]
               if d["deployment"] == "Gen")
    assert dep["ttft_p50"] is not None
    serve.delete("rt_gen")


def test_slow_replica_skew_verdict_e2e(serve_cluster, tmp_path):
    """Two replicas, one deliberately slowed with serial execution: the
    merged verdict must name the slow replica and attribute its latency
    to QUEUE wait (requests pile up behind the slow handler), not to
    execute."""
    sentinel = str(tmp_path / "slow_replica_winner")

    @serve.deployment(num_replicas=2, max_ongoing_requests=1)
    class Uneven:
        def __init__(self):
            import os

            # exactly one replica wins the sentinel and becomes slow
            try:
                fd = os.open(sentinel, os.O_CREAT | os.O_EXCL)
                os.close(fd)
                self.slow = True
            except FileExistsError:
                self.slow = False

        def __call__(self, request=None):
            time.sleep(0.15 if self.slow else 0.005)
            return "slow" if self.slow else "fast"

    handle = serve.run(Uneven.bind(), name="rt_skew",
                       route_prefix="/rt_skew")
    # concurrent burst: requests queue behind the slow replica's serial
    # handler (max_ongoing_requests=1), so ITS requests accumulate queue
    # wait far beyond their 150ms execute
    futs = [handle.remote() for _ in range(30)]
    outs = [f.result(timeout_s=60) for f in futs]
    assert "slow" in outs and "fast" in outs

    def has_verdict(m):
        return any(v["deployment"] == "Uneven"
                   for v in m.get("verdicts") or ())

    merged = _summary(retries=20, want=has_verdict)
    verdicts = [v for v in merged.get("verdicts") or ()
                if v["deployment"] == "Uneven"]
    assert verdicts, (merged.get("replicas"), merged.get("verdicts"))
    v = verdicts[0]
    assert v["kind"] == "slow_replica"
    assert v["dominant_phase"] == "queue", v
    # ... and the named replica really is the slow one: its requests
    # returned "slow"
    reps = {r["replica"]: r for r in merged["replicas"]
            if r["deployment"] == "Uneven"}
    assert v["replica"] in reps
    assert reps[v["replica"]]["mean_total"] > 1.5 * min(
        r["mean_total"] for r in reps.values())
    serve.delete("rt_skew")


def test_blind_spot_gauges_on_cluster_scrape(serve_cluster):
    """Satellite surfaces: serve_replica_queue_depth (tagged with the
    replica), serve_handle_inflight, and the serve_batch_* histograms
    all appear on the merged cluster scrape after traffic."""
    from ray_tpu._private import metrics_core
    from ray_tpu.util import metrics as m

    @serve.deployment
    class Mx:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.02)
        async def __call__(self, items):
            return items

    handle = serve.run(Mx.bind(), name="rt_mx", route_prefix="/rt_mx")
    futs = [handle.remote(i) for i in range(8)]
    for f in futs:
        f.result(timeout_s=30)
    deadline = time.monotonic() + 30
    need = {"serve_replica_queue_depth", "serve_handle_inflight",
            "serve_batch_size", "serve_batch_occupancy",
            "serve_batch_wait_seconds"}
    got = set()
    while time.monotonic() < deadline and not need <= got:
        summary = metrics_core.summarize(
            m.cluster_snapshot().get("merged", {}))
        got = {name for name in summary if name in need}
        time.sleep(0.5)
    assert need <= got, f"missing {need - got}"
    qd = summary["serve_replica_queue_depth"]["series"]
    assert any(s["tags"].get("replica", "").startswith("SERVE_REPLICA")
               for s in qd)
    bs = summary["serve_batch_size"]["series"]
    assert any(s.get("count", 0) > 0 for s in bs)
    serve.delete("rt_mx")


def test_dashboard_and_agent_serve_endpoints(serve_cluster):
    """Head /api/v0/serve_requests + /api/v0/serve_timeline and the
    node agent's /api/v0/reqtrace all answer with live JSON."""
    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    from ray_tpu.util.state import _agent_addr, _gcs_request

    @serve.deployment
    def ping(request):
        return "pong"

    serve.run(ping.bind(), name="rt_dash", route_prefix="/rt_dash")
    assert requests.get(_url("/rt_dash"), timeout=30).text == "pong"
    port = start_dashboard()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v0/serve_requests", timeout=60
        ) as resp:
            sv = json.loads(resp.read())
        assert "requests" in sv and "deployments" in sv
        assert any(d["deployment"] == "ping" for d in sv["deployments"])
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v0/serve_timeline", timeout=60
        ) as resp:
            trace = json.loads(resp.read())
        assert isinstance(trace, list)
        assert any(ev.get("ph") == "X" for ev in trace)
        # the SPA ships the Serve tab + its fetch wiring
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10
        ) as resp:
            body = resp.read().decode()
        assert "serve_requests" in body and '"serve"' in body
    finally:
        stop_dashboard()
    # node agent: node-local rings behind /api/v0/reqtrace
    nodes = [n for n in _gcs_request("get_nodes") if n.get("alive")]
    base = next((b for b in (_agent_addr(n) for n in nodes) if b), None)
    assert base, "no node agent registered"
    with urllib.request.urlopen(f"{base}/api/v0/reqtrace",
                                timeout=30) as resp:
        node_view = json.loads(resp.read())
    assert "processes" in node_view
    assert any(p.get("records") for p in node_view["processes"]
               if not p.get("error"))
    serve.delete("rt_dash")


def test_load_harness_smoke(serve_cluster):
    """The open-loop harness drives a 2-replica deployment through the
    real proxy and reports latency/TTFT percentiles + queue-depth
    samples (CI-sized: ``run_load`` takes 1k connections as well)."""
    from ray_tpu.serve.load_harness import run_load

    @serve.deployment(num_replicas=2, max_ongoing_requests=256)
    class L:
        async def __call__(self, request):
            return b"ok"

    serve.run(L.bind(), name="rt_load", route_prefix="/rt_load")
    out = run_load(_url("/rt_load"), rps=40, duration_s=2.0,
                   connections=64, depth_sampler=lambda: 0.0,
                   depth_sample_interval_s=0.5)
    assert out["ok"] >= 0.9 * out["requests"], out["error_kinds"]
    assert out["latency"]["p50"] > 0
    assert out["ttft"]["count"] > 0
    assert out["queue_depth_series"], "no depth samples collected"
    assert out["peak_inflight"] >= 1
    # open-loop: offered schedule spans ~duration_s regardless of service
    assert out["wall_s"] >= 1.5
    serve.delete("rt_load")


def test_delete_drains_replica_rings(serve_cluster):
    """Deleting a deployment before any scrape must not lose its
    replica-side spans: the controller fires one final reqtrace scrape
    before killing replicas (steptrace parity: the BackendExecutor's
    shutdown scrape), so joined rows survive the delete."""
    from ray_tpu.util import state

    @serve.deployment(num_replicas=2)
    class Drained:
        def __call__(self, request=None):
            return b"ok"

    handle = serve.run(Drained.bind(), name="rt_drain",
                       route_prefix="/rt_drain")
    futs = [handle.remote() for _ in range(6)]
    assert [f.result(timeout_s=30) for f in futs] == [b"ok"] * 6
    # no serve_summary() here: the delete itself must capture the rings
    serve.delete("rt_drain")

    merged = state.serve_summary()
    rows = [r for r in merged.get("requests") or ()
            if r["deployment"] == "Drained"]
    assert rows, "no rows survived the delete"
    joined = [r for r in rows if r["missing"] is None]
    assert joined, "every surviving row lost its replica side"
    phases = {p["phase"] for r in joined for p in r["phases"]}
    assert {"queue", "execute"} <= phases, phases
