"""schedsim: determinism, chaos replay, policy A/B, smoke-at-scale.

The tier-1 gates for the gang scheduler's simulation harness:

* determinism — same SimSpec (seed + chaos spec) -> byte-identical
  event trace (the property every chaos replay and policy A/B rests on);
* the 500-node smoke finishes fast (<10s even on a loaded CI core) with
  the contention policy's aggregate ring overlap no worse than the
  resource-fit baseline's;
* chaos rules in faultsim syntax actually kill / stall simulated nodes
  and the requeue bookkeeping stays consistent (all capacity returned
  once every gang departs).

The full 10k-node acceptance run is ``python -m ray_tpu schedsim --nodes
10000 --ab`` (about 9 s).
"""

import time

import pytest

from ray_tpu._private import schedsim

pytestmark = pytest.mark.schedsim


def spec(**kw):
    kw.setdefault("nodes", 200)
    kw.setdefault("seed", 11)
    return schedsim.SimSpec(**kw)


def test_same_seed_same_trace_bytes():
    r1, t1 = schedsim.run_with_trace(spec(policy="contention"))
    r2, t2 = schedsim.run_with_trace(spec(policy="contention"))
    assert t1 == t2
    assert r1["trace_sha256"] == r2["trace_sha256"]
    assert r1 == r2


def test_same_seed_same_trace_with_chaos():
    chaos = "sim000[0-7]:drop:1:42;sim001.:delay:1:43:500"
    r1, t1 = schedsim.run_with_trace(spec(seed=3, chaos=chaos))
    r2, t2 = schedsim.run_with_trace(spec(seed=3, chaos=chaos))
    assert t1 == t2 and r1["trace_sha256"] == r2["trace_sha256"]


def test_different_seed_different_trace():
    _, t1 = schedsim.run_with_trace(spec(seed=1))
    _, t2 = schedsim.run_with_trace(spec(seed=2))
    assert t1 != t2


def test_smoke_500_nodes_contention_no_worse_than_baseline():
    """The tier-1 A/B gate: 500 simulated nodes, both policies, fast,
    and the contention policy must not create MORE ring overlap than
    resource-fit placement (on this workload it eliminates it)."""
    t0 = time.monotonic()
    cont = schedsim.run(spec(nodes=500, seed=7, policy="contention"))
    base = schedsim.run(spec(nodes=500, seed=7, policy="baseline"))
    wall = time.monotonic() - t0
    assert wall < 10.0, f"500-node smoke took {wall:.1f}s"
    assert cont["placed"] > 0 and base["placed"] > 0
    assert cont["total_contention"] <= base["total_contention"]
    # the policies see the same workload
    assert cont["gangs"] == base["gangs"]
    for r in (cont, base):
        lat = r["placement_latency_s"]
        assert 0.0 < lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
        assert 0.0 < r["utilization"] < 1.0


def test_chaos_kill_requeues_and_books_balance():
    """A drop rule kills matching nodes; their gangs requeue and the
    books balance: after the event horizon every gang has departed, so
    reserved capacity returns to zero (the epoch guard on start/depart
    events is what keeps a requeued gang from being double-freed)."""
    chaos = "sim0000[0-9]:drop:1:5"  # kill 10 of 100 nodes
    sim = schedsim.SchedSim(spec(nodes=100, seed=9, chaos=chaos))
    report = sim.run()
    trace = sim.trace.text()
    assert " kill " in trace
    dead = [nid for nid, n in sim.nodes.items() if not n.alive]
    assert len(dead) == 10
    assert sim._used_cpu == pytest.approx(0.0)
    assert not sim.placed
    assert report["placed"] >= report["gangs"]  # requeues re-place


def test_chaos_heartbeat_delay_restores_node():
    chaos = "sim00000:delay:1:5:200"
    sim = schedsim.SchedSim(spec(nodes=50, seed=2, chaos=chaos))
    sim.run()
    trace = sim.trace.text()
    assert "hb_delay ms=200 node=sim00000" in trace
    assert "hb_restore node=sim00000" in trace
    assert sim.nodes["sim00000"].alive  # the stall healed


def test_bad_policy_rejected():
    with pytest.raises(ValueError):
        schedsim.SchedSim(spec(policy="nope"))


def test_report_shape():
    r = schedsim.run(spec(nodes=100, seed=1))
    for key in ("policy", "nodes", "gangs", "placed", "failed", "repacks",
                "placement_latency_s", "utilization", "mean_contention",
                "total_contention", "final_ring_overlap_ratio", "events",
                "trace_sha256"):
        assert key in r, key


def test_repack_fires_under_fragmentation():
    """Drive the sim's repack path directly: a strict-spread gang that
    can't place on the live view gets placed after migrating an idle
    (placed-but-not-started) bundle of another gang — the same
    plan_repack the GCS executes over RPC."""
    s = spec(nodes=4, seed=1, gang_size=3, gangs=1,
             big_node_every=0, policy="contention")
    sim = schedsim.SchedSim(s)
    nodes = sorted(sim.nodes)
    # hand-fragment: one big node, one busy node, one idle-bundle node
    sim.nodes[nodes[3]].resources_total = {"CPU": 8.0}
    sim.nodes[nodes[3]].resources_available = {"CPU": 8.0}
    sim.nodes[nodes[1]].resources_available = {"CPU": 0.0}  # running
    blocker = schedsim._Gang(
        gang_id="blocker", bundles=[{"CPU": 4.0}], strategy="PACK",
        arrival_t=0.0, hold_s=100.0,
        placement=[nodes[0]], placed_t=0.0, running=False)
    sim.nodes[nodes[0]].resources_available = {"CPU": 0.0}
    sim.placed["blocker"] = blocker
    gang = schedsim._Gang(
        gang_id="g0", bundles=[{"CPU": 4.0}] * 3,
        strategy="STRICT_SPREAD", arrival_t=0.0, hold_s=1.0)
    sim._try_place(gang)
    assert gang.placement is not None
    assert sim.repacks == 1
    assert blocker.placement == [nodes[3]]  # parked on the big node
    assert "repack" in sim.trace.text()
