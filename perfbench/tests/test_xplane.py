"""The trace reduction: interval arithmetic on a hand-made trace whose
answers can be worked out on paper, then the same functions on a small
trace recorded on the chip (perfbench/tests/data/, see its README)."""

import os

import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _trace():
    """Two chips, three steps of 10 ms device work 2 ms apart, a 1 ms data
    span inside each gap, then a 20 ms save with the device idle.
    Chip 0, each step: 6 ms compute, all-gather in flight 4..9 ms with
    compute running beside it 6..8 ms, 1 ms synchronous all-reduce."""
    ops0, ops1, spans, modules = [], [], [], []
    for i in range(3):
        t = i * 12 * MS
        spans.append(("bench/data", t - 1 * MS, t - 0 * MS - 1))
        spans.append(("bench/step", t, t + 11 * MS))
        ops0 += [("fusion.1", t, t + 4 * MS),
                 ("all-gather-start.3", t + 4 * MS, t + 4 * MS + 1000),
                 ("fusion.2", t + 6 * MS, t + 8 * MS),
                 ("all-gather-done.3", t + 8 * MS, t + 9 * MS),
                 ("all-reduce.7", t + 9 * MS, t + 10 * MS)]
        ops1 += [("fusion.1", t, t + 5 * MS)]
        # the device's clock runs 0.1 ms ahead of the host's
        modules.append(("jit_step(1)", t - MS // 10, t + 10 * MS))
    spans.append(("bench/ckpt", 35 * MS, 55 * MS))
    trace = xplane.Trace(ops={0: sorted(ops0, key=lambda o: o[1]),
                              1: ops1}, modules={0: modules},
                         spans=sorted(spans, key=lambda s: s[1]))
    return trace


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.length([(0, 3), (5, 7)]) == 5
    assert xplane.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert xplane.overlap([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == 4
    assert xplane.subtract([(0, 10), (20, 30)],
                           [(1, 2), (4, 6), (9, 22)]) == [
        (0, 1), (2, 4), (6, 9), (22, 30)]


def test_busy_idle_steps_gaps_and_collectives_on_a_hand_made_trace():
    trace = _trace()
    lo, hi = xplane.window(trace)
    assert (lo, hi) == (-1 * MS, 55 * MS)
    busy_s, window_s = xplane.busy_and_window_s(trace)
    # chip 0: 3 x (4 + 0.001 + 2 + 1 + 1) ms, chip 1: 3 x 5 ms; the mean
    assert window_s == pytest.approx(0.056)
    assert busy_s == pytest.approx((3 * 8.001 + 3 * 5) / 2 / 1e3)
    assert xplane.device_step_ms(trace) == pytest.approx(8.001)
    # device gap between steps 2 ms, of which bench/data covers 1 ms less 1 ns
    # 1.9 ms from program end to program start on the device's clock, of
    # which bench/data covers 0.9 ms
    assert xplane.host_gap_ms(trace) == pytest.approx(1.0)
    coll_ms, exposed = xplane.collective_ms_and_exposed_pct(trace)
    # in flight 4..9 (gather) and 9..10 (reduce) = 6 ms; compute runs beside
    # it for 2 ms (fusion.2), so 4 of 6 ms are exposed
    assert coll_ms == pytest.approx(6.0)
    assert exposed == pytest.approx(100 * 4 / 6)
    out = xplane.breakdown(trace)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.012)]
    assert out["idle_gaps"][0] == ["bench/ckpt", pytest.approx(0.021)]
    sums = {k: v for k, v in out["idle_gaps"] if k.startswith("sum:")}
    assert sums["sum:bench/ckpt"] == pytest.approx(0.020)
    assert sums["sum:bench/data"] == pytest.approx(3 * (MS - 1) / 1e9)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_readers_return_nothing_when_there_is_nothing_to_read():
    from perfbench import worker

    root = os.path.dirname(os.path.dirname(DATA))
    reading = worker._Reading(trace=xplane.Trace(), host={}, plan_bytes=0,
                              peaks=None, chips=1, flops_per_token=1.0)
    for name in os.listdir(os.path.join(root, "metrics")):
        reader = worker._load_reader(root, "metrics", name[:-3])
        assert reader.read(reading) is None, name
    # a quantity split by cell reads through the quantity's own file
    assert worker._load_reader(root, "metrics", "host_gap_ms.job").read(
        reading) is None


def test_reduction_of_a_trace_recorded_on_the_chip():
    """data/tiny_job.xplane.pb (see data/README.txt): 6 steps of a toy model
    fed from a dataset, then one save, on one TPU v5 lite."""
    trace = xplane.load(os.path.join(DATA, "tiny_job.xplane.pb"))
    assert sorted(trace.ops) == [0] and len(trace.ops[0]) == 4057
    assert [n for n, _, _ in trace.spans].count("bench/step") == 6
    assert [n for n, _, _ in trace.spans].count("bench/ckpt") == 1
    steps = xplane.step_device_work(trace, 0)
    assert len(steps) == 6  # every span found its program on the device
    assert all(0 < busy <= end - start for start, end, busy, _ in steps)
    busy_s, window_s = xplane.busy_and_window_s(trace)
    assert busy_s == pytest.approx(0.000520105, rel=1e-6)
    assert window_s == pytest.approx(0.322386329, rel=1e-6)
    # a toy model keeps the chip busy for 66 microseconds a step
    assert xplane.device_step_ms(trace) == pytest.approx(0.0663865, rel=1e-4)
    assert 1.0 < xplane.host_gap_ms(trace) < 4.0
    assert xplane.collective_ms_and_exposed_pct(trace) is None  # one chip
    out = xplane.breakdown(trace)
    assert out["idle_gaps"][0][0] == "bench/ckpt"
    assert out["idle_gaps"][0][1] == pytest.approx(0.2823, rel=1e-3)
    assert all(" = " not in name and not name.startswith("while")
               for name, _ in out["device_ops"])
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
