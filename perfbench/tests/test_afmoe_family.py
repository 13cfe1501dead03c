"""The ``afmoe`` family as the benchmark runs it: its toy configuration through
``run.py`` to ``correct`` on the CPU, and the two readers the family brought
(``attn_window_ms``, ``attn_masked_roofline_pct``) on hand-made traces whose
answers can be worked out on paper and on the small trace recorded on the
chip (data/tiny_afmoe_step.xplane.pb, see data/README_tiny_afmoe_step.txt)."""

import json
import os

import pytest

from perfbench import flops, worker, xplane
from perfbench.tests.test_rehearsal import _checks, _run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
BENCH = os.path.join("perfbench", "tests", "rehearsal_afmoe.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12}


def _toy(name, traffic):
    tests = os.path.join(ROOT, "perfbench", "tests")
    with open(os.path.join(tests, "configs", name + ".json")) as f:
        model = json.load(f)
    with open(os.path.join(tests, "traffic", traffic + ".json")) as f:
        return model, json.load(f)


def _read(name, trace, model=None, traffic=None, peaks=PEAKS):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=peaks,
                              chips=1, flops_per_token=1.0, model=model or {},
                              traffic=traffic or {})
    return worker._load_reader(ROOT, "perfbench/metrics", name).read(reading)


def _reader(name):
    return worker._load_reader(ROOT, "perfbench/metrics", name)


def test_the_family_rehearses_to_correct_through_run_py(tmp_path):
    proc, last = _run("tiny-afmoe.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/afmoe.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_the_benchmark_file_gained_the_cell_and_nothing_else_moved():
    """The cell joins the lists ISSUE 44 names, at their ends, and stays off
    the two whose readers would misread it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "trinity-mini.step-16k"
    assert bench["workloads"][-1]["name"] == cell
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["name"] == "trinity-mini"
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if cell in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "attn_window_ms", "attn_masked_roofline_pct"}
    for m in bench["per_layer"] + bench["end_to_end"]:
        if cell in m.get("workloads", ()):
            assert m["workloads"][-1] == cell
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "attn_window_ms", "attn_masked_roofline_pct"]
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [cell] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"


# ----------------------------------------------------------------------
# hand-made traces
# ----------------------------------------------------------------------

def _kernel(kind, n, b, b_kv, t, d, window=None):
    name = f"flash_{kind}" + (f"_w{window}" if window else "")
    q = f"bf16[{b},{t},{d}]{{2,1,0}} %q"
    k = f"bf16[{b_kv},{t},{d}]{{2,1,0}} %k"
    third = (f"bf16[{b_kv},{d},{t}]{{2,1,0}} %vt" if kind == "fwd"
             else f"bf16[{b_kv},{t},{d}]{{2,1,0}} %v")
    return (f"%{name}.{n} = (bf16[{b},{d},{t}]{{2,1,0}}, "
            f"f32[{b},1,{t}]{{2,1,0}}) custom-call({q}, {k}, {third}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def _steps(ops_of_step, n=3):
    """``n`` steps of 10 ms, each holding ``ops_of_step(t0)``."""
    ops, spans, modules = [], [], []
    for i in range(n):
        t = i * 12 * MS
        spans.append(("bench/step", t, t + 11 * MS))
        modules.append(("jit_step(1)", t, t + 10 * MS))
        ops += [("%fusion.1 = bf16[4,128] fusion(%p)", t, t + MS)]
        ops += ops_of_step(t)
    return xplane.Trace(ops={0: sorted(ops, key=lambda o: o[1])},
                        modules={0: modules}, spans=spans)


def test_a_calls_needed_operations_follow_its_own_mask():
    """Query heads from q's operand (keys and values have 4), the window
    from the call's name: Trinity-Mini's two kinds of call at 16,384."""
    reader = _reader("attn_masked_roofline_pct")
    full, window = 134_225_920, 31_458_304
    assert reader.attended_pairs(16384, 16384) == full
    assert reader.attended_pairs(16384, 16384, 2048) == window
    assert reader.attended_pairs(16384, 16384, 16384) == full
    per = {"fwd": 2 * (128 + 128), "bwd": 2 * (3 * 128 + 2 * 128)}
    for kind in ("fwd", "bwd"):
        assert reader.needed_flops(_kernel(
            kind, 1, 32, 4, 16384, 128)) == 32 * full * per[kind]
        assert reader.needed_flops(_kernel(
            kind, 2, 32, 4, 16384, 128, 2048)) == 32 * window * per[kind]
    # a step of the cell: one full layer and four window layers
    step = 32 * (full + 4 * window) * (per["fwd"] + per["bwd"])
    assert step == pytest.approx(1.49e13, rel=5e-3)
    assert reader.needed_flops("%fusion.3 = bf16[4] fusion(%p)") is None
    # what ``attn_kernel_roofline_pct`` would make of the windowed call:
    # over four times its share, which is why the cell is not on its list
    other = _reader("attn_kernel_roofline_pct")
    assert other.needed_flops(_kernel("fwd", 2, 32, 4, 16384, 128, 2048)) \
        == pytest.approx(4.27 * 32 * window * per["fwd"], rel=1e-2)


def test_both_readers_on_hand_made_kernels():
    """A windowed forward call that needs its operations in 0.1 ms at a
    tenth of the peak, and a call without a window twice as long at a
    twentieth: the window's time is the first alone, the share is over
    both."""
    reader = _reader("attn_masked_roofline_pct")
    b, t, d, w = 8, 1024, 128, 256
    windowed = reader.needed_flops(_kernel("fwd", 1, b, 2, t, d, w))
    plain = reader.needed_flops(_kernel("fwd", 2, b, 2, t, d))
    assert windowed == b * (w * t - w * (w - 1) // 2) * 2 * (d + d)
    ns_w = int(windowed / 19.7e12 * 1e9)
    ns_p = int(plain / 9.85e12 * 1e9)
    trace = _steps(lambda t0: [
        (_kernel("fwd", 1, b, 2, t, d, w), t0 + 2 * MS, t0 + 2 * MS + ns_w),
        (_kernel("fwd", 2, b, 2, t, d), t0 + 5 * MS, t0 + 5 * MS + ns_p)])
    assert _read("attn_window_ms", trace) == pytest.approx(ns_w / 1e6)
    assert _read("attn_kernel_ms", trace) == pytest.approx(
        (ns_w + ns_p) / 1e6)
    assert _read("attn_masked_roofline_pct", trace) == pytest.approx(
        100 * (windowed + plain) / ((ns_w + ns_p) / 1e9 * 197e12), rel=1e-6)
    # a program without the window (the parent of PR 44), no kernel, no
    # peak, no trace: nothing, and nothing raised
    no_window = _steps(lambda t0: [
        (_kernel("fwd", 2, b, 2, t, d), t0 + 5 * MS, t0 + 5 * MS + ns_p)])
    assert _read("attn_window_ms", no_window) is None
    assert _read("attn_masked_roofline_pct", no_window) == pytest.approx(
        5.0, rel=1e-3)
    for name in ("attn_window_ms", "attn_masked_roofline_pct"):
        assert _read(name, _steps(lambda t0: [])) is None
        assert _read(name, None) is None
    assert _read("attn_masked_roofline_pct", trace, peaks=None) is None


# ----------------------------------------------------------------------
# the trace recorded on the chip
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return xplane.load(os.path.join(DATA, "tiny_afmoe_step.xplane.pb"))


def test_the_recorded_trace_holds_both_kinds_of_call(recorded):
    """4 traced steps of the toy configuration with the kernels forced: five
    layers, four of them under a window of 128 keys, 2 x 4 query heads on
    2 x 2 key-value heads of 128 at T = 512, recomputed under
    ``remat_policy``: 5 forward and 5 backward calls a step, the window
    layers' named after their window."""
    model, traffic = _toy("tiny-afmoe-flash", "step-afmoe-flash")
    reader = _reader("attn_masked_roofline_pct")
    windowed = _reader("attn_window_ms").WINDOWED
    steps = xplane.step_device_work(recorded, 0)
    assert len(steps) == traffic["traced_steps"]
    calls = [name for name, _, _ in steps[0][3] if reader.needed_flops(name)]
    under = [c for c in calls if windowed.match(c)]
    assert len(calls) == 10 and len(under) == 8
    assert {windowed.match(c).group(2) for c in under} == {"128"}
    assert sum("flash_fwd" in c[:12] for c in calls) == 5
    heads, t, d = 2 * 4, 512, 128
    assert "bf16[8,512,128]" in calls[0] and "bf16[4,512,128]" in calls[0]
    pairs = {True: 128 * 512 - 128 * 127 // 2, False: 512 * 513 // 2}
    assert {reader.needed_flops(c) for c in calls} == {
        heads * pairs[w] * per for w in pairs
        for per in (2 * (d + d), 2 * (3 * d + 2 * d))}
    share = _read("attn_masked_roofline_pct", recorded, model, traffic,
                  flops.peaks("TPU v5 lite"))
    kernel_ms = _read("attn_kernel_ms", recorded, model, traffic)
    window_ms = _read("attn_window_ms", recorded, model, traffic)
    assert 0 < share < 100 and 0 < window_ms < kernel_ms
    needed = sum(reader.needed_flops(c) for c in calls)
    assert share == pytest.approx(
        100 * needed / (kernel_ms / 1e3 * 197e12), rel=0.05)
    # the family's two older recorded traces hold no windowed call
    other = xplane.load(os.path.join(DATA, "tiny_mla_moe_step.xplane.pb"))
    assert _read("attn_window_ms", other) is None
    assert 0 < _read("attn_masked_roofline_pct", other,
                     peaks=flops.peaks("TPU v5 lite")) < 100
