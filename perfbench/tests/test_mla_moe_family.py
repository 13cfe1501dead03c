"""The latent-attention, routed-expert family as the benchmark runs it: its
toy configuration through ``run.py`` to ``correct`` on the CPU, the same
comparison telling bfloat16 from float32, and the two readers the family
brought (``attn_kernel_roofline_pct``, ``moe_ms``) on hand-made traces whose
answers can be worked out on paper and on the small trace recorded on the
chip (data/tiny_mla_moe_step.xplane.pb, see data/README_tiny_mla_moe_step.txt)."""

import json
import os

import pytest

from perfbench import flops, worker, xplane
from perfbench.tests.test_rehearsal import _checks, _run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
BENCH = os.path.join("perfbench", "tests", "rehearsal_mla_moe.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12}


def _toy(name):
    with open(os.path.join(ROOT, "perfbench", "tests", "configs",
                           name + ".json")) as f:
        model = json.load(f)
    with open(os.path.join(ROOT, "perfbench", "tests", "traffic",
                           "step-mla-moe.json")) as f:
        return model, json.load(f)


def _read(name, trace, model=None, traffic=None, peaks=PEAKS):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=peaks,
                              chips=1, flops_per_token=1.0, model=model or {},
                              traffic=traffic or {})
    return worker._load_reader(ROOT, "perfbench/metrics", name).read(reading)


def test_the_family_rehearses_to_correct_through_run_py(tmp_path):
    proc, last = _run("tiny-mla-moe.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/mla_moe.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


# ----------------------------------------------------------------------
# attn_kernel_roofline_pct
# ----------------------------------------------------------------------

def _kernel(kind, n, b, t, d_qk, d_v):
    q = f"bf16[{b},{t},{d_qk}]{{2,1,0}} %q"
    third = (f"bf16[{b},{d_v},{t}]{{2,1,0}} %vt" if kind == "fwd"
             else f"bf16[{b},{t},{d_v}]{{2,1,0}} %v")
    return (f"%flash_{kind}.{n} = (bf16[{b},{d_v},{t}]{{2,1,0}}, "
            f"f32[{b},1,{t}]{{2,1,0}}) custom-call({q}, {q}, {third}), "
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


def test_a_kernels_needed_operations_from_its_own_text():
    reader = worker._load_reader(ROOT, "perfbench/metrics",
                                 "attn_kernel_roofline_pct")
    pairs = 64 * (8192 * 8193 // 2)
    assert reader.needed_flops(_kernel("fwd", 1, 64, 8192, 192, 128)) == \
        pairs * 2 * (192 + 128)
    assert reader.needed_flops(_kernel("bwd", 2, 64, 8192, 192, 128)) == \
        pairs * 2 * (3 * 192 + 2 * 128)
    assert reader.needed_flops("%fusion.3 = bf16[4] fusion(%p)") is None


def test_roofline_share_of_hand_made_kernels():
    """A forward call that needs 1.97e9 operations in 0.1 ms runs at 19.7
    TFLOP/s: 10% of 197. Two of them a step and nothing else."""
    b, t, d = 1, 1024, 64
    per_call = b * (t * (t + 1) // 2) * 2 * (d + d)
    ns = int(per_call / 19.7e12 * 1e9)
    trace = _steps(lambda t0: [
        (_kernel("fwd", 1, b, t, d, d), t0 + 2 * MS, t0 + 2 * MS + ns),
        (_kernel("fwd", 2, b, t, d, d), t0 + 5 * MS, t0 + 5 * MS + ns)])
    assert _read("attn_kernel_roofline_pct", trace) == pytest.approx(
        10.0, rel=1e-3)
    # no kernel, no peak, no trace: nothing, and nothing raised
    assert _read("attn_kernel_roofline_pct", _steps(lambda t0: [])) is None
    assert _read("attn_kernel_roofline_pct", trace, peaks=None) is None
    assert _read("attn_kernel_roofline_pct", None) is None


# ----------------------------------------------------------------------
# moe_ms
# ----------------------------------------------------------------------

CUT = {"num_experts_per_tok": 8, "n_routed_experts_published": 256}
STEP_8K = {"batch": 2, "seq": 8192}


def test_moe_ms_counts_the_routed_path_and_nothing_else():
    routed = [
        "%ragged-dot-none.3 = bf16[131072,1536]{1,0} custom-call("
        "bf16[131072,2048]{1,0} %rows, bf16[16,2048,1536]{2,1,0} %wi)",
        "%sort.5 = (s32[131072]{0}, s32[131072]{0}) sort(%key, %iota)",
        "%fusion.7 = f32[16384,256]{1,0} fusion(bf16[16384,2048]{1,0} %x)",
        "%fusion.9 = bf16[16384,2048]{1,0} fusion(bf16[16384,8,2048]{2,1,0} "
        "%pairs, f32[16384,8]{1,0} %w)"]
    others = [
        "%fusion.11 = bf16[16384,2048]{1,0} fusion(bf16[16384,7168]{1,0} %h)",
        "%flash_fwd.1 = (bf16[64,128,8192]{2,1,0}) custom-call(%q)",
        "%while.2 = (s32[], bf16[131072,2048]{1,0}) while(%tuple)",
        # attention's backward joins dk_nope | dv: 128 + 128 is the router's
        # width too, and is no mark of the routed path; nor is the router's
        # own [d, 256] in the optimizer's update
        "%maximum_bitcast_fusion.3 = bf16[2,32,1024,8,256]{4,3,2,1,0} "
        "fusion(bf16[64,8192,192]{2,1,0} %dk, bf16[64,8192,128]{2,1,0} %dv)",
        "%copy.9 = bf16[2,32,1024,8,256]{4,2,1,3,0} copy("
        "bf16[2,32,1024,8,256]{4,3,2,1,0} %maximum_bitcast_fusion.3)",
        "%fusion.13 = f32[2048,256]{1,0} fusion(f32[2048,256]{1,0} %mu)"]

    def ops(t0):
        out = [(text, t0 + (i + 1) * MS, t0 + (i + 1) * MS + MS // 2)
               for i, text in enumerate(routed)]
        return out + [(text, t0 + (i + 12) * MS // 2, t0 + (i + 13) * MS // 2)
                      for i, text in enumerate(others)]

    assert _read("moe_ms", _steps(ops), CUT, STEP_8K) == pytest.approx(2.0)
    # a configuration without routed experts, a trace without the path
    assert _read("moe_ms", _steps(ops), {}, STEP_8K) is None
    assert _read("moe_ms", _steps(lambda t0: []), CUT, STEP_8K) is None
    assert _read("moe_ms", None, CUT, STEP_8K) is None


# ----------------------------------------------------------------------
# the trace recorded on the chip
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return xplane.load(os.path.join(DATA, "tiny_mla_moe_step.xplane.pb"))


def test_the_recorded_trace_holds_the_kernels_at_both_widths(recorded):
    """4 traced steps of the toy configuration with the kernels forced:
    four attention layers (three of the trunk, the module's), recomputed:
    8 forward and 4 backward calls a step, keys 32 and values 16 wide."""
    model, traffic = _toy("tiny-mla-moe-flash")
    reader = worker._load_reader(ROOT, "perfbench/metrics",
                                 "attn_kernel_roofline_pct")
    steps = xplane.step_device_work(recorded, 0)
    assert len(steps) == traffic["traced_steps"]
    calls = [name for name, _, _ in steps[0][3]
             if reader.needed_flops(name)]
    assert sum("flash_fwd" in c[:12] for c in calls) == 8
    assert sum("flash_bwd" in c[:12] for c in calls) == 4
    pairs = 16 * (128 * 129 // 2)       # batch 4 x 4 heads, T = 128
    assert {reader.needed_flops(c) for c in calls} == {
        pairs * 2 * (32 + 16), pairs * 2 * (3 * 32 + 2 * 16)}
    share = _read("attn_kernel_roofline_pct", recorded, model, traffic,
                  flops.peaks("TPU v5 lite"))
    kernel_ms = _read("attn_kernel_ms", recorded, model, traffic)
    assert 0 < share < 100 and kernel_ms > 0
    # the share is the needed operations over the kernels' own time
    needed = sum(reader.needed_flops(c) for c in calls)
    assert share == pytest.approx(
        100 * needed / (kernel_ms / 1e3 * 197e12), rel=0.05)


def test_moe_ms_on_the_recorded_trace(recorded):
    model, traffic = _toy("tiny-mla-moe-flash")
    moe_ms = _read("moe_ms", recorded, model, traffic)
    step_ms = _read("device_step_ms", recorded, model, traffic)
    assert 0 < moe_ms < step_ms
    # the grouped matmuls are among what it counts: 8 a layer (2 forward, 2
    # recomputed, 4 backward), three expert layers
    reader = worker._load_reader(ROOT, "perfbench/metrics", "moe_ms")
    routed = reader.pattern(model, traffic)
    grouped = [name for name, _, _ in xplane.step_device_work(recorded, 0)[0][3]
               if name.startswith("%ragged-dot-none") and routed.search(name)]
    assert len(grouped) == 24
    # GPT-2's recorded trace has no such path and reads nothing
    other = xplane.load(os.path.join(DATA, "tiny_flash_step.xplane.pb"))
    assert _read("moe_ms", other, {"vocab_size": 512}, traffic) is None
