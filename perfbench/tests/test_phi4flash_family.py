"""The ``phi4flash`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU, its adapter's counts, and the
two readers the family brought (``ssm_scan_ms``, ``ssm_scan_roofline_pct``)
on canned event texts and hand-made traces whose answers can be worked out
on paper."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_phi4flash.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "phi-4-mini-flash.step-one-seq"


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _read(name, trace, peaks=PEAKS):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=peaks,
                              chips=1, flops_per_token=1.0, model={},
                              traffic={})
    return worker._load_reader(ROOT, "perfbench/metrics", name).read(reading)


def _reader(name):
    return worker._load_reader(ROOT, "perfbench/metrics", name)


def test_the_family_rehearses_to_correct_through_run_py(tmp_path):
    proc, last = _run("tiny-phi4flash.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/phi4flash.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_the_adapter_counts_what_the_file_says():
    """``num_params`` by kind of layer at the published widths (ISSUE 48's
    table), and the state the toy's program makes."""
    model = _json("perfbench", "configs", "phi-4-mini-flash.json")
    family = worker.load_family(ROOT, model)
    assert family.kinds_run(model) == ("ssm", "window", "ssm", "full", "gmu",
                                       "cross")
    sizes = family._sizes(model)
    mixers = {k: sizes["matmul"][k] + sizes["rest"][k] for k in sizes["matmul"]}
    assert mixers == {"ssm": 41_241_600, "window": 19_668_864,
                      "full": 19_668_864, "gmu": 26_214_400,
                      "cross": 13_112_704}
    assert sizes["mlp"] + sizes["norms"] == 78_653_440
    assert sizes["table"] == 25008 * 2560
    assert family.num_params(model) == 697_094_272
    # operations a token at 16,384: the matrices, the pairs the masks
    # leave, the recurrence
    pairs = family.attended_pairs_per_token(model, 16384)
    assert pairs == pytest.approx(2 * 8192.5 + (512 * 16384 - 512 * 511 / 2)
                                  / 16384)
    per_token = family.train_flops_per_token(model, 16384)
    assert per_token == pytest.approx(
        6 * family.matmul_params_per_token(model) + 6 * 40 * 192 * pairs
        + 2 * 22 * 5120 * 16)
    toy = _json("perfbench", "tests", "configs", "tiny-phi4flash.json")
    import jax

    built = worker.load_family(ROOT, toy).build(
        toy, {"batch": 4, "seq": 64, "remat": True}, None)
    shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == family.num_params(toy)


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 48 names and brings two metrics of
    its own; it stays off the lists whose readers find nothing in it."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "phi-4-mini-flash", "traffic": "step-one-seq",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    config = next(c for c in bench["configs"]
                  if c["name"] == "phi-4-mini-flash")
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == "perfbench/configs/phi-4-mini-flash.json"
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "attn_window_ms", "attn_masked_roofline_pct",
        "ssm_scan_ms", "ssm_scan_roofline_pct"}
    for name in ("ssm_scan_ms", "ssm_scan_roofline_pct"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # 25,008 rows equal no other dimension of the step: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    model = _json(config["file"])
    others = {2560, 10240, 20480, 5120, 2 * 5120, 1280, 160 + 32, 160, 16384,
              16384 * 16, 40, 20, 64, 128, 512}
    assert model["vocab_size"] == 25008 and 25008 not in others


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

def _scan_call(kind, n, batch, t, channels, states, chunk=128):
    rows = f"[{batch},{t},{channels}]{{2,1,0}}"
    spread = f"bf16[{batch},{t * states},128]{{2,1,0}}"
    ins = [f"bf16{rows} %x", f"f32{rows} %delta",
           f"f32[{states},{channels}]{{1,0}} %at", f"{spread} %bs",
           f"{spread} %cs", f"f32[1,{channels}]{{1,0}} %skip"]
    bounds = f"f32[{batch},{t // chunk},{states},{channels}]{{3,2,1,0}}"
    if kind == "fwd":
        outs = f"(bf16{rows}, {bounds})"
    else:
        ins += [f"bf16{rows} %dy", f"{bounds} %bounds"]
        wide = f"f32[{batch},{t * states},128]{{2,1,0}}"
        outs = (f"(bf16{rows}, f32{rows}, {wide}, {wide}, "
                f"f32[{batch},{states},{channels}]{{2,1,0}})")
    return (f"%ssm_scan_{kind}.{n} = {outs} custom-call({', '.join(ins)}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_what_a_call_needs_is_read_from_its_operands():
    """The cell's calls: one sequence of 16,384, 5,120 channels of 16
    states. B and C count as the [T, states] operands they are made from,
    not as the lane tiles the kernel is handed."""
    reader = _reader("ssm_scan_roofline_pct")
    t, d, n = 16384, 5120, 16
    rows, narrow, small = t * d, t * n * 2, (n + 1) * d * 4
    fwd = reader.needed(_scan_call("fwd", 1, 1, t, d, n))
    assert fwd == {"bytes": rows * 2 + rows * 4 + 2 * narrow + small
                   + rows * 2, "flops": 7 * rows * n}
    assert fwd["bytes"] == 672_485_376
    bwd = reader.needed(_scan_call("bwd", 2, 1, t, d, n))
    bounds = (t // 128) * n * d * 4
    assert bwd == {"bytes": fwd["bytes"] + rows * 2 + bounds + rows * 4
                   + 2 * narrow + n * d * 4, "flops": 20 * rows * n}
    # the bytes bound both, by a wide margin
    for call in (fwd, bwd):
        assert call["bytes"] / 819e9 > 10 * call["flops"] / 197e12
    assert reader.needed("%fusion.3 = bf16[4] fusion(%p)") is None
    assert reader.needed(
        '%flash_fwd.1 = bf16[2,64,8] custom-call(bf16[2,8,64] %q), '
        'custom_call_target="tpu_custom_call"') is None


def test_both_readers_on_hand_made_kernels():
    """A forward call that takes four times its memory floor and a backward
    call that takes five times its own: the time is their sum, the share is
    over both."""
    reader = _reader("ssm_scan_roofline_pct")
    fwd_text = _scan_call("fwd", 1, 1, 1024, 256, 16)
    bwd_text = _scan_call("bwd", 2, 1, 1024, 256, 16)
    floor = lambda text: reader.needed(text)["bytes"] / 819e9 * 1e9
    ns_f, ns_b = int(4 * floor(fwd_text)), int(5 * floor(bwd_text))
    trace = _steps(lambda t0: [
        (fwd_text, t0 + 2 * MS, t0 + 2 * MS + ns_f),
        (bwd_text, t0 + 5 * MS, t0 + 5 * MS + ns_b)])
    assert _read("ssm_scan_ms", trace) == pytest.approx((ns_f + ns_b) / 1e6)
    assert _read("ssm_scan_roofline_pct", trace) == pytest.approx(
        100 * (floor(fwd_text) + floor(bwd_text)) / (ns_f + ns_b), rel=1e-4)
    # a program without the kernels (the parent of PR 48, the chunked
    # lax.scan), no trace, no peaks: nothing, and nothing raised
    plain = _steps(lambda t0: [])
    for name in ("ssm_scan_ms", "ssm_scan_roofline_pct"):
        assert _read(name, plain) is None
        assert _read(name, None) is None
    assert _read("ssm_scan_roofline_pct", trace, peaks=None) is None
    # the flash kernels' readers find none of theirs in a scan's call
    assert _read("attn_kernel_ms", trace) is None


def test_the_recorded_traces_hold_no_scan():
    """The older families' traces recorded on the chip: both readers are
    silent there, as they are on the parent's program."""
    data = os.path.join(HERE, "data")
    for name in ("tiny_afmoe_step.xplane.pb", "tiny_mla_moe_step.xplane.pb"):
        trace = xplane.load(os.path.join(data, name))
        assert _read("ssm_scan_ms", trace) is None
        assert _read("ssm_scan_roofline_pct", trace) is None
