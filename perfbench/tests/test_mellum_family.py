"""The ``mellum`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU, its adapter's counts, the
cell's entries in BENCHMARK.json (held by name, not by their place at a
list's end: entries are only ever appended), the records the program's new
parts leave in the worker's ring, and the two readers the family brought
(``grouped_matmul_ms``, ``grouped_matmul_roofline_pct``) on canned event
texts and hand-made traces whose answers can be worked out on paper."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_mellum.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "mellum2-12b-a2.5b.step-8k"


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
    proc, last = _run("tiny-mellum.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/mellum.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_a_traced_rehearsal_leaves_out_what_a_cpu_cannot_read(tmp_path):
    """``--trace 1`` on the CPU: the run ends, and the kernels' readers
    (the new two among them) find nothing and leave their metrics out of
    the line rather than raise."""
    proc, last = _run("tiny-mellum.step", 1, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True
    assert not {"grouped_matmul_ms", "grouped_matmul_roofline_pct",
                "attn_window_ms", "attn_kernel_ms"} & set(last["metrics"])


def test_the_adapter_counts_what_the_file_says():
    """``num_params`` by part at the published widths (ISSUE 62's count),
    the operations a token, and the state the toy's program makes."""
    model = _json("perfbench", "configs", "mellum2-12b-a2.5b.json")
    family = worker.load_family(ROOT, model)
    assert family.layer_types(model) == ("sliding_attention",) * 3 + (
        "full_attention",)
    sizes = family._sizes(model)
    assert sizes["attn"] == 2 * 9_437_184 + 2 * 1_179_648
    assert sizes["head_norms"] + sizes["block_norms"] == 256 + 4_608
    assert sizes["router"] + sizes["router_bias"] == 147_456 + 64
    assert sizes["expert"] == 6_193_152
    assert sizes["table"] == 24576 * 2304 == 56_623_104
    layer = (sizes["attn"] + 256 + 4_608 + 147_456 + 64
             + 16 * sizes["expert"])
    assert layer == 120_476_480
    assert family.num_params(model) == 4 * layer + 2 * 56_623_104 + 2_304 \
        == 595_154_432
    # 16 bytes a parameter: weights, two moments, the gradient
    assert 16 * family.num_params(model) == pytest.approx(9.52e9, rel=1e-3)
    # a token's matrices: four attentions, four routers, two of the eight
    # chosen experts a layer (16 of 64 held), the head once
    per_token = family.matmul_params_per_token(model)
    assert per_token == 4 * (sizes["attn"] + 147_456 + 2.0 * 6_193_152) \
        + 56_623_104
    window = (1024 * 8192 - 1024 * 1023 / 2) / 8192
    flops = family.train_flops_per_token(model, 8192)
    assert flops == pytest.approx(
        6 * per_token + 6 * 32 * 2 * 128 * (8193 / 2 + 3 * window))
    # a window layer needs under a quarter of a full layer's pairs, and the
    # three of them 70% of its
    assert window / (8193 / 2) == pytest.approx(0.234, abs=0.001)
    assert flops * 2 * 8192 == pytest.approx(24.46e12, rel=2e-3)
    # shorter than the window, a window layer is a full one
    assert family.attended_pairs_per_token(model, 512) == 4 * 513 / 2
    toy = _json("perfbench", "tests", "configs", "tiny-mellum.json")
    import jax

    built = worker.load_family(ROOT, toy).build(
        toy, {"batch": 4, "seq": 64, "remat": True}, None)
    shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == family.num_params(toy)
    with pytest.raises(ValueError):
        family.build(dict(toy, num_experts=3), {"batch": 4, "seq": 64}, None)


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 62 names and brings two metrics of
    its own; it stays off the lists whose readers would misread it."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "mellum2-12b-a2.5b", "traffic": "step-8k",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "mellum2-12b-a2.5b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "perfbench/configs/mellum2-12b-a2.5b.json"
    assert len(config["why"]) <= 200
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "attn_window_ms", "attn_masked_roofline_pct", "loss_head_ms",
        "moe_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "grouped_matmul_ms",
        "grouped_matmul_roofline_pct"}
    # ``attn_kernel_roofline_pct`` knows no window: three of the cell's four
    # calls a pass would be counted at a full layer's pairs, over 100%
    older = [w["name"] for w in bench["workloads"]]
    older = set(older[:older.index(CELL)])
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists:   # appended: after every older cell
            assert set(lists[:lists.index(CELL)]) <= older
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("grouped_matmul_roofline_pct") \
        == names.index("grouped_matmul_ms") + 1 > names.index(
            "causal_conv_roofline_pct")
    for name in ("grouped_matmul_ms", "grouped_matmul_roofline_pct"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # 24,576 rows equal no other dimension of the step: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    model, traffic = _json(config["file"]), _json(
        "perfbench", "traffic", "step-8k.json")
    tokens = traffic["batch"] * traffic["seq"]
    others = {2304, 4096, 512, 128, 64, 896, 1792, 32, 4, 8, 16, 1024,
              traffic["seq"], tokens, tokens // model["train"]["loss_chunks"],
              tokens * model["num_experts_per_tok"]}
    assert (traffic["batch"], traffic["seq"]) == (2, 8192)
    assert model["vocab_size"] == 24576 and 24576 not in others
    # ``moe_ms`` finds the routed path by the row buffer's 131,072 pairs, by
    # (tokens, 8) and by the router's (tokens, 64), the width the
    # configuration states under the reader's key; the rotary tables are
    # [1, 8192, 64] and a head's halves [2, 8192, 32, 64]: 64 beside 8,192
    # or 32, never beside 16,384
    assert model["n_routed_experts_published"] \
        == model["num_experts_published"] == 64
    routed = _reader("moe_ms").pattern(model, traffic)
    for text in ("bf16[131072,2304]", "bf16[131072,1792]", "f32[16384,64]",
                 "s32[16384,8]"):
        assert routed.search(f"%fusion.1 = {text}{{1,0}} fusion(%p)"), text
    for text in ("f32[1,8192,64]", "bf16[2,8192,32,64]", "bf16[64,8192,128]",
                 "bf16[8,8192,128]", "bf16[16384,2304]", "bf16[2,8192,4096]"):
        assert not routed.search(f"%fusion.1 = {text}{{1,0}} fusion(%p)")


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

_WALK = ("s32[528]{0:T(1024)} %g, s32[528]{0:T(1024)} %t, s32[16]{0} %s, "
         "s32[16]{0} %e, s32[1]{0} %n")


def _call(form, n, rows, k, cols, held=16, dtype="bf16"):
    tiled = "{1,0:T(8,128)(2,1)}"
    if form == "matrices":
        ins = (f"{dtype}[{rows},{k}]{tiled} %rows, "
               f"{dtype}[{rows},{cols}]{tiled} %d_out")
        out = f"{dtype}[{held},{k},{cols}]{{2,1,0:T(8,128)(2,1)}}"
    else:
        w = (held, cols, k) if form == "rows_t" else (held, k, cols)
        ins = (f"{dtype}[{rows},{k}]{tiled} %rows, "
               f"{dtype}[{w[0]},{w[1]},{w[2]}]{{2,1,0:T(8,128)(2,1)}} %w")
        out = f"{dtype}[{rows},{cols}]{tiled}"
    return (f"%grouped_matmul_{form}.{n} = {out} custom-call(s32[] %z, "
            f"{_WALK}, {ins}), custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={}")


def test_what_a_call_needs_is_read_from_its_operands():
    """The cell's seven calls a layer: K and N from the operands, whichever
    way the matrices lie, ``held`` from the stack, the buffer's rows kept
    apart from the rows present."""
    reader = _reader("grouped_matmul_roofline_pct")
    pairs = 131072
    want = {
        ("rows", 2304, 1792), ("rows", 896, 2304), ("rows_t", 2304, 896),
        ("rows_t", 1792, 2304), ("matrices", 2304, 1792),
        ("matrices", 896, 2304)}
    got = set()
    for n, (form, k, cols) in enumerate(sorted(want)):
        call = reader.shape_of(_call(form, n, pairs, k, cols))
        assert call == {"form": form, "held": 16, "k": k, "n": cols,
                        "rows_buffered": pairs}
        got.add((call["form"], call["k"], call["n"]))
    assert got == want
    # 2,048 rows a held expert: 32,768 rows present of the buffer's 131,072
    text = _call("rows", 1, pairs, 2304, 1792)
    assert reader.needed(text, 2048.0) == 2.0 * 32768 * 2304 * 1792
    assert reader.needed(text, 2048.0) * 4 == 2.0 * pairs * 2304 * 1792
    f32 = reader.shape_of(_call("rows_t", 2, 512, 128, 256, held=2,
                                dtype="f32"))
    assert f32 == {"form": "rows_t", "held": 2, "k": 128, "n": 256,
                   "rows_buffered": 512}
    for other in ("%fusion.3 = bf16[4] fusion(%p)",
                  '%to_tokens.1 = bf16[16384,2304] custom-call(bf16[131072,'
                  '2304] %r), custom_call_target="tpu_custom_call"',
                  '%ragged-dot.1 = bf16[131072,1792] ragged-dot(%a, %b, %c)',
                  '%grouped_matmul_rows.1 = bf16[8,8] custom-call(bf16[8] %r)'
                  ', custom_call_target="tpu_custom_call"'):
        assert reader.shape_of(other) is None
        assert reader.needed(other, 1.0) is None


def _with_ring(loads):
    """The process's ring holding one ``train/step_aux`` record a load."""
    from ray_tpu._private import steptrace

    steptrace.set_enabled(True)
    steptrace.reset()
    for load in loads:
        steptrace.record_counters("train/step_aux", load)


def test_both_readers_on_hand_made_kernels():
    """Three traced steps of two calls each, the rows present falling from
    step to step: the time is the calls' sum, the share is the rows
    PRESENT's multiply-adds over the peak in that time, each step under its
    own load, the ring's last three records."""
    from ray_tpu._private import steptrace

    rows_text = _call("rows", 1, 131072, 2304, 1792)
    back_text = _call("matrices", 2, 131072, 896, 2304)
    flops = lambda load: 2.0 * load * 16 * (2304 * 1792 + 896 * 2304)
    ns_a, ns_b = 3 * MS, 2 * MS
    trace = _steps(lambda t0: [
        (rows_text, t0 + 2 * MS, t0 + 2 * MS + ns_a),
        (back_text, t0 + 6 * MS, t0 + 6 * MS + ns_b)])
    assert _read("grouped_matmul_ms", trace) == pytest.approx(5.0)
    loads = [2100.0, 2048.0, 2000.0]
    try:
        # an older record (a warm-up step's) stands before the traced three
        _with_ring([{"loss": 1.0, "expert_tokens_mean": 9999.0}]
                   + [{"loss": 1.0, "expert_tokens_mean": x} for x in loads])
        share = _read("grouped_matmul_roofline_pct", trace)
        assert share == pytest.approx(
            100 * sum(flops(x) for x in loads) / (3 * 5e-3 * 197e12),
            rel=1e-6)
        assert 40 < share < 50
        # counting the buffer's rows would read four times as much
        assert 100 * 3 * flops(8192.0) / (3 * 5e-3 * 197e12) > 100
        # fewer records than traced steps, or none with a load: nothing
        _with_ring([{"loss": 1.0, "expert_tokens_mean": 2048.0}] * 2)
        assert _read("grouped_matmul_roofline_pct", trace) is None
        _with_ring([{"loss": 1.0}] * 3)
        assert _read("grouped_matmul_roofline_pct", trace) is None
        _with_ring([{"loss": 1.0, "expert_tokens_mean": 2048.0}] * 3)
        # a program without the kernels (the parent of PR 61, a mesh, the
        # compiler's ragged-dot), no trace, no peaks: nothing, nothing raised
        plain = _steps(lambda t0: [
            ("%ragged-dot.1 = bf16[131072,1792] ragged-dot(%a, %b, %c)",
             t0 + 2 * MS, t0 + 3 * MS)])
        for name in ("grouped_matmul_ms", "grouped_matmul_roofline_pct"):
            assert _read(name, plain) is None
            assert _read(name, None) is None
        assert _read("grouped_matmul_roofline_pct", trace, peaks=None) is None
    finally:
        steptrace.set_enabled(False)
        steptrace.reset()
    # the other kernels' readers find none of theirs in these calls
    for name in ("attn_kernel_ms", "attn_window_ms", "ssd_ms",
                 "short_conv_ms", "delta_rule_ms"):
        assert _read(name, trace) is None
    # nor these in the older families' traces recorded on the chip (before
    # PR 61: the compiler's ragged-dot)
    data = os.path.join(HERE, "data")
    for name in ("tiny_afmoe_step.xplane.pb", "tiny_mla_moe_step.xplane.pb"):
        old = xplane.load(os.path.join(data, name))
        assert _read("grouped_matmul_ms", old) is None
        assert _read("grouped_matmul_roofline_pct", old) is None


def test_the_windowed_calls_readers_take_the_cells_calls():
    """``attn_window_ms`` and ``attn_masked_roofline_pct`` on the cell's own
    calls: three window layers named ``flash_*_w1024`` and a full layer, 64
    folded query heads on 8 of keys and values, 8,192 tokens."""
    reader = _reader("attn_masked_roofline_pct")
    full, window = 33_558_528, 7_864_832
    assert reader.attended_pairs(8192, 8192) == full
    assert reader.attended_pairs(8192, 8192, 1024) == window

    def kernel(kind, n, w=None):
        name = f"flash_{kind}" + (f"_w{w}" if w else "")
        third = "bf16[8,128,8192]" if kind == "fwd" else "bf16[8,8192,128]"
        return (f"%{name}.{n} = bf16[2,8192,4096] custom-call("
                f"bf16[64,8192,128] %q, bf16[8,8192,128] %k, {third} %v), "
                'custom_call_target="tpu_custom_call"')

    per = {"fwd": 2 * (128 + 128), "bwd": 2 * (3 * 128 + 2 * 128)}
    for kind in ("fwd", "bwd"):
        assert reader.needed_flops(kernel(kind, 1)) == 64 * full * per[kind]
        assert reader.needed_flops(kernel(kind, 2, 1024)) \
            == 64 * window * per[kind]
    step = 64 * (full + 3 * window) * (per["fwd"] + per["bwd"])
    assert step == pytest.approx(6.555e12, rel=1e-3)
    ns = {"fwd": 4 * MS, "bwd": 6 * MS}
    trace = _steps(lambda t0: [
        (kernel(kind, i, w), t0 + (2 * i + (kind == "bwd")) * MS // 2,
         t0 + (2 * i + (kind == "bwd")) * MS // 2 + ns[kind] // 10)
        for i, w in enumerate((1024, 1024, 1024, None))
        for kind in ("fwd", "bwd")])
    assert _read("attn_window_ms", trace) == pytest.approx(3 * 1.0)
    assert _read("attn_kernel_ms", trace) == pytest.approx(4 * 1.0)
    assert _read("attn_masked_roofline_pct", trace) == pytest.approx(
        100 * step / (4e-3 * 197e12), rel=1e-6)
