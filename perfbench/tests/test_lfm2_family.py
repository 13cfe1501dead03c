"""The ``lfm2`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU, its adapter's counts, the
cell's entries in BENCHMARK.json (held by name, not by their place at a
list's end: entries are only ever appended), the records the program's new
parts leave in the worker's ring, and the two readers the family brought
(``short_conv_ms``, ``short_conv_roofline_pct``) on canned event texts and
hand-made traces whose answers can be worked out on paper."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_lfm2.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "lfm2-8b-a1b.step-8k"


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
    proc, last = _run("tiny-lfm2.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/lfm2.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_the_adapter_counts_what_the_file_says():
    """``num_params`` by part at the published widths (ISSUE 52's table),
    the operations a token, and the state the toy's program makes."""
    model = _json("perfbench", "configs", "lfm2-8b-a1b.json")
    family = worker.load_family(ROOT, model)
    assert family.layers_run(model) == (
        (0, "conv", True), (2, "full_attention", False), (3, "conv", False),
        (4, "conv", False), (5, "conv", False))
    sizes = family._sizes(model)
    assert sizes["conv"] + sizes["taps"] == 16_783_360
    assert sizes["attn"] + sizes["head_norms"] == 10_485_888
    assert sizes["dense_mlp"] == 44_040_192
    assert sizes["expert"] == 11_010_048
    assert sizes["router"] + sizes["router_bias"] == 65_568
    assert sizes["table"] == 16384 * 2048 == 33_554_432
    assert family.num_params(model) == 507_820_288
    # 16 bytes a parameter: weights, two moments, the gradient
    assert 8.1e9 < 16 * family.num_params(model) < 8.2e9
    # a token's matrices: four convolution operators, one attention, the
    # dense feed-forward, four routers and one expert's worth of the four
    # chosen (4 x 8 / 32), the tied table once
    per_token = family.matmul_params_per_token(model)
    assert per_token == (4 * 16_777_216 + 10_485_760 + 44_040_192
                         + 4 * (65_536 + 11_010_048) + 33_554_432)
    flops = family.train_flops_per_token(model, 8192)
    assert flops == pytest.approx(
        6 * per_token + 6 * 32 * 128 * 8193 / 2 + 22 * 2048 * 4)
    assert flops * 4 * 8192 == pytest.approx(42.5e12, rel=2e-3)
    toy = _json("perfbench", "tests", "configs", "tiny-lfm2.json")
    import jax

    built = worker.load_family(ROOT, toy).build(
        toy, {"batch": 4, "seq": 64, "remat": True}, None)
    shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == family.num_params(toy)


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 52 names and brings two metrics of
    its own; it stays off the lists whose readers find nothing in it or
    would take another layer's operations for theirs."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "lfm2-8b-a1b", "traffic": "step-8k-b4",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert config["file"] == "perfbench/configs/lfm2-8b-a1b.json"
    assert len(config["why"]) <= 200
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "attn_kernel_roofline_pct", "moe_ms",
        "short_conv_ms", "short_conv_roofline_pct"}
    older = [w["name"] for w in bench["workloads"]]
    older = set(older[:older.index(CELL)])
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists:   # appended: after every older cell
            assert set(lists[:lists.index(CELL)]) <= older
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("short_conv_roofline_pct") \
        == names.index("short_conv_ms") + 1 > names.index(
            "ssm_scan_roofline_pct")
    for name in ("short_conv_ms", "short_conv_roofline_pct"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # 16,384 rows equal no other dimension of the step: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    model, traffic = _json(config["file"]), _json(
        "perfbench", "traffic", "step-8k-b4.json")
    tokens = traffic["batch"] * traffic["seq"]
    chunk = tokens // model["train"]["loss_chunks"]
    others = {2048, 3 * 2048, 7168, 1792, 2 * 1792, 32 * 64, 8 * 64, 64, 32,
              8, traffic["seq"], tokens, chunk, 4 * 32, 4 * 8,
              tokens * model["num_experts_per_tok"]}
    assert (traffic["batch"], traffic["seq"]) == (4, 8192)
    assert model["vocab_size"] == 16384 and 16384 not in others


def test_a_rehearsal_leaves_the_new_parts_records_in_the_workers_ring():
    """What the program's new parts write into the tracing the repo has: a
    compilation of the toy's step leaves a ``model/layer_kinds`` record, a
    call of the family's step a ``train/step_aux`` record with the held
    experts' load, and with the convolution's kernels on the path (interpret
    mode here) one ``conv/short`` record a traced pass."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import steptrace
    from ray_tpu.ops import conv

    toy = _json("perfbench", "tests", "configs", "tiny-lfm2.json")
    traffic = {"batch": 4, "seq": 64, "remat": True}
    built = worker.load_family(ROOT, toy).build(toy, traffic, None)
    params, opt_state = jax.jit(built.make_state)(jax.random.PRNGKey(0))
    ids = jnp.zeros((4, 64), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}
    auto = conv.auto_impl
    conv.auto_impl = lambda bcx, taps: ("pallas_interpret"
                                        if conv.fits(bcx, taps) else "jnp")
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        built.step(params, opt_state, batch)
        records = [r for r in steptrace.snapshot() if r["kind"] == "counters"]
    finally:
        conv.auto_impl = auto
        steptrace.set_enabled(False)
        jax.clear_caches()
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r["values"])
    assert by_name["model/layer_kinds"][-1] == {
        "conv": 4, "full_attention": 1, "dense": 1, "expert": 4, "layers": 5,
        "published_layers": 8}
    assert {r["backward"] for r in by_name["conv/short"]} == {0, 1}
    for r in by_name["conv/short"]:
        assert (r["channels"], r["taps"], r["tokens"], r["sequences"]) == (
            128, 3, 256, 4)
    (aux,) = by_name["train/step_aux"]
    assert aux["rows_present"] > 0 and "rows_fill" in aux and "loss" in aux


def test_the_two_older_readers_the_cell_joined_count_its_own_calls():
    """``attn_kernel_roofline_pct`` on the cell's own flash calls (their
    operands as the traced step's event text has them: 4 x 32 query heads
    on 4 x 8 key-value heads of 64, 8,192 tokens): the hand count, 32 query
    heads a sequence and T (T + 1) / 2 pairs a head. ``moe_ms`` finds the
    routed path by the row buffer's 131,072 pairs, by (tokens, 4) and by
    the router's (tokens, 32), the width the configuration states under the
    reader's key; attention's arrays have 32 heads beside 8,192 tokens, not
    beside 32,768, and are not taken."""
    q, k, vt = ("bf16[128,8192,64]{2,1,0}", "bf16[32,8192,64]{2,1,0}",
                "bf16[32,64,8192]{2,1,0}")
    tail = ('custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")
    fwd = (f"%flash_fwd.1 = (bf16[128,64,8192]{{2,1,0}}, f32[128,1,8192]"
           f"{{2,1,0}}) custom-call({q} %q, {k} %k, {vt} %v), {tail}")
    bwd = (f"%flash_bwd.1 = (f32[128,64,8192]{{2,1,0}}, {k}, {k}) "
           f"custom-call({q} %q, {k} %k, {k} %v, {vt} %ot, {q} %do), {tail}")
    pairs = 4 * 32 * (8192 * 8193 // 2)
    needed = _reader("attn_kernel_roofline_pct").needed_flops
    assert needed(fwd) == pairs * 2 * (64 + 64) == 1_099_645_845_504
    assert needed(bwd) == pairs * 2 * (3 * 64 + 2 * 64) == 2_749_114_613_760
    model = _json("perfbench", "configs", "lfm2-8b-a1b.json")
    assert model["n_routed_experts_published"] \
        == model["num_experts_published"] == 32
    routed = _reader("moe_ms").pattern(model, {"batch": 4, "seq": 8192})
    for text in ("bf16[131072,2048]", "f32[32768,4,1024]", "f32[32768,32]",
                 "s32[32768,4]"):
        assert routed.search(f"%fusion.1 = {text}{{1,0}} fusion(%p)"), text
    for text in ("bf16[4,8192,32,64]", "bf16[128,8192,64]", "f32[128,1,8192]",
                 "bf16[4,8192,6144]", "f32[16384,2048]", "bf16[32768,2048]"):
        assert not routed.search(f"%fusion.1 = {text}{{1,0}} fusion(%p)")


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

def _conv_call(kind, n, batch, t, channels, dtype="bf16"):
    bcx = f"{dtype}[{batch},{t},{3 * channels}]{{2,1,0}} %bcx"
    dy = f"{dtype}[{batch},{t},{channels}]{{2,1,0}} %dy"
    taps = f"f32[3,{channels}]{{1,0}} %taps"
    if kind == "fwd":
        ins = [bcx] * 5 + [taps]
        outs = f"{dtype}[{batch},{t},{channels}]{{2,1,0}}"
    else:
        ins = [bcx] * 3 + [dy] + [bcx] * 3 + [dy, taps]
        outs = (f"({dtype}[{batch},{t},{3 * channels}]{{2,1,0}}, "
                f"f32[{batch},3,{channels}]{{2,1,0}})")
    return (f"%short_conv_{kind}.{n} = {outs} custom-call({', '.join(ins)}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_what_a_call_needs_is_read_from_its_operands():
    """The cell's calls: four sequences of 8,192, 2,048 channels, bfloat16.
    ``bcx`` is handed over once a block the kernel reads of it and counts
    once."""
    reader = _reader("short_conv_roofline_pct")
    cells, taps = 4 * 8192 * 2048, 3 * 2048 * 4
    fwd = reader.needed(_conv_call("fwd", 1, 4, 8192, 2048))
    assert fwd == {"bytes": 8 * cells + taps, "flops": 7 * cells}
    assert fwd["bytes"] == 536_895_488
    bwd = reader.needed(_conv_call("bwd", 2, 4, 8192, 2048))
    assert bwd == {"bytes": 14 * cells + 2 * taps, "flops": 21 * cells}
    # ISSUE 52's floors: 0.66 ms forward, 1.15 backward a layer
    assert fwd["bytes"] / 819e9 == pytest.approx(0.66e-3, rel=0.01)
    assert bwd["bytes"] / 819e9 == pytest.approx(1.15e-3, rel=0.01)
    # the bytes bound both, by a wide margin
    for call in (fwd, bwd):
        assert call["bytes"] / 819e9 > 50 * call["flops"] / 197e12
    f32 = reader.needed(_conv_call("fwd", 3, 1, 64, 128, "f32"))
    assert f32 == {"bytes": 4 * 4 * 64 * 128 + 3 * 128 * 4,
                   "flops": 7 * 64 * 128}
    assert reader.needed("%fusion.3 = bf16[4] fusion(%p)") is None
    assert reader.needed(
        '%ssm_scan_fwd.1 = bf16[2,64,8] custom-call(bf16[2,8,64] %q), '
        'custom_call_target="tpu_custom_call"') is None
    assert reader.needed(
        '%short_conv_fwd.1 = bf16[2,64,8] custom-call(bf16[2,64,24] %a, '
        'f32[3,9] %w), custom_call_target="tpu_custom_call"') is None


def test_both_readers_on_hand_made_kernels():
    """Two forward calls that take twice their memory floor and a backward
    call that takes four times its own: the time is their sum, the share is
    over all three."""
    reader = _reader("short_conv_roofline_pct")
    fwd_text = _conv_call("fwd", 1, 2, 1024, 256)
    bwd_text = _conv_call("bwd", 2, 2, 1024, 256)
    floor = lambda text: reader.needed(text)["bytes"] / 819e9 * 1e9
    ns_f, ns_b = int(2 * floor(fwd_text)), int(4 * floor(bwd_text))
    trace = _steps(lambda t0: [
        (fwd_text, t0 + 2 * MS, t0 + 2 * MS + ns_f),
        (fwd_text, t0 + 4 * MS, t0 + 4 * MS + ns_f),
        (bwd_text, t0 + 6 * MS, t0 + 6 * MS + ns_b)])
    assert _read("short_conv_ms", trace) == pytest.approx(
        (2 * ns_f + ns_b) / 1e6)
    share = _read("short_conv_roofline_pct", trace)
    assert share == pytest.approx(
        100 * (2 * floor(fwd_text) + floor(bwd_text)) / (2 * ns_f + ns_b),
        rel=1e-4)
    assert 25 < share < 50
    # a program without the kernels (the parent of PR 52, the jnp form), no
    # trace, no peaks: nothing, and nothing raised
    plain = _steps(lambda t0: [])
    for name in ("short_conv_ms", "short_conv_roofline_pct"):
        assert _read(name, plain) is None
        assert _read(name, None) is None
    assert _read("short_conv_roofline_pct", trace, peaks=None) is None
    # the other kernels' readers find none of theirs in a convolution's call
    assert _read("attn_kernel_ms", trace) is None
    assert _read("ssm_scan_ms", trace) is None


def test_the_recorded_traces_hold_no_short_convolution():
    """The older families' traces recorded on the chip: both readers are
    silent there, as they are on the parent's program."""
    data = os.path.join(HERE, "data")
    for name in ("tiny_afmoe_step.xplane.pb", "tiny_mla_moe_step.xplane.pb"):
        trace = xplane.load(os.path.join(data, name))
        assert _read("short_conv_ms", trace) is None
        assert _read("short_conv_roofline_pct", trace) is None
