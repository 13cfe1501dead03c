"""The ``nemotron_h`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU, its adapter's counts, the
cell's entries in BENCHMARK.json (held by name, not by their place at a
list's end: entries are only ever appended), the state the family's
``make_state`` levels and the records the program's new parts leave in the
worker's ring, and the four readers the PR brought (``ssd_ms``,
``ssd_roofline_pct``, ``causal_conv_ms``, ``causal_conv_roofline_pct``) on
canned event texts and hand-made traces whose answers can be worked out on
paper."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_nemotron_h.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "nemotron-3-nano-30b-a3b.step-8k"
NEW = ("ssd_ms", "ssd_roofline_pct", "causal_conv_ms",
       "causal_conv_roofline_pct")


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
    proc, last = _run("tiny-nemotron-h.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/nemotron_h.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_the_adapter_counts_what_the_file_says():
    """``num_params`` by part at the published widths (ISSUE 58's table),
    the operations a token, and the state the toy's program makes."""
    model = _json("perfbench", "configs", "nemotron-3-nano-30b-a3b.json")
    family = worker.load_family(ROOT, model)
    assert family.layers_run(model) == tuple(enumerate((
        "mamba", "expert", "mamba", "expert", "mamba", "attention", "expert",
        "mamba", "expert")))
    sizes, d = family._sizes(model), 2688
    assert d + sizes["mamba"] + sizes["mamba_rest"] == 38_744_896
    assert d + sizes["attention"] == 23_399_040
    assert sizes["expert"] == 9_977_856 and sizes["shared"] == 19_955_712
    assert (d + sizes["router"] + sizes["router_bias"] + sizes["shared"]
            + 8 * sizes["expert"]) == 100_125_440
    assert sizes["table"] == 16384 * 2688 == 44_040_192
    assert family.num_params(model) == 666_963_456
    # 16 bytes a parameter: weights, two moments, the gradient
    assert 10.6e9 < 16 * family.num_params(model) < 10.7e9
    # a token's matrices: four Mamba mixers, one attention, four routers,
    # shared experts and 6 x 8 / 128 of an expert's worth of the six chosen,
    # the head once
    per_token = family.matmul_params_per_token(model)
    assert per_token == (4 * 38_707_200 + 23_396_352 + 4 * (
        344_064 + 19_955_712 + 0.375 * 9_977_856) + 44_040_192)
    flops = family.train_flops_per_token(model, 8192)
    assert flops == pytest.approx(
        6 * per_token + 6 * 32 * 2 * 128 * 8193 / 2
        + 4 * (16 * 64 * 64 * 128 + 33 * 6144))
    # by needed arithmetic the Mamba blocks are the largest part
    mamba = 4 * (6 * 38_707_200 + 16 * 64 * 64 * 128 + 33 * 6144)
    assert mamba / flops == pytest.approx(0.45, abs=0.01)
    bad = dict(model, hybrid_override_pattern="ME-M" + "M" * 48)
    with pytest.raises(ValueError):
        family.layers_run(bad)
    toy = _json("perfbench", "tests", "configs", "tiny-nemotron-h.json")
    import jax

    built = worker.load_family(ROOT, toy).build(
        toy, {"batch": 4, "seq": 64, "remat": True}, None)
    shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == family.num_params(toy)


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 58 names and brings four metrics; it
    stays off the lists whose readers find nothing, or the wrong thing, in
    it."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "nemotron-3-nano-30b-a3b",
        "traffic": "step-8k", "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    assert len(cells) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "nemotron-3-nano-30b-a3b")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == "perfbench/configs/nemotron-3-nano-30b-a3b.json"
    assert len(config["why"]) <= 200
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    # ``attn_kernel_roofline_pct``: its count of the cell's two (128, 128)
    # calls is the hand count (my chip run, PR 58); not ``moe_ms``: its
    # pattern also takes attention's keys and values as XLA folds them,
    # [2 key-value heads, 16,384, 128], beside the router's (16,384, 128)
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "attn_kernel_roofline_pct", *NEW}
    attn = _reader("attn_kernel_roofline_pct")
    pairs = 2 * 32 * 8192 * 8193 // 2
    for kind, per_pair, operands in (
            ("fwd", 512, "bf16[64,8192,128] %q, bf16[4,8192,128] %k, "
             "bf16[4,128,8192] %v"),
            ("bwd", 1280, "bf16[64,8192,128] %q, bf16[4,8192,128] %k, "
             "bf16[4,8192,128] %v")):
        assert attn.needed_flops(
            f"%flash_{kind}.1 = bf16[2,8192,4096] custom-call({operands}), "
            'custom_call_target="tpu_custom_call"') == pairs * per_pair
    older = [w["name"] for w in bench["workloads"]]
    older = set(older[:older.index(CELL)])
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists:   # appended: after every older cell
            assert set(lists[:lists.index(CELL)]) <= older
    names = [m["name"] for m in bench["per_layer"]]
    assert [names.index(n) for n in NEW] == list(range(
        names.index("ssd_ms"), names.index("ssd_ms") + 4))
    assert names.index("ssd_ms") > names.index("delta_rule_roofline_pct")
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # the vocabulary slice, 16,384 rows, equals the step's tokens: what
    # ``loss_head_ms`` takes for vocabulary-wide is every flattened token
    # array, so the cell stays off its list
    model, traffic = _json(config["file"]), _json(
        "perfbench", "traffic", "step-8k.json")
    assert model["vocab_size"] == traffic["batch"] * traffic["seq"] == 16384
    loss_head = next(m for m in bench["per_layer"]
                     if m["name"] == "loss_head_ms")
    assert CELL not in loss_head["workloads"]


def test_a_run_starts_level_and_leaves_the_new_parts_records_in_the_ring():
    """``make_state`` of the family moves the held experts' entries of each
    expert block's selection bias until each held expert receives its level
    share of the cell's one batch, and leaves every other parameter the
    program's own. A call of the family's step writes a
    ``model/layer_kinds`` record, one ``ssd/scan`` record a traced pass and a
    ``train/step_aux`` record with the held experts' load, hands the worker
    the loss alone and moves the held entries of the bias by the update
    rate, towards the level share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import traffic as traffic_mod
    from ray_tpu._private import steptrace

    toy = _json("perfbench", "tests", "configs", "tiny-nemotron-h.json")
    traffic = {"batch": 4, "seq": 64, "remat": True}
    family = worker.load_family(ROOT, toy)
    seed = 2147483777
    key = jax.random.PRNGKey(seed % 2**32)
    built = family.build(toy, traffic, None)
    params, opt_state = jax.jit(built.make_state)(key)
    no_sweep = dict(toy["train"], selection_bias=dict(
        toy["train"]["selection_bias"], sweeps=0))
    plain = jax.jit(family.build(
        dict(toy, train=no_sweep), traffic, None).make_state)(key)[0]
    held, index = toy["n_routed_experts"], toy["expert_shard"]["index"]
    mine = slice(index * held, (index + 1) * held)
    moved = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(plain)):
        a, b = np.asarray(a), np.asarray(b)
        if path[-1].key == "router_bias":
            assert not b.any() and a[mine].any()
            a = a.copy()
            a[mine] = 0
            moved += 1
        np.testing.assert_array_equal(a, b)
    assert moved == 2          # the toy's two expert blocks
    tokens = traffic_mod.resident_tokens(seed, traffic, toy["vocab_size"])
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    share = (4 * 64 * toy["num_experts_per_tok"]
             / toy["n_routed_experts_published"])
    parts = jax.jit(built.loss_with_parts)
    load = np.asarray(parts(params, batch)[1]["tokens_per_expert"])
    unlevelled = np.asarray(parts(plain, batch)[1]["tokens_per_expert"])
    assert load.shape == unlevelled.shape == (2, held)
    assert np.abs(load - share).max() <= 0.08 * share
    assert np.abs(unlevelled - share).max() > np.abs(load - share).max()

    before = np.array(plain["layers_1"]["mixer"]["router_bias"])
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        after, _, loss = built.step(plain, opt_state, batch)
        records = [r for r in steptrace.snapshot() if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert isinstance(loss, float) and np.isfinite(loss)
    step = np.asarray(after["layers_1"]["mixer"]["router_bias"]) - before
    np.testing.assert_allclose(
        step[mine], 0.001 * np.sign(share - unlevelled[0]), rtol=1e-3)
    assert not step[:mine.start].any() and not step[mine.stop:].any()
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r["values"])
    assert by_name["model/layer_kinds"][-1] == {
        "mamba": 2, "attention": 1, "expert": 2, "layers": 5,
        "published_layers": 8}
    assert {r["backward"] for r in by_name["ssd/scan"]} == {0, 1}
    for r in by_name["ssd/scan"]:
        assert (r["heads"], r["groups"], r["head_dim"], r["states"],
                r["tokens"], r["sequences"], r["chunk"], r["stride"]) == (
            4, 2, 16, 16, 256, 4, 16, 16)
    (aux,) = by_name["train/step_aux"]
    assert aux["rows_present"] > 0 and "rows_fill" in aux and "loss" in aux


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

def _scan_call(kind, n, batch, t, heads, groups, head_dim=64, states=128,
               dtype="bf16", stride=256, block=1024, chunk=128):
    xs = f"{dtype}[{batch},{t},{heads * head_dim}]{{2,1,0}}"
    bc = f"{dtype}[{batch},{t},{groups * states}]{{2,1,0}}"
    block = min(block, t)
    gate = (f"f32[{batch},{heads},{t // block},{block // chunk},{chunk}]"
            "{4,3,2,1,0}")
    skip = f"f32[1,{heads * head_dim}]{{1,0}}"
    bounds = (f"f32[{batch},{t // stride},{heads // 2},{2 * head_dim},"
              f"{states}]{{4,3,2,1,0}}")
    ins = [f"{xs} %x", f"{bc} %b", f"{bc} %c", f"{gate} %a", f"{gate} %dt",
           f"{skip} %d"]
    if kind == "fwd":
        outs = f"({xs}, {bounds})"
    else:
        ins += [f"{xs} %dy", f"{bounds} %s"]
        outs = f"({xs}, {bc}, {bc}, {gate}, {gate}, {gate})"
    return (f"%ssd_{kind}.{n} = {outs} custom-call({', '.join(ins)}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def _conv_call(kind, n, batch, t, channels, rows=5, dtype="bf16"):
    x = f"{dtype}[{batch},{t},{channels}]{{2,1,0}}"
    taps = f"f32[{rows},{channels}]{{1,0}}"
    if kind == "fwd":
        ins, outs = [f"{x} %x", f"{x} %x", f"{taps} %w"], x
    else:
        ins = [f"{x} %x", f"{x} %dy", f"{x} %x", f"{taps} %w"]
        outs = f"({x}, f32[{batch},{rows},{channels}]{{2,1,0}})"
    return (f"%causal_conv_{kind}.{n} = {outs} custom-call({', '.join(ins)}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_what_a_scan_call_needs_is_read_from_its_operands():
    """The cell's calls: two sequences of 8,192, 64 heads of 64 over 8
    groups of 128 states, bfloat16: the recurrence's bytes and operations,
    whatever the chunk, the stride and the block."""
    reader = _reader("ssd_roofline_pct")
    tokens, entries = 2 * 8192, 2 * 8192 * 64 * 64 * 128
    fwd = reader.needed(_scan_call("fwd", 1, 2, 8192, 64, 8))
    assert fwd == {"bytes": tokens * 20_736, "flops": 5 * entries}
    bwd = reader.needed(_scan_call("bwd", 2, 2, 8192, 64, 8))
    assert bwd == {"bytes": tokens * 33_280, "flops": 11 * entries}
    # the floors a block at 16,384 tokens: the bytes bound both passes
    assert fwd["bytes"] / 819e9 == pytest.approx(0.415e-3, rel=0.01)
    assert bwd["bytes"] / 819e9 == pytest.approx(0.666e-3, rel=0.01)
    for call in (fwd, bwd):
        assert call["bytes"] / 819e9 > call["flops"] / 197e12
    assert reader.needed(_scan_call("fwd", 3, 2, 8192, 64, 8, stride=128,
                                    block=512, chunk=64)) == fwd
    f32 = reader.needed(_scan_call("fwd", 4, 1, 256, 4, 2, dtype="f32",
                                   stride=128))
    assert f32 == {"bytes": 256 * (2 * 256 * 4 + 2 * 256 * 4 + 4 * 4),
                   "flops": 5 * 256 * 4 * 64 * 128}
    assert reader.needed("%fusion.3 = bf16[4] fusion(%p)") is None
    assert reader.needed(
        '%ssm_scan_fwd.1 = bf16[2,64,8] custom-call(bf16[2,8,64] %q), '
        'custom_call_target="tpu_custom_call"') is None
    assert reader.needed(
        '%ssd_fwd.1 = bf16[2,64,8] custom-call(bf16[2,64,24] %a, '
        'f32[3,9] %w), custom_call_target="tpu_custom_call"') is None
    # the kernels' own record of a traced pass counts the same bytes
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    x = jax.ShapeDtypeStruct((2, 8192, 64, 64), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
    assert ssm.ssd_bytes_needed(x, b, False) == fwd["bytes"]
    assert ssm.ssd_bytes_needed(x, b, True) == bwd["bytes"]


def test_what_a_convolution_call_needs_is_read_from_its_operands():
    """The cell's calls over x (4,096 channels) and over B or C (1,024),
    four taps and a bias: ``ops.conv.causal_needed_bytes``'s count, read
    from the text; without a bias the taps' array is a row shorter."""
    from ray_tpu.ops import conv

    reader = _reader("causal_conv_roofline_pct")
    for channels in (4096, 1024):
        for kind, backward in (("fwd", False), ("bwd", True)):
            call = reader.needed(_conv_call(kind, 1, 2, 8192, channels))
            assert call["bytes"] == conv.causal_needed_bytes(
                2 * 8192, channels, 4, 2, backward, bias=True)
            assert call["bytes"] / 819e9 > call["flops"] / 197e12
    plain = reader.needed(_conv_call("fwd", 2, 2, 8192, 8192, rows=4))
    assert plain["bytes"] == conv.causal_needed_bytes(
        2 * 8192, 8192, 4, 2, False)
    assert reader.needed(
        '%short_conv_fwd.1 = bf16[2,64,8] custom-call(bf16[2,64,24] %a, '
        'f32[3,8] %w), custom_call_target="tpu_custom_call"') is None
    assert reader.needed(
        '%causal_conv_fwd.1 = bf16[2,64,8] custom-call(bf16[2,64,8] %a, '
        'f32[3,9] %w), custom_call_target="tpu_custom_call"') is None


def test_the_four_readers_on_hand_made_kernels():
    """Two scan calls forward at twice their memory floor and one backward
    at four times its own; a convolution each way at three times: each time
    is its kernels' sum, each share is over its kernels alone."""
    scan, conv = (_reader("ssd_roofline_pct"),
                  _reader("causal_conv_roofline_pct"))
    texts = {"sf": _scan_call("fwd", 1, 2, 1024, 8, 2),
             "sb": _scan_call("bwd", 2, 2, 1024, 8, 2),
             "cf": _conv_call("fwd", 3, 2, 1024, 512),
             "cb": _conv_call("bwd", 4, 2, 1024, 512)}
    floor = lambda r, text: r.needed(text)["bytes"] / 819e9 * 1e9
    ns = {"sf": int(2 * floor(scan, texts["sf"])),
          "sb": int(4 * floor(scan, texts["sb"])),
          "cf": int(3 * floor(conv, texts["cf"])),
          "cb": int(3 * floor(conv, texts["cb"]))}
    trace = _steps(lambda t0: [
        (texts["sf"], t0 + 1 * MS, t0 + 1 * MS + ns["sf"]),
        (texts["cf"], t0 + 2 * MS, t0 + 2 * MS + ns["cf"]),
        (texts["sf"], t0 + 3 * MS, t0 + 3 * MS + ns["sf"]),
        (texts["sb"], t0 + 4 * MS, t0 + 4 * MS + ns["sb"]),
        (texts["cb"], t0 + 6 * MS, t0 + 6 * MS + ns["cb"])])
    assert _read("ssd_ms", trace) == pytest.approx(
        (2 * ns["sf"] + ns["sb"]) / 1e6)
    assert _read("causal_conv_ms", trace) == pytest.approx(
        (ns["cf"] + ns["cb"]) / 1e6)
    assert _read("ssd_roofline_pct", trace) == pytest.approx(
        100 * (2 * floor(scan, texts["sf"]) + floor(scan, texts["sb"]))
        / (2 * ns["sf"] + ns["sb"]), rel=1e-3)
    assert 25 < _read("ssd_roofline_pct", trace) < 50
    assert _read("causal_conv_roofline_pct", trace) == pytest.approx(
        100 / 3, rel=1e-3)
    # a program without the kernels (the parent of PR 58, the twin), no
    # trace, no peaks: nothing, and nothing raised
    plain = _steps(lambda t0: [])
    for name in NEW:
        assert _read(name, plain) is None
        assert _read(name, None) is None
    for name in ("ssd_roofline_pct", "causal_conv_roofline_pct"):
        assert _read(name, trace, peaks=None) is None
    # the other kernels' readers find none of theirs in these calls
    for name in ("attn_kernel_ms", "ssm_scan_ms", "short_conv_ms",
                 "delta_rule_ms"):
        assert _read(name, trace) is None
    # nor these in the older families' traces recorded on the chip
    data = os.path.join(HERE, "data")
    for name in ("tiny_afmoe_step.xplane.pb", "tiny_mla_moe_step.xplane.pb"):
        old = xplane.load(os.path.join(data, name))
        for reader in NEW:
            assert _read(reader, old) is None
