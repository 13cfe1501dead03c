"""The ``qwen3_next`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU, its adapter's counts, the
cell's entries in BENCHMARK.json (held by name, not by their place at a
list's end: entries are only ever appended), the state the family's
``make_state`` levels and the records the program's new parts leave in the
worker's ring, and the two readers the family brought (``delta_rule_ms``,
``delta_rule_roofline_pct``) on canned event texts and hand-made traces
whose answers can be worked out on paper."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_qwen3_next.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "qwen3-next-80b-a3b.step-8k"


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
    proc, last = _run("tiny-qwen3-next.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/qwen3_next.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_the_adapter_counts_what_the_file_says():
    """``num_params`` by part at the published widths (ISSUE 56's table),
    the operations a token, and the state the toy's program makes."""
    model = _json("perfbench", "configs", "qwen3-next-80b-a3b.json")
    family = worker.load_family(ROOT, model)
    assert family.layers_run(model) == (
        (0, "linear_attention"), (1, "linear_attention"),
        (2, "linear_attention"), (3, "full_attention"))
    sizes = family._sizes(model)
    assert sizes["linear"] + sizes["linear_rest"] == 33_718_464
    assert sizes["attn"] + sizes["head_norms"] == 27_263_488
    assert sizes["router"] == 1_048_576 and sizes["shared"] == 3_147_776
    assert sizes["expert"] == 3_145_728
    assert sizes["table"] == 18992 * 2048 == 38_895_616
    # ISSUE 56's count, and the selection bias's 512 a layer
    assert family.num_params(model) == 625_667_136 + 4 * 512
    # 16 bytes a parameter: weights, two moments, the gradient
    assert 10.0e9 < 16 * family.num_params(model) < 10.02e9
    # a token's matrices: three linear mixers, one attention, four routers,
    # shared experts with their gates and 10 x 32 / 512 of an expert's
    # worth of the ten chosen, the head once
    per_token = family.matmul_params_per_token(model)
    assert per_token == (3 * 33_685_504 + 27_262_976 + 4 * (
        1_048_576 + 3_147_776 + 0.625 * 3_145_728) + 38_895_616)
    flops = family.train_flops_per_token(model, 8192)
    assert flops == pytest.approx(
        6 * per_token + 6 * 16 * 2 * 256 * 8193 / 2
        + 3 * (22 * 32 * 128 * 128 + 31 * 8192))
    # the one full layer's pairs are 15% of the arithmetic at 8,192
    assert 6 * 16 * 2 * 256 * 8193 / 2 / flops == pytest.approx(0.145,
                                                                abs=0.005)
    assert flops * 2 * 8192 == pytest.approx(22.7e12, rel=3e-3)
    bad = dict(model, layer_types=["full_attention"] * 48)
    with pytest.raises(ValueError):
        family.layers_run(bad)
    toy = _json("perfbench", "tests", "configs", "tiny-qwen3-next.json")
    import jax

    built = worker.load_family(ROOT, toy).build(
        toy, {"batch": 4, "seq": 64, "remat": True}, None)
    shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == family.num_params(toy)


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 56 names and brings two metrics of
    its own; it stays off the lists whose readers find nothing in it."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "qwen3-next-80b-a3b", "traffic": "step-8k",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "qwen3-next-80b-a3b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "perfbench/configs/qwen3-next-80b-a3b.json"
    assert len(config["why"]) <= 200
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "attn_kernel_roofline_pct", "moe_ms",
        "delta_rule_ms", "delta_rule_roofline_pct"}
    older = [w["name"] for w in bench["workloads"]]
    older = set(older[:older.index(CELL)])
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists:   # appended: after every older cell
            assert set(lists[:lists.index(CELL)]) <= older
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("delta_rule_roofline_pct") \
        == names.index("delta_rule_ms") + 1 > names.index(
            "short_conv_roofline_pct")
    for name in ("delta_rule_ms", "delta_rule_roofline_pct"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # 18,992 rows equal no other dimension of the step: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    model, traffic = _json(config["file"]), _json(
        "perfbench", "traffic", "step-8k.json")
    tokens = traffic["batch"] * traffic["seq"]
    chunk = tokens // model["train"]["loss_chunks"]
    others = {2048, 12288, 8192, 4096, 64, 512, 1024, 256, 128, 32, 16, 10,
              traffic["seq"], tokens, chunk,
              tokens * model["num_experts_per_tok"]}
    assert (traffic["batch"], traffic["seq"]) == (2, 8192)
    assert model["vocab_size"] == 18992 and 18992 not in others
    # ``moe_ms`` finds the routed path by the row buffer's 163,840 pairs, by
    # (tokens, 10) and by the router's (tokens, 512), the width the
    # configuration states under the reader's key; the key-value
    # projection's and the shared expert's 512 stand beside [2, 8192], not
    # beside 16,384
    assert model["n_routed_experts_published"] \
        == model["num_experts_published"] == 512
    routed = _reader("moe_ms").pattern(model, traffic)
    for text in ("bf16[163840,2048]", "bf16[163840,1024]", "f32[16384,512]",
                 "s32[16384,10]"):
        assert routed.search(f"%fusion.1 = {text}{{1,0}} fusion(%p)"), text
    for text in ("bf16[2,8192,512]", "bf16[2,8192,16,512]",
                 "bf16[32,8192,256]", "f32[2048,512]", "bf16[16384,2048]",
                 "bf16[2,8192,12288]"):
        assert not routed.search(f"%fusion.1 = {text}{{1,0}} fusion(%p)")


def test_a_run_starts_level_and_leaves_the_new_parts_records_in_the_ring():
    """``make_state`` of the family moves the held experts' entries of each
    layer's selection bias (zero is the published router) until each held
    expert receives its uniform share of the cell's one batch, made again
    from the seed as ``run.py`` makes it, and leaves every other parameter
    the program's own. A call of the family's step writes a
    ``model/layer_kinds`` record, one ``delta/rule`` record a traced pass
    and a ``train/step_aux`` record with the held experts' load, hands the
    worker the loss alone and moves the held entries of the bias by the
    update rate, towards the uniform share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import traffic as traffic_mod
    from ray_tpu._private import steptrace

    toy = _json("perfbench", "tests", "configs", "tiny-qwen3-next.json")
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
    held, index = toy["num_experts"], toy["expert_shard"]["index"]
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
    assert moved == 4
    tokens = traffic_mod.resident_tokens(seed, traffic, toy["vocab_size"])
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    share = 4 * 64 * toy["num_experts_per_tok"] / toy["num_experts_published"]
    parts = jax.jit(built.loss_with_parts)
    load = np.asarray(parts(params, batch)[1]["tokens_per_expert"])
    unlevelled = np.asarray(parts(plain, batch)[1]["tokens_per_expert"])
    assert load.shape == unlevelled.shape == (4, held)
    assert np.abs(load - share).max() <= 0.08 * share
    assert np.abs(unlevelled - share).max() > 0.15 * share

    # from the unlevelled state, whose loads are off their share (a copy:
    # the step donates its state)
    before = np.array(plain["layers_1"]["moe"]["router_bias"])
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
    step = np.asarray(after["layers_1"]["moe"]["router_bias"]) - before
    np.testing.assert_allclose(
        step[mine], 0.001 * np.sign(share - unlevelled[1]), rtol=1e-3)
    assert step[mine].any()
    assert not step[:mine.start].any() and not step[mine.stop:].any()
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r["values"])
    assert by_name["model/layer_kinds"][-1] == {
        "linear_attention": 3, "full_attention": 1, "expert": 4, "layers": 4,
        "published_layers": 8}
    assert {r["backward"] for r in by_name["delta/rule"]} == {0, 1}
    for r in by_name["delta/rule"]:
        assert (r["heads"], r["key_heads"], r["d_k"], r["d_v"], r["tokens"],
                r["sequences"], r["chunk"]) == (4, 2, 16, 16, 256, 4, 64)
    (aux,) = by_name["train/step_aux"]
    assert aux["rows_present"] > 0 and "rows_fill" in aux and "loss" in aux


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

def _rule_call(kind, n, batch, t, key_heads, heads, d_k=128, d_v=128,
               dtype="bf16", stride=256, chunk=64):
    qk = f"{dtype}[{batch},{t},{key_heads * d_k}]{{2,1,0}}"
    vo = f"{dtype}[{batch},{t},{heads * d_v}]{{2,1,0}}"
    gate = (f"f32[{batch},{heads},{t // stride},{stride // chunk},{chunk}]"
            "{4,3,2,1,0}")
    bounds = f"f32[{batch},{heads},{t // stride},{d_k},{d_v}]{{4,3,2,1,0}}"
    if kind == "fwd":
        ins = [f"{qk} %q", f"{qk} %k", f"{vo} %v", f"{gate} %g", f"{gate} %b"]
        outs = f"({vo}, {bounds})"
    else:
        ins = [f"{qk} %q", f"{qk} %k", f"{vo} %v", f"{gate} %g", f"{gate} %b",
               f"{vo} %do", f"{bounds} %s"]
        outs = f"({qk}, {qk}, {vo}, {gate}, {gate})"
    return (f"%gated_delta_{kind}.{n} = {outs} custom-call({', '.join(ins)}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_what_a_call_needs_is_read_from_its_operands():
    """The cell's calls: two sequences of 8,192, 32 value heads on 16 key
    heads of 128 x 128, bfloat16: the recurrence's bytes and operations,
    whatever the chunk and the stride of the kept states."""
    reader = _reader("delta_rule_roofline_pct")
    tokens, entries = 2 * 8192, 2 * 8192 * 32 * 128 * 128
    fwd = reader.needed(_rule_call("fwd", 1, 2, 8192, 16, 32))
    assert fwd == {"bytes": tokens * 24_832, "flops": 7 * entries}
    bwd = reader.needed(_rule_call("bwd", 2, 2, 8192, 16, 32))
    assert bwd == {"bytes": tokens * 41_472, "flops": 15 * entries}
    # the floors a layer at 16,384 tokens: the bytes bound both passes
    assert fwd["bytes"] / 819e9 == pytest.approx(0.497e-3, rel=0.01)
    assert bwd["bytes"] / 819e9 == pytest.approx(0.830e-3, rel=0.01)
    for call in (fwd, bwd):
        assert call["bytes"] / 819e9 > call["flops"] / 197e12
    # neither the chunk nor the stride enters
    assert reader.needed(_rule_call("fwd", 3, 2, 8192, 16, 32, stride=128,
                                    chunk=32)) == fwd
    f32 = reader.needed(_rule_call("fwd", 4, 1, 256, 1, 2, dtype="f32"))
    assert f32 == {"bytes": 256 * (2 * 128 * 4 + 2 * 256 * 4 + 2 * 2 * 4),
                   "flops": 7 * 256 * 2 * 128 * 128}
    assert reader.needed("%fusion.3 = bf16[4] fusion(%p)") is None
    assert reader.needed(
        '%ssm_scan_fwd.1 = bf16[2,64,8] custom-call(bf16[2,8,64] %q), '
        'custom_call_target="tpu_custom_call"') is None
    assert reader.needed(
        '%gated_delta_fwd.1 = bf16[2,64,8] custom-call(bf16[2,64,24] %a, '
        'f32[3,9] %w), custom_call_target="tpu_custom_call"') is None
    # the kernels' own record of a traced pass counts the same bytes
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    q = jax.ShapeDtypeStruct((2, 8192, 16, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
    assert delta.bytes_needed(q, v, False) == fwd["bytes"]
    assert delta.bytes_needed(q, v, True) == bwd["bytes"]


def test_both_readers_on_hand_made_kernels():
    """Two forward calls that take twice their memory floor and a backward
    call that takes four times its own: the time is their sum, the share is
    over all three."""
    reader = _reader("delta_rule_roofline_pct")
    fwd_text = _rule_call("fwd", 1, 2, 1024, 2, 4)
    bwd_text = _rule_call("bwd", 2, 2, 1024, 2, 4)
    floor = lambda text: reader.needed(text)["bytes"] / 819e9 * 1e9
    ns_f, ns_b = int(2 * floor(fwd_text)), int(4 * floor(bwd_text))
    trace = _steps(lambda t0: [
        (fwd_text, t0 + 2 * MS, t0 + 2 * MS + ns_f),
        (fwd_text, t0 + 4 * MS, t0 + 4 * MS + ns_f),
        (bwd_text, t0 + 6 * MS, t0 + 6 * MS + ns_b)])
    assert _read("delta_rule_ms", trace) == pytest.approx(
        (2 * ns_f + ns_b) / 1e6)
    share = _read("delta_rule_roofline_pct", trace)
    assert share == pytest.approx(
        100 * (2 * floor(fwd_text) + floor(bwd_text)) / (2 * ns_f + ns_b),
        rel=1e-3)
    assert 25 < share < 50
    # a program without the kernels (the parent of PR 56, the scan twin), no
    # trace, no peaks: nothing, and nothing raised
    plain = _steps(lambda t0: [])
    for name in ("delta_rule_ms", "delta_rule_roofline_pct"):
        assert _read(name, plain) is None
        assert _read(name, None) is None
    assert _read("delta_rule_roofline_pct", trace, peaks=None) is None
    # the other kernels' readers find none of theirs in the rule's calls
    for name in ("attn_kernel_ms", "ssm_scan_ms", "short_conv_ms"):
        assert _read(name, trace) is None
    # nor these in the older families' traces recorded on the chip
    data = os.path.join(HERE, "data")
    for name in ("tiny_afmoe_step.xplane.pb", "tiny_mla_moe_step.xplane.pb"):
        old = xplane.load(os.path.join(data, name))
        assert _read("delta_rule_ms", old) is None
        assert _read("delta_rule_roofline_pct", old) is None
