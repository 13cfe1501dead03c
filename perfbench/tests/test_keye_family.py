"""The ``keye`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU (traced: the kernels' readers
find nothing there and leave their metrics out), the cell's entries in
BENCHMARK.json (held by name, not by their place at a list's end: entries
are only ever appended), and the five readers the family brought on canned
event texts and hand-made traces whose answers can be worked out on paper.
The adapter's counts against a hand count are ``tests/test_keye.py``'s."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_keye.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "keye-vl-2.0-30b-a3b.step-16k-img"
NEW = ["attn_selected_ms", "attn_selected_roofline_pct", "index_select_ms",
       "index_select_roofline_pct", "index_loss_ms"]


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


def test_the_family_rehearses_to_correct_through_run_py_traced(tmp_path):
    """``--trace 1`` on the CPU: the run ends ``correct`` against the
    float32 reference (loss and gradient, the indexer's leaves among them),
    and the kernels' readers, the new five among them, find nothing and
    leave their metrics out of the line rather than raise."""
    proc, last = _run("tiny-keye.step", 1, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert "hbm_plan_gib" in last["metrics"]       # a counter: any device
    assert not {*NEW, "attn_kernel_ms", "grouped_matmul_ms"} & set(
        last["metrics"])
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/keye.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 67 names (and ``attn_kernel_ms``,
    whose reader finds the kernels under a selection by their prefix), not
    ``moe_ms`` nor the causal roofline readers, and brings five metrics."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) >= 13 and len(bench["configs"]) >= 11
    assert cells[CELL] == {
        "name": CELL, "config": "keye-vl-2.0-30b-a3b",
        "traffic": "step-16k-img", "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    for said in ("16384", "2,048", "23%", "1,024 rows", "~8x"):
        assert said in cells[CELL]["why"], said
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "keye-vl-2.0-30b-a3b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "perfbench/configs/keye-vl-2.0-30b-a3b.json"
    assert len(config["why"]) <= 200
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "grouped_matmul_ms",
        "grouped_matmul_roofline_pct", *NEW}
    older = [w["name"] for w in bench["workloads"]]
    older = set(older[:older.index(CELL)])
    assert len(older) == 12
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists:   # appended: after every older cell
            assert set(lists[:lists.index(CELL)]) <= older
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW[0]):][:5] == NEW      # side by side
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"][0] == CELL and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # 18,992 rows equal no other dimension of the step: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    model = _json(config["file"])
    traffic = _json("perfbench", "traffic", "step-16k-img.json")
    seq = traffic["seq"]
    others = {2048, 4096, 1024, 512, 128, 64, 768, 1536, 32, 4, 8, 16, seq,
              seq // model["train"]["loss_chunks"],
              seq * model["num_experts_per_tok"]}
    assert model["vocab_size"] == 18992 and 18992 not in others
    assert "n_routed_experts_published" not in model   # ``moe_ms``'s key
    # the traffic: four spans of 32 x 32 between text runs
    assert {k: traffic[k] for k in ("batch", "seq", "feed", "remat",
                                    "save_every_steps", "warmup_steps",
                                    "traced_steps")} == {
        k: v for k, v in _json("perfbench", "traffic",
                               "step-16k.json").items() if k in traffic}
    assert (traffic["images"], traffic["image_grid"],
            traffic["image_offsets"]) == (4, [32, 32],
                                          [1024, 5120, 9216, 13312])


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

def _flash(kind, n, mask="_sel2048", heads=32, kv=4, seq=16384, d=128):
    third = f"bf16[{kv},{d},{seq}]" if kind == "fwd" else f"bf16[{kv},{seq},{d}]"
    return (f"%flash_{kind}{mask}.{n} = bf16[1,{seq},{heads * d}] "
            f"custom-call(bf16[{heads},{seq},{d}] %q, bf16[{kv},{seq},{d}] "
            f'%k, {third} %v, s8[1,{seq},{seq}] %m), '
            'custom_call_target="tpu_custom_call"')


def _select(n, seq=16384, heads=16, width=64):
    return (f"%index_select_top2048.{n} = (s8[1,{seq},{seq}], f32[1,1,{seq}]) "
            f"custom-call(bf16[1,{seq},{width}] %k, bf16[1,{heads},{width},"
            f'{seq}] %q, f32[1,{heads},{seq}] %w), '
            'custom_call_target="tpu_custom_call"')


def _kl(n):
    return (f"%index_kl.{n} = (f32[1,1,16384]) custom-call(bf16[1,32,16384,"
            '128] %q), custom_call_target="tpu_custom_call"')


def test_the_calls_needed_operations_follow_the_selection():
    """The cell's calls: 32 query heads on 4 of keys and values, 16,384
    positions, 2,048 keys a query: 31,458,304 pairs a head of the causal
    mask's 134,225,920 (23.4%); the index scores over all of those."""
    reader = _reader("attn_selected_roofline_pct")
    pairs = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert reader.selected_pairs(16384, 2048) == pairs == 31_458_304
    assert reader.selected_pairs(6, 2) == 1 + 2 + 4 * 2      # by hand
    assert reader.selected_pairs(5, 9) == 15                 # all of them
    causal = 16384 * 16385 // 2
    assert pairs / causal == pytest.approx(0.234, abs=1e-3)
    per = {"fwd": 2 * (128 + 128), "bwd": 2 * (3 * 128 + 2 * 128)}
    for kind in ("fwd", "bwd"):
        assert reader.needed_flops(_flash(kind, 1)) == 32 * pairs * per[kind]
        assert reader.needed_flops(_flash(kind, 1, "_sel4")) \
            == 32 * reader.selected_pairs(16384, 4) * per[kind]
        # a call under another mask is not this reader's
        for other in ("", "_w1024", "_bd4"):
            assert reader.needed_flops(_flash(kind, 1, other)) is None
    assert reader.needed_flops("%fusion.3 = bf16[4] fusion(%p)") is None
    # what the family counts for the same calls: 6 H 2 D a pair a layer
    # (the backward kernel's fifth matmul, the scores again, is recomputed
    # work and no needed operation of the step)
    model = _json("perfbench", "configs", "keye-vl-2.0-30b-a3b.json")
    family = worker.load_family(ROOT, model)
    parts = family.attention_flops(model, 16384)
    assert parts["selected"] == 32 * pairs * 6 * 2 * 128
    assert parts["index_loss"] == pairs * (2 * 32 * 128 + 6 * 16 * 64)
    scores = _reader("index_select_roofline_pct")
    assert scores.needed_flops(_select(1)) == causal * 2 * 16 * 64 \
        == parts["index_scores"]
    assert scores.needed_flops(_flash("fwd", 1)) is None
    assert reader.needed_flops(_select(1)) is None


def test_the_readers_on_hand_made_kernels():
    """Three steps of six layers: a selection of 0.01 ms twice a layer (the
    recomputed block's among them), a forward call of 0.02 ms, a backward
    call of 0.04, a KL call of 0.03, and one causal call that only
    ``attn_kernel_ms`` counts."""
    def step(t0):
        ops, at = [], t0 + MS
        for i in range(6):
            for text, ms in ((_select(2 * i), 1), (_select(2 * i + 1), 1),
                             (_flash("fwd", i), 2), (_kl(i), 3),
                             (_flash("bwd", i), 4)):
                ops.append((text, at, at + ms * MS // 100))
                at += MS // 4
        return ops + [(_flash("fwd", 9, ""), at, at + MS // 100)]

    trace = _steps(step)
    assert _read("attn_selected_ms", trace) == pytest.approx(6 * 0.06)
    assert _read("attn_kernel_ms", trace) == pytest.approx(6 * 0.06 + 0.01)
    assert _read("index_select_ms", trace) == pytest.approx(6 * 0.02)
    assert _read("index_loss_ms", trace) == pytest.approx(6 * 0.03)
    needed = 6 * 32 * 31_458_304 * (512 + 1280)
    assert _read("attn_selected_roofline_pct", trace) == pytest.approx(
        100 * needed / (3.6e-4 * 197e12), rel=1e-6)
    scores = 12 * (16384 * 16385 // 2) * 2 * 16 * 64
    assert _read("index_select_roofline_pct", trace) == pytest.approx(
        100 * scores / (1.2e-4 * 197e12), rel=1e-6)
    for name in ("attn_selected_roofline_pct", "index_select_roofline_pct"):
        assert _read(name, trace, peaks=None) is None
    # a program without the selection (the parent's), a CPU: nothing to
    # read, nothing raised
    plain = _steps(lambda t0: [(_flash("fwd", 1, ""), t0, t0 + MS)])
    old = xplane.load(os.path.join(HERE, "data", "tiny_afmoe_step.xplane.pb"))
    for name in NEW:
        assert _read(name, plain) is None
        assert _read(name, old) is None
