"""The ``sdar`` family as the benchmark runs it: its toy configuration
through ``run.py`` to ``correct`` on the CPU, traced and untraced, the
cell's entries in BENCHMARK.json (held by name, not by their place at a
list's end: entries are only ever appended), and the two readers the family
brought (``attn_blockdiff_ms``, ``attn_blockdiff_roofline_pct``) on canned
event texts and hand-made traces whose answers can be worked out on paper.
The adapter's counts against a hand count are ``tests/test_sdar.py``'s."""

import json
import os

import pytest

from perfbench import worker, xplane
from perfbench.tests.test_afmoe_family import _steps
from perfbench.tests.test_rehearsal import _checks, _run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join("perfbench", "tests", "rehearsal_sdar.json")
MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "sdar-30b-a3b-chat.step-bd-4k"


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
    proc, last = _run("tiny-sdar.step", 0, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    checks = _checks(proc)
    assert checks and set(checks.values()) == {"ok"}, checks
    assert "perfbench/families/sdar.py" in proc.stdout
    assert "'grad_cosine'" in proc.stdout     # the toy compares the gradient


def test_a_traced_rehearsal_leaves_out_what_a_cpu_cannot_read(tmp_path):
    """``--trace 1`` on the CPU: the run ends, and the kernels' readers
    (the new two among them) find nothing and leave their metrics out of
    the line rather than raise."""
    proc, last = _run("tiny-sdar.step", 1, tmp_path, bench_file=BENCH)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["correct"] is True
    assert not {"attn_blockdiff_ms", "attn_blockdiff_roofline_pct",
                "attn_kernel_ms", "grouped_matmul_ms"} & set(last["metrics"])


def test_the_benchmark_file_gained_the_cell():
    """The cell joins the lists ISSUE 65 names, but ``moe_ms``, and brings
    two metrics of its own; it stays off the lists whose readers would
    misread it."""
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "sdar-30b-a3b-chat", "traffic": "step-bd-4k",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    for said in ("16,384 positions", "1,024 rows", "8,192", "1.7x"):
        assert said in cells[CELL]["why"], said
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "sdar-30b-a3b-chat")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "perfbench/configs/sdar-30b-a3b-chat.json"
    assert len(config["why"]) <= 200
    joined = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        "tokens_per_s_per_chip", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "loss_head_ms", "compile_s", "step_trace_lower_s", "step_backend_s",
        "step_cache_hit_pct", "grouped_matmul_ms",
        "grouped_matmul_roofline_pct", "attn_blockdiff_ms",
        "attn_blockdiff_roofline_pct"}
    # ``attn_kernel_roofline_pct`` and ``attn_masked_roofline_pct`` count a
    # causal mask's pairs, twice this one's; ``moe_ms`` counts a step's
    # tokens as batch x seq, half of the positions this program routes
    # (PERF.md section 7 says which edit it needs)
    older = [w["name"] for w in bench["workloads"]]
    older = set(older[:older.index(CELL)])
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists:   # appended: after every older cell
            assert set(lists[:lists.index(CELL)]) <= older
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-2:] == ["attn_blockdiff_ms", "attn_blockdiff_roofline_pct"]
    for name in names[-2:]:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "kernel"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    # 18,992 rows equal no other dimension of the step: what
    # ``loss_head_ms`` reads as vocabulary-wide is the head and the embedding
    model, traffic = _json(config["file"]), _json(
        "perfbench", "traffic", "step-bd-4k.json")
    positions = 2 * traffic["batch"] * traffic["seq"]
    others = {2048, 4096, 512, 128, 64, 768, 1536, 32, 4, 8, 16,
              traffic["seq"], 2 * traffic["seq"], positions,
              positions // 2 // model["train"]["loss_chunks"],
              positions * model["num_experts_per_tok"]}
    assert model["vocab_size"] == 18992 and 18992 not in others
    assert "n_routed_experts_published" not in model   # ``moe_ms``'s key


# ----------------------------------------------------------------------
# canned event texts, hand-made traces
# ----------------------------------------------------------------------

def _kernel(kind, n, mask="_bd4", heads=64, kv=8, seq=8192, d=128):
    third = f"bf16[{kv},{d},{seq}]" if kind == "fwd" else f"bf16[{kv},{seq},{d}]"
    return (f"%flash_{kind}{mask}.{n} = bf16[2,{seq},{heads // 2 * d}] "
            f"custom-call(bf16[{heads},{seq},{d}] %q, bf16[{kv},{seq},{d}] "
            f'%k, {third} %v), custom_call_target="tpu_custom_call"')


def test_a_calls_needed_operations_follow_the_block_mask():
    """The cell's call: 64 folded query heads on 8 of keys and values,
    8,192 positions (two streams of 4,096), blocks of 4: ``L^2 + L D`` pairs
    a head, a quarter of the square and half of a causal mask's."""
    reader = _reader("attn_blockdiff_roofline_pct")
    pairs = 4096 * 4 + 4096 * 4092 // 2 + 4096 * 4100 // 2
    assert reader.live_pairs(4096, 4) == pairs == 4096 ** 2 + 4096 * 4 \
        == 16_793_600
    assert reader.live_pairs(8, 2) == 16 + 24 + 40      # by hand
    causal = _reader("attn_masked_roofline_pct").attended_pairs(8192, 8192)
    assert pairs / causal == pytest.approx(0.5004, abs=1e-4)
    per = {"fwd": 2 * (128 + 128), "bwd": 2 * (3 * 128 + 2 * 128)}
    for kind in ("fwd", "bwd"):
        assert reader.needed_flops(_kernel(kind, 1)) == 64 * pairs * per[kind]
        assert reader.needed_flops(_kernel(kind, 1, "_bd8")) \
            == 64 * (4096 ** 2 + 4096 * 8) * per[kind]
        # a call under another mask is not this reader's
        assert reader.needed_flops(_kernel(kind, 1, "")) is None
        assert reader.needed_flops(_kernel(kind, 1, "_w1024")) is None
    assert reader.needed_flops("%fusion.3 = bf16[4] fusion(%p)") is None
    # a step of the cell: six layers, forward and backward
    step = 6 * 64 * pairs * (per["fwd"] + per["bwd"])
    assert step == pytest.approx(1.15e13, rel=5e-3)
    # what the family counts for the same calls: 6 H 2 D a pair
    model = _json("perfbench", "configs", "sdar-30b-a3b-chat.json")
    family = worker.load_family(ROOT, model)
    assert 2 * 4096 * (family.train_flops_per_token(model, 4096)
                       - 6 * family.matmul_params_per_token(model)) \
        == pytest.approx(step / (per["fwd"] + per["bwd"]) * 6 * 2 * 128)


def test_both_readers_on_hand_made_kernels():
    """Three steps of six forward calls of 1 ms and six backward calls of 2
    ms under the block mask, and one causal call that only
    ``attn_kernel_ms`` counts."""
    ns = {"fwd": MS, "bwd": 2 * MS}
    trace = _steps(lambda t0: [
        (_kernel(kind, i), t0 + i * MS // 2, t0 + i * MS // 2 + ns[kind] // 10)
        for i in range(6) for kind in ("fwd", "bwd")]
        + [(_kernel("fwd", 9, ""), t0 + 5 * MS, t0 + 5 * MS + MS // 10)])
    assert _read("attn_blockdiff_ms", trace) == pytest.approx(6 * 0.3)
    assert _read("attn_kernel_ms", trace) == pytest.approx(6 * 0.3 + 0.1)
    needed = 6 * 64 * 16_793_600 * (512 + 1280)
    assert _read("attn_blockdiff_roofline_pct", trace) == pytest.approx(
        100 * needed / (1.8e-3 * 197e12), rel=1e-6)
    assert _read("attn_blockdiff_roofline_pct", trace, peaks=None) is None
    # a program without the mask, a CPU: nothing to read, nothing raised
    plain = _steps(lambda t0: [(_kernel("fwd", 1, ""), t0, t0 + MS)])
    assert _read("attn_blockdiff_ms", plain) is None
    assert _read("attn_blockdiff_roofline_pct", plain) is None
    data = os.path.join(HERE, "data")
    for name in ("tiny_afmoe_step.xplane.pb", "tiny_mla_moe_step.xplane.pb"):
        old = xplane.load(os.path.join(data, name))
        assert _read("attn_blockdiff_ms", old) is None
        assert _read("attn_blockdiff_roofline_pct", old) is None
