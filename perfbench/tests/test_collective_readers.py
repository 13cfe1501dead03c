"""allgather_ms and reducescatter_ms on a hand-made trace whose answer can
be worked out on paper (the instruction texts are the TPU compiler's, from
the compiled GPT-2 XL step), on the recorded one-chip traces, which hold
neither, on a small trace recorded on four chips that holds both
(data/tiny_fsdp4_step.xplane.pb), and the new cell as ``load_cell`` reads it
from BENCHMARK.json."""

import json
import os

import pytest

from perfbench import run, worker, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(DATA))
MS = 1_000_000
START = ("%async-collective-start.{n} = (bf16[400,1600]{{1,0}}, "
         "bf16[1600,1600]{{1,0}}, s32[2]{{0}}) fusion(%convert.4), "
         "kind=kCustom, calls=%fused_computation.986")
DONE = ("%async-collective-done.{n} = bf16[1600,1600]{{1,0}} "
        "fusion(%get-tuple-element.18), kind=kCustom, "
        "calls=%fused_computation.988")
GATHER = ("%all-gather.458 = bf16[50257,1600]{0,1} all-gather(%convert.10), "
          "channel_id=4, replica_groups=[1,4]<=[4], dimensions={1}")
SCATTER = ("%fusion.20 = bf16[1792,1600]{1,0} fusion(%get-tuple-element.25), "
           "kind=kCustom, calls=%all-reduce-scatter.15, metadata={}")
REDUCE = "%all-reduce.3 = f32[1600]{0} all-reduce(%x), channel_id=9"
COMPUTE = "%fusion.1 = bf16[16,1024,1600]{2,1,0} fusion(%p), kind=kOutput"


def _read(name, trace):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=None,
                              chips=4, flops_per_token=1.0)
    return worker._load_reader(ROOT, "metrics", name).read(reading)


def _hand_made(gather_ms, scatter_ms):
    """Steps of 20 ms. Step i holds one asynchronous all-gather in flight
    for gather_ms[i] (its start and its done 0.1 ms each, compute between
    them), one synchronous all-gather of 0.5 ms, a reduce-scatter fusion of
    scatter_ms[i], and an all-reduce that is neither."""
    ops, spans, modules = [], [], []
    for i, (g, sc) in enumerate(zip(gather_ms, scatter_ms)):
        t = i * 22 * MS
        spans.append(("bench/step", t, t + 21 * MS))
        modules.append(("jit_step(1)", t, t + 20 * MS))
        ops.append((COMPUTE, t + MS // 10, t + 9 * MS))
        if g:
            ops += [(START.format(n=i), t, t + MS // 10),
                    (DONE.format(n=i), t + int(g * MS) - MS // 10,
                     t + int(g * MS)),
                    (GATHER, t + 10 * MS, t + 10 * MS + MS // 2)]
        if sc:
            ops.append((SCATTER, t + 12 * MS, t + 12 * MS + int(sc * MS)))
        ops.append((REDUCE, t + 18 * MS, t + 19 * MS))
    return xplane.Trace(ops={0: sorted(ops, key=lambda o: o[1])},
                        modules={0: modules}, spans=spans)


def test_median_over_the_steps_of_the_time_in_flight():
    trace = _hand_made(gather_ms=[4.0, 2.0, 3.0], scatter_ms=[1.0, 5.0, 2.0])
    # start to done, and the synchronous one: 4.5, 2.5, 3.5
    assert _read("allgather_ms", trace) == pytest.approx(3.5)
    assert _read("reducescatter_ms", trace) == pytest.approx(2.0)
    # xplane.COLLECTIVE does not know the fusion: collective_ms is the
    # all-gathers and the all-reduce, (5.5 + 3.5 + 4.5) / 3
    assert _read("collective_ms", trace) == pytest.approx(4.5)


def test_a_program_without_them_reads_absent_and_not_zero():
    """Today's one-chip steps, and the step before PR 26 on four chips,
    which all-reduced its gradients."""
    only_gathers = _hand_made(gather_ms=[4.0, 2.0], scatter_ms=[0, 0])
    assert _read("reducescatter_ms", only_gathers) is None
    assert _read("allgather_ms", only_gathers) == pytest.approx(3.5)
    neither = _hand_made(gather_ms=[0, 0], scatter_ms=[0, 0])
    for name in ("allgather_ms", "reducescatter_ms"):
        assert _read(name, neither) is None
        assert _read(name, xplane.Trace()) is None
        assert _read(name, None) is None


@pytest.mark.parametrize("recorded", ["tiny_job.xplane.pb",
                                      "tiny_flash_step.xplane.pb"])
def test_the_recorded_one_chip_traces_read_nothing(recorded):
    trace = xplane.load(os.path.join(DATA, recorded))
    assert _read("allgather_ms", trace) is None
    assert _read("reducescatter_ms", trace) is None


def test_a_trace_recorded_on_four_chips_with_both_in_it():
    """data/tiny_fsdp4_step.xplane.pb (README_tiny_fsdp4_step.txt): 3
    traced steps of the toy model under fsdp=4 on a v5e 2x2 host."""
    trace = xplane.load(os.path.join(DATA, "tiny_fsdp4_step.xplane.pb"))
    assert sorted(trace.ops) == [0, 1, 2, 3]
    steps = xplane.step_device_work(trace, 0)
    assert len(steps) == 3
    gather = worker._load_reader(ROOT, "metrics", "allgather_ms").ALL_GATHER
    scatter = worker._load_reader(
        ROOT, "metrics", "reducescatter_ms").REDUCE_SCATTER
    for _, _, _, ops in steps:
        names = [n for n, _, _ in ops]
        gathers = [n for n in names if gather.match(n)]
        assert len(gathers) == 16 + 16 + 3  # starts, dones, synchronous
        assert sum(bool(scatter.match(n)) for n in names) == 9
        assert not any(gather.match(n) and scatter.match(n) for n in names)
    allgather, scattered = (_read("allgather_ms", trace),
                            _read("reducescatter_ms", trace))
    # both are part of the step's device time, and the all-gathers (and the
    # all-reduces) are all that collective_ms sees of them
    assert 0 < scattered < xplane.device_step_ms(trace)
    assert 0 < allgather <= _read("collective_ms", trace)


def test_the_new_cell_as_the_harness_loads_it():
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    loaded = run.load_cell(bench, "gpt2-xl.step-fsdp4")
    assert loaded["cell"]["chips"] == 4 == loaded["model"]["layout"]["chips"]
    assert loaded["model"]["layout"]["mesh"] == {"fsdp": 4}
    assert (loaded["model"]["n_embd"], loaded["model"]["n_layer"],
            loaded["model"]["n_head"]) == (1600, 48, 25)
    assert loaded["traffic"]["batch"] == 64 and loaded["traffic"]["remat"]
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "tokens_per_s_per_chip", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == [
        "gang_start_s", "host_gap_ms", "device_step_ms", "mfu_pct",
        "device_idle_pct", "hbm_plan_gib", "report_ms", "attn_kernel_ms",
        "collective_ms", "collective_exposed_pct", "allgather_ms",
        "reducescatter_ms"]
    for m in loaded["per_layer"]:  # every name has a reader
        assert callable(worker._load_reader(
            os.path.dirname(ROOT), loaded["metrics_dir"], m["name"]).read)
    # the one-chip cells read none of the four
    step = run.load_cell(bench, "gpt2-124m.step")
    assert not {"collective_ms", "allgather_ms", "reducescatter_ms"} & {
        m["name"] for m in step["per_layer"]}
