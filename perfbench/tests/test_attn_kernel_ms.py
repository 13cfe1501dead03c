"""attn_kernel_ms's reader: on a hand-made trace whose answer can be worked
out on paper, on a small trace recorded on the chip that holds the Pallas
flash kernels (data/tiny_flash_step.xplane.pb, see data/README.txt), and on
the parent's recorded trace, which holds none."""

import os

import pytest

from perfbench import worker, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(DATA))
MS = 1_000_000
FWD = ('%flash_fwd.{n} = (bf16[8,16,128]{{2,1,0}}, f32[8,1,128]{{2,1,0}}) '
       'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"')
BWD = FWD.replace("flash_fwd", "flash_bwd")
OTHER = ('%custom-call.2 = f32[512,64]{0,1} custom-call(), '
         'custom_call_target="AllocateBuffer"')


def _read(name, trace):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=None,
                              chips=1, flops_per_token=1.0)
    return worker._load_reader(ROOT, "metrics", name).read(reading)


def _hand_made(kernel_ms):
    """Three steps of 10 ms; step i holds a forward kernel of kernel_ms[i]
    and a backward kernel twice as long, among operations that are none."""
    ops, spans, modules = [], [], []
    for i, k in enumerate(kernel_ms):
        t = i * 12 * MS
        spans.append(("bench/step", t, t + 11 * MS))
        modules.append(("jit_step(1)", t, t + 10 * MS))
        ops += [("%fusion.1 = bf16[4,128] fusion(%p)", t, t + 2 * MS),
                (OTHER, t + 2 * MS, t + 2 * MS + 1000)]
        if k:
            ops += [(FWD.format(n=i), t + 3 * MS, t + 3 * MS + int(k * MS)),
                    (BWD.format(n=i), t + 6 * MS,
                     t + 6 * MS + int(2 * k * MS))]
    return xplane.Trace(ops={0: sorted(ops, key=lambda o: o[1])},
                        modules={0: modules}, spans=spans)


@pytest.mark.parametrize("name", ["attn_kernel_ms", "attn_kernel_ms.job"])
def test_median_over_the_steps_of_the_kernels_time(name):
    # 3 x (1 + 2), 3 x (0.5 + 1), 3 x (0.4 + 0.8) ms: the median is 1.5
    assert _read(name, _hand_made([1.0, 0.5, 0.4])) == pytest.approx(1.5)


def test_a_window_without_kernels_reads_nothing():
    assert _read("attn_kernel_ms", _hand_made([0, 0, 0])) is None
    assert _read("attn_kernel_ms", xplane.Trace()) is None
    assert _read("attn_kernel_ms", None) is None


def test_the_parents_recorded_trace_reads_nothing_and_raises_nothing():
    """data/tiny_job.xplane.pb was recorded before the kernel was chosen by
    anything: XLA attention, a save, 4057 operations, none a kernel."""
    trace = xplane.load(os.path.join(DATA, "tiny_job.xplane.pb"))
    assert _read("attn_kernel_ms", trace) is None
    assert _read("attn_kernel_ms.job", trace) is None


def test_a_trace_recorded_on_the_chip_with_the_kernels_in_it():
    """data/tiny_flash_step.xplane.pb: 4 traced steps of the toy model with
    attention="flash" on one TPU v5 lite: 2 layers, so 2 forward and 2
    backward kernels a step."""
    trace = xplane.load(os.path.join(DATA, "tiny_flash_step.xplane.pb"))
    steps = xplane.step_device_work(trace, 0)
    assert len(steps) == 4
    reader = worker._load_reader(ROOT, "metrics", "attn_kernel_ms")
    for _, _, busy, ops in steps:
        mine = [n for n, _, _ in ops if reader.KERNEL.match(n)]
        assert len(mine) == 4
        assert sorted(xplane.short_name(n).split(".")[0] for n in mine) == [
            "flash_bwd", "flash_bwd", "flash_fwd", "flash_fwd"]
    value = _read("attn_kernel_ms", trace)
    # kernel time is part of the step's device time, and not all of it
    assert 0 < value < xplane.device_step_ms(trace)
