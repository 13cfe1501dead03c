"""loss_head_ms's reader on a hand-made trace whose answer can be worked
out on paper: vocabulary-wide fusions (the dimension in a result or in an
operand), a ``while`` that carries a vocabulary-wide operand and must not
count, operations that merely hold the number in a name or a layout, and a
step with none."""

import os

import pytest

from perfbench import worker, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000
VOCAB = 50257
# the head's matmul: vocabulary-wide in its result
LOGITS = ("%fusion.2009 = (bf16[16,128]{1,0:T(8,128)(2,1)S(1)}, "
          "bf16[16,128,50257]{1,2,0:T(8,128)(2,1)}) fusion("
          "bf16[50257,768]{1,0:T(8,128)(2,1)} %gte.1, "
          "bf16[8,16,128,768]{3,2,1,0:T(8,128)(2,1)} %gte.2), kind=kOutput")
# the exponential pass: vocabulary-wide only in an operand
EXP = ("%exponential_reduce_fusion.2 = f32[16,128]{1,0:T(8,128)S(1)} "
       "fusion(bf16[16,128,50257]{1,2,0:T(8,128)(2,1)} %gte.3, "
       "bf16[16,128]{1,0:T(8,128)(2,1)S(1)} %gte.4), kind=kLoop")
# the scan over the chunks: carries the table, encloses the two above
WHILE = ("%while.3 = (s32[]{:T(128)}, f32[]{:T(128)}, "
         "f32[50257,768]{1,0:T(8,128)}) while((s32[]{:T(128)}, "
         "f32[]{:T(128)}, f32[50257,768]{1,0:T(8,128)}) %tuple.9), "
         "condition=%cond.1, body=%body.1")
# none of these is of vocabulary width: the number in an instruction's
# name, inside a longer dimension, and a block of another width
NOT_WIDE = [
    "%fusion.50257 = bf16[16,1024,768]{2,1,0:T(8,128)(2,1)} fusion("
    "bf16[16,1024,768]{2,1,0:T(8,128)(2,1)} %p.50257), kind=kLoop",
    "%fusion.7 = f32[150257,768]{1,0:T(8,128)} fusion("
    "f32[502570]{0:T(1024)} %p.1), kind=kLoop",
    "%fusion.8 = bf16[16,1024,3072]{2,1,0:T(8,128)(2,1)} fusion("
    "bf16[768,3072]{1,0:T(8,128)(2,1)} %p.2), kind=kOutput",
]


def _read(name, trace, vocab=VOCAB):
    reading = worker._Reading(trace=trace, host={}, plan_bytes=0, peaks=None,
                              chips=1, flops_per_token=1.0,
                              model={"vocab_size": vocab})
    return worker._load_reader(ROOT, "metrics", name).read(reading)


def _hand_made(head_ms):
    """Steps of 20 ms; step i holds, inside a ``while`` of 9 ms, a logits
    fusion of head_ms[i] and an exponential pass half as long, among
    operations that are not of vocabulary width."""
    ops, spans, modules = [], [], []
    for i, k in enumerate(head_ms):
        t = i * 22 * MS
        spans.append(("bench/step", t, t + 21 * MS))
        modules.append(("jit_step(1)", t, t + 20 * MS))
        ops += [(text, t + j * MS, t + (j + 1) * MS)
                for j, text in enumerate(NOT_WIDE)]
        if k:
            ops += [(WHILE, t + 4 * MS, t + 13 * MS),
                    (LOGITS, t + 4 * MS, t + 4 * MS + int(k * MS)),
                    (EXP, t + 9 * MS, t + 9 * MS + int(k * MS / 2))]
    return xplane.Trace(ops={0: sorted(ops, key=lambda o: o[1])},
                        modules={0: modules}, spans=spans)


@pytest.mark.parametrize("name", ["loss_head_ms", "loss_head_ms.job"])
def test_median_over_the_steps_of_the_vocabulary_wide_time(name):
    # 4 + 2, 2 + 1, 1 + 0.5 ms, and a step with none that does not count:
    # the median of (6, 3, 1.5) is 3; the 9 ms of the while are in none
    assert _read(name, _hand_made([4.0, 2.0, 0, 1.0])) == pytest.approx(3.0)


def test_the_width_is_the_configurations():
    # read for another vocabulary the same trace holds nothing that wide;
    # for 3072 it holds the MLP's block, 1 ms a step
    trace = _hand_made([4.0, 2.0, 1.0])
    assert _read("loss_head_ms", trace, vocab=50258) is None
    assert _read("loss_head_ms", trace, vocab=3072) == pytest.approx(1.0)


def test_a_window_without_such_operations_reads_nothing():
    assert _read("loss_head_ms", _hand_made([0, 0, 0])) is None
    assert _read("loss_head_ms", xplane.Trace()) is None
    assert _read("loss_head_ms", None) is None


def test_overlapping_operations_count_once():
    """Two vocabulary-wide events that overlap (an asynchronous copy of the
    table beside the head's matmul) count for their union."""
    trace = _hand_made([4.0])
    trace.ops[0].append((EXP, 5 * MS, 10 * MS))
    trace.ops[0].sort(key=lambda o: o[1])
    # [4, 8] + [5, 10] + [9, 11] -> [4, 11]
    assert _read("loss_head_ms", trace) == pytest.approx(7.0)
