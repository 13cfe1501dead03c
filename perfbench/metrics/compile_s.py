"""compile_s: seconds of set-up inside jax's compilation path, for every
function compiled in the worker before the traced window (the step, the
state's making, the benchmark's own jits, the float32 reference's): the
length of the union of the ring's ``compile`` records (tracing, lowering,
and XLA's compile or the cached entry's load)."""

from perfbench import setupspans


def read(r):
    return setupspans.compile_s(r)
