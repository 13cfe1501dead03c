"""first_save_s: what the loop was blocked for by the process's first
``save_pytree`` (the warm-up save): its ``ckpt/setup`` (makedirs, the orbax
import, a new checkpointer), ``ckpt/snapshot`` and ``ckpt/commit`` spans."""

from perfbench import setupspans


def read(r):
    return setupspans.first_save_s(r)
