"""ckpt_persist_ms: the driver's ``ckpt/persist`` (a save copied into the trial
directory while the worker trains on), a mean per save up to the traced
window's end."""

from perfbench import clusterspans


def read(r):
    return clusterspans.ckpt_persist_ms(r)
