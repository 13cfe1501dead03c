"""ckpt_snapshot_ms: time inside the program's ``ckpt/snapshot`` spans per
save of the traced window (save_pytree: orbax's save(), which returns once
the tree is on the host and a thread has the write)."""

from perfbench import progspans


def read(r):
    return progspans.total_ms_per(r, "ckpt/snapshot", "bench/ckpt")
