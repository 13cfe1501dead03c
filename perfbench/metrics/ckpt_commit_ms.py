"""ckpt_commit_ms: time inside the program's ``ckpt/commit`` spans per save
of the traced window (save_pytree: wait_until_finished(), the wait for the
files: what an asynchronous save would hide)."""

from perfbench import progspans


def read(r):
    return progspans.total_ms_per(r, "ckpt/commit", "bench/ckpt")
