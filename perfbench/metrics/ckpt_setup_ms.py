"""ckpt_setup_ms: time inside the program's ``ckpt/setup`` spans per save
of the traced window (save_pytree: makedirs, the orbax import and a new
StandardCheckpointer: what every call pays before a byte moves)."""

from perfbench import progspans


def read(r):
    return progspans.total_ms_per(r, "ckpt/setup", "bench/ckpt")
