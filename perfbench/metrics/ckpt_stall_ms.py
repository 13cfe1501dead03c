"""ckpt_stall_ms: mean duration of a bench/ckpt span: last step boundary
before the save -> the loop is free to start the next step (save_pytree
and train.report(checkpoint=...))."""

from perfbench import xplane


def read(r):
    saves = r.trace and xplane.spans_named(r.trace, "bench/ckpt")
    if not saves:
        return None
    return xplane.length(saves) / len(saves) / 1e6
