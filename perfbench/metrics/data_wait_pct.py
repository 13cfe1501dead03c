"""data_wait_pct: share of the traced window, the bench/ckpt spans left
out, that lies inside bench/data spans (next batch from the dataset
iterator, host to device)."""

from perfbench import xplane


def read(r):
    w = r.trace and xplane.window(r.trace)
    data = w and xplane.spans_named(r.trace, "bench/data")
    if not data:
        return None
    saves = xplane.length(xplane.spans_named(r.trace, "bench/ckpt"))
    return 100.0 * xplane.length(data) / (w[1] - w[0] - saves)
