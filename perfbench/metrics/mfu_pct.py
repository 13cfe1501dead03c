"""mfu_pct: operations the traced steps' forward and backward passes
require (perfbench/flops.py; recomputed operations do not count) over the
traced window's seconds x chips x the published bf16 peak
(perfbench/peaks.json). Only from a traced device: never from a CPU."""

from perfbench import xplane


def read(r):
    bw = r.trace and xplane.busy_and_window_s(r.trace)
    if not bw or not r.peaks:
        return None
    needed = r.flops_per_token * r.host["traced_tokens"]
    return 100.0 * needed / (bw[1] * r.chips * r.peaks["bf16_flops_per_s"])
