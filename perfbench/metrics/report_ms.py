"""report_ms: median duration of the program's ``train/report`` span
(session.report: step mark, payload, queue.put) over the traced window.
What of host_gap_ms is the session's; the rest of that gap is the loss
read back and jax's dispatch of the next step."""

from perfbench import progspans


def read(r):
    return progspans.median_ms(r, "train/report")
