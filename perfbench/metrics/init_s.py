"""init_s: ``ray_tpu.init`` entered -> returned, from the driver's own ring
(the span ``init``: the GCS process, the raylet process, this driver's
connection). ``init/connect`` is this less ``init_gcs_s`` and
``init_raylet_s``."""

from perfbench import clusterspans


def read(r):
    return clusterspans.span_s(r, "driver", "init")
