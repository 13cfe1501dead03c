"""init_gcs_s: the driver's span ``init/gcs``: the GCS process spawned -> its
port file read (interpreter, the package's import, the server's start)."""

from perfbench import clusterspans


def read(r):
    return clusterspans.span_s(r, "driver", "init/gcs")
