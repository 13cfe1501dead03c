"""init_raylet_s: the driver's span ``init/raylet``: the raylet process spawned
-> its port file read (interpreter, the package's import, the object store,
the registration with the GCS)."""

from perfbench import clusterspans


def read(r):
    return clusterspans.span_s(r, "driver", "init/raylet")
