"""gang_workers_s: the driver's span ``gang/workers``: ``WorkerGroup(...)`` ->
every ``setup_session`` acknowledged. What of it neither ``worker_boot_s``
nor the worker's ``gang/session`` covers is the GCS's scheduling of the actor,
the lease and the raylet's spawn or hand-out of a process."""

from perfbench import clusterspans


def read(r):
    return clusterspans.span_s(r, "driver", "gang/workers")
