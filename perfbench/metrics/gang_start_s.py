"""gang_start_s: fit() called in the driver -> the first line of the train
loop runs in the worker (BackendExecutor, WorkerGroup, placement group,
worker pool), on the host's clock."""


def read(r):
    return r.host.get("gang_start_s")
