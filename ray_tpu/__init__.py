"""ray_tpu: a TPU-native distributed AI runtime with the capabilities of Ray.

Core API parity with the reference (ray: python/ray/__init__.py): tasks,
actors, objects, placement groups — scheduled over nodes that advertise TPU
chips and ICI topology as first-class resources; the device plane is JAX/XLA
(pjit/shard_map over meshes, Pallas kernels) instead of CUDA/NCCL.
"""

from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.serialization import TaskError
from ray_tpu._private.worker import (
    ActorDiedError,
    GetTimeoutError,
    TaskCancelledError,
    WorkerDiedError,
)
from ray_tpu.api import (
    ActorClass,
    ActorHandle,
    RayContext,
    RemoteFunction,
    available_resources,
    cancel,
    cluster_resources,
    get,
    get_actor,
    init,
    is_initialized,
    kill,
    method,
    nodes,
    put,
    remote,
    shutdown,
    wait,
)
from ray_tpu.runtime_context import get_runtime_context


def timeline(filename=None, limit=None):
    """Chrome-trace dump of cluster task events + tracing spans (ray
    parity: ray.timeline, _private/state.py:416 chrome_tracing_dump).
    ``limit`` caps the raw events fetched from the GCS."""
    from ray_tpu.util.state import timeline as _timeline

    return _timeline(filename, limit=limit)


__version__ = "0.1.0"


def __getattr__(name):
    # Lazy subpackage access (ray parity: ray.data / ray.train / ... are
    # importable attributes) without paying their import cost up front.
    if name in ("data", "train", "tune", "serve", "air", "rllib", "util",
                "workflow", "dag"):
        import importlib

        try:
            mod = importlib.import_module(f"ray_tpu.{name}")
        except ModuleNotFoundError as e:
            # keep hasattr()/getattr(default) semantics for not-yet-built
            # subpackages
            raise AttributeError(
                f"module 'ray_tpu' has no attribute {name!r}"
            ) from e
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'ray_tpu' has no attribute {name!r}")

__all__ = [
    "ActorClass",
    "ActorDiedError",
    "WorkerDiedError",
    "ActorHandle",
    "GetTimeoutError",
    "ObjectRef",
    "RayContext",
    "RemoteFunction",
    "TaskCancelledError",
    "TaskError",
    "available_resources",
    "cancel",
    "cluster_resources",
    "nodes",
    "get",
    "get_actor",
    "get_runtime_context",
    "init",
    "is_initialized",
    "kill",
    "method",
    "put",
    "remote",
    "shutdown",
    "wait",
]
