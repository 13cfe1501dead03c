from ray_tpu.parallel.collectives import (
    all_gather,
    compiled_allreduce,
    pmean,
    ppermute_next,
    psum,
    reduce_scatter,
)
from ray_tpu.parallel.mesh_utils import (
    auto_mesh,
    create_hybrid_mesh,
    create_mesh,
    data_sharding,
    logical_to_physical,
    mesh_from_cluster,
    replicated,
    shard_params_fsdp,
)
from ray_tpu.parallel.train_step import (
    build_train_step,
    place_train_state,
    state_shardings,
)

__all__ = [
    "all_gather",
    "auto_mesh",
    "build_train_step",
    "compiled_allreduce",
    "create_hybrid_mesh",
    "create_mesh",
    "data_sharding",
    "logical_to_physical",
    "mesh_from_cluster",
    "place_train_state",
    "pmean",
    "ppermute_next",
    "psum",
    "reduce_scatter",
    "replicated",
    "shard_params_fsdp",
    "state_shardings",
]
