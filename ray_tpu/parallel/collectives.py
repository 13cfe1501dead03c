"""In-graph XLA collectives over a mesh — the ICI fast path.

These are the operations the reference obtains from NCCL
(ray: python/ray/util/collective/collective_group/nccl_collective_group.py);
TPU-native they are XLA ops inside `shard_map`/`pjit`, compiled onto ICI
rings by the partitioner. Use these inside jitted step functions; the
out-of-graph API (ray_tpu.util.collective) is for orchestration-sized data.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def shard_map_norep(body, *, mesh, in_specs, out_specs):
    """shard_map with the varying-axes (output replication) check off, for
    bodies whose replicated outputs cannot be inferred statically."""
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def psum(x, axis: str):
    return jax.lax.psum(x, axis_name=axis)


def pmean(x, axis: str):
    return jax.lax.pmean(x, axis_name=axis)


def all_gather(x, axis: str, *, axis_index: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name=axis, axis=axis_index, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0):
    return jax.lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_axis,
                                tiled=True)


def ppermute_next(x, axis: str, mesh: Mesh):
    """Rotate shards to the next rank on the axis ring (ring-attention step)."""
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def compiled_allreduce(mesh: Mesh, axis: str = "data", dtype=jnp.float32):
    """Build a jitted allreduce over one mesh axis: the benchmarkable unit
    for ICI allreduce scaling (north-star metric #2). Input is sharded over
    ``axis``; output is the full psum on every shard."""
    in_spec = PartitionSpec(axis)
    out_spec = PartitionSpec(axis)

    def _body(x):
        return jax.lax.psum(x, axis_name=axis)

    fn = shard_map(_body, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec)
    return jax.jit(
        fn,
        in_shardings=NamedSharding(mesh, in_spec),
        out_shardings=NamedSharding(mesh, out_spec),
    )
