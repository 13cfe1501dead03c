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


def chunked_psum(x, axis: str, *, chunks: int = 4):
    """psum issued as ``chunks`` independent collectives over equal slices
    of the flattened operand — the in-graph twin of the store backend's
    chunked allreduce. Splitting the reduction lets XLA's latency-hiding
    scheduler start moving chunk 0 while upstream compute producing later
    chunks is still running, instead of waiting for one fused op's full
    operand. For tensors smaller than ``chunks`` elements (or chunks<=1)
    this degenerates to a plain psum."""
    if chunks <= 1 or x.size < chunks:
        return jax.lax.psum(x, axis_name=axis)
    flat = x.reshape(-1)
    pad = (-flat.size) % chunks
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    parts = jnp.split(flat, chunks)
    out = jnp.concatenate([jax.lax.psum(p, axis_name=axis) for p in parts])
    if pad:
        out = out[: x.size]
    return out.reshape(x.shape)


def quantized_psum(x, axis: str, *, mean: bool = False):
    """EQuARX-style int8 allreduce inside the graph: each shard block-
    quantizes its contribution (symmetric, scale = max|x|/127), int8 wire
    rides the all_gather, and every shard dequantizes + sums locally — so
    the cross-ICI bytes drop ~4x for fp32 at the cost of one rounding per
    contribution. Matches the store backend's ``quant="int8"`` semantics:
    SUM (or MEAN with ``mean=True``) only; result is float32."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    # zero-safe: all-zero block keeps scale 1 so dequant stays exact zeros
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    qs = jax.lax.all_gather(q, axis_name=axis)          # [W, ...] int8
    scales = jax.lax.all_gather(scale, axis_name=axis)  # [W]
    deq = qs.astype(jnp.float32) * scales.reshape((-1,) + (1,) * x.ndim)
    out = jnp.sum(deq, axis=0)
    if mean:
        out = out / qs.shape[0]
    return out


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
