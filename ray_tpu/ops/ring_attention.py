"""Ring attention: exact attention over sequences sharded across a mesh
axis, overlapping compute with neighbor KV exchange on the ICI ring.

The sequence axis is sharded over mesh axis ``axis_name``; each device holds
q/k/v chunks of shape (..., s/n, d). The kernel loops n times: fold the
resident KV chunk into flash accumulators (``online_block_update``), then
``lax.ppermute`` the KV chunk to the next ring neighbor — XLA overlaps the
permute with the next block's compute. Memory stays O(s/n) per device and
the softmax is exact (online renormalization), unlike approximations.

This is the sequence-parallel capability the reference lacks natively
(ray SURVEY §5: "no ring attention / context parallel in-repo") built the
TPU way: collectives ride the ICI ring via ppermute rather than NCCL P2P.

Use ``ring_self_attention`` for the shard_map-wrapped entry, or call
``ring_attention`` inside your own shard_map/pjit region.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import finalize_flash, online_block_update


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Exact attention with KV rotating around the ``axis_name`` ring.

    Call inside shard_map/pjit where q,k,v are the per-device sequence
    chunks: (..., s_local, d). Requires the same s_local on every device.
    """
    sm_scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    s_q = q.shape[-2]
    s_k = k.shape[-2]
    d = q.shape[-1]
    lead = q.shape[:-2]

    qf = q.astype(jnp.float32)
    # Derive the initial accumulators from q so they carry q's exact
    # varying-manual-axes type (scan requires carry-in == carry-out types;
    # fresh constants would be "unvarying" under newer shard_map).
    l0 = qf[..., 0] * 0.0
    m0 = l0 - jnp.inf
    a0 = qf * 0.0

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        m, l, acc, kk, vv = carry
        # the KV chunk we hold at `step` originated on device (me - step) % n
        src = jnp.mod(me - step, n)
        m, l, acc = online_block_update(
            qf, kk.astype(jnp.float32), vv.astype(jnp.float32), m, l, acc,
            sm_scale=sm_scale, q_offset=me * s_q, k_offset=src * s_k,
            causal=causal,
        )
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return (m, l, acc, kk, vv), None

    (m, l, acc, _, _), _ = lax.scan(
        body, (m0, l0, a0, k, v), jnp.arange(n)
    )
    return finalize_flash(m, l, acc, q.dtype)


def ring_self_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "sp",
                        causal: bool = False,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """shard_map wrapper: q,k,v are GLOBAL (b, h, s, d) arrays whose s dim
    is (or will be) sharded over ``seq_axis``; returns the global output
    with the same sharding."""
    from jax import shard_map

    spec = P(None, None, seq_axis, None)
    fn = shard_map(
        functools.partial(
            ring_attention, axis_name=seq_axis, causal=causal,
            sm_scale=sm_scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
