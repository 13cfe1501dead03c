"""The chunk scheme that the gated delta rule (``ops/delta.py``) and the
scalar-decay state-space scan (``ops/ssm.py:ssd_scan``) share: a sequence
walked in chunks of C positions whose inside is matmuls, a boundary state
kept every stride of whole chunks. Here are the matmul forms, the turns
between a chunk's numbers along the lanes and down the sublanes, and the
views of a [B, T, ...] operand by stride and chunk that both their twins and
both their kernels take.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

NN = (((1,), (0,)), ((), ()))  # a @ b
NT = (((1,), (1,)), ((), ()))  # a @ b.T
TN = (((0,), (0,)), ((), ()))  # a.T @ b


def dot(a, b, dims=NN, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def iota(chunk: int):
    return (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0),
            lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def as_col(row_vec, chunk: int):
    """[1, C] -> [C, 1]: the numbers a position each, down the sublanes."""
    row, col = iota(chunk)
    return jnp.sum(jnp.where(row == col, row_vec, 0.0), axis=1, keepdims=True)


def as_row(col_vec, chunk: int):
    """[C, 1] -> [1, C]."""
    row, col = iota(chunk)
    return jnp.sum(jnp.where(row == col, col_vec, 0.0), axis=0, keepdims=True)


def grouped(t, stride, chunk):
    """[B, T, ...] -> [T / stride, stride / chunk, B, chunk, ...]."""
    b, length = t.shape[:2]
    t = t.reshape(b, length // stride, stride // chunk, chunk, *t.shape[2:])
    return jnp.moveaxis(t, 0, 2)


def ungrouped(t):
    t = jnp.moveaxis(t, 2, 0)
    return t.reshape(t.shape[0], -1, *t.shape[4:])


def folded(t):
    """[B, T, H, d] -> [B, T, H x d]: the model's own array."""
    return t.reshape(*t.shape[:2], -1)


def gates(t, chunk, stride):
    """[B, T, H] float32 -> [B, H, T / stride, stride / chunk, chunk]: a
    chunk's numbers along the lanes."""
    batch, length, heads = t.shape
    return jnp.transpose(
        t.reshape(batch, length // stride, stride // chunk, chunk, heads),
        (0, 4, 1, 2, 3))


def ungated(t):
    batch, heads = t.shape[:2]
    return jnp.transpose(t, (0, 2, 3, 4, 1)).reshape(batch, -1, heads)
