"""The selective scan of a state-space layer (Mamba-1's recurrence), forward
and backward: a Pallas TPU kernel pair and a chunked ``lax.scan`` that
computes the same.

    h_t = exp(delta_t A) * h_{t-1} + (delta_t x_t) B_t^T     [channels, states]
    y_t = h_t C_t + D x_t

The decay is per channel and per state, so the recurrence has no matmul
form: every position is a handful of vector operations and one exponential
on a [states, channels] state. Written out in XLA it is either a ``while``
of T tiny launches or a [T, channels, states] float32 array. Both paths
here walk the sequence in chunks of ``chunk`` positions and keep one
boundary state a chunk (the state a chunk starts from); the backward pass
recomputes a chunk's states from its boundary and walks the chunk in
reverse. One ``custom_vjp`` holds both: the forward rule's outputs are
named ``ssm_scan_out`` / ``ssm_scan_bounds`` (``SCAN_REMAT_NAMES``:
``ops.remat.remat_policy`` keeps them, so a recomputed block does not run the
forward scan again).

The kernels (``ssm_scan_fwd`` / ``ssm_scan_bwd``: the benchmark's readers
find them by these names). Channels lie on lanes, the states on sublanes.
The grid is (batch, chunks of time, blocks of channels), the channel blocks
innermost, so that a chunk's ``B`` and ``C`` are fetched once and a chunk's
``dB`` / ``dC`` accumulate over the channel blocks in VMEM; the state of
every channel block is carried from chunk to chunk in VMEM scratch
([blocks, states, block] float32: 327 KB at 5,120 channels of 16 states).
Inside a chunk the state of one channel block (8 vector registers at 512
channels) is a loop's carry. What a position needs along the sublanes while
the data has it along the lanes (``B_t`` and ``C_t``, one number a state) is
handed in spread over a lane tile ([T x states, 128], made by XLA from the
[T, states] operand: the kernel reads it once a chunk), and what it sums
over the lanes (``dB_t``, ``dC_t``: a sum over channels) leaves as a lane
tile of partial sums that XLA adds up. State, decay, exponentials and every
accumulation are float32 whatever the operands' dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops.chunks import (NT, TN, as_col, as_row, dot, folded, gates,
                                grouped, iota, ungated, ungrouped)
from ray_tpu.ops.mosaic import (compiler_params, per_batch_shard,
                                takes_kernels)

CHUNK = 128          # positions a chunk: one boundary state each
_CHANNEL_BLOCK = 512  # lanes a grid step: 8 vector registers of state
_UNROLL = 8          # positions a loop step, written out
_LANES = 128
_F32 = jnp.float32


# ---------------------------------------------------------------------------
# the chunked lax.scan: any backend
# ---------------------------------------------------------------------------

def _chunk_states(h, x, delta, a, b, c):
    """One chunk from the state ``h`` [B, D, N] it starts in: x, delta
    [B, L, D], b, c [B, L, N], all float32 -> (end state, y [B, L, D]
    without the skip term)."""

    def step(h, inputs):
        x_t, d_t, b_t, c_t = inputs
        decay = jnp.exp(d_t[..., None] * a)
        h = decay * h + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    swap = lambda t: jnp.swapaxes(t, 0, 1)
    h, y = lax.scan(step, h, tuple(map(swap, (x, delta, b, c))))
    return h, swap(y)


def _chunked(t, chunk):
    """[B, T, ...] -> [T / chunk, B, chunk, ...]."""
    b, length = t.shape[:2]
    return jnp.moveaxis(t.reshape(b, length // chunk, chunk, *t.shape[2:]),
                        1, 0)


def _unchunked(t):
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape(t.shape[0], t.shape[1] * t.shape[2], *t.shape[3:])


def _scan_fwd(x, delta, a, b, c, skip, chunk):
    """-> (y [B, T, D] float32, bounds [B, T / chunk, N, D] float32: the
    state each chunk starts from, states leading as the kernel keeps it)."""
    f = lambda t: _chunked(t.astype(_F32), chunk)

    def one(h, inputs):
        x_c, d_c, b_c, c_c = inputs
        end, y = _chunk_states(h, x_c, d_c, a, b_c, c_c)
        return end, (y, jnp.swapaxes(h, 1, 2))

    h0 = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), _F32)
    _, (y, bounds) = lax.scan(one, h0, (f(x), f(delta), f(b), f(c)))
    return (_unchunked(y) + skip * x.astype(_F32), jnp.moveaxis(bounds, 0, 1))


def _scan_bwd(x, delta, a, b, c, skip, bounds, dy, chunk):
    """The gradients of ``_scan_fwd``'s y, a chunk at a time from the last:
    each chunk's states are made again from its boundary (``jax.vjp`` of the
    chunk), the gradient of the state handed to the chunk before."""
    f = lambda t: _chunked(t.astype(_F32), chunk)
    dy32 = dy.astype(_F32)

    def one(carry, inputs):
        dh, da = carry
        x_c, d_c, b_c, c_c, h0, dy_c = inputs
        _, pull = jax.vjp(_chunk_states, jnp.swapaxes(h0, 1, 2), x_c, d_c, a,
                          b_c, c_c)
        dh, dx, dd, da_c, db, dc = pull((dh, dy_c))
        return (dh, da + da_c), (dx, dd, db, dc)

    zero = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), _F32)
    (_, da), (dx, dd, db, dc) = lax.scan(
        one, (zero, jnp.zeros_like(a)),
        (f(x), f(delta), f(b), f(c), jnp.moveaxis(bounds, 1, 0), f(dy32)),
        reverse=True)
    dx = _unchunked(dx) + skip * dy32
    dskip = (dy32 * x.astype(_F32)).sum((0, 1))
    return dx, _unchunked(dd), da, _unchunked(db), _unchunked(dc), dskip


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _spread(t):
    """[B, T, N] -> [B, T x N, 128]: each number over a lane tile, so that
    a position's N lie along the sublanes."""
    b, length, n = t.shape
    return jnp.broadcast_to(t.reshape(b, length * n, 1),
                            (b, length * n, _LANES))


def _over_tiles(col, tiles: int):
    """[N, 128] -> [N, tiles x 128]: the same registers, a lane tile each."""
    return col if tiles == 1 else jnp.concatenate([col] * tiles, axis=1)


def _tile_sum(v, tiles: int):
    """[N, tiles x 128] -> [N, 128]: the lane tiles added up."""
    out = v[:, :_LANES]
    for k in range(1, tiles):
        out = out + v[:, k * _LANES:(k + 1) * _LANES]
    return out


def _row(ref, t, states: int):
    """Row ``t`` of a float32 [chunk, block] buffer, over ``states``
    sublanes."""
    row = ref[pl.ds(t, 1), :]
    return row, jnp.broadcast_to(row, (states, row.shape[1]))


def _col(ref, t, states: int, tiles: int):
    """Position ``t``'s ``states`` numbers of a spread operand, along the
    sublanes of every lane tile."""
    start = pl.multiple_of(t * states, states)
    return _over_tiles(ref[pl.ds(start, states), :].astype(_F32), tiles)


def _fwd_kernel(x_ref, d_ref, at_ref, bs_ref, cs_ref, skip_ref, y_ref,
                bound_ref, h_scr, dx_scr, y_scr, *, chunk: int, states: int,
                tiles: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], _F32)

    h0 = h_scr[j]
    bound_ref[...] = h0
    a_t = at_ref[...]
    x = x_ref[...].astype(_F32)
    dx_scr[...] = d_ref[...] * x

    def group(g, h):
        for s in range(_UNROLL):
            t = g * _UNROLL + s
            _, d_b = _row(d_ref, t, states)
            _, dx_b = _row(dx_scr, t, states)
            h = (jnp.exp(d_b * a_t) * h
                 + dx_b * _col(bs_ref, t, states, tiles))
            y_scr[pl.ds(t, 1), :] = jnp.sum(
                h * _col(cs_ref, t, states, tiles), axis=0, keepdims=True)
        return h

    h_scr[j] = lax.fori_loop(0, chunk // _UNROLL, group, h0)
    y_ref[...] = (y_scr[...] + skip_ref[...] * x).astype(y_ref.dtype)


def _bwd_kernel(x_ref, d_ref, at_ref, bs_ref, cs_ref, skip_ref, dy_ref,
                bound_ref, dx_ref, dd_ref, dbs_ref, dcs_ref, dat_ref,
                g_scr, da_scr, hs_scr, x_scr, dxin_scr, dy_scr, dxout_scr, *,
                chunk: int, states: int, tiles: int):
    i, j = pl.program_id(1), pl.program_id(2)   # chunk i from the last

    @pl.when(i == 0)
    def _():
        g_scr[j] = jnp.zeros(g_scr.shape[1:], _F32)
        da_scr[j] = jnp.zeros(da_scr.shape[1:], _F32)

    @pl.when(j == 0)
    def _():
        dbs_ref[...] = jnp.zeros(dbs_ref.shape, _F32)
        dcs_ref[...] = jnp.zeros(dcs_ref.shape, _F32)

    a_t = at_ref[...]
    x_scr[...] = x_ref[...].astype(_F32)
    dxin_scr[...] = d_ref[...] * x_scr[...]
    dy_scr[...] = dy_ref[...].astype(_F32)

    # the chunk's states again, from its boundary: hs[t] is the state that
    # position t starts from, hs[t + 1] the one it leaves
    hs_scr[0] = bound_ref[...]

    def again(g, h):
        for s in range(_UNROLL):
            t = g * _UNROLL + s
            _, d_b = _row(d_ref, t, states)
            _, dx_b = _row(dxin_scr, t, states)
            h = (jnp.exp(d_b * a_t) * h
                 + dx_b * _col(bs_ref, t, states, tiles))
            hs_scr[t + 1] = h
        return h

    lax.fori_loop(0, chunk // _UNROLL, again, hs_scr[0])

    def back(g, carry):
        gn, da = carry      # decay_{t+1} * dL/dh_{t+1}; dL/dA^T so far
        for s in range(_UNROLL):
            t = chunk - 1 - (g * _UNROLL + s)
            d_row, d_b = _row(d_ref, t, states)
            x_row, _ = _row(x_scr, t, states)
            dy_row, dy_b = _row(dy_scr, t, states)
            _, dx_b = _row(dxin_scr, t, states)
            b_col = _col(bs_ref, t, states, tiles)
            at = pl.multiple_of(t * states, states)
            grad = dy_b * _col(cs_ref, t, states, tiles) + gn
            dcs_ref[pl.ds(at, states), :] += _tile_sum(dy_b * hs_scr[t + 1],
                                                       tiles)
            dbs_ref[pl.ds(at, states), :] += _tile_sum(grad * dx_b, tiles)
            decay = jnp.exp(d_b * a_t)
            through = grad * hs_scr[t] * decay        # dL/d(delta_t A)
            ddx = jnp.sum(grad * b_col, axis=0, keepdims=True)
            dd_ref[pl.ds(t, 1), :] = (
                jnp.sum(through * a_t, axis=0, keepdims=True) + ddx * x_row)
            dxout_scr[pl.ds(t, 1), :] = ddx * d_row + skip_ref[...] * dy_row
            da = da + through * d_b
            gn = decay * grad
        return gn, da

    g_scr[j], da_scr[j] = lax.fori_loop(
        0, chunk // _UNROLL, back, (g_scr[j], da_scr[j]))
    dat_ref[...] = da_scr[j]
    dx_ref[...] = dxout_scr[...].astype(dx_ref.dtype)


def _geometry(x, a, chunk):
    batch, length, channels = x.shape
    states = a.shape[1]
    block = _CHANNEL_BLOCK if channels % _CHANNEL_BLOCK == 0 else _LANES
    assert channels % block == 0 and states % 8 == 0 and length % chunk == 0 \
        and chunk % 16 == 0, (x.shape, a.shape, chunk)
    return batch, length // chunk, channels // block, block, states


def _record(x, a, chunk, backward: bool):
    """One ``counters`` record a traced pass (none a step): what the scan
    walks and what its boundary states weigh."""
    batch, length, channels = x.shape
    steptrace.record_counters("ssm/scan", {
        "channels": channels, "states": a.shape[1], "tokens": batch * length,
        "chunk": chunk, "chunks": length // chunk,
        "boundary_bytes": batch * (length // chunk) * channels * a.shape[1] * 4,
        "backward": int(backward)})


def _params(interpret: bool):
    return compiler_params(interpret, ("parallel", "arbitrary", "arbitrary"),
                           48 * 2**20)


def _pallas_fwd(x, delta, a, b, c, skip, chunk, interpret):
    batch, n_chunks, n_blocks, block, states = _geometry(x, a, chunk)
    channels = x.shape[2]
    _record(x, a, chunk, False)
    rows = pl.BlockSpec((None, chunk, block), lambda bi, i, j: (bi, i, j))
    cols = pl.BlockSpec((None, chunk * states, _LANES),
                        lambda bi, i, j: (bi, i, 0))
    y, bounds = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, states=states,
                          tiles=block // _LANES),
        grid=(batch, n_chunks, n_blocks),
        in_specs=[
            rows, rows,
            pl.BlockSpec((states, block), lambda bi, i, j: (0, j)),
            cols, cols,
            pl.BlockSpec((1, block), lambda bi, i, j: (0, j)),
        ],
        out_specs=[
            rows,
            pl.BlockSpec((None, None, states, block),
                         lambda bi, i, j: (bi, i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, n_chunks, states, channels), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_blocks, states, block), _F32),
            pltpu.VMEM((chunk, block), _F32),
            pltpu.VMEM((chunk, block), _F32),
        ],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(x, delta.astype(_F32), a.T.astype(_F32), _spread(b), _spread(c),
      skip.astype(_F32)[None, :])
    return y, bounds


def _pallas_bwd(x, delta, a, b, c, skip, bounds, dy, chunk, interpret):
    batch, n_chunks, n_blocks, block, states = _geometry(x, a, chunk)
    length, channels = x.shape[1:]
    _record(x, a, chunk, True)
    last = n_chunks - 1
    rows = pl.BlockSpec((None, chunk, block),
                        lambda bi, i, j: (bi, last - i, j))
    cols = pl.BlockSpec((None, chunk * states, _LANES),
                        lambda bi, i, j: (bi, last - i, 0))
    per_block = pl.BlockSpec((states, block), lambda bi, i, j: (0, j))
    dx, dd, dbs, dcs, dat = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, states=states,
                          tiles=block // _LANES),
        grid=(batch, n_chunks, n_blocks),
        in_specs=[
            rows, rows, per_block, cols, cols,
            pl.BlockSpec((1, block), lambda bi, i, j: (0, j)),
            rows,
            pl.BlockSpec((None, None, states, block),
                         lambda bi, i, j: (bi, last - i, 0, j)),
        ],
        out_specs=[
            rows, rows, cols, cols,
            # written at every chunk with the sum so far: the last stands
            pl.BlockSpec((None, states, block), lambda bi, i, j: (bi, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(x.shape, _F32),
            jax.ShapeDtypeStruct((batch, length * states, _LANES), _F32),
            jax.ShapeDtypeStruct((batch, length * states, _LANES), _F32),
            jax.ShapeDtypeStruct((batch, states, channels), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_blocks, states, block), _F32),
            pltpu.VMEM((n_blocks, states, block), _F32),
            pltpu.VMEM((chunk + 1, states, block), _F32),
            pltpu.VMEM((chunk, block), _F32),
            pltpu.VMEM((chunk, block), _F32),
            pltpu.VMEM((chunk, block), _F32),
            pltpu.VMEM((chunk, block), _F32),
        ],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(x, delta.astype(_F32), a.T.astype(_F32), _spread(b), _spread(c),
      skip.astype(_F32)[None, :], dy, bounds)
    gathered = lambda t: t.sum(-1).reshape(batch, length, states)
    dskip = (dy.astype(_F32) * x.astype(_F32)).sum((0, 1))
    return dx, dd, dat.sum(0).T, gathered(dbs), gathered(dcs), dskip


# ---------------------------------------------------------------------------
# one differentiable function over both
# ---------------------------------------------------------------------------

def _forward(x, delta, a, b, c, skip, chunk, impl):
    if impl == "scan":
        y, bounds = _scan_fwd(x, delta, a, b, c, skip, chunk)
        return y.astype(x.dtype), bounds
    return _pallas_fwd(x, delta, a, b, c, skip, chunk,
                       impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_diff(x, delta, a, b, c, skip, chunk, impl):
    return _forward(x, delta, a, b, c, skip, chunk, impl)[0]


# What recomputation keeps of the scan (``ops.remat.remat_policy``): its
# output [B, T, channels] in the compute dtype and the state each chunk
# starts from, [B, T / chunk, states, channels] float32. A block without a
# scan has no such name, and its program is the one it was.
SCAN_REMAT_NAMES = ("ssm_scan_out", "ssm_scan_bounds")


def _scan_diff_fwd(x, delta, a, b, c, skip, chunk, impl):
    y, bounds = map(ad_checkpoint.checkpoint_name,
                    _forward(x, delta, a, b, c, skip, chunk, impl),
                    SCAN_REMAT_NAMES)
    return y, (x, delta, a, b, c, skip, bounds)


def _scan_diff_bwd(chunk, impl, res, dy):
    x, delta, a, b, c, skip, bounds = res
    if impl == "scan":
        grads = _scan_bwd(x, delta, a, b, c, skip, bounds, dy, chunk)
    else:
        grads = _pallas_bwd(x, delta, a, b, c, skip, bounds, dy, chunk,
                            impl == "pallas_interpret")
    return tuple(g.astype(r.dtype) for g, r in zip(grads, res))


_scan_diff.defvjp(_scan_diff_fwd, _scan_diff_bwd)


def auto_impl(x, a) -> str:
    """What ``impl=None`` runs: the kernels where the layout fits them
    (channels a multiple of 128, states of 8) and ``x`` is traced where a
    kernel may run (``mosaic.takes_kernels``); the chunked ``lax.scan``
    elsewhere."""
    fits = x.shape[2] % _LANES == 0 and a.shape[1] % 8 == 0
    return "pallas" if fits and takes_kernels(x) else "scan"


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def selective_scan(x, delta, A, B, C, D, *, chunk: Optional[int] = None,
                   impl: Optional[str] = None) -> jax.Array:
    """``y`` [B, T, channels] of the recurrence in the module's docstring:
    ``x``, ``delta`` [B, T, channels] (``delta`` positive, after its
    softplus), ``A`` [channels, states] (negative), ``B``, ``C``
    [B, T, states], ``D`` [channels]. ``y`` has ``x``'s dtype; the state and
    every accumulation are float32. ``chunk`` positions lie between two kept
    boundary states (a multiple of 16; ``CHUNK`` if left out, or the whole
    length rounded up where that is shorter); a length it does not divide is
    padded with positions that leave the state as it is (``delta`` 0).
    ``impl``: "pallas" | "pallas_interpret" | "scan"; None: ``auto_impl``.
    """
    length = x.shape[1]
    impl = impl or auto_impl(x, A)
    chunk = min(chunk or CHUNK, -(-length // 16) * 16)
    assert chunk % 16 == 0, chunk
    pad = -length % chunk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        x, delta, B, C = (jnp.pad(t, widths) for t in (x, delta, B, C))

    def scan(x, delta, a, b, c, skip):
        return _scan_diff(x, delta, a, b, c, skip, chunk, impl)

    if impl != "scan":
        scan = per_batch_shard(
            scan, x, (True, True, False, True, True, False), "selective_scan")
    y = scan(x, delta, A.astype(_F32), B, C, D.astype(_F32))
    return y[:, :length] if pad else y


# ===========================================================================
# Mamba-2's recurrence (SSD: Dao and Gu, arXiv:2405.21060): a scalar decay a
# head and position.
#
#     S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T        [head_dim, states]
#     y_t = S_t C_t + D x_t
#
# a head (``A``, ``D`` one scalar each; ``B_t``, ``C_t`` shared by the heads
# of a group), zero state at a sequence's start. With one scalar decay a
# chunk of C positions IS matmuls: ``a = cumsum(dt A)`` inside the chunk,
#
#     Y  = (L o (C B^T)) (dt x) + exp(a) C S^T + D x,   L_ij = exp(a_i - a_j)
#                                                       for i >= j, else 0
#     S <- exp(a_C) S + (exp(a_C - a) dt x)^T B
#
# ``ops/delta.py``'s scheme without its triangular solve (what the two share
# is ``ops/chunks.py``). A decay only ever appears as ``exp`` of a difference
# that is <= 0. ``a``, ``L``, the state, ``dt x`` and every accumulation are
# float32; the matmuls take their operands in the inputs' dtype. ``C B^T`` is
# made once a group and chunk and used by the group's heads.
#
# Both paths keep one boundary state every ``ssd_stride_of`` positions (so
# that the boundaries weigh no more than the output: 256 positions for
# bfloat16 at 128 states); the backward pass walks the strides from the
# last, makes a stride's inner states again from its boundary and walks its
# chunks in reverse with the state's gradient as the carry. One
# ``custom_vjp`` holds both; the forward rule's outputs are named
# ``ssd_out`` / ``ssd_bounds`` (``SSD_REMAT_NAMES``) for
# ``ops.remat.remat_policy``.
#
# The kernels (``ssd_fwd`` / ``ssd_bwd``: the benchmark's readers find them
# by these names) address the model's own arrays, x and y as [B, T, heads x
# 64] and B, C as [B, T, groups x states]: two 64-wide heads are one lane
# tile, worked on together (their two ``L`` differ, so the products that
# take ``L`` are made a head each over the pair's 128 lanes and chosen by
# lane; the products with the state contract or produce both heads' rows at
# once). The grid is (batch, groups, blocks of up to 1,024 positions), the
# blocks innermost and in order; a group's states, [heads a group x 64,
# states] float32, are carried from block to block in VMEM scratch.
# ===========================================================================

SSD_CHUNK = 128       # positions a chunk, as published
_SSD_BLOCK = 1024     # positions a grid step at most
_SSD_HEAD = 64        # the head width the kernels take: two a lane tile


def ssd_stride_of(chunk: int, states: int, itemsize: int) -> int:
    """Positions between two kept boundary states: whole chunks, and enough
    of them that a head's boundary ([head_dim, states] float32) weighs no
    more than its output over them ([stride, head_dim] of ``itemsize``)."""
    return chunk * -(-(states * 4) // (itemsize * chunk))


def ssd_bytes_needed(x, b, backward: bool) -> int:
    """What a call has to move whatever its form: forward x, B, C, dt in and
    y out; backward those and y's cotangent in, the four gradients out."""
    batch, length, heads, head_dim = x.shape
    groups, states = b.shape[2:]
    tokens, size = batch * length, x.dtype.itemsize
    xs, bc, dts = (tokens * heads * head_dim * size,
                   2 * tokens * groups * states * size, tokens * heads * 4)
    return (3 * xs + 2 * bc + 2 * dts) if backward else 2 * xs + bc + dts


def _ssd_record(x, b, chunk, stride, backward: bool):
    """One ``counters`` record a traced pass (none a step)."""
    batch, length, heads, head_dim = x.shape
    groups, states = b.shape[2:]
    steptrace.record_counters("ssd/scan", {
        "heads": heads, "groups": groups, "head_dim": head_dim,
        "states": states, "tokens": batch * length, "sequences": batch,
        "chunk": chunk, "stride": stride,
        "boundary_bytes": (batch * (length // stride) * heads * head_dim
                           * states * 4),
        "bytes_needed": ssd_bytes_needed(x, b, backward),
        "backward": int(backward)})


# --- the chunked twin: any backend -----------------------------------------

def _ssd_chunk(state, inputs):
    """One chunk from the state [B, H, P, N] it starts in: x [B, C, H, P],
    dt, la [B, C, H] (``la`` = dt A), b, c [B, C, H, N] (one a head
    already), all float32 -> (end state, y [B, C, H, P] without D x)."""
    x, dt, la, b, c = inputs
    chunk = x.shape[1]
    a = jnp.cumsum(la, axis=1)                                  # [B, C, H]
    by_head = jnp.moveaxis(a, 1, 2)                             # [B, H, C]
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    diff = by_head[..., :, None] - by_head[..., None, :]
    decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, diff, 0.0)),
                      0.0)                                      # [B, H, C, C]
    scores = jnp.einsum("bihn,bjhn->bhij", c, b)
    v = dt[..., None] * x
    y = (jnp.einsum("bhij,bjhp->bihp", scores * decay, v)
         + jnp.einsum("bihn,bhpn->bihp", c * jnp.exp(a)[..., None], state))
    last = a[:, -1:, :]                                         # [B, 1, H]
    state = (jnp.exp(last)[:, 0, :, None, None] * state
             + jnp.einsum("bjhp,bjhn->bhpn",
                          v * jnp.exp(last - a)[..., None], b))
    return state, y


def _ssd_stride(state, inputs, skip, rep):
    """The chunks of one stride: (state, (x, dt, la, b, c) [chunks, B, C,
    ...] with b, c a group each) -> (end state, y with the skip term)."""
    x, dt, la, b, c = inputs
    wide = lambda t: jnp.repeat(t, rep, axis=3) if rep > 1 else t
    state, y = lax.scan(_ssd_chunk, state, (x, dt, la, wide(b), wide(c)))
    return state, y + skip[:, None] * x


def _ssd_twin_operands(x, dt, la, b, c, chunk, stride):
    return tuple(grouped(t.astype(_F32), stride, chunk)
                 for t in (x, dt, la, b, c))


def _ssd_twin_fwd(x, dt, la, b, c, skip, chunk, stride):
    """-> (y [B, T, H, P] float32, bounds [B, T / stride, H, P, N] float32:
    the state each stride starts from)."""
    batch, _, heads, head_dim = x.shape
    groups, states = b.shape[2:]
    one_stride = functools.partial(_ssd_stride, skip=skip,
                                   rep=heads // groups)

    def one(state, inputs):
        end, y = one_stride(state, inputs)
        return end, (y, state)

    zero = jnp.zeros((batch, heads, head_dim, states), _F32)
    _, (y, bounds) = lax.scan(
        one, zero, _ssd_twin_operands(x, dt, la, b, c, chunk, stride))
    return ungrouped(y), jnp.moveaxis(bounds, 0, 1)


def _ssd_twin_bwd(x, dt, la, b, c, skip, bounds, dy, chunk, stride):
    """The gradients of ``_ssd_twin_fwd``'s y, a stride at a time from the
    last: each stride's states are made again from its boundary (``jax.vjp``
    of the stride), the gradient of the state handed to the stride before."""
    rep = x.shape[2] // b.shape[2]

    def one(carry, inputs):
        dstate, dskip = carry
        *operands, start, dy_s = inputs
        _, pull = jax.vjp(
            lambda state, ops, skip: _ssd_stride(state, ops, skip, rep),
            start, tuple(operands), skip)
        dstate, grads, dskip_s = pull((dstate, dy_s))
        return (dstate, dskip + dskip_s), grads

    (_, dskip), grads = lax.scan(
        one, (jnp.zeros(bounds.shape[:1] + bounds.shape[2:], _F32),
              jnp.zeros_like(skip)),
        (*_ssd_twin_operands(x, dt, la, b, c, chunk, stride),
         jnp.moveaxis(bounds, 1, 0),
         grouped(dy.astype(_F32), stride, chunk)),
        reverse=True)
    return (*map(ungrouped, grads), dskip)


# --- the kernels -------------------------------------------------------------

def _ssd_pair(a_ref, dt_ref, x_ref, skip_ref, scores, t, ci, rows, chunk,
              states):
    """What both kernels make of head pair ``t`` of the group over chunk
    ``ci`` of the block (positions ``rows``) before a state enters: a
    head's numbers are ``[u]`` of a list of two, what both heads share lies
    over the pair's 128 lanes, head 0 in the first 64. ``scores`` is the
    group's ``C B^T`` [C, C], or None where only the state's walk is
    wanted."""
    width = 2 * _SSD_HEAD
    lanes = slice(t * width, (t + 1) * width)
    row, col = iota(chunk)
    seen = row >= col
    first = lax.broadcasted_iota(jnp.int32, (chunk, width), 1) < _SSD_HEAD
    a_row = [a_ref[2 * t + u, pl.ds(ci, 1), :] for u in (0, 1)]    # [1, C]
    a_col = [as_col(r, chunk) for r in a_row]                    # [C, 1]
    dt_col = [as_col(dt_ref[2 * t + u, pl.ds(ci, 1), :], chunk)
              for u in (0, 1)]
    last = [jnp.sum(jnp.where(col[:1] == chunk - 1, r, 0.0), axis=1,
                    keepdims=True) for r in a_row]                 # [1, 1]
    decay = [jnp.where(seen, jnp.exp(jnp.where(seen, c - r, 0.0)), 0.0)
             for c, r in zip(a_col, a_row)]                        # [C, C]
    by_lane = lambda pair: jnp.where(first, pair[0], pair[1])      # [C, 128]
    x = x_ref[rows, lanes]
    x32 = x.astype(_F32)
    v32 = by_lane(dt_col) * x32
    to_end = by_lane([jnp.exp(l - c) for l, c in zip(last, a_col)])
    # a head's 64 rows of the pair's state [128, N]
    upper = lax.broadcasted_iota(jnp.int32, (width, states), 0) < _SSD_HEAD
    grown = [jnp.broadcast_to(jnp.exp(l), (1, states)) for l in last]
    return dict(
        lanes=lanes, first=first, seen=seen, by_lane=by_lane, dt=x.dtype,
        x32=x32, v32=v32, v=v32.astype(x.dtype), decay=decay,
        weights=scores if scores is None else [scores * d for d in decay],
        dt_lane=by_lane(dt_col),
        grow=by_lane([jnp.exp(c) for c in a_col]), to_end=to_end,
        ve=(v32 * to_end).astype(x.dtype), last=last,
        last_rows=jnp.where(upper, grown[0], grown[1]),
        skip=skip_ref[:, lanes])


def _ssd_block_loops(steps: int, per_stride: int, body, reverse: bool):
    """``body(s, r)`` over the block's strides ``s`` (a loop) and a stride's
    chunks ``r`` (written out), from the last where ``reverse``."""

    def one(n, carry):
        s = steps - 1 - n if reverse else n
        body(s, None)
        for r in (reversed(range(per_stride)) if reverse
                  else range(per_stride)):
            body(s, r)
        return carry

    lax.fori_loop(0, steps, one, 0)


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, a_ref, dt_ref, skip_ref, y_ref,
                    bound_ref, s_scr, *, chunk: int, per_stride: int,
                    steps: int, pairs: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    def body(s, r):
        if r is None:           # a stride starts: keep the state it starts in
            bound_ref[s] = s_scr[...]
            return
        ci = s * per_stride + r
        rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
        b, c = b_ref[rows, :], c_ref[rows, :]
        scores = dot(c, b, NT)                               # [C, C]
        for t in range(pairs):
            p = _ssd_pair(a_ref, dt_ref, x_ref, skip_ref, scores, t, ci,
                          rows, chunk, b.shape[1])
            dt = p["dt"]
            state = s_scr[t]
            within = p["by_lane"]([dot(w.astype(dt), p["v"])
                                   for w in p["weights"]])
            y = (within + p["grow"] * dot(c, state.astype(dt), NT)
                 + p["skip"] * p["x32"])
            y_ref[rows, p["lanes"]] = y.astype(y_ref.dtype)
            s_scr[t] = p["last_rows"] * state + dot(p["ve"], b, TN)

    _ssd_block_loops(steps, per_stride, body, reverse=False)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, a_ref, dt_ref, skip_ref, dy_ref,
                    bound_ref, dx_ref, db_ref, dc_ref, da_ref, ddt_ref,
                    dskip_ref, ds_scr, starts_scr, *, chunk: int,
                    per_stride: int, steps: int, pairs: int):
    @pl.when(pl.program_id(2) == 0)     # the sequence's last block
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    rowsum = lambda t: jnp.sum(t, axis=1, keepdims=True)

    def halves_rows(m):
        upper = lax.broadcasted_iota(jnp.int32, m.shape, 0) < _SSD_HEAD
        return jnp.where(upper, m, 0.0), jnp.where(upper, 0.0, m)

    def body(s, r):
        if r is None:
            # the stride's states again, from its boundary: starts[r] is the
            # state chunk r starts from
            starts_scr[0] = bound_ref[s]
            for q in range(per_stride - 1):
                ci = s * per_stride + q
                rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
                b = b_ref[rows, :]
                for t in range(pairs):
                    p = _ssd_pair(a_ref, dt_ref, x_ref, skip_ref, None, t,
                                  ci, rows, chunk, b.shape[1])
                    starts_scr[q + 1, t] = (
                        p["last_rows"] * starts_scr[q, t]
                        + dot(p["ve"], b, TN))
            return
        ci = s * per_stride + r
        rows = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
        b, c = b_ref[rows, :], c_ref[rows, :]
        scores = dot(c, b, NT)
        dscores = jnp.zeros((chunk, chunk), _F32)
        db = jnp.zeros(b.shape, _F32)
        dc = jnp.zeros(c.shape, _F32)
        lane = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        for t in range(pairs):
            p = _ssd_pair(a_ref, dt_ref, x_ref, skip_ref, scores, t, ci,
                          rows, chunk, b.shape[1])
            dt, first, by_lane = p["dt"], p["first"], p["by_lane"]
            cast = lambda m: m.astype(dt)
            halves = lambda m: (jnp.where(first, m, 0.0),
                                jnp.where(first, 0.0, m))
            start, dstate = starts_scr[r, t], ds_scr[t]
            start_dt, dstate_dt = cast(start), cast(dstate)
            dy = dy_ref[rows, p["lanes"]]
            dy32 = dy.astype(_F32)
            # dv = M^T dy + e o (B dS'^T)
            b_ds = dot(b, dstate_dt, NT)                   # [C, 128]
            dv = (by_lane([dot(cast(w), dy, TN)
                           for w in p["weights"]]) + p["to_end"] * b_ds)
            # dM = mask(dy v^T) a head: the other head's lanes set to zero
            dweights = [jnp.where(p["seen"], dot(cast(h), p["v"], NT),
                                  0.0) for h in halves(dy32)]
            for dw, d in zip(dweights, p["decay"]):
                dscores = dscores + dw * d
            through = [dw * w for dw, w in zip(dweights, p["weights"])]
            # the state's part of y, and what the decays to the end carry
            inter = p["grow"] * dot(c, start_dt, NT)
            dy_grown = cast(dy32 * p["grow"])
            dc = dc + dot(dy_grown, start_dt)
            db = db + dot(p["ve"], dstate_dt)
            carried = halves(p["v32"] * p["to_end"] * b_ds)
            grown = halves(dy32 * inter)
            direct = halves(dv * p["x32"])
            skipped = halves(dy32 * p["x32"])
            kept = halves_rows(start * dstate)
            for u in (0, 1):
                head = 2 * t + u
                carried_u = rowsum(carried[u])                   # [C, 1]
                dlast = (jnp.sum(carried_u, axis=0, keepdims=True)
                         + jnp.exp(p["last"][u]) * jnp.sum(
                             rowsum(kept[u]), axis=0, keepdims=True))
                da_col = rowsum(through[u]) + rowsum(grown[u]) - carried_u
                da_ref[head, pl.ds(ci, 1), :] = (
                    as_row(da_col, chunk)
                    - jnp.sum(through[u], axis=0, keepdims=True)
                    + jnp.where(lane == chunk - 1, dlast, 0.0))
                ddt_ref[head, pl.ds(ci, 1), :] = as_row(
                    rowsum(direct[u]), chunk)
                dskip_ref[head, pl.ds(ci, 1), :] = as_row(
                    rowsum(skipped[u]), chunk)
            dx_ref[rows, p["lanes"]] = (
                p["dt_lane"] * dv + p["skip"] * dy32).astype(dx_ref.dtype)
            ds_scr[t] = p["last_rows"] * dstate + dot(dy_grown, c, TN)
        dscores_dt = dscores.astype(b.dtype)
        dc_ref[rows, :] = (dc + dot(dscores_dt, b)).astype(dc_ref.dtype)
        db_ref[rows, :] = (db + dot(dscores_dt, c, TN)).astype(
            db_ref.dtype)

    _ssd_block_loops(steps, per_stride, body, reverse=True)


def _ssd_geometry(x, b, chunk, stride):
    """-> (batch, groups, blocks, strides a block, chunks a stride, head
    pairs a group, states)."""
    batch, length, heads, head_dim = x.shape
    groups, states = b.shape[2:]
    assert (length % stride == 0 and stride % chunk == 0
            and head_dim == _SSD_HEAD and heads % (2 * groups) == 0
            and states % _LANES == 0 and chunk % _LANES == 0), (
                x.shape, b.shape, chunk, stride)
    strides = length // stride
    steps = next(n for n in range(max(1, _SSD_BLOCK // stride), 0, -1)
                 if strides % n == 0)
    return (batch, groups, strides // steps, steps, stride // chunk,
            heads // groups // 2, states)


def _ssd_params(interpret: bool):
    return compiler_params(interpret, ("parallel", "parallel", "arbitrary"),
                           64 * 2**20)


def _ssd_specs(x, b, chunk, stride, block_of):
    """The block specs both kernels share: (x or y, B or C, a gate, the
    skip's lanes, the boundary states), the block a grid step works on by
    ``block_of``."""
    _, _, _, steps, per_stride, pairs, states = _ssd_geometry(
        x, b, chunk, stride)
    block, width = steps * stride, 2 * pairs * _SSD_HEAD
    return (
        pl.BlockSpec((None, block, width),
                     lambda n, g, i: (n, block_of(i), g)),
        pl.BlockSpec((None, block, states),
                     lambda n, g, i: (n, block_of(i), g)),
        pl.BlockSpec((None, 2 * pairs, None, block // chunk, chunk),
                     lambda n, g, i: (n, g, block_of(i), 0, 0)),
        pl.BlockSpec((1, width), lambda n, g, i: (0, g)),
        pl.BlockSpec((None, steps, pairs, 2 * _SSD_HEAD, states),
                     lambda n, g, i: (n, block_of(i), g, 0, 0)))


def _ssd_kernel_operands(x, dt, la, b, c, skip, chunk, block):
    """The arrays as the kernels take them: the model's own x, B, C; the
    log-decay summed from each chunk's start and dt, a chunk's numbers along
    the lanes; D spread over its head's lanes."""
    by_chunk = lambda t: gates(t.astype(_F32), chunk, block)
    return (folded(x), folded(b), folded(c),
            jnp.cumsum(by_chunk(la), axis=-1), by_chunk(dt),
            jnp.repeat(skip.astype(_F32), x.shape[3])[None, :])


def _ssd_pallas_fwd(x, dt, la, b, c, skip, chunk, stride, interpret):
    batch, groups, blocks, steps, per_stride, pairs, states = _ssd_geometry(
        x, b, chunk, stride)
    heads, head_dim = x.shape[2:]
    xs, bc, gate, lanes, bound = _ssd_specs(x, b, chunk, stride, lambda i: i)
    y, bounds = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, chunk=chunk,
                          per_stride=per_stride, steps=steps, pairs=pairs),
        grid=(batch, groups, blocks),
        in_specs=[xs, bc, bc, gate, gate, lanes],
        out_specs=[xs, bound],
        out_shape=[
            jax.ShapeDtypeStruct(folded(x).shape, x.dtype),
            jax.ShapeDtypeStruct((batch, blocks * steps, heads // 2,
                                  2 * head_dim, states), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((pairs, 2 * head_dim, states), _F32)],
        compiler_params=_ssd_params(interpret),
        interpret=interpret,
        name="ssd_fwd",
    )(*_ssd_kernel_operands(x, dt, la, b, c, skip, chunk, steps * stride))
    return y.reshape(x.shape), bounds.reshape(
        batch, blocks * steps, heads, head_dim, states)


def _ssd_pallas_bwd(x, dt, la, b, c, skip, bounds, dy, chunk, stride,
                    interpret):
    batch, groups, blocks, steps, per_stride, pairs, states = _ssd_geometry(
        x, b, chunk, stride)
    heads, head_dim = x.shape[2:]
    block = steps * stride
    xs, bc, gate, lanes, bound = _ssd_specs(x, b, chunk, stride,
                                            lambda i: blocks - 1 - i)
    dgate = jax.ShapeDtypeStruct(
        (batch, heads, blocks, block // chunk, chunk), _F32)
    operands = _ssd_kernel_operands(x, dt, la, b, c, skip, chunk, block)
    dx, db, dc, da, ddt, dskip = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, chunk=chunk,
                          per_stride=per_stride, steps=steps, pairs=pairs),
        grid=(batch, groups, blocks),
        in_specs=[xs, bc, bc, gate, gate, lanes, xs, bound],
        out_specs=[xs, bc, bc, gate, gate, gate],
        out_shape=[
            jax.ShapeDtypeStruct(operands[0].shape, x.dtype),
            jax.ShapeDtypeStruct(operands[1].shape, b.dtype),
            jax.ShapeDtypeStruct(operands[2].shape, c.dtype),
            dgate, dgate, dgate,
        ],
        scratch_shapes=[
            pltpu.VMEM((pairs, 2 * head_dim, states), _F32),
            pltpu.VMEM((per_stride, pairs, 2 * head_dim, states), _F32),
        ],
        compiler_params=_ssd_params(interpret),
        interpret=interpret,
        name="ssd_bwd",
    )(*operands, folded(dy), bounds.reshape(
        batch, blocks * steps, heads // 2, 2 * head_dim, states))
    # la_t enters every a from t to its chunk's end
    dla = jnp.flip(jnp.cumsum(jnp.flip(da, -1), axis=-1), -1)
    return (dx.reshape(x.shape), ungated(ddt), ungated(dla),
            db.reshape(b.shape), dc.reshape(c.shape),
            dskip.sum((0, 2, 3, 4)))


# --- one differentiable function over both --------------------------------------

def _ssd_forward(x, dt, la, b, c, skip, chunk, stride, impl):
    _ssd_record(x, b, chunk, stride, False)
    if impl == "scan":
        y, bounds = _ssd_twin_fwd(x, dt, la, b, c, skip, chunk, stride)
        return y.astype(x.dtype), bounds
    return _ssd_pallas_fwd(x, dt, la, b, c, skip, chunk, stride,
                           impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd_diff(x, dt, la, b, c, skip, chunk, stride, impl):
    return _ssd_forward(x, dt, la, b, c, skip, chunk, stride, impl)[0]


# What recomputation keeps of the scalar-decay scan
# (``ops.remat.remat_policy``): its output [B, T, heads x head_dim] in the
# compute dtype and the state each stride starts from, [B, T / stride, heads,
# head_dim, states] float32, no more bytes than the output
SSD_REMAT_NAMES = ("ssd_out", "ssd_bounds")


def _ssd_diff_fwd(x, dt, la, b, c, skip, chunk, stride, impl):
    y, bounds = _ssd_forward(x, dt, la, b, c, skip, chunk, stride, impl)
    # kept as the kernels wrote it, [B, T, heads x head_dim]: a kept [B, T,
    # 64, 64] array crosses into the recomputed block with the tokens minor
    # (64 lanes would half fill a tile), a relayout each way (PERF.md
    # section 6, PR 64)
    y = ad_checkpoint.checkpoint_name(
        y.reshape(*y.shape[:2], -1), SSD_REMAT_NAMES[0]).reshape(y.shape)
    bounds = ad_checkpoint.checkpoint_name(bounds, SSD_REMAT_NAMES[1])
    return y, (x, dt, la, b, c, skip, bounds)


def _ssd_diff_bwd(chunk, stride, impl, res, dy):
    x, dt, la, b, c, skip, bounds = res
    _ssd_record(x, b, chunk, stride, True)
    if impl == "scan":
        grads = _ssd_twin_bwd(x, dt, la, b, c, skip, bounds, dy, chunk,
                              stride)
    else:
        grads = _ssd_pallas_bwd(x, dt, la, b, c, skip, bounds, dy, chunk,
                                stride, impl == "pallas_interpret")
    return tuple(g.astype(r.dtype) for g, r in zip(grads, res))


_ssd_diff.defvjp(_ssd_diff_fwd, _ssd_diff_bwd)


def ssd_auto_impl(x, b) -> str:
    """What ``ssd_scan(impl=None)`` runs: the kernels where the layout fits
    them (heads 64 wide, an even number of them a group, the states whole
    lane tiles) and ``x`` is traced where a kernel may run
    (``mosaic.takes_kernels``); the chunked twin elsewhere."""
    heads, head_dim = x.shape[2:]
    groups, states = b.shape[2:]
    fits = (head_dim == _SSD_HEAD and heads % (2 * groups) == 0
            and states % _LANES == 0)
    return "pallas" if fits and takes_kernels(x) else "scan"


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_scan(x, dt, A, B, C, D, *, chunk: Optional[int] = None,
             impl: Optional[str] = None) -> jax.Array:
    """``y`` [B, T, heads, head_dim] of the scalar-decay recurrence above,
    each of the B sequences from a zero state: ``x`` [B, T, heads,
    head_dim], ``dt`` [B, T, heads] (positive, after its softplus), ``A``
    [heads] (negative), ``B``, ``C`` [B, T, groups, states] (group ``h //
    (heads / groups)`` serves head ``h``), ``D`` [heads]. ``y`` has ``x``'s
    dtype; the state, the decays and every accumulation are float32.
    ``chunk`` positions are one set of matmuls (``SSD_CHUNK`` if left out;
    the kernels want a multiple of 128); a boundary state is kept every
    ``ssd_stride_of(chunk, states, itemsize)`` positions, and a length that
    is no multiple of that is padded with positions that leave the state as
    it is (``dt`` 0) and whose output is dropped. ``impl``: "pallas" |
    "pallas_interpret" | "scan"; None: ``ssd_auto_impl``."""
    length = x.shape[1]
    impl = impl or ssd_auto_impl(x, B)
    chunk = chunk or SSD_CHUNK
    stride = ssd_stride_of(chunk, B.shape[3], x.dtype.itemsize)
    pad = -length % stride
    if pad:
        widths = lambda t: ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)
        x, dt, B, C = (jnp.pad(t, widths(t)) for t in (x, dt, B, C))
    dt = dt.astype(_F32)

    def scan(x, dt, la, b, c, skip):
        return _ssd_diff(x, dt, la, b, c, skip, chunk, stride, impl)

    if impl != "scan":
        scan = per_batch_shard(scan, x, (True,) * 5 + (False,), "ssd_scan")
    y = scan(x, dt, dt * A.astype(_F32), B, C, D.astype(_F32))
    return y[:, :length] if pad else y
