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
named ``ssm_scan_out`` / ``ssm_scan_bounds`` (``ops.attention.remat_policy``
keeps them, so a recomputed block does not run the forward scan again).

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
from jax.sharding import PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu.ops.attention import (SCAN_REMAT_NAMES, _batch_axes,
                                   unmapped_mesh_axes)

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
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=48 * 2**20)


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
    """What ``impl=None`` runs: the kernels on a TPU where the layout fits
    them (channels a multiple of 128, states of 8) and the mesh ``x`` is
    traced under has no axis of more than one device but the batch's
    (``data`` / ``fsdp``: the kernel then runs per batch shard, as the
    flash kernel does); the chunked ``lax.scan`` elsewhere."""
    fits = x.shape[2] % _LANES == 0 and a.shape[1] % 8 == 0
    if jax.default_backend() == "tpu" and fits and not unmapped_mesh_axes(x):
        return "pallas"
    return "scan"


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

    mesh, axes = _batch_axes(x) if impl != "scan" else (None, ())
    if axes:
        rows, whole = PartitionSpec(axes), PartitionSpec()
        scan = jax.shard_map(
            scan, mesh=mesh, in_specs=(rows, rows, whole, rows, rows, whole),
            out_specs=rows, axis_names=set(axes), check_vma=False)
    y = scan(x, delta, A.astype(_F32), B, C, D.astype(_F32))
    return y[:, :length] if pad else y
