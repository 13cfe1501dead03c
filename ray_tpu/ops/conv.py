"""The gated short convolution (a causal depthwise convolution of a few taps
between two gates), forward and backward: a Pallas TPU kernel pair and the
same arithmetic in ``jax.numpy``; below it, ``causal_conv``'s pair (PR 57).

    [B | C | x] = bcx                       three chunks of the last axis
    z_t = B_t * x_t
    c_t = sum_k taps[k] * z_{t - K + 1 + k}   K taps, zeros before position 0
    y_t = C_t * c_t

Per channel, with no bias and no activation: the token mixer of a layer
whose in-projection makes ``bcx`` and whose out-projection takes ``y``. Every
position is a dozen vector operations on 8 bytes read and written, so the
operation is bound by memory, and written out in XLA it is what XLA fuses of
it: the padded slices, the products and the sum, forward, and their
transposes backward. Both paths here make one pass over their operands: the
forward reads ``bcx`` and writes ``y`` (6 and 2 bytes a position and channel
in bfloat16), the backward reads ``bcx`` and ``dy`` and writes ``dbcx`` (8
and 6) and the taps' gradient as float32 partial sums. One ``custom_vjp``
holds both; its residuals are its two arguments, so a layer keeps nothing
the size of ``y`` for it: the backward makes ``z`` and ``c`` again.

``z`` is rounded to the operands' dtype, as the product of two arrays of
that dtype is; the sum over the taps, ``C_t * c_t`` before its one rounding
and the taps' gradient are float32.

The kernels (``short_conv_fwd`` / ``short_conv_bwd``: the benchmark's
readers find them by these names). Channels lie on lanes, positions on
sublanes. The grid is (sequences, blocks of positions); a block holds every
channel of its positions, the three chunks as three operands over the one
array. The positions before a block (backward: also the ones after it) come
as a second, ``_HALO``-row block of the same array, zeroed at a sequence's
first (last) block: nothing crosses from one sequence into the next. Inside
a block the kernel walks ``_ROWS`` positions of ``_LANE_SLAB`` channels at a
time, a few vector registers an array, shifting along the sublanes by a
rotation whose wrapped rows are replaced by the neighbouring rows'.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops.mosaic import (compiler_params, per_batch_shard,
                                takes_kernels)

TAPS = 3             # what the kernels are written for: z_{t-2}, z_{t-1}, z_t
_HALO = 16           # rows of the neighbouring block: one bfloat16 tile
_ROWS = 32           # positions a loop step
_LANE_SLAB = 256     # channels a loop step: 8 vector registers an array
_LANES = 128
_BLOCK_BYTES = 2**20  # of one chunk of a block of positions
_F32 = jnp.float32


# ---------------------------------------------------------------------------
# the same arithmetic in jax.numpy: any backend
# ---------------------------------------------------------------------------

def _behind(t, k: int):
    """``t`` [B, T, h] moved ``k`` positions on: row i holds ``t[i - k]``,
    zeros before the sequence's start."""
    return jnp.pad(t, ((0, 0), (k, 0), (0, 0)))[:, :t.shape[1]] if k else t


def _ahead(t, k: int):
    """Row i holds ``t[i + k]``, zeros past the sequence's end."""
    return jnp.pad(t, ((0, 0), (0, k), (0, 0)))[:, k:] if k else t


def _gated(bcx, taps):
    """-> (B, C, x in float32, z rounded and in float32, c)."""
    b, c, x = (t.astype(_F32) for t in jnp.split(bcx, 3, axis=-1))
    z = (b * x).astype(bcx.dtype).astype(_F32)
    last = taps.shape[0] - 1
    conv = sum(taps[k] * _behind(z, last - k) for k in range(last + 1))
    return b, c, x, z, conv


def _jnp_fwd(bcx, taps):
    _, c, _, _, conv = _gated(bcx, taps)
    return (c * conv).astype(bcx.dtype)


def _jnp_bwd(bcx, taps, dy):
    b, c, x, z, conv = _gated(bcx, taps)
    dy = dy.astype(_F32)
    dc = dy * c
    last = taps.shape[0] - 1
    dz = sum(taps[k] * _ahead(dc, last - k) for k in range(last + 1))
    dtaps = jnp.stack([(dc * _behind(z, last - k)).sum((0, 1))
                       for k in range(last + 1)])
    dbcx = jnp.concatenate([dz * x, dy * conv, dz * b], axis=-1)
    return dbcx.astype(bcx.dtype), dtaps


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _shifted_behind(t, before, k: int):
    """``t`` [rows, n] moved ``k`` rows on, its first ``k`` rows the last
    ``k`` of ``before`` [_HALO, n]."""
    turned, edge = pltpu.roll(t, k, axis=0), pltpu.roll(before, k, axis=0)
    row = lax.broadcasted_iota(jnp.int32, edge.shape, 0)
    return jnp.concatenate(
        [jnp.where(row < k, edge, turned[:_HALO]), turned[_HALO:]], axis=0)


def _shifted_ahead(t, after, k: int):
    """``t`` [rows, n] moved ``k`` rows back, its last ``k`` rows the first
    ``k`` of ``after`` [_HALO, n]."""
    rows = t.shape[0]
    turned = pltpu.roll(t, rows - k, axis=0)
    edge = pltpu.roll(after, _HALO - k, axis=0)
    row = lax.broadcasted_iota(jnp.int32, edge.shape, 0)
    return jnp.concatenate(
        [turned[:rows - _HALO],
         jnp.where(row >= _HALO - k, edge, turned[rows - _HALO:])], axis=0)


def _rounded_product(a, b, dtype):
    """``a * b`` rounded to ``dtype`` as a product of two such arrays is,
    in float32."""
    return (a.astype(_F32) * b.astype(_F32)).astype(dtype).astype(_F32)


def _neighbour(block_ref, halo_ref, at_edge, start, lanes):
    """[_HALO, n] rows of the block at ``start``, or where ``at_edge`` the
    neighbouring block's."""
    inside = block_ref[pl.ds(pl.multiple_of(start, _HALO), _HALO), lanes]
    return jnp.where(at_edge, halo_ref[:, lanes], inside)


def _z_before(b_ref, x_ref, hb_ref, hx_ref, first, j, start, lanes, dtype):
    """``z`` of the _HALO rows before loop step ``j`` (at ``start``) of a
    block: the block's own, the block before's at its first step, zeros in
    a sequence's ``first`` block."""
    at = jnp.maximum(start - _HALO, 0)
    before = _rounded_product(_neighbour(b_ref, hb_ref, j == 0, at, lanes),
                              _neighbour(x_ref, hx_ref, j == 0, at, lanes),
                              dtype)
    return jnp.where(first & (j == 0), 0.0, before)


def _slab(channels: int) -> int:
    return _LANE_SLAB if channels % _LANE_SLAB == 0 else _LANES


def _fwd_kernel(b_ref, c_ref, x_ref, hb_ref, hx_ref, w_ref, y_ref):
    block, channels = y_ref.shape
    dtype = y_ref.dtype
    first = pl.program_id(1) == 0
    n = _slab(channels)
    for s in range(channels // n):
        lanes = pl.ds(s * n, n)
        w0, w1, w2 = (w_ref[k:k + 1, lanes] for k in range(TAPS))

        def chunk(j, _):
            start = pl.multiple_of(j * _ROWS, _ROWS)
            rows = pl.ds(start, _ROWS)
            z = _rounded_product(b_ref[rows, lanes], x_ref[rows, lanes],
                                 dtype)
            before = _z_before(b_ref, x_ref, hb_ref, hx_ref, first, j, start,
                               lanes, dtype)
            conv = (w2 * z + w1 * _shifted_behind(z, before, 1)
                    + w0 * _shifted_behind(z, before, 2))
            y_ref[rows, lanes] = (c_ref[rows, lanes].astype(_F32)
                                  * conv).astype(dtype)
            return 0

        lax.fori_loop(0, block // _ROWS, chunk, 0)


def _bwd_kernel(b_ref, c_ref, x_ref, dy_ref, hb_ref, hx_ref, hc_ref, hdy_ref,
                w_ref, d_ref, dw_ref):
    block, channels = dy_ref.shape
    dtype = d_ref.dtype
    i = pl.program_id(1)
    first, final = i == 0, i == pl.num_programs(1) - 1
    steps = block // _ROWS

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    n = _slab(channels)
    for s in range(channels // n):
        lanes = pl.ds(s * n, n)
        w0, w1, w2 = (w_ref[k:k + 1, lanes] for k in range(TAPS))

        def chunk(j, sums):
            start = pl.multiple_of(j * _ROWS, _ROWS)
            rows = pl.ds(start, _ROWS)
            b = b_ref[rows, lanes].astype(_F32)
            x = x_ref[rows, lanes].astype(_F32)
            dy = dy_ref[rows, lanes].astype(_F32)
            z = _rounded_product(b, x, dtype)
            before = _z_before(b_ref, x_ref, hb_ref, hx_ref, first, j, start,
                               lanes, dtype)
            z1 = _shifted_behind(z, before, 1)
            z2 = _shifted_behind(z, before, 2)
            conv = w2 * z + w1 * z1 + w0 * z2
            dc = dy * c_ref[rows, lanes].astype(_F32)
            # dL/dc of the _HALO rows after this step: the block's own, the
            # block after's at its last step, zeros at a sequence's last
            last = j == steps - 1
            at = jnp.minimum(start + _ROWS, block - _HALO)
            after = (_neighbour(dy_ref, hdy_ref, last, at, lanes).astype(_F32)
                     * _neighbour(c_ref, hc_ref, last, at, lanes).astype(_F32))
            after = jnp.where(final & last, 0.0, after)
            dz = (w2 * dc + w1 * _shifted_ahead(dc, after, 1)
                  + w0 * _shifted_ahead(dc, after, 2))
            for k, chunk_of in enumerate((dz * x, dy * conv, dz * b)):
                d_ref[rows, pl.ds(k * channels + s * n, n)] = chunk_of.astype(
                    dtype)
            # eight sublanes of partial sums a tap: whole registers added
            fold = lambda t: sum(t[r:r + 8] for r in range(0, _ROWS, 8))
            return tuple(acc + fold(dc * zk)
                         for acc, zk in zip(sums, (z2, z1, z)))

        zero = jnp.zeros((8, n), _F32)
        sums = lax.fori_loop(0, steps, chunk, (zero,) * TAPS)
        for k, acc in enumerate(sums):
            dw_ref[k:k + 1, lanes] += acc.sum(axis=0, keepdims=True)


def block_rows(length: int, channels: int, itemsize: int) -> int:
    """Positions a grid step of the kernels holds for sequences of
    ``length``: the largest ``_ROWS x 2^n`` that divides it whose chunk is
    within ``_BLOCK_BYTES``; 0 where ``_ROWS`` does not divide the length
    (such a call is the ``jnp`` form's)."""
    if length % _ROWS:
        return 0
    rows = _ROWS
    while (length % (2 * rows) == 0
           and 2 * rows * channels * itemsize <= _BLOCK_BYTES):
        rows *= 2
    return rows


def needed_bytes(tokens: int, channels: int, itemsize: int,
                 backward: bool) -> int:
    """What a pass over ``tokens`` positions has to move: ``bcx`` in and
    ``y`` out, or ``bcx`` and ``dy`` in and ``dbcx`` out with the taps'
    float32 gradient; the taps themselves either way."""
    cells = tokens * channels * itemsize
    return (7 if backward else 4) * cells + (2 if backward else 1) * (
        TAPS * channels * 4)


def _record(bcx, backward: bool):
    """One ``counters`` record a traced pass (none a step)."""
    batch, length, wide = bcx.shape
    steptrace.record_counters("conv/short", {
        "channels": wide // 3, "taps": TAPS, "tokens": batch * length,
        "sequences": batch,
        "bytes_needed": needed_bytes(batch * length, wide // 3,
                                     bcx.dtype.itemsize, backward),
        "backward": int(backward)})


def _params(interpret: bool, semantics):
    return compiler_params(interpret, semantics, 48 * 2**20)


def _specs(bcx):
    """The grid and the operands' blocks: the three chunks of a block of
    positions, and of one chunk the ``_HALO`` rows before and after it."""
    batch, length, wide = bcx.shape
    channels = wide // 3
    block = block_rows(length, channels, bcx.dtype.itemsize)
    assert block and channels % _LANES == 0, bcx.shape
    per, tiles = block // _HALO, length // _HALO
    chunk = lambda k: pl.BlockSpec((None, block, channels),
                                   lambda b, i: (b, i, k))
    before = lambda k: pl.BlockSpec(
        (None, _HALO, channels),
        lambda b, i: (b, jnp.maximum(i * per - 1, 0), k))
    after = lambda k: pl.BlockSpec(
        (None, _HALO, channels),
        lambda b, i: (b, jnp.minimum((i + 1) * per, tiles - 1), k))
    taps = pl.BlockSpec((TAPS, channels), lambda b, i: (0, 0))
    whole = pl.BlockSpec((None, block, wide), lambda b, i: (b, i, 0))
    return (batch, length // block), chunk, before, after, taps, whole


def _pallas_fwd(bcx, taps, interpret):
    grid, chunk, before, _, taps_spec, _ = _specs(bcx)
    batch, length, wide = bcx.shape
    _record(bcx, False)
    return pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[chunk(0), chunk(1), chunk(2), before(0), before(2),
                  taps_spec],
        out_specs=chunk(0),
        out_shape=jax.ShapeDtypeStruct((batch, length, wide // 3), bcx.dtype),
        compiler_params=_params(interpret, ("parallel", "parallel")),
        interpret=interpret, name="short_conv_fwd",
    )(bcx, bcx, bcx, bcx, bcx, taps)


def _pallas_bwd(bcx, taps, dy, interpret):
    grid, chunk, before, after, taps_spec, whole = _specs(bcx)
    batch, length, wide = bcx.shape
    _record(bcx, True)
    dbcx, dtaps = pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[chunk(0), chunk(1), chunk(2), chunk(0), before(0),
                  before(2), after(1), after(0), taps_spec],
        out_specs=[
            whole,
            # a sequence's sum, added to at each of its blocks
            pl.BlockSpec((None, TAPS, wide // 3), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
            jax.ShapeDtypeStruct((batch, TAPS, wide // 3), _F32),
        ],
        compiler_params=_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret, name="short_conv_bwd",
    )(bcx, bcx, bcx, dy, bcx, bcx, bcx, dy, taps)
    return dbcx, dtaps.sum(0)


# ---------------------------------------------------------------------------
# one differentiable function over both
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_diff(bcx, taps, impl):
    if impl == "jnp":
        return _jnp_fwd(bcx, taps)
    return _pallas_fwd(bcx, taps, impl == "pallas_interpret")


def _conv_diff_fwd(bcx, taps, impl):
    return _conv_diff(bcx, taps, impl), (bcx, taps)


def _conv_diff_bwd(impl, res, dy):
    bcx, taps = res
    if impl == "jnp":
        return _jnp_bwd(bcx, taps, dy)
    return _pallas_bwd(bcx, taps, dy, impl == "pallas_interpret")


_conv_diff.defvjp(_conv_diff_fwd, _conv_diff_bwd)


def fits(bcx, taps) -> bool:
    """Whether the kernels' layout takes the call: three taps, channels a
    multiple of 128, a length ``_ROWS`` divides."""
    _, length, wide = bcx.shape
    return bool(taps.shape[0] == TAPS and wide % (3 * _LANES) == 0
                and block_rows(length, wide // 3, bcx.dtype.itemsize))


def auto_impl(bcx, taps) -> str:
    """What ``impl=None`` runs: the kernels where the layout ``fits`` them
    and ``bcx`` is traced where a kernel may run (``mosaic.takes_kernels``);
    the ``jnp`` form elsewhere."""
    return "pallas" if fits(bcx, taps) and takes_kernels(bcx) else "jnp"


@functools.partial(jax.jit, static_argnames=("impl",))
def gated_short_conv(bcx, taps, *, impl: Optional[str] = None) -> jax.Array:
    """``y`` [B, T, h] of the equations in the module's docstring: ``bcx``
    [B, T, 3 h] (the chunks ``B``, ``C``, ``x`` in that order), ``taps``
    [K, h] (``taps[K - 1]`` weighs a position's own ``z``). ``y`` has
    ``bcx``'s dtype. Each row of the batch is one sequence.
    ``impl``: "pallas" | "pallas_interpret" | "jnp"; None: ``auto_impl``."""
    impl = impl or auto_impl(bcx, taps)
    taps = taps.astype(_F32)
    conv = lambda bcx, taps: _conv_diff(bcx, taps, impl)
    if impl != "jnp":
        conv = per_batch_shard(conv, bcx, (True, False), "gated_short_conv")
    return conv(bcx, taps)


# ---------------------------------------------------------------------------
# the plain causal depthwise convolution, with its activation
# ---------------------------------------------------------------------------
#
#     s_t = sum_k taps[k] * x_{t - K + 1 + k}   K taps, zeros before position 0
#     y_t = activation(s_t + bias)              bias: a number a channel, or none
#
# One chunk, no gates, an activation whose derivative the backward needs: a
# second kernel pair (``causal_conv_fwd`` / ``causal_conv_bwd``) over the
# helpers above, and the XLA form as its ``jnp`` twin. The forward reads ``x``
# and writes ``y``; the backward reads ``x`` and ``dy``, makes ``s`` again and
# writes ``dx`` and the taps' float32 partial sums a sequence. The grid is
# (sequences, slabs of channels, blocks of positions): a grid step holds one
# ``_slab`` of lanes, so a loop step is a few vector registers an array with
# no loop over slabs (read on the chip at [2, 8192, 8192] bfloat16, PR 57: a
# block of 256 channels forward 1.008 ms and backward 1.662; of 512 in two
# slabs 1.038 / 1.710; of 2,048 in eight 1.044 / 1.767). The rows before a block
# come as a ``_HALO``-row block of ``x``, as the gated kernels' do; inside a
# block each loop step hands its last rows on to the next. The backward needs
# ``ds = dy * activation'(s)`` of the rows AFTER a block, which no operand
# holds: it walks a sequence's blocks, and a block's loop steps, from the last
# to the first, and hands the first ``_HALO`` rows of each step's ``ds`` on to
# the step before it (across blocks in a VMEM scratch), zeros at a sequence's
# last block. The sigmoid is the exact one (XLA's ``logistic``: ``y`` equals
# the XLA form's to the bit on the chip); it is a fifth of the forward's
# time and a quarter of the backward's (PERF.md section 6, PR 57).

_CAUSAL_TAPS = (2, 3, 4)


def _causal_conv(x, taps, activation, bias=None):
    length, last = x.shape[1], taps.shape[0] - 1
    padded = jnp.pad(x, ((0, 0), (last, 0), (0, 0)))
    y = sum(taps[k].astype(_F32) * padded[:, k:k + length].astype(_F32)
            for k in range(last + 1))
    if bias is not None:
        y = y + bias.astype(_F32)
    return (y if activation is None else activation(y)).astype(x.dtype)


def _behind_by_tap(x, before, taps: int):
    """[x_{t-K+1}, ..., x_{t-1}, x_t]: what each tap weighs."""
    return [_shifted_behind(x, before, taps - 1 - k)
            for k in range(taps - 1)] + [x]


def _weighed(w, by_tap):
    """sum_k w[k] * by_tap[k], in the taps' order."""
    return functools.reduce(lambda total, t: total + t,
                            (wk * xk for wk, xk in zip(w, by_tap)))


def _rows_before_block(hx_ref, first):
    """The _HALO rows before a block, in ``x``'s dtype: the block before's,
    zeros in a sequence's ``first`` block."""
    before = hx_ref[...]
    return jnp.where(first, jnp.zeros_like(before), before)


def _causal_fwd_kernel(x_ref, hx_ref, w_ref, y_ref, *, taps, silu,
                       bias=False):
    # with a ``bias`` it is the row after the taps' of ``w_ref``
    w = [w_ref[k:k + 1] for k in range(taps)]

    def chunk(j, before):
        # ``before``: the last rows of the step before, handed on
        rows = pl.ds(pl.multiple_of(j * _ROWS, _ROWS), _ROWS)
        x = x_ref[rows].astype(_F32)
        total = _weighed(w, _behind_by_tap(x, before, taps))
        if bias:
            total = total + w_ref[taps:taps + 1]
        if silu:
            total = total * jax.nn.sigmoid(total)
        y_ref[rows] = total.astype(y_ref.dtype)
        return x[_ROWS - _HALO:]

    first = pl.program_id(2) == 0
    lax.fori_loop(0, y_ref.shape[0] // _ROWS, chunk,
                  _rows_before_block(hx_ref, first).astype(_F32))


def _causal_bwd_kernel(x_ref, dy_ref, hx_ref, w_ref, dx_ref, dw_ref,
                       ahead_ref, *, taps, silu, bias=False):
    """Grid step ``i`` of a sequence holds its block ``blocks - 1 - i``:
    ``ahead_ref`` [_HALO, channels] is ``ds`` of the first rows of the block
    after it, left there by the grid step before. With a ``bias`` (the row
    after the taps' of ``w_ref``) its gradient, the sum of ``ds``, is the
    row after the taps' of ``dw_ref``."""
    block, channels = dy_ref.shape
    i = pl.program_id(2)
    first, final = i == pl.num_programs(2) - 1, i == 0
    steps = block // _ROWS

    @pl.when(final)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)
        ahead_ref[...] = jnp.zeros(ahead_ref.shape, _F32)

    w = [w_ref[k:k + 1] for k in range(taps)]
    edge = _rows_before_block(hx_ref, first)

    def chunk(r, carried):
        # from the block's last step to its first; each step reads the rows
        # of the step before it and hands them on as that step's x
        x, after, sums = carried
        j = steps - 1 - r
        start = pl.multiple_of(j * _ROWS, _ROWS)
        rows = pl.ds(start, _ROWS)
        behind = x_ref[pl.ds(pl.multiple_of(
            jnp.maximum(start - _ROWS, 0), _ROWS), _ROWS)]
        before = jnp.where(j == 0, edge, behind[_ROWS - _HALO:])
        x = x.astype(_F32)
        by_tap = _behind_by_tap(x, before.astype(_F32), taps)
        ds = dy_ref[rows].astype(_F32)
        if silu:
            total = _weighed(w, by_tap)
            if bias:
                total = total + w_ref[taps:taps + 1]
            gate = jax.nn.sigmoid(total)
            ds = ds * (gate * (1.0 + total * (1.0 - gate)))
        dx = _weighed(w, [_shifted_ahead(ds, after, taps - 1 - k)
                          for k in range(taps - 1)] + [ds])
        dx_ref[rows] = dx.astype(dx_ref.dtype)
        # eight sublanes of partial sums a tap: whole registers added
        fold = lambda t: sum(t[q:q + 8] for q in range(0, _ROWS, 8))
        weighed_by = by_tap + [jnp.ones_like(ds)] if bias else by_tap
        return behind, ds[:_HALO], tuple(
            acc + fold(ds * xk) for acc, xk in zip(sums, weighed_by))

    zero = jnp.zeros((8, channels), _F32)
    _, after, sums = lax.fori_loop(
        0, steps, chunk,
        (x_ref[pl.ds(block - _ROWS, _ROWS)], ahead_ref[...],
         (zero,) * (taps + int(bias))))
    ahead_ref[...] = after
    for k, acc in enumerate(sums):
        dw_ref[k:k + 1] += acc.sum(axis=0, keepdims=True)


def causal_needed_bytes(tokens: int, channels: int, taps: int, itemsize: int,
                        backward: bool, bias: bool = False) -> int:
    """What a pass over ``tokens`` positions has to move: ``x`` in and ``y``
    out, or ``x`` and ``dy`` in and ``dx`` out with the taps' float32
    gradient; the taps themselves either way, a ``bias`` as one tap more."""
    cells = tokens * channels * itemsize
    return (3 if backward else 2) * cells + (2 if backward else 1) * (
        (taps + int(bias)) * channels * 4)


def _causal_record(x, taps, silu: bool, backward: bool, bias: bool = False):
    """One ``counters`` record a traced pass of the kernels (none a step,
    none where the XLA form runs). ``taps``: without the bias's row."""
    batch, length, channels = x.shape
    steptrace.record_counters("conv/causal", {
        "channels": channels, "taps": taps,
        "tokens": batch * length, "sequences": batch,
        "activation": int(silu),      # 0: none, 1: a SiLU
        "bias": int(bias),
        "bytes_needed": causal_needed_bytes(
            batch * length, channels, taps, x.dtype.itemsize, backward,
            bias),
        "backward": int(backward)})


def _causal_specs(x, taps, backward: bool):
    """The grid and the operands' blocks: a block of positions and channels,
    the ``_HALO`` rows before it, the taps. The backward's grid walks a
    sequence from its last block to its first."""
    batch, length, channels = x.shape
    wide = _slab(channels)
    block = block_rows(length, wide, x.dtype.itemsize)
    assert block, x.shape     # causal_fits: whole lane tiles, whole steps
    per, blocks = block // _HALO, length // block
    at = (lambda i: blocks - 1 - i) if backward else (lambda i: i)
    rows = pl.BlockSpec((None, block, wide), lambda b, c, i: (b, at(i), c))
    before = pl.BlockSpec(
        (None, _HALO, wide),
        lambda b, c, i: (b, jnp.maximum(at(i) * per - 1, 0), c))
    weights = pl.BlockSpec((taps.shape[0], wide), lambda b, c, i: (0, c))
    return (batch, channels // wide, blocks), rows, before, weights, wide


def _causal_pallas_fwd(x, taps, silu, interpret, bias=False):
    """``taps``: with a ``bias``, [K + 1, channels], the bias its last row."""
    grid, rows, before, weights, _ = _causal_specs(x, taps, False)
    taps_n = taps.shape[0] - int(bias)
    _causal_record(x, taps_n, silu, False, bias)
    return pl.pallas_call(
        functools.partial(_causal_fwd_kernel, taps=taps_n, silu=silu,
                          bias=bias),
        grid=grid, in_specs=[rows, before, weights], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "parallel")),
        interpret=interpret, name="causal_conv_fwd",
    )(x, x, taps)


def _causal_pallas_bwd(x, taps, dy, silu, interpret, bias=False):
    grid, rows, before, weights, wide = _causal_specs(x, taps, True)
    batch, _, channels = x.shape
    taps_n = taps.shape[0] - int(bias)
    _causal_record(x, taps_n, silu, True, bias)
    dx, dtaps = pl.pallas_call(
        functools.partial(_causal_bwd_kernel, taps=taps_n, silu=silu,
                          bias=bias),
        grid=grid, in_specs=[rows, rows, before, weights],
        out_specs=[
            rows,
            # a sequence's sum, added to at each of its blocks
            pl.BlockSpec((None, taps.shape[0], wide),
                         lambda b, c, i: (b, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, taps.shape[0], channels), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((_HALO, wide), _F32)],
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="causal_conv_bwd",
    )(x, dy, x, taps)
    return dx, dtaps.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _causal_conv_diff(x, taps, activation, impl):
    if impl == "jnp":
        return _causal_conv(x, taps, activation)
    return _causal_pallas_fwd(x, taps, activation is not None,
                              impl == "pallas_interpret")


def _causal_conv_fwd(x, taps, activation, impl):
    return _causal_conv_diff(x, taps, activation, impl), (x, taps)


def _causal_conv_bwd(activation, impl, res, dy):
    # nothing the size of y is kept: the sum over the taps is made again
    if impl == "jnp":
        return jax.vjp(functools.partial(_causal_conv, activation=activation),
                       *res)[1](dy)
    x, taps = res
    return _causal_pallas_bwd(x, taps, dy, activation is not None,
                              impl == "pallas_interpret")


_causal_conv_diff.defvjp(_causal_conv_fwd, _causal_conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _causal_conv_bias_diff(x, taps, bias, activation, impl):
    """``_causal_conv_diff`` with a bias: the kernels take it as one row
    more of the taps' array and hand its gradient back the same way."""
    if impl == "jnp":
        return _causal_conv(x, taps, activation, bias)
    return _causal_pallas_fwd(
        x, jnp.concatenate([taps, bias[None]]), activation is not None,
        impl == "pallas_interpret", bias=True)


def _causal_conv_bias_fwd(x, taps, bias, activation, impl):
    return (_causal_conv_bias_diff(x, taps, bias, activation, impl),
            (x, taps, bias))


def _causal_conv_bias_bwd(activation, impl, res, dy):
    if impl == "jnp":
        return jax.vjp(lambda x, taps, bias: _causal_conv(
            x, taps, activation, bias), *res)[1](dy)
    x, taps, bias = res
    dx, dweights = _causal_pallas_bwd(
        x, jnp.concatenate([taps, bias[None]]), dy, activation is not None,
        impl == "pallas_interpret", bias=True)
    return dx, dweights[:-1], dweights[-1]


_causal_conv_bias_diff.defvjp(_causal_conv_bias_fwd, _causal_conv_bias_bwd)


def causal_fits(x, taps, activation) -> bool:
    """Whether the causal kernels take the call: 2 to 4 taps, channels a
    multiple of 128, a length ``_ROWS`` divides, no activation or
    ``jax.nn.silu`` itself."""
    _, length, channels = x.shape
    return bool(taps.shape[0] in _CAUSAL_TAPS and channels % _LANES == 0
                and length % _ROWS == 0
                and (activation is None or activation is jax.nn.silu))


def causal_auto_impl(x, taps, activation) -> str:
    """What ``impl=None`` runs: the kernels where ``causal_fits`` and ``x``
    is traced where a kernel may run (``mosaic.takes_kernels``); the ``jnp``
    form elsewhere."""
    fits = causal_fits(x, taps, activation)
    return "pallas" if fits and takes_kernels(x) else "jnp"


def causal_conv(x, taps, activation=None, bias=None, *,
                impl: Optional[str] = None):
    """``y_t = activation(sum_k taps[k] * x_{t - K + 1 + k} + bias)`` per
    channel: the depthwise causal convolution of ``x`` [B, T, channels] with
    ``taps`` [K, channels], zeros before a sequence's first position, plus
    ``bias`` [channels] (None: none, and the program is the one without the
    argument), then ``activation`` (None: none; a function of the float32
    sum, such as ``jax.nn.silu``). The sum over the taps and the bias's
    addition are float32, ``y`` has ``x``'s dtype. Its residuals are its two arguments (the backward pass makes the
    sum again). ``impl``: "pallas" | "pallas_interpret" (the kernels
    ``causal_conv_fwd`` / ``causal_conv_bwd``: one pass over the operands
    each way, where ``causal_fits``) | "jnp" (plain XLA on every backend: the
    padded slices, the products, the sum and the activation, and their
    transposes); None: ``causal_auto_impl``."""
    impl = impl or causal_auto_impl(x, taps, activation)
    if bias is None:
        diff, weights = _causal_conv_diff, (taps,)
    else:
        diff, weights = _causal_conv_bias_diff, (taps, bias)
    if impl == "jnp":
        return diff(x, *weights, activation, impl)
    assert causal_fits(x, taps, activation), (x.shape, taps.shape, activation)
    conv = per_batch_shard(
        lambda x, *weights: diff(x, *weights, activation, impl), x,
        (True,) + (False,) * len(weights), "causal_conv")
    return conv(x, *(w.astype(_F32) for w in weights))
