"""The gated short convolution (a causal depthwise convolution of a few taps
between two gates), forward and backward: a Pallas TPU kernel pair and the
same arithmetic in ``jax.numpy``.

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
from jax.sharding import PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu.ops.attention import _batch_axes, unmapped_mesh_axes

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
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=48 * 2**20)


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
    """What ``impl=None`` runs: the kernels on a TPU where the layout
    ``fits`` them and the mesh ``bcx`` is traced under has no axis of more
    than one device but the batch's (the rule of ``ops.ssm.auto_impl``);
    the ``jnp`` form elsewhere."""
    if (jax.default_backend() == "tpu" and fits(bcx, taps)
            and not unmapped_mesh_axes(bcx)):
        return "pallas"
    return "jnp"


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
    mesh, axes = _batch_axes(bcx) if impl != "jnp" else (None, ())
    if axes:
        rows, whole = PartitionSpec(axes), PartitionSpec()
        conv = jax.shard_map(conv, mesh=mesh, in_specs=(rows, whole),
                             out_specs=rows, axis_names=set(axes),
                             check_vma=False)
    return conv(bcx, taps)


# ---------------------------------------------------------------------------
# the plain causal depthwise convolution, with its activation
# ---------------------------------------------------------------------------

def _causal_conv(x, taps, activation):
    length, last = x.shape[1], taps.shape[0] - 1
    padded = jnp.pad(x, ((0, 0), (last, 0), (0, 0)))
    y = sum(taps[k].astype(_F32) * padded[:, k:k + length].astype(_F32)
            for k in range(last + 1))
    return (y if activation is None else activation(y)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _causal_conv_diff(x, taps, activation):
    return _causal_conv(x, taps, activation)


def _causal_conv_fwd(x, taps, activation):
    return _causal_conv(x, taps, activation), (x, taps)


def _causal_conv_bwd(activation, res, dy):
    # nothing the size of y is kept: the sum over the taps is made again
    return jax.vjp(functools.partial(_causal_conv, activation=activation),
                   *res)[1](dy)


_causal_conv_diff.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def causal_conv(x, taps, activation=None):
    """``y_t = activation(sum_k taps[k] * x_{t - K + 1 + k})`` per channel:
    the depthwise causal convolution of ``x`` [B, T, channels] with ``taps``
    [K, channels], zeros before a sequence's first position, no bias, then
    ``activation`` (None: none; a function of the float32 sum, such as
    ``jax.nn.silu``). The sum over the taps is float32, ``y`` has ``x``'s
    dtype. Plain XLA on every backend: the padded slices, the products, the
    sum and the activation fuse into one pass over ``x``; its residuals are
    its two arguments (the backward pass makes the sum again)."""
    return _causal_conv_diff(x, taps, activation)
