"""The prologue of a normed, rotated attention layer: an RMSNorm over each
head's width, then the rotation of the head's first half against its second
by the position's angles (``models/llama.RMSNorm`` then
``models/afmoe.rotate_halves``, as ``models/mellum.py`` and
``models/afmoe.py`` call them on q and on k between the projections and the
attention), as one function with a Pallas kernel each way.

Left to XLA, the pair is some twenty passes a layer over arrays the size of
q between two custom calls, half of them in float32 (the statistics, the
scale's gradient, the halves split and joined again, relayouts between the
norm's, the rotation's and the flash kernels' views, dQ^T turned and
rounded): 59 ms of a 347 ms step at 2 x 8,192 tokens of 32 heads of 128,
where the bytes that have to move take 1.5 ms a layer (PERF.md section 6, PR
63). The kernels here read the projection's own [B, T, H x 128] result and
write the flash kernels' [B x H, T, 128] operand (``head_rotary_fwd``), and
read the flash backward's dQ as it leaves that kernel, the float32 [B x H,
128, T] sum, or dK in the model's [B, T, H x 128], and write the gradient
the projection's weight gradient reads (``head_rotary_bwd``): one trip
through HBM each way. ``ops/attention.py:normed_rotary_self_attention`` is
the one differentiable function over them and the flash call.

``head_rotary`` is the same arithmetic in ``jnp``: the twin where the
kernels do not run (off a TPU, under a mesh axis that is not the batch's,
at a shape ``fits`` refuses) and their reference. Statistics, scale and
rotation are float32 and the result is rounded to the input's type ONCE
(the two modules rounded the normed value, widened it and rounded again).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_kernels import _largest_block
from ray_tpu.ops.mosaic import compiler_params

_F32 = jnp.float32
_LANES = 128
# Read on a v5e at (2, 8192, 32 and 4, 128) and (1, 16384, 32 and 4, 128);
# the tables are in ``benches/head_rotary.py``'s docstring, with the command.
# Tokens a grid step, heads a grid step (a step moves tokens x heads x 128
# entries each way, 1 MiB in bfloat16: the largest block the backward's
# float32 operand leaves room for in the compiler's own 16 MiB of VMEM), and
# the rows of one pass of a step's loop (the body is compiled once, for a
# pass, which joins the tables' halves once for its four heads).
_BLOCK_TOKENS, _HEADS_A_STEP, _ROWS_A_PASS = 1024, 4, 512


def tables(cos, sin):
    """A layer kind's cos and sin as the kernels read them, [T, head_dim /
    2] float32, from what a model hands over ([1, T, head_dim / 2]: one
    table for every row of the batch)."""
    return (cos.reshape(cos.shape[-2:]).astype(_F32),
            sin.reshape(sin.shape[-2:]).astype(_F32))


def fits(x, cos) -> bool:
    """Whether the kernels take ``x`` [B, T, heads, head_dim] with the table
    ``cos`` ([1, T, head_dim / 2], or None for a layer that is normed and
    not rotated), read from the call's shapes alone: a head is one lane tile
    (a width of 64 is half a tile, two heads a tile: the twin's, as a wider
    head is until a chip has read it), the rotation is over all of the
    head's width or there is none (a partial rotation leaves lanes that only
    the norm touches: the twin's), and one table serves every row. What
    the table's angles were made of is not asked: one whose frequency pairs
    read three position rows (``llama.rope_table`` under ``mrope_section``)
    fits while it is [1, T, 64], one layout for every row of the batch."""
    d = x.shape[-1]
    whole = cos is None or (cos.shape[-1] * 2 == d and cos.ndim == 3
                            and cos.shape[0] == 1)
    return d == _LANES and whole


def head_rotary(x, scale, cos, sin, *, eps: float):
    """``x`` [B, T, heads, head_dim] normed over ``head_dim`` (``x /
    rms(x) * scale``, ``scale`` [head_dim]) and, given ``cos`` and ``sin``
    [B or 1, T, head_dim / 2], turned by the position's angles, dimension
    ``i`` against ``i + head_dim / 2``. float32 throughout, one rounding to
    ``x``'s type at the end."""
    xf = x.astype(_F32)
    n = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    n = n * scale.astype(_F32)
    if cos is not None:
        x1, x2 = jnp.split(n, 2, axis=-1)
        c, s = cos[:, :, None, :], sin[:, :, None, :]
        n = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return n.astype(x.dtype)


def _both_halves(cos_ref, sin_ref, rows):
    """(C, S) [rows, 128] of a pass's rows of the tables: ``[c | c]`` and
    ``[-s | s]``, so that ``u C + swapped(u) S`` turns ``u``'s halves (and
    ``g C + swapped(g S)`` turns a cotangent back)."""
    c, s = cos_ref[rows, :], sin_ref[rows, :]
    return (jnp.concatenate([c, c], axis=1),
            jnp.concatenate([-s, s], axis=1))


def _swapped(x):
    """``x`` [rows, 128] with its halves exchanged: a rotation of the lanes
    by half a tile."""
    return pltpu.roll(x, _LANES // 2, axis=1)


def _passes(block: int, rows: int, body, carry=None):
    """``body(rows' slice, carry)`` over a block's rows, ``rows`` a pass."""
    def step(i, carry):
        return body(pl.ds(pl.multiple_of(i * rows, rows), rows), carry)
    return lax.fori_loop(0, block // rows, step, carry)


def _fwd_kernel(x_ref, scale_ref, *rest, eps: float, heads: int, rows: int):
    """One block of tokens of ``heads`` adjacent heads: ``x_ref`` (tokens,
    heads x 128) of the projection's result, ``o_ref`` (heads, tokens, 128)
    of the flash kernels' operand. ``rest``: the tables' blocks (tokens,
    64) where the layer is rotated, then ``o_ref``."""
    *tables, o_ref = rest
    scale = scale_ref[...].astype(_F32)

    def a_pass(at, _):
        if tables:
            cc, ss = _both_halves(*tables, at)
        for h in range(heads):
            x = x_ref[at, h * _LANES:(h + 1) * _LANES].astype(_F32)
            n = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            n = n * scale
            if tables:
                n = n * cc + _swapped(n) * ss
            o_ref[h, at, :] = n.astype(o_ref.dtype)

    _passes(x_ref.shape[0], rows, a_pass)


def _bwd_kernel(g_ref, x_ref, scale_ref, *rest, eps: float, heads: int,
                rows: int, turned: bool):
    """The backward of one block of tokens of ``heads`` adjacent heads.
    ``g_ref``: with ``turned`` (heads, 128, tokens) float32, the flash
    backward's dQ^T sum, each (128, rows) tile turned here; else (tokens,
    heads x 128), dK as that kernel writes it. ``x_ref`` as the forward's,
    from which a row's statistic is made again. ``rest``: the tables'
    blocks, then ``dx_ref`` (tokens, heads x 128) and ``dscale_ref`` (1,
    128) float32, this grid step's share of the scale's gradient."""
    *tables, dx_ref, dscale_ref = rest
    scale = scale_ref[...].astype(_F32)

    def a_pass(at, dscale):
        if tables:
            cc, ss = _both_halves(*tables, at)
        for h in range(heads):
            lanes = slice(h * _LANES, (h + 1) * _LANES)
            g = (g_ref[h, :, at].T if turned else g_ref[at, lanes]).astype(
                _F32)
            if tables:
                g = g * cc + _swapped(g * ss)
            x = x_ref[at, lanes].astype(_F32)
            r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            n = x * r
            dscale = dscale + jnp.sum(g * n, axis=0, keepdims=True)
            dn = g * scale
            dx = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dx_ref[at, lanes] = dx.astype(dx_ref.dtype)
        return dscale

    dscale_ref[...] = _passes(x_ref.shape[0], rows, a_pass,
                              jnp.zeros((1, _LANES), _F32))


def _grid(x, heads: int, cos):
    """What both calls share for ``x`` [B, T, heads x 128]: (grid, the
    blocks' specs by name, the kernels' static arguments). ``lanes`` is a
    (tokens, heads a step x 128) block of such an array, ``folded`` and
    ``folded_t`` a step's heads of [B x heads, T, 128] and of [B x heads,
    128, T], ``tables`` the two tables' blocks where there is a rotation.
    The heads of a step lie innermost in the grid, so a block of the tables
    is fetched once for all of a row's heads."""
    b, seq, lanes = x.shape
    assert lanes == heads * _LANES, (x.shape, heads)
    tokens = _largest_block(seq, _BLOCK_TOKENS, _LANES)
    a_step = _largest_block(heads, _HEADS_A_STEP, 1)
    steps = heads // a_step
    table = pl.BlockSpec((tokens, _LANES // 2), lambda bi, ti, hi: (ti, 0))
    specs = dict(
        lanes=pl.BlockSpec((None, tokens, a_step * _LANES),
                           lambda bi, ti, hi: (bi, ti, hi)),
        scale=pl.BlockSpec((1, _LANES), lambda bi, ti, hi: (0, 0)),
        tables=[] if cos is None else [table, table],
        folded=pl.BlockSpec((a_step, tokens, _LANES),
                            lambda bi, ti, hi: (bi * steps + hi, ti, 0)),
        folded_t=pl.BlockSpec((a_step, _LANES, tokens),
                              lambda bi, ti, hi: (bi * steps + hi, 0, ti)))
    return ((b, seq // tokens, steps), specs,
            dict(heads=a_step, rows=_largest_block(tokens, _ROWS_A_PASS, 8)))


_PARALLEL = ("parallel", "parallel", "parallel")


def head_rotary_fwd(x, scale, cos, sin, *, heads: int, eps: float,
                    interpret: bool = False):
    """``x`` [B, T, heads x 128], a projection's result -> [B x heads, T,
    128], what ``head_rotary`` makes of it, laid out as the flash kernels
    take q and k. ``cos``, ``sin``: [T, 64] float32, or None where the layer
    is normed and not rotated."""
    b, seq, _ = x.shape
    grid, specs, static = _grid(x, heads, cos)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, **static), grid=grid,
        in_specs=[specs["lanes"], specs["scale"], *specs["tables"]],
        out_specs=specs["folded"],
        out_shape=jax.ShapeDtypeStruct((b * heads, seq, _LANES), x.dtype),
        compiler_params=compiler_params(interpret, _PARALLEL),
        interpret=interpret, name="head_rotary_fwd",
    )(x, scale.reshape(1, _LANES), *(() if cos is None else (cos, sin)))


def head_rotary_bwd(g, x, scale, cos, sin, *, heads: int, eps: float,
                    turned: bool, interpret: bool = False):
    """-> (dx [B, T, heads x 128] in ``x``'s type, dscale [128] float32) of
    ``head_rotary_fwd``'s call from ``g``, the cotangent of its result:
    with ``turned`` float32 [B x heads, 128, T], dQ^T as the flash backward
    leaves it; else [B, T, heads x 128], as that kernel writes dK."""
    b, seq, _ = x.shape
    grid, specs, static = _grid(x, heads, cos)
    dx, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, turned=turned, **static),
        grid=grid,
        in_specs=[specs["folded_t" if turned else "lanes"], specs["lanes"],
                  specs["scale"], *specs["tables"]],
        out_specs=[
            specs["lanes"],
            # a grid step's own sum, added up outside
            pl.BlockSpec((None, None, None, 1, _LANES),
                         lambda bi, ti, hi: (bi, ti, hi, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((*grid, 1, _LANES), _F32)],
        compiler_params=compiler_params(interpret, _PARALLEL),
        interpret=interpret, name="head_rotary_bwd",
    )(g, x, scale.reshape(1, _LANES), *(() if cos is None else (cos, sin)))
    return dx, dscale.sum((0, 1, 2, 3))


def needed_bytes(tokens: int, heads: int, itemsize: int, *, backward: bool,
                 turned: bool = False) -> int:
    """The bytes one call has to move for ``tokens`` (batch x length) of
    ``heads`` heads of 128 in a type of ``itemsize`` bytes: the array read
    once and written once forward; backward the cotangent (float32 where it
    is the flash kernel's dQ^T sum), the projection's result and the
    gradient. The tables and the scale are small beside them."""
    entries = tokens * heads * _LANES
    if not backward:
        return 2 * entries * itemsize
    return entries * ((4 if turned else itemsize) + 2 * itemsize)
