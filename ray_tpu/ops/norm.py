"""The gated, grouped RMSNorm between a Mamba-2 block's scan and its
out-projection, forward and backward: a Pallas TPU kernel pair and the same
arithmetic in ``jax.numpy``.

    g = y * silu(z)                          the scan's output under its gate
    r = rsqrt(mean(g^2 over a group) + eps)  ``groups`` equal runs of the width
    o = g * r * scale                        one weight a channel

float32 until the one rounding to ``y``'s type, the sigmoid the exact one.
Every entry is some thirty vector operations on 6 bytes read and written, so
the operation is bound by memory where the vector unit keeps up; written out
in XLA inside a recomputed, differentiated block it was some seventeen
float32 passes and relayouts a block over [B, T, W] (the groups' [.., 8,
512] view is no bitcast of [.., 4096] under the (8, 128) tiling: the gated
product is copied into it and the statistic broadcast back out of it), 12.6
ms a block at 2 x 8,192 x 4,096 where the bytes take 1.8 (PERF.md section 6,
PR 64). The kernels here make one pass each way over the model's own [B, T,
W] arrays: ``group_norm_fwd`` reads ``y`` and ``z`` and writes ``o``;
``group_norm_bwd`` reads ``y``, ``z`` and ``o``'s cotangent, makes the
statistic again and writes dy, dz and the scale's gradient as float32
partial sums a grid step. One ``custom_vjp`` holds both; its residuals are
its three arguments, so a block keeps nothing for it (a recomputed block
runs the forward kernel again).

A grid step holds a block of one sequence's tokens with all W lanes, so a
row's groups lie in whole lane tiles side by side (a group's width is a
whole number of tiles: else the twin) and its DMAs are contiguous; inside,
a loop over passes of ``_ROWS_A_PASS`` rows, compiled once, walks the
groups of a pass one after another: a group's mean square is a sum over
its lane tiles and one reduction along the lanes.

``gated_group_rms_norm_jnp`` is the twin where the kernels do not run (off
a TPU, under a mesh axis that is not the batch's, at a shape ``fits``
refuses) and their reference; its gradient is jax's own.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops.mosaic import compiler_params, per_batch_shard, takes_kernels

_F32 = jnp.float32
_LANES = 128
# Read on a v5e at (2, 8192, 4096, 8 groups, bfloat16); the tables are in
# ``benches/gated_norm.py``'s docstring, with the command. The bytes of one
# operand's block a grid step, forward (three arrays stream) and backward
# (five), and the rows of one pass of a step's loop.
_FWD_BLOCK_BYTES, _BWD_BLOCK_BYTES, _ROWS_A_PASS = 2 * 2**20, 2**20, 32
# The widest group read there: the backward holds a dozen float32 arrays of
# a pass's rows of one group, 512 KiB each at this width, in the compiler's
# own 16 MiB of VMEM (at 64 rows a pass of 4,096 lanes it ran out).
_MAX_GROUP_LANES = 4096


def gated_group_rms_norm_jnp(y, z, scale, *, groups: int, eps: float):
    """``y * silu(z)`` [..., W] normed over each of ``groups`` equal runs of
    W and scaled by ``scale`` [W]: float32, rounded once to ``y``'s type."""
    width = y.shape[-1]
    gated = (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).reshape(
        *y.shape[:-1], groups, width // groups)
    normed = gated * lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (normed.reshape(y.shape) * scale).astype(y.dtype)


def block_tokens(length: int, width: int, itemsize: int,
                 backward: bool) -> int:
    """The tokens of a grid step's block for sequences of ``length`` tokens
    of ``width`` channels: the largest whole number of passes that divides
    ``length`` within the direction's bytes a block; 0 where there is none
    (a length ``_ROWS_A_PASS`` does not divide, a row too wide for one
    pass)."""
    most = (_BWD_BLOCK_BYTES if backward else _FWD_BLOCK_BYTES) // (
        width * itemsize)
    for block in range(min(most, length) // _ROWS_A_PASS * _ROWS_A_PASS, 0,
                       -_ROWS_A_PASS):
        if length % block == 0:
            return block
    return 0


def fits(y, groups: int) -> bool:
    """Whether the kernels take ``y`` [B, T, W] in ``groups`` groups, read
    from the call's shapes alone: a group is a whole number of lane tiles,
    no more than ``_MAX_GROUP_LANES``, and T a whole number of the kernels'
    blocks, each way (no padding path)."""
    if y.ndim != 3 or y.shape[2] % groups:
        return False
    _, length, width = y.shape
    run = width // groups
    return run % _LANES == 0 and run <= _MAX_GROUP_LANES and all(
        block_tokens(length, width, y.dtype.itemsize, backward)
        for backward in (False, True))


def auto_impl(y, groups: int) -> str:
    """What ``impl=None`` runs: the kernels where the shapes ``fits`` them
    and ``y`` is traced where a kernel may run (``mosaic.takes_kernels``);
    the ``jnp`` form elsewhere."""
    return "pallas" if fits(y, groups) and takes_kernels(y) else "jnp"


def needed_bytes(tokens: int, width: int, itemsize: int,
                 backward: bool) -> int:
    """What a pass over ``tokens`` (batch x length) rows of ``width`` has to
    move: ``y`` and ``z`` in and the result out, or ``y``, ``z`` and the
    cotangent in and dy and dz out. The scale and its gradient's partial
    sums are small beside them."""
    return tokens * width * itemsize * (5 if backward else 3)


def _record(y, groups: int, backward: bool, kernel: bool):
    """One ``counters`` record a traced pass (none a step)."""
    tokens, width = math.prod(y.shape[:-1]), y.shape[-1]
    steptrace.record_counters("norm/gated_group", {
        "tokens": tokens, "width": width, "groups": groups,
        "bytes_needed": needed_bytes(tokens, width, y.dtype.itemsize,
                                     backward),
        "backward": int(backward), "kernel": int(kernel)})


def _passes(block: int, body):
    """``body(rows' slice)`` over a block's rows, ``_ROWS_A_PASS`` a pass."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * _ROWS_A_PASS, _ROWS_A_PASS),
                   _ROWS_A_PASS))
        return carry
    lax.fori_loop(0, block // _ROWS_A_PASS, step, 0)


def _group_lanes(width: int, groups: int):
    run = width // groups
    return [slice(j * run, (j + 1) * run) for j in range(groups)]


def _fwd_kernel(y_ref, z_ref, scale_ref, o_ref, *, groups: int, eps: float):
    """One block of a sequence's tokens, (tokens, W) each."""
    block, width = y_ref.shape

    def a_pass(at):
        for lanes in _group_lanes(width, groups):
            z = z_ref[at, lanes].astype(_F32)
            g = y_ref[at, lanes].astype(_F32) * (z * jax.nn.sigmoid(z))
            r = lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            o_ref[at, lanes] = (g * r * scale_ref[:, lanes]).astype(
                o_ref.dtype)

    _passes(block, a_pass)


def _bwd_kernel(y_ref, z_ref, scale_ref, do_ref, dy_ref, dz_ref, dscale_ref,
                sums_ref, *, groups: int, eps: float):
    """The backward of one block of tokens. ``dscale_ref`` (1, W) float32:
    this grid step's share of the scale's gradient, summed over its rows in
    ``sums_ref`` (8, W), eight sublanes of partial sums (whole registers
    added) that meet once, at the step's end."""
    block, width = y_ref.shape
    sums_ref[...] = jnp.zeros(sums_ref.shape, _F32)

    def a_pass(at):
        for lanes in _group_lanes(width, groups):
            y = y_ref[at, lanes].astype(_F32)
            z = z_ref[at, lanes].astype(_F32)
            do = do_ref[at, lanes].astype(_F32)
            gate = jax.nn.sigmoid(z)
            silu = z * gate
            g = y * silu
            r = lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            n = g * r
            weighed = do * n
            sums_ref[:, lanes] += sum(
                weighed[q:q + 8] for q in range(0, _ROWS_A_PASS, 8))
            dn = do * scale_ref[:, lanes]
            dg = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dy_ref[at, lanes] = (dg * silu).astype(dy_ref.dtype)
            dz_ref[at, lanes] = (
                dg * y * (gate * (1.0 + z * (1.0 - gate)))).astype(
                    dz_ref.dtype)

    _passes(block, a_pass)
    dscale_ref[...] = sums_ref[...].sum(axis=0, keepdims=True)


_PARALLEL = ("parallel", "parallel")


def _specs(y, backward: bool):
    """(grid, a (tokens, W) block of a [B, T, W] array, the scale's)."""
    batch, length, width = y.shape
    block = block_tokens(length, width, y.dtype.itemsize, backward)
    assert block, y.shape       # ``fits``: whole blocks of whole passes
    return ((batch, length // block),
            pl.BlockSpec((None, block, width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, width), lambda b, i: (0, 0)))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def group_norm_fwd(y, z, scale, *, groups: int, eps: float,
                   interpret: bool = False):
    """``y``, ``z`` [B, T, W], ``scale`` [W] float32 -> [B, T, W] in ``y``'s
    type, what ``gated_group_rms_norm_jnp`` makes of them. Jitted, as the
    backward is: blocks of one shape share one trace and one lowering."""
    grid, rows, whole = _specs(y, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps), grid=grid,
        in_specs=[rows, rows, whole], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=compiler_params(interpret, _PARALLEL),
        interpret=interpret, name="group_norm_fwd",
    )(y, z, scale.reshape(1, -1))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def group_norm_bwd(y, z, scale, do, *, groups: int, eps: float,
                   interpret: bool = False):
    """-> (dy, dz [B, T, W] in ``y``'s type, dscale [W] float32) of
    ``group_norm_fwd``'s call from ``do``, its result's cotangent."""
    width = y.shape[2]
    grid, rows, whole = _specs(y, True)
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps), grid=grid,
        in_specs=[rows, rows, whole, rows],
        out_specs=[
            rows, rows,
            # a grid step's own sum, added up outside
            pl.BlockSpec((None, None, 1, width), lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((*grid, 1, width), _F32)],
        scratch_shapes=[pltpu.VMEM((8, width), _F32)],
        compiler_params=compiler_params(interpret, _PARALLEL),
        interpret=interpret, name="group_norm_bwd",
    )(y, z, scale.reshape(1, -1), do)
    return dy, dz, dscale.sum((0, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm_kernels(y, z, scale, groups, eps, interpret):
    _record(y, groups, backward=False, kernel=True)
    return group_norm_fwd(y, z, scale, groups=groups, eps=eps,
                          interpret=interpret)


def _norm_kernels_fwd(y, z, scale, groups, eps, interpret):
    return _norm_kernels(y, z, scale, groups, eps, interpret), (y, z, scale)


def _norm_kernels_bwd(groups, eps, interpret, res, do):
    # nothing the size of the result is kept: the statistic is made again
    y, z, scale = res
    _record(y, groups, backward=True, kernel=True)
    return group_norm_bwd(y, z, scale, do, groups=groups, eps=eps,
                          interpret=interpret)


_norm_kernels.defvjp(_norm_kernels_fwd, _norm_kernels_bwd)


def gated_group_rms_norm(y, z, scale, *, groups: int, eps: float,
                         impl: Optional[str] = None):
    """``y * silu(z)`` normed over each of ``groups`` equal runs of the last
    axis and scaled: ``y``, ``z`` [B, T, W] (the twin: any leading axes),
    ``scale`` [W]; the result has ``y``'s dtype, float32 until its one
    rounding. ``impl``: "pallas" | "pallas_interpret" (the kernels
    ``group_norm_fwd`` / ``group_norm_bwd``, one pass over the operands each
    way, where ``fits``; under a mesh's batch axes a batch shard each) |
    "jnp" (``gated_group_rms_norm_jnp`` and jax's gradient of it); None:
    ``auto_impl``. One ``counters`` record ``norm/gated_group`` a traced
    pass says which ran."""
    impl = impl or auto_impl(y, groups)
    if impl == "jnp":
        _record(y, groups, backward=False, kernel=False)
        return gated_group_rms_norm_jnp(y, z, scale, groups=groups, eps=eps)
    assert fits(y, groups), (y.shape, groups)
    norm = per_batch_shard(
        lambda y, z, scale: _norm_kernels(y, z, scale, groups, eps,
                                          impl == "pallas_interpret"),
        y, (True, True, False), "gated_group_rms_norm")
    return norm(y, z, scale.astype(_F32))
