"""Grouped matmuls over the row ranges a sort by group leaves: Pallas kernels
for the three products a layer of held experts needs (``ops.moe``), each over
rows ``[R, K]`` of which the first ``sizes.sum()`` belong, range after range,
to the ``held`` groups of ``sizes``:

- ``by_group(rows, w, sizes)``: ``rows [R, K] x w [held, K, N] -> [R, N]``,
  each row by its group's matrix;
- ``by_group(rows, w, sizes, transposed=True)``: the same against ``w [held,
  N, K]`` transposed, read in place;
- ``per_group(rows, d_out, sizes)``: ``rows^T [K, R] x d_out [R, N] -> [held,
  K, N]``, each group's rows against its own (an empty group's is zero).

bfloat16 or float32 operands, float32 sums, one rounding at the store: what
``jax.lax.ragged_dot`` and its cotangents give. A kernel's grid walks the
VISITS, the (group, tile of rows) pairs in which a group has rows (an empty
group has one, on the tile where it would begin), and stops after the last:
the cost follows the rows present, not R. A tile that two groups share is
visited once for each and masked by rows. ``by_group``'s result past the last
visited tile is UNWRITTEN, as the TPU's ``ragged_dot`` leaves it; inside that
tile, rows past the last group's are zero.

The tiles are the shapes' (``tiles_by_group``, ``tiles_per_group``), in the
VMEM the device's kind is known to have (``mosaic.vmem_bytes``: a v5e's; the
blocks are wider than the compiler's own 16 MiB takes): where they find none
(operands ``_taken`` refuses, a buffer that is no whole tiles, a kind of
device nobody read the kernels on) the caller keeps ``ragged_dot``.
``benches/grouped_matmul.py`` holds the readings they rest on, and those
of jax's own ``megablox`` (``gmm`` / ``tgmm``) beside them at the five expert
cells' shapes: the same answers, 7-43% behind in most calls at the best of its
tilings (0.3-1.0% of a step), with no rule that picks one.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.mosaic import compiler_params, vmem_bytes

_ROWS_A_TILE = 256
# The columns ``by_group``'s body multiplies at a time: it walks its block of
# columns in a loop of such passes, so that the text Mosaic compiles is one
# pass's and not the block's, unrolled. A block of 2,048 columns in one
# product compiled 0.5-0.9 s a kernel where XLA's ``ragged-dot`` takes 0.2-0.4
# (for a described v5e, on a CPU), and the benchmark's state-making program,
# which no cache holds, compiles a layer's two forward kernels on every start:
# the qwen cell's warm set-up rose 6-9%. Passes of 512 compile in 0.3-0.5 s
# and cost 2-7% of a call on the chip, passes of 256 4-15%
# (``benches/grouped_matmul.py``). ``per_group`` is in no program but the
# step, which the cache holds, and lost 3-12% to passes of 512: it multiplies
# its blocks whole.
_COLUMNS_A_PASS = 512


def _vmem_limit() -> int:
    """What a call asks of VMEM: half of what a core of this device has
    (``mosaic.vmem_bytes``: 64 of the v5e's 128 MiB, where the compiler's
    own limit is 16 and every reading of the bench was made); 0 on a kind
    of device nobody read these kernels on."""
    return vmem_bytes() // 2


def _block_bytes() -> int:
    """What the tiles may fill of ``_vmem_limit()``, the blocks two of each
    and the float32 product or sum: three quarters (48 MiB on a v5e), the
    rest being the compiler's. Where it is 0 no block fits and ``tiles_*``
    find none: the caller keeps ``ragged_dot``."""
    return _vmem_limit() * 3 // 4


def _visits(sizes, rows: int, tile: int):
    """The walk of a grouped matmul over ``rows`` rows in tiles of ``tile``:
    (group, tile) of each visit in order, padded to the most there can be
    (tiles + held), each group's first row and the row after its last, and
    how many visits there are, as a vector of one."""
    held, tiles = sizes.shape[0], rows // tile
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tile - first, 0) + 1
    upto = jnp.cumsum(count)
    visit = jnp.arange(tiles + held, dtype=jnp.int32)
    group = jnp.minimum((visit[:, None] >= upto).sum(axis=1), held - 1)
    at = jnp.minimum(first[group] + visit - (upto - count)[group], tiles - 1)
    return (group.astype(jnp.int32), at.astype(jnp.int32),
            starts.astype(jnp.int32), ends.astype(jnp.int32),
            upto[-1:].astype(jnp.int32))


def _inside(tile_index, tile: int, start, end):
    """(tile, 1) bool: which rows of the tile lie in [start, end)."""
    row = tile_index * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), 0)
    return (row >= start) & (row < end)


def _exact(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _in_passes(cols: int, one_pass):
    """``one_pass(first column, how many)`` over ``cols`` columns: a loop of
    passes ``_COLUMNS_A_PASS`` wide, then what is left by itself."""
    whole = cols // _COLUMNS_A_PASS

    def nth(j, carry):
        one_pass(pl.multiple_of(j * _COLUMNS_A_PASS, _COLUMNS_A_PASS),
                 _COLUMNS_A_PASS)
        return carry

    if whole:
        jax.lax.fori_loop(0, whole, nth, 0)
    if cols % _COLUMNS_A_PASS:
        one_pass(whole * _COLUMNS_A_PASS, cols % _COLUMNS_A_PASS)


def _by_group_kernel(group_of, tile_of, starts, ends, _, rows_ref, w_ref,
                     *refs, transposed: bool):
    """One visit of ``by_group``: the tile's rows times the group's block of
    columns, stored where the rows are the group's. The first visit of a
    tile starts its block from zeros; a later one (the next group's) finds
    the block as the last left it: the visits of a tile follow each other,
    so the block has not left VMEM. (``refs``: the result's block, after
    the array it starts from where the caller gave one.)"""
    out_ref = refs[-1]
    visit = pl.program_id(1)
    group, at = group_of[visit], tile_of[visit]
    start, end = starts[group], ends[group]

    @pl.when((visit == 0) | (tile_of[jnp.maximum(visit - 1, 0)] != at))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(end > start)
    def _():
        inside = _inside(at, out_ref.shape[0], start, end)

        def one_pass(first, cols):
            at_cols = pl.ds(first, cols)
            product = jax.lax.dot_general(
                rows_ref[...],
                w_ref[0, at_cols, :] if transposed else w_ref[0, :, at_cols],
                (((1,), (1 if transposed else 0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_exact(rows_ref.dtype)).astype(out_ref.dtype)
            out_ref[:, at_cols] = jnp.where(inside, product,
                                            out_ref[:, at_cols])

        _in_passes(out_ref.shape[1], one_pass)


def by_group(rows, w, sizes, *, transposed: bool = False,
             tiles: Tuple[int, int], into=None, interpret: bool = False):
    """``rows [R, K] x w [held, K, N]`` (``transposed``: ``w [held, N, K]``)
    -> ``[R, N]`` by group, in ``tiles`` = (rows a tile, columns a block) as
    ``tiles_by_group`` chooses them. ``into``: the array the result starts
    from (a test's marked buffer); None: nobody's."""
    (length, k), (tile, cols) = rows.shape, tiles
    held, n = w.shape[0], w.shape[1 if transposed else 2]
    assert length % tile == 0 and w.shape[2 if transposed else 1] == k
    if transposed:
        w_spec = pl.BlockSpec((1, cols, k), lambda j, v, g, t, *_: (g[v], j, 0))
    else:
        w_spec = pl.BlockSpec((1, k, cols), lambda j, v, g, t, *_: (g[v], 0, j))
    walk = _visits(sizes, length, tile)
    start_from = () if into is None else (into,)
    return pl.pallas_call(
        functools.partial(_by_group_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk),
            grid=(pl.cdiv(n, cols), walk[-1][0]),
            in_specs=[pl.BlockSpec((tile, k), lambda j, v, g, t, *_: (t[v], 0)),
                      w_spec] + [pl.BlockSpec(memory_space=pl.ANY)
                                 for _ in start_from],
            out_specs=pl.BlockSpec((tile, cols),
                                   lambda j, v, g, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((length, n), rows.dtype),
        input_output_aliases={len(walk) + 2: 0} if start_from else {},
        compiler_params=compiler_params(
            interpret, ("parallel", "arbitrary"), _vmem_limit() or None),
        interpret=interpret,
        name="grouped_matmul_" + ("rows_t" if transposed else "rows"),
    )(*walk, rows, w, *start_from)


def _per_group_kernel(group_of, tile_of, starts, ends, visits, rows_ref,
                      d_out_ref, out_ref, sum_ref):
    """One visit of ``per_group``: the group's rows of the tile, transposed,
    times its rows of ``d_out``, summed in float32 over the group's visits,
    which follow each other, and rounded into the result's block with the
    last."""
    visit = pl.program_id(2)
    group, at = group_of[visit], tile_of[visit]
    start, end = starts[group], ends[group]
    last = group_of.shape[0] - 1

    @pl.when((visit == 0) | (group_of[jnp.maximum(visit - 1, 0)] != group))
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(end > start)
    def _():
        # both operands masked: what a row outside holds (the next group's,
        # nobody's) may be NaN, and 0 x NaN is NaN
        inside = _inside(at, rows_ref.shape[0], start, end)
        rows, d_out = rows_ref[...], d_out_ref[...]
        sum_ref[...] += jax.lax.dot_general(
            jnp.where(inside, rows, jnp.zeros_like(rows)),
            jnp.where(inside, d_out, jnp.zeros_like(d_out)),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_exact(rows.dtype))

    @pl.when((visit == visits[0] - 1)
             | (group_of[jnp.minimum(visit + 1, last)] != group))
    def _():
        out_ref[0] = sum_ref[...].astype(out_ref.dtype)


def per_group(rows, d_out, sizes, *, tiles: Tuple[int, int, int],
              interpret: bool = False):
    """``rows^T [K, R] x d_out [R, N] -> [held, K, N]`` by group, of the
    rows' type, in ``tiles`` = (rows a tile, K's block, N's block) as
    ``tiles_per_group`` chooses them."""
    (length, k), n, held = rows.shape, d_out.shape[1], sizes.shape[0]
    tile, block_k, block_n = tiles
    assert length % tile == 0 and d_out.shape[0] == length
    walk = _visits(sizes, length, tile)
    return pl.pallas_call(
        _per_group_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk),
            grid=(pl.cdiv(k, block_k), pl.cdiv(n, block_n), walk[-1][0]),
            in_specs=[pl.BlockSpec((tile, block_k),
                                   lambda i, j, v, g, t, *_: (t[v], i)),
                      pl.BlockSpec((tile, block_n),
                                   lambda i, j, v, g, t, *_: (t[v], j))],
            out_specs=pl.BlockSpec((1, block_k, block_n),
                                   lambda i, j, v, g, t, *_: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((held, k, n), rows.dtype),
        compiler_params=compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), _vmem_limit() or None),
        interpret=interpret, name="grouped_matmul_matrices",
    )(*walk, rows, d_out)


def _column_blocks(n: int, fits):
    """A block of ``n`` columns that ``fits(block)``: all of them, else the
    whole lane tiles that cover them in 2, 3, ... equal blocks with the
    fewest columns past ``n`` (the last block's, the compiler's to mask, and
    arithmetic for nothing), of those the widest; None where not even one
    lane tile fits."""
    blocks = [n] + [-(-n // (parts * 128)) * 128
                    for parts in range(2, -(-n // 128) + 1)]
    return min((block for block in blocks if fits(block)),
               key=lambda block: (-(-n // block) * block, -block),
               default=None)


def _taken(*arrays) -> bool:
    """Operands a kernel takes: of one type, bfloat16 or float32, and none
    narrower than a lane tile (nobody measured such a layer on a chip)."""
    return (all(a.dtype == arrays[0].dtype for a in arrays)
            and arrays[0].dtype in (jnp.bfloat16, jnp.float32)
            and min(min(a.shape[-2:]) for a in arrays) >= 128)


def tiles_by_group(rows, w, transposed: bool = False
                   ) -> Optional[Tuple[int, int]]:
    """(rows a tile, columns a block) of ``by_group(rows, w, ...)``, or None
    where it does not run: operands ``_taken`` refuses, a buffer that is
    not whole tiles. K goes in whole (a block of the rows is (tile, K), of
    a matrix (K, columns)): no sum over blocks of K, no scratch, and K may
    be any width. Columns in one block where they fit: the rows are read
    once a block of columns."""
    (length, k), size = rows.shape, rows.dtype.itemsize
    n = w.shape[1 if transposed else 2]
    tile = _ROWS_A_TILE
    if not _taken(rows, w) or length % tile:
        return None
    cols = _column_blocks(n, lambda cols: (
        2 * size * (tile * k + k * cols + tile * cols) + 4 * tile * cols
        <= _block_bytes()))
    return cols and (tile, cols)


def tiles_per_group(rows, d_out) -> Optional[Tuple[int, int, int]]:
    """(rows a tile, K's block, N's block) of ``per_group(rows, d_out,
    ...)``, or None where it does not run (``tiles_by_group``'s reasons).
    The float32 sum of a (K's block, N's block) stays in VMEM over a
    group's visits beside the result's two blocks; ``rows`` is read once a
    block of N and ``d_out`` once a block of K."""
    (length, k), n, size = rows.shape, d_out.shape[1], rows.dtype.itemsize
    tile = _ROWS_A_TILE
    if not _taken(rows, d_out) or length % tile:
        return None

    def fits(block_k, block_n):
        return (2 * size * tile * (block_k + block_n)
                + (4 + 2 * size) * block_k * block_n <= _block_bytes())

    block_n = _column_blocks(n, lambda block: fits(128, block))
    block_k = block_n and _column_blocks(k, lambda block: fits(block, block_n))
    return block_k and (tile, block_k, block_n)
