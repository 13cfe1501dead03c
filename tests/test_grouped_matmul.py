"""The grouped-matmul kernels of ``ray_tpu/ops/grouped_matmul.py``,
interpreted on the CPU at small shapes, held to ``jax.lax.ragged_dot`` and
its ``jax.vjp``; and the rules that choose their tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import mosaic

_ROWS, _TILE, _HELD = 256, 32, 4
# groups that end inside a tile, an empty group between two, all rows on one
# group, nothing at all, every row taken with the tiles' edges the groups'
_SIZES = {"ends_inside_a_tile": (40, 7, 70, 30), "an_empty_group": (33, 0, 0, 50),
          "all_on_one": (0, 0, 200, 0), "none": (0, 0, 0, 0),
          "whole_tiles": (64, 64, 64, 64), "small_then_large": (1, 2, 3, 250)}
_WIDTHS = [(128, 128), (192, 232), (232, 192), (232, 128)]


def _operands(k, n, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (_ROWS, k), dtype),
            (jax.random.normal(keys[1], (_HELD, k, n)) * 0.1).astype(dtype),
            jax.random.normal(keys[2], (_ROWS, n), dtype))


def _close(got, want, dtype):
    loose = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else dict(
        rtol=2.0**-7, atol=2.0**-7)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **loose)


@pytest.mark.parametrize("case", list(_SIZES))
@pytest.mark.parametrize("k,n", _WIDTHS)
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["rows", "rows_transposed"])
def test_by_group_is_ragged_dot_and_leaves_the_rest_alone(
        transposed, k, n, case):
    """``rows x w`` and ``rows x w^T`` by group at widths that are and are
    not whole lane tiles: the groups' rows are ``ragged_dot``'s; the result
    starts from a marked buffer, and past the last visited tile the marks
    stand; inside that tile rows past the count are zero. A row outside the
    groups may hold NaN: no row of a group reads it."""
    dtype = jnp.float32
    rows, w, _ = _operands(k, n, dtype, seed=k + n)
    sizes = jnp.asarray(_SIZES[case], jnp.int32)
    total = int(sizes.sum())
    live = (jnp.arange(_ROWS) < total)[:, None]
    want = jax.lax.ragged_dot(jnp.where(live, rows, 0.0), w, sizes)
    got = gm.by_group(
        jnp.where(live, rows, jnp.nan), w.swapaxes(1, 2) if transposed else w,
        sizes, transposed=transposed, tiles=(_TILE, 128),
        into=jnp.full((_ROWS, n), 7.0, dtype), interpret=True)
    assert got.shape == (_ROWS, n) and got.dtype == dtype
    _close(got[:total], want[:total], dtype)
    visited = max(-(-total // _TILE), 1) * _TILE
    assert not np.asarray(got[total:visited]).any()
    assert (np.asarray(got[visited:]) == 7.0).all()


@pytest.mark.parametrize("case", list(_SIZES))
@pytest.mark.parametrize("k,n", _WIDTHS)
def test_per_group_is_ragged_dots_cotangent(k, n, case):
    """``rows^T x d_out`` by group is the ``jax.vjp`` of ``ragged_dot`` in
    its matrices; an expert with no rows gets zeros; rows outside the groups
    may hold NaN in both operands."""
    dtype = jnp.float32
    rows, w, d_out = _operands(k, n, dtype, seed=k - n)
    sizes = jnp.asarray(_SIZES[case], jnp.int32)
    live = (jnp.arange(_ROWS) < sizes.sum())[:, None]
    want = jax.vjp(lambda m: jax.lax.ragged_dot(
        jnp.where(live, rows, 0.0), m, sizes), w)[1](
            jnp.where(live, d_out, 0.0))[0]
    got = gm.per_group(jnp.where(live, rows, jnp.nan),
                       jnp.where(live, d_out, jnp.nan), sizes,
                       tiles=(_TILE, 128, 128), interpret=True)
    assert got.shape == (_HELD, k, n)
    _close(got, want, dtype)
    for group, size in enumerate(_SIZES[case]):
        assert size or not np.asarray(got[group]).any()


@pytest.mark.parametrize("case", ["ends_inside_a_tile", "an_empty_group",
                                  "small_then_large"])
@pytest.mark.parametrize("form", ["rows", "rows_transposed", "matrices"])
def test_a_block_of_columns_goes_in_passes(form, case):
    """A block wider than ``_COLUMNS_A_PASS`` (576 columns: two passes of
    the kernel body's loop and 64 left over) gives what ``ragged_dot`` and
    its cotangent give, NaN in every row outside the groups."""
    k, n, dtype = 192, 2 * gm._COLUMNS_A_PASS + 64, jnp.float32
    rows, w, d_out = _operands(k, n, dtype, seed=11)
    sizes = jnp.asarray(_SIZES[case], jnp.int32)
    total = int(sizes.sum())
    live = (jnp.arange(_ROWS) < total)[:, None]
    marked = jnp.where(live, rows, jnp.nan)
    if form == "matrices":
        want = jax.vjp(lambda m: jax.lax.ragged_dot(
            jnp.where(live, rows, 0.0), m, sizes), w)[1](
                jnp.where(live, d_out, 0.0))[0]
        got = gm.per_group(marked, jnp.where(live, d_out, jnp.nan), sizes,
                           tiles=(_TILE, k, n), interpret=True)
        _close(got, want, dtype)
        return
    want = jax.lax.ragged_dot(jnp.where(live, rows, 0.0), w, sizes)
    got = gm.by_group(marked, w.swapaxes(1, 2) if form != "rows" else w,
                      sizes, transposed=form != "rows", tiles=(_TILE, n),
                      interpret=True)
    _close(got[:total], want[:total], dtype)


@pytest.mark.parametrize("form", ["rows", "rows_transposed", "matrices"])
def test_bfloat16_operands_sum_in_float32_and_round_once(form):
    """bfloat16 rows and matrices: the float32 product of the rounded
    operands, rounded once."""
    k, n = 192, 232
    rows, w, d_out = _operands(k, n, jnp.bfloat16, seed=5)
    sizes = jnp.asarray(_SIZES["ends_inside_a_tile"], jnp.int32)
    total = int(sizes.sum())
    wide = [a.astype(jnp.float32) for a in (rows, w, d_out)]
    if form == "matrices":
        live = (jnp.arange(_ROWS) < total)[:, None]
        want = jax.vjp(lambda m: jax.lax.ragged_dot(wide[0], m, sizes),
                       wide[1])[1](jnp.where(live, wide[2], 0.0))[0]
        got = gm.per_group(rows, d_out, sizes, tiles=(_TILE, 128, 128),
                           interpret=True)
        assert got.dtype == jnp.bfloat16
        # one rounding: the float32 sums' nearest bfloat16, to a last place
        # where the two sums' orders differ
        _close(got, want, jnp.bfloat16)
        return
    want = jax.lax.ragged_dot(wide[0], wide[1], sizes)
    got = gm.by_group(rows, w.swapaxes(1, 2) if form != "rows" else w, sizes,
                      transposed=form != "rows", tiles=(_TILE, 128),
                      interpret=True)
    assert got.dtype == jnp.bfloat16
    _close(got[:total], want[:total], jnp.bfloat16)


def test_the_walk_visits_each_groups_tiles_and_an_empty_group_once():
    sizes = jnp.asarray([40, 0, 70, 30], jnp.int32)
    group, tile, starts, ends, visits = (
        np.asarray(a) for a in gm._visits(sizes, _ROWS, _TILE))
    assert visits.tolist() == [2 + 1 + 3 + 2]
    assert group[:8].tolist() == [0, 0, 1, 2, 2, 2, 3, 3]
    assert tile[:8].tolist() == [0, 1, 1, 1, 2, 3, 3, 4]
    assert starts.tolist() == [0, 40, 40, 110] and ends.tolist() == [
        40, 40, 110, 140]
    assert len(group) == len(tile) == _ROWS // _TILE + _HELD
    # every row taken and the last group empty: its visit is the last tile's
    full = jnp.asarray([100, 156, 0, 0], jnp.int32)
    group, tile, _, _, visits = (
        np.asarray(a) for a in gm._visits(full, _ROWS, _TILE))
    assert tile[:visits[0]].max() == _ROWS // _TILE - 1
    assert group[:visits[0]].tolist() == [0] * 4 + [1] * 5 + [2, 3]


def test_the_tiles_follow_the_shapes(monkeypatch):
    """``tiles_by_group`` / ``tiles_per_group`` on a v5e at the five expert
    cells' shapes (rows of the buffer, d, the way up's columns, the width):
    K whole and every block within the VMEM the calls ask for; none where an
    operand is narrower than a lane tile, the buffer is no whole tiles, or
    the types differ."""
    monkeypatch.setattr(mosaic, "device_kind", lambda: "TPU v5 lite")
    assert (gm._vmem_limit(), gm._block_bytes()) == (64 * 2**20, 48 * 2**20)
    bf16 = jnp.bfloat16

    def spec(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype)

    for length, d, up, width, held in (
            (98304, 2688, 1856, 1856, 8), (163840, 2048, 1024, 512, 32),
            (131072, 2048, 2048, 1024, 8), (131072, 2048, 1536, 768, 16),
            (65536, 2048, 3584, 1792, 8)):
        for k, n in ((d, up), (width, d)):
            for transposed in (False, True):
                w = spec(held, n, k) if transposed else spec(held, k, n)
                tile, cols = gm.tiles_by_group(spec(length, k), w, transposed)
                assert length % tile == 0 and (cols == n or cols % 128 == 0)
                assert 2 * 2 * (tile * k + k * cols + tile * cols) + (
                    4 * tile * cols) <= gm._block_bytes()
            tile, block_k, block_n = gm.tiles_per_group(
                spec(length, k), spec(length, n))
            assert length % tile == 0
            assert block_k == k or block_k % 128 == 0
            assert block_n == n or block_n % 128 == 0
            assert (4 + 2 * 2) * block_k * block_n <= gm._block_bytes()
    # the readings' choices (benches/grouped_matmul.py): every column in
    # one block where VMEM holds it, nemotron's odd widths whole; else equal
    # blocks that cover the columns with none to spare (1,024 x 2, not
    # 768 x 3 of 2,048)
    assert gm.tiles_by_group(spec(98304, 2688), spec(8, 2688, 1856)) == (
        256, 1856)
    assert gm.tiles_by_group(spec(65536, 2048), spec(8, 2048, 3584)) == (
        256, 3584)
    assert gm.tiles_per_group(spec(98304, 2688), spec(98304, 1856)) == (
        256, 2688, 1856)
    assert gm.tiles_per_group(spec(65536, 2048), spec(65536, 3584)) == (
        256, 1024, 3584)
    assert gm._column_blocks(2048, lambda block: block <= 900) == 512
    assert gm._column_blocks(1856, lambda block: block <= 1000) == 640
    assert gm._column_blocks(1856, lambda block: block < 128) is None
    rows = spec(1024, 256)
    assert gm.tiles_by_group(rows, spec(4, 256, 64)) is None
    assert gm.tiles_by_group(spec(1024, 64), spec(4, 64, 256)) is None
    assert gm.tiles_by_group(spec(1000, 256), spec(4, 256, 256)) is None
    assert gm.tiles_by_group(rows, spec(4, 256, 256, dtype=jnp.float32)) is None
    assert gm.tiles_by_group(spec(1024, 256, dtype=jnp.float16),
                             spec(4, 256, 256, dtype=jnp.float16)) is None
    assert gm.tiles_per_group(rows, spec(1024, 64)) is None
    assert gm.tiles_per_group(rows, spec(1024, 256)) is not None
    assert gm.tiles_by_group(rows, spec(4, 256, 232)) is not None


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU7x"])
def test_a_kind_of_device_nobody_read_finds_no_tiles(kind, monkeypatch):
    """The blocks are sized for the VMEM of a kind the bench was read on
    (``mosaic.vmem_bytes``); on any other the rules find no tiles, at shapes
    a v5e takes, so the caller keeps ``ragged_dot`` where a limit the core
    may not have would fail the compilation."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    monkeypatch.setattr(mosaic, "device_kind", lambda: kind)
    assert mosaic.vmem_bytes() == 0
    for transposed in (False, True):
        assert gm.tiles_by_group(spec(1024, 256), spec(4, 256, 256),
                                 transposed) is None
    assert gm.tiles_per_group(spec(1024, 256), spec(1024, 256)) is None
    monkeypatch.setattr(mosaic, "device_kind", lambda: "TPU v5 lite")
    assert gm.tiles_by_group(spec(1024, 256), spec(4, 256, 256)) == (256, 256)
    assert gm.tiles_per_group(spec(1024, 256), spec(1024, 256)) == (
        256, 256, 256)
