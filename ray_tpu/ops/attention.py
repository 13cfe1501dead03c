"""Attention ops: Pallas TPU flash-attention kernel + chunked JAX fallback.

TPU-native replacement for the attention math the reference delegates to
torch/CUDA ecosystems (ray SURVEY §5: sequence-parallel/long-context paths
are absent in-repo and arrive via external stacks run on Ray). Here they are
first-class ops:

- ``flash_attention``: O(seq) memory online-softmax attention. On TPU it runs
  a Pallas kernel tiled for the MXU (tiles of queries x keys, accumulators
  in VMEM, tile sizes chosen from the sequence lengths); elsewhere it runs a
  numerically identical ``lax.scan`` formulation, so tests validate the
  same math on CPU.
- ``attention_reference``: naive full-matrix attention for numerics tests.

All paths are differentiable: the fallback natively, the Pallas path via
custom VJP (one backward kernel that recomputes the probabilities from
q, k and the saved logsumexp).
"""

from __future__ import annotations

import collections
import functools
import itertools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu.parallel.mesh_utils import traced_mesh_axes

NEG_INF = -1e30


def _per_query_head(q, kv):
    """``kv`` with each key-value head repeated for the query heads that
    read it: query head j reads head j // group, on the heads' axis, which
    is the one before the sequence's (batch x heads where they are folded).
    ``kv`` itself where the heads are as many."""
    group, rest = divmod(q.shape[-3], kv.shape[-3])
    assert group >= 1 and not rest, (q.shape, kv.shape)
    return kv if group == 1 else jnp.repeat(kv, group, axis=-3)


def _seen(qi, ki, window: Optional[int]):
    """Whether, under a causal mask, the query at key position ``qi`` sees
    the key at ``ki``: none past itself, and under a ``window`` only the
    last ``window`` keys, its own position among them."""
    return (qi >= ki) if window is None else (qi >= ki) & (qi - ki < window)


def attention_reference(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Naive softmax(QK^T)V. Shapes: (..., h, s, d); ``k`` and ``v`` may
    have fewer heads, each read by a group of query heads. Under ``window``
    a query sees its own position and the ``window - 1`` before it."""
    sm_scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    assert causal or window is None, "a window is a causal mask's"
    k, v = _per_query_head(q, k), _per_query_head(q, v)
    s = jnp.einsum("...qd,...kd->...qk", q, k) * sm_scale
    if causal:
        q_len, k_len = s.shape[-2], s.shape[-1]
        qi = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        ki = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(_seen(qi + (k_len - q_len), ki, window), s,
                      NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v).astype(q.dtype)


# ----------------------------------------------------------------------
# online-softmax block update (shared by fallback + ring attention)
# ----------------------------------------------------------------------

def online_block_update(q, k, v, m, l, acc, *, sm_scale: float,
                        q_offset=0, k_offset=0, causal: bool = False,
                        k_total: Optional[int] = None,
                        window: Optional[int] = None):
    """Fold one KV block into flash accumulators.

    q: (..., bq, d); k/v: (..., bk, d); m,l: (..., bq); acc: (..., bq, d).
    Offsets are the blocks' global sequence positions (for causal masks in
    blockwise/ring execution). ``k_total`` masks padding columns whose
    global position is past the true sequence end; under ``window`` a query
    sees the last ``window`` keys up to its own position.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * sm_scale
    bq, bk = s.shape[-2], s.shape[-1]
    qi = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    ki = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_offset
    if causal:
        s = jnp.where(_seen(qi, ki, window), s, NEG_INF)
    if k_total is not None:
        s = jnp.where(ki < k_total, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard: fully-masked rows keep m at -inf; exp(s - (-inf)) must not NaN
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(m_new)[..., None], p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def finalize_flash(m, l, acc, dtype):
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(dtype)


# ----------------------------------------------------------------------
# chunked JAX fallback (CPU / any backend; differentiable)
# ----------------------------------------------------------------------

def _flash_scan(q, k, v, *, causal: bool, sm_scale: float, block_k: int,
                window: Optional[int] = None):
    k, v = _per_query_head(q, k), _per_query_head(q, v)
    *lead, q_len, d = q.shape
    d_v = v.shape[-1]
    k_len = k.shape[-2]
    block_k = min(block_k, k_len)
    nk = -(-k_len // block_k)
    pad = nk * block_k - k_len
    if pad:
        kp = jnp.pad(k, [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)])
        vp = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, pad), (0, 0)])
    else:
        kp, vp = k, v
    kb = kp.reshape(*lead, nk, block_k, d)
    vb = vp.reshape(*lead, nk, block_k, d_v)

    m0 = jnp.full((*lead, q_len), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((*lead, q_len), jnp.float32)
    a0 = jnp.zeros((*lead, q_len, d_v), jnp.float32)

    def body(carry, ib):
        m, l, acc = carry
        kk, vv, i = ib
        m2, l2, a2 = online_block_update(
            q, kk, vv, m, l, acc, sm_scale=sm_scale,
            q_offset=k_len - q_len, k_offset=i * block_k, causal=causal,
            k_total=k_len if pad else None, window=window,
        )
        return (m2, l2, a2), None

    # move block axis to front for scan
    kb_t = jnp.moveaxis(kb, -3, 0)
    vb_t = jnp.moveaxis(vb, -3, 0)
    idx = jnp.arange(nk)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), (kb_t, vb_t, idx))
    return finalize_flash(m, l, acc, q.dtype)


# ----------------------------------------------------------------------
# Pallas TPU kernels
# ----------------------------------------------------------------------
#
# Scores are computed transposed, s^T = K Q^T of shape (keys, queries): the
# queries lie along the 128 lanes. The softmax statistics (running max, sum,
# lse, delta) are then row vectors (1, queries) that broadcast along
# sublanes, the accumulators are (d, queries) and fill whole registers at
# d = 64, and every matmul is NN or NT on operands as they arrive — given
# V^T (forward) and K^T (backward), and with O^T and dQ^T turned back.
# Operands go to the MXU in the inputs' dtype and accumulate in float32; the
# statistics and accumulators are float32.
#
# Who turns them is the call's boundary, chosen at trace time from its shapes
# (``heads_a_lane_tile``; one ``attention/boundary`` record a traced call of
# ``causal_self_attention`` says which). Where a head is one block of keys
# and its width divides the 128 lanes (every GPT-2 call: 1024 tokens, heads
# of 64), the kernels take (T, 128) blocks of the model's own [B, T, H x d]
# arrays, two 64-wide heads a grid step, and turn V, K, dO and O once a step
# in VMEM, O^T and dQ^T once back (``_fwd_kernel_lanes``,
# ``_bwd_kernel_lanes``): XLA copies nothing round them. Elsewhere (several
# blocks of keys a head, grouped key-value heads, keys and values of two
# widths, ``flash_attention``'s (b, h, s, d) entry) the operands are
# [B x H, T, d] and XLA makes V^T and K^T and turns O^T and dQ^T back, in
# HBM, through arrays whose 64-wide rows are padded to the lanes: in GPT-2's
# step that was 84 copies, 12 asynchronous copies and 48 asynchronous slices
# of head-shaped arrays, 0.64 ms a layer beside the kernels' 1.45 (PERF.md
# section 6, PR 51).
#
# One grid step holds up to ``_MAX_RESIDENT`` queries and as many keys (a
# whole head at GPT-2's 1024) and computes on tiles of ``block_q`` queries
# by ``block_k`` keys. Under a causal mask a tile row ends at the diagonal
# and only the tiles the diagonal crosses are masked. Where one grid step
# holds the whole sequence, which tiles are live is known when the kernel
# is traced and the walk over them is straight-line code. Where a head is
# several grid blocks, a block's place in the grid says what the mask leaves
# of it (``_grid_kinds``): whole, on the diagonal or dead. The kernel
# branches on the place and walks each kind with constant bounds
# (``_walk_by_kind``): a diagonal block as a lone block is walked, a whole
# block's rows as one loop whose body is a row's tiles in straight-line
# code (``_walk_rows``), a dead block not at all (in the forward it still
# owes the scratch's start and the outputs' write; in the backward nothing:
# dQ^T is summed over a head's blocks of keys by the kernel itself, in HBM,
# by the live steps alone, ``_bwd_kernel``), and the index maps clamp dead
# blocks to the last live one, which the pipeline does not fetch again. Only
# a masked call that is no self-attention in square blocks (lengths that
# differ, ``res_q != res_k``) keeps loops with bounds computed from the grid
# position.
#
# Who reaches which walk, and what a loop costs (my chip runs, PRs 25 and
# 38; PERF.md section 6). One block a head is every call up to 2048 tokens,
# GPT-2's 1024 in four benchmark cells among them. Several blocks are any
# longer sequence: the cell ``joyai-llm-flash.step-8k`` sends 8192 (4 x 4
# blocks a head: 6 whole, 4 diagonal, 6 dead) and ``attention="auto"`` takes
# 3072, 4096 and 8192 (3.3x to 47x faster than XLA's attention there,
# forward plus backward). A loop whose trip count the compiler does not
# know is neither unrolled nor scheduled across: with the loop alone,
# forward plus backward at 1024 take 17% longer (2.53 against 2.16 ms a
# layer; ``fori_loop(..., unroll=True)`` on static bounds reads as the
# Python loop does, 2.165). At 8192, 64 heads, keys 192 and values 128 wide,
# a call's kernel time by walk (forward, backward; needed at the MXU's peak
# 6.98 and 18.14 ms): every block in loops with traced bounds 15.48 and
# 28.72 ms; by kind with a whole block's rows one loop 13.22 and 23.62; two
# or four rows a loop step 23.42 and 23.32; a row's loop over its tiles, 1 /
# 2 / 4 tiles a step 26.76 / 25.11 / 24.32; every tile of a whole block
# written out 13.20 and 44.73 (64 tile bodies of five matmuls on 256 lanes:
# at keys 128 wide the same code reads 16.38 against 17.40 for the rows'
# loop, and compiles in 10.8 s against 6.1).

_MAX_RESIDENT = 2048
# (block_q, block_k) targets: the fastest measured for each kernel alone on
# a v5e at (192, 1024, 64) bf16 causal (PERF.md, PR 25). Both kernels sit
# near what the MXU allows at d = 64, half of whose depth a pass fills; the
# backward's five matmuls pay more for the dead half of a diagonal tile
# than for the loop steps that smaller tiles add.
_FWD_TILES, _BWD_TILES = (512, 512), (256, 256)
# The most bytes of a block of queries' float32 dQ^T sum that one DMA moves,
# in a backward step over several blocks of keys (``_bwd_kernel``: a copy is
# whole rows of tiles, at least one). Read on the chip (PERF.md section 6,
# PR 50; ``flash_bwd`` alone at the three cells' shapes, ms, by rows of 256
# queries a copy: 1 / 2 / 4 / all 8; the parent's, which wrote partials,
# last): keys 192 wide at 8,192 tokens (a row 192 KiB) 23.49 / 23.48 / 23.71
# / 24.48, parent 23.62; 128 wide at 16,384 under a window of 2,048 (a row
# 128 KiB) 9.65 / 9.23 / 9.10 / 9.27, parent 8.92, and with no window 33.91
# / 33.59 / 33.51 / 34.20, parent 34.15; 64 wide (a row 64 KiB) 19.51 /
# 19.32 / 19.26 / 19.23, parent 19.28. A copy costs its start and its wait
# (about 30 ns each: 32 of them a live step are 1 us), and a large one
# stands in the way of the pipeline's own.
_COPY_BYTES = 512 * 1024
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scale_folds(dtype, sm_scale: float) -> bool:
    """Whether q * sm_scale is exact enough to take the place of scaling
    every score: always in float32, in a narrower dtype only for a power
    of two (d = 64: 1/8)."""
    return dtype == jnp.float32 or math.frexp(sm_scale)[0] == 0.5


def _clamp(x, hi: int):
    """x held to [0, hi]: a Python int stays one."""
    if isinstance(x, int):
        return min(max(x, 0), hi)
    return jnp.clip(x, 0, hi)


def _live_tiles(rel, block_q: int, block_k: int, n_tiles: int, causal: bool):
    """(n_full, n_live): of the resident keys' ``n_tiles`` tiles, the first
    n_full are seen whole by every query of a q tile and those up to n_live
    by some. ``rel`` is the q tile's first row less the first resident key,
    both in key positions (a Python int where the grid does not move it)."""
    if not causal:
        return n_tiles, n_tiles
    return (_clamp((rel + 1) // block_k, n_tiles),
            _clamp((rel + block_q - 1 + block_k) // block_k, n_tiles))


def _window_tiles(rel, block_q: int, block_k: int, n_tiles: int, window: int):
    """(n_start, n_clear) under a window of ``window`` keys, as
    ``_live_tiles`` counts for the causal edge: the tiles before n_start lie
    behind the window for every query of the q tile, those from n_clear on
    inside it for every query; the window's far edge crosses the ones
    between."""
    return (_clamp((rel + 1 - window) // block_k, n_tiles),
            _clamp((rel + block_q - 1 - window + block_k) // block_k, n_tiles))


def _tile(c, size: int, n_tiles: int):
    """Slice of tile ``c`` among ``n_tiles`` of ``size``; static where
    ``c`` is, and for a lone tile, whose size need not fill a hardware tile."""
    if n_tiles == 1:
        c = 0
    if isinstance(c, int):
        return pl.ds(c * size, size)
    return pl.ds(pl.multiple_of(c * size, size), size)


def _scaled(q, sm_scale: float, fold: bool):
    return (q.astype(jnp.float32) * sm_scale).astype(q.dtype) if fold else q


# What ``step`` is told of a tile, by (the diagonal crosses it, the window's
# far edge crosses it): False, no mask; True, the causal edge alone, which
# is every masked tile of a call without a window
_EDGES = {(False, False): False, (True, False): True,
          (False, True): "window", (True, True): "both"}


def _scores(k, q, c, *, sm_scale: float, fold: bool, masked,
            block_k: int, rel, window: Optional[int] = None):
    """s^T (block_k, block_q) of key tile ``c``, keys along sublanes: scaled
    here unless q came scaled, and masked (``_EDGES``) where the diagonal
    or the window's far edge crosses."""
    s = _dot(k, q, _NT)
    if not fold:
        s = s * sm_scale
    if masked:
        # query position less key position, within the tile and then overall
        ahead = (lax.broadcasted_iota(jnp.int32, s.shape, 1)
                 - lax.broadcasted_iota(jnp.int32, s.shape, 0))
        edge = c * block_k - rel
        seen = None if masked == "window" else ahead >= edge
        if masked in ("window", "both"):
            near = ahead < edge + window
            seen = near if seen is None else seen & near
        s = jnp.where(seen, s, NEG_INF)
    return s


def _walk(step, carry, n_full, n_live, n_start=None, n_clear=None):
    """Fold ``step(c, carry, masked)`` over the live key tiles: unmasked up
    to n_full, masked from there to n_live. Static bounds unroll. Under a
    window (``_window_tiles``) the live tiles start at n_start and those
    before n_clear are masked by its edge too: told apart tile by tile
    where the bounds are static, else every masked tile gets both edges."""
    static = isinstance(n_full, int) and isinstance(n_live, int)
    if n_start is None:
        if static:
            for c in range(n_live):
                carry = step(c, carry, c >= n_full)
            return carry
        carry = lax.fori_loop(0, n_full, lambda c, x: step(c, x, False),
                              carry)
        return lax.fori_loop(n_full, n_live, lambda c, x: step(c, x, True),
                             carry)
    if static and isinstance(n_start, int) and isinstance(n_clear, int):
        for c in range(n_start, n_live):
            carry = step(c, carry, _EDGES[c >= n_full, c < n_clear])
        return carry
    clear = jnp.clip(n_clear, n_start, n_live)
    full = jnp.clip(n_full, clear, n_live)
    edged = lambda c, x: step(c, x, "both")
    carry = lax.fori_loop(n_start, clear, edged, carry)
    carry = lax.fori_loop(clear, full, lambda c, x: step(c, x, False), carry)
    return lax.fori_loop(full, n_live, edged, carry)


def _rows(rel0, n_q: int, n_k: int, block_q: int, block_k: int, causal: bool,
          alike_loop: bool, window: Optional[int] = None,
          longest_first: bool = False):
    """(bounds, order) of a grid block's ``n_q`` rows of tiles: row ``j``'s
    bounds from ``_live_tiles``, under a window with those of
    ``_window_tiles`` after them, and the order in which ``_walk_rows``
    walks the rows: None for one loop over ``j``, which with ``alike_loop``
    rows are whose bounds are constants, the same for all and leave no tile
    masked (a whole grid block's, a dead one's); else their indices, with
    ``longest_first`` from the last row up where bounds are constants and
    leave the last row more live tiles than the first (a diagonal
    block's)."""
    def bounds_of(j):
        rel = rel0 + j * block_q
        live = _live_tiles(rel, block_q, block_k, n_k, causal)
        if window is None:
            return live
        return live + _window_tiles(rel, block_q, block_k, n_k, window)

    bounds = [bounds_of(j) for j in range(n_q)]
    n_full, n_live = bounds[0][:2]
    if (alike_loop and isinstance(n_full, int) and n_full == n_live
            and bounds[0][2:] in ((), (0, 0))
            and bounds.count(bounds[0]) == n_q > 1):
        return bounds, None
    # live tiles of a row: n_live, less n_start under a window
    tiles = lambda b: b[1] - sum(b[2:3])
    if (longest_first and all(isinstance(n, int) for b in bounds for n in b)
            and tiles(bounds[-1]) > tiles(bounds[0])):
        return bounds, range(n_q - 1, -1, -1)
    return bounds, range(n_q)


def _walk_rows(row, rel0, n_q: int, n_k: int, block_q: int, block_k: int,
               causal: bool, alike_loop: bool, window: Optional[int] = None,
               longest_first: bool = False):
    """``row(j, n_full, n_live)`` for each of a grid block's ``n_q`` rows of
    tiles, under a window ``row(j, n_full, n_live, n_start, n_clear)``, in
    the order ``_rows`` gives: rows alike as one loop over ``j``, a row a
    step, so that the row's tiles stay straight-line code and the kernel's
    size stays a row's."""
    bounds, order = _rows(rel0, n_q, n_k, block_q, block_k, causal,
                          alike_loop, window, longest_first)
    if order is None:
        lax.fori_loop(0, len(bounds), lambda j, _: row(j, *bounds[0]), None)
        return
    for j in order:
        row(j, *bounds[j])


_KINDS = ("whole", "diagonal", "trailing", "dead", "looped")


def _kinds_told_apart(nq: int, nk: int, res_q: int, res_k: int, offset: int,
                      causal: bool, window: Optional[int]) -> bool:
    """Whether a grid block's place says what the mask leaves of it
    (``_grid_kinds``)."""
    if not causal or nq == nk == 1:
        return True
    return (not offset and res_q == res_k
            and (window is None or window % res_k == 0))


def _grid_kinds(nq: int, nk: int, res_q: int, res_k: int, offset: int,
                causal: bool, window: Optional[int] = None) -> dict:
    """{kind: grid blocks of it a head}, every kind of ``_KINDS`` in their
    order: what the mask leaves of a block whose first query stands
    ``rel0`` key positions past its first key is "whole" (every query sees
    every key), "dead" (none sees any), "diagonal" (the causal edge crosses
    it) or, under a window, "trailing" (the window's far edge crosses it:
    the diagonal's complementary triangle). Kinds are told apart where the
    grid is one block, which is no variable of the grid whatever its offset
    (and is called diagonal whichever edges cross it), or self-attention in
    square blocks (equal lengths, ``res_q == res_k``: every training call)
    under a window of whole blocks or none, where an edge runs through a
    block from corner to corner or not at all; under a mask any other grid,
    which no model here sends and no chip run has measured, is "looped"
    throughout."""
    if not _kinds_told_apart(nq, nk, res_q, res_k, offset, causal, window):
        return dict.fromkeys(_KINDS, 0) | {"looped": nq * nk}

    def left_by_mask(rel0):
        if not causal:
            return "whole"
        if rel0 + res_q - 1 < 0 or (window and rel0 - (res_k - 1) >= window):
            return "dead"
        if rel0 + 1 < res_k:
            return "diagonal"
        return ("whole" if not window or rel0 + res_q - 1 < window
                else "trailing")

    found = collections.Counter(
        left_by_mask(qi * res_q + offset - ki * res_k)
        for qi, ki in itertools.product(range(nq), range(nk)))
    return {kind: found[kind] for kind in _KINDS}


def _walk_by_kind(walk, rel0, res_q: int, res: int, kinds,
                  window: Optional[int] = None, live=None):
    """``walk(rel0)`` for this grid block, with ``rel0`` a Python integer
    wherever the block's kind fixes which tiles are live: a whole, a
    diagonal, a trailing and a dead block each get a branch of their own
    whose tile bounds are constants (of a whole block only ``rel0 + 1 >=
    res`` matters, of a dead one ``rel0 <= -res``; under a window a whole
    block stands between the edges, as the one at ``rel0 == res`` does, and
    the trailing one at ``rel0 == window``); a grid that is "looped"
    throughout gets the loops over bounds computed from the traced
    ``rel0``. ``kinds`` are the kinds the call's grid holds
    (``_grid_kinds``), ``res`` its resident keys, ``res_q`` its resident
    queries: no branch is made for a kind that is absent, and none at all
    where there is one kind. Given ``live``, whether the mask leaves this
    block anything (the backward's), a block it leaves nothing of is not
    walked at all; without it (the forward's) such a block is walked as a
    dead one."""
    if isinstance(rel0, int):
        return walk(rel0)
    if kinds == ("looped",):
        if live is None:
            return walk(rel0)
        return pl.when(live)(functools.partial(walk, rel0))
    if window is None:
        straight = {"whole": (rel0 + 1 >= res, res - 1),
                    "diagonal": (rel0 == 0, 0),
                    "dead": (rel0 + res - 1 < 0, -res)}
    else:
        straight = {"whole": ((rel0 + 1 >= res) & (rel0 + res - 1 < window),
                              res),
                    "diagonal": (rel0 == 0, 0),
                    "trailing": (rel0 == window, window),
                    "dead": ((rel0 + res_q - 1 < 0)
                             | (rel0 - (res - 1) >= window), -res)}
    if len(kinds) == 1:
        return walk(straight[kinds[0]][1])
    for kind in kinds:
        if live is None or kind != "dead":
            here, rel = straight[kind]
            pl.when(here)(functools.partial(walk, rel))


def _fold_tile(s, carry, masked, values_t):
    """One key tile's scores ``s`` (block_k, block_q) folded into a q
    tile's online softmax ``carry`` (running max and sum as rows (1,
    block_q), accumulator (d_v, block_q)); ``values_t()`` loads the tile's
    V^T (d_v, block_k)."""
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
    # a query with no live key yet keeps m at NEG_INF: exponentiate against
    # 0 so that its masked scores give p == 0, not exp(0)
    m_exp = jnp.where(m_new > NEG_INF / 2, m_new, 0.0) if masked else m_new
    p = jnp.exp(s - m_exp)
    alpha = jnp.exp(m - m_exp)
    l = l * alpha + p.sum(axis=0, keepdims=True)
    acc = acc * alpha
    vt = values_t()
    return m_new, l, acc + _dot(vt, p.astype(vt.dtype), _NN)


def _fwd_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                offset: int, static: bool, kinds, window: Optional[int],
                rows_out: bool = False):
    """``o_ref`` is this block of queries' O^T (d_v, resident queries), or
    with ``rows_out`` its O (resident queries, d_v): a head's lanes of a
    model's own [B, T, H x d_v] array (``results_in_model_arrays``), for
    which a row of tiles' float32 accumulator is turned here, in VMEM."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    res_q, res_k = q_ref.shape[0], k_ref.shape[0]
    n_q, n_k = res_q // block_q, res_k // block_k
    rel0 = offset if static else qi * res_q + offset - ki * res_k
    fold = _scale_folds(q_ref.dtype, sm_scale)
    scores = functools.partial(_scores, sm_scale=sm_scale, fold=fold,
                               block_k=block_k, window=window)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def walk(rel0):
        def row(j, *bounds):
            cols = _tile(j, block_q, n_q)
            rel = rel0 + j * block_q
            q = _scaled(q_ref[cols, :], sm_scale, fold)

            def step(c, carry, masked):
                rows = _tile(c, block_k, n_k)
                s = scores(k_ref[rows, :], q, c, masked=masked, rel=rel)
                return _fold_tile(s, carry, masked, lambda: vt_ref[:, rows])

            m, l, acc = _walk(
                step, (m_scr[:, cols], l_scr[:, cols], acc_scr[:, cols]),
                *bounds)
            m_scr[:, cols], l_scr[:, cols], acc_scr[:, cols] = m, l, acc

            @pl.when(ki == nk - 1)
            def _finalize():
                l_safe = jnp.where(l == 0.0, 1.0, l)
                if rows_out:
                    o_ref[cols, :] = (acc / l_safe).T.astype(o_ref.dtype)
                else:
                    o_ref[:, cols] = (acc / l_safe).astype(o_ref.dtype)
                # queries with no live key get lse=+inf => p == 0 in the
                # backward
                lse_ref[:, cols] = jnp.where(
                    l == 0.0, jnp.inf,
                    jnp.where(m > NEG_INF / 2, m, 0.0) + jnp.log(l_safe))

        _walk_rows(row, rel0, n_q, n_k, block_q, block_k, causal, not static,
                   window)

    _walk_by_kind(walk, rel0, res_q, res_k, kinds, window)


def _bwd_kernel(q_ref, k_ref, v_ref, kt_ref, do_ref, lse_ref, delta_ref,
                dqt_ref, dk_ref, dv_ref, dk_scr, dv_scr, *sums,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                offset: int, static: bool, kinds, window: Optional[int],
                nq: int, group: int, o_rows: bool = False):
    """dQ^T of this (resident keys, resident queries) pair, and dK, dV
    accumulated over the queries: s and p are recomputed once for all
    three. The last grid axis walks the ``nq`` blocks of queries of each of
    the ``group`` query heads that read this key-value head, one head after
    another, so dK and dV gather the whole group in the scratch.

    One block of keys a head: ``dqt_ref`` is this block of queries' place
    in VMEM and a row of tiles writes its dQ^T there. Several: a block of
    queries comes back once for every block of keys, never on consecutive
    steps, so its float32 sum lives in HBM (``dqt_ref`` is the whole array)
    and ``sums`` are two (d, resident queries) buffers cut into the pieces
    that one copy moves (rows of tiles up to ``_COPY_BYTES``), two rows of
    DMA semaphores and a flag. A live step starts the fetch of what the
    blocks of keys before it left, piece by piece in the order in which it
    walks its rows (the longest first); the first row of a piece waits for
    it when its own tiles are computed, every row stores its dQ^T added to
    what was fetched, and the last row of a piece starts the piece's way
    back: the copies trickle through the step, and a row's has had every
    row's time before it. The first block of keys that a block of queries
    sees fetches nothing and assigns, so nothing is filled with zeros
    first; a step the mask leaves nothing of does none of this. A piece's
    way back is waited for where its buffer is next filled, a live step
    later, or at the last step of this block of keys (``pending`` says
    whether one is under way): no two steps of one block of keys touch the
    same block of queries, so nothing reads a sum before it has landed.

    ``delta_ref`` is the queries' row of ``delta``, or with ``o_rows``
    (``results_in_model_arrays``) this block of queries' O itself, a head's
    lanes of the model's [B, T, H x d_v] as ``do_ref`` is of its cotangent,
    from which a row of tiles makes its ``delta`` here; ``dk_ref`` and
    ``dv_ref`` are then a key-value head's lanes of such arrays too, which
    changes nothing in here."""
    ki, step_q = pl.program_id(1), pl.program_id(2)
    n_steps = pl.num_programs(2)
    qi = step_q if group == 1 else step_q % nq
    res_q, res_k = q_ref.shape[0], k_ref.shape[0]
    n_q, n_k = res_q // block_q, res_k // block_k
    rel0 = offset if static else qi * res_q + offset - ki * res_k
    fold = _scale_folds(q_ref.dtype, sm_scale)
    scores = functools.partial(_scores, sm_scale=sm_scale, fold=fold,
                               block_k=block_k, window=window)
    live = None
    if sums:
        nk = pl.num_programs(1)
        sum_scr, had_scr, sems, pending = sums
        first_k = (0 if window is None else
                   _first_live_k(qi, res_q, res_k, offset, nk, window))
        last_k = (_last_live_k(qi, res_q, res_k, offset, nk) if causal
                  else nk - 1)
        live, adds = (ki >= first_k) & (ki <= last_k), ki != first_k
        head = pl.program_id(0) * group + step_q // nq
        copies, _, wide = sum_scr.shape
        rows_a_copy = wide // block_q

        def place(c):
            return dqt_ref.at[head, :,
                              _tile(qi * copies + c, wide, nq * copies)]

        def fetch(c):
            """What the blocks of keys before left of piece ``c``."""
            return pltpu.make_async_copy(place(c), had_scr.at[c],
                                         sems.at[0, c])

        def write(c):
            """Piece ``c``'s sum, back to its place (waited for by any
            step: only the semaphore and the bytes are the copy's)."""
            return pltpu.make_async_copy(sum_scr.at[c], place(c),
                                         sems.at[1, c])

        def where(here, do_this):
            """``do_this`` now, or in a loop where its counter says."""
            if not isinstance(here, bool):
                pl.when(here)(do_this)
            elif here:
                do_this()

    @pl.when(step_q == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if sums:
            pending[0] = 0

    def delta_of(cols, do):
        """A query's sum of dO x O over its head's lanes, as the row that
        the transposed scores need: ``delta_ref``'s, where XLA made it; with
        ``o_rows`` that operand is this block of queries' O, laid out as dO
        is, and the row is made here, by one float32 turn (XLA reaches it
        from the model's arrays only by way of a float32 copy of the whole
        product: 0.25 GiB written, copied and read a layer at 16,384 tokens
        of 32 heads)."""
        if not o_rows:
            return delta_ref[:, cols]
        return (do.astype(jnp.float32) * delta_ref[cols, :].astype(
            jnp.float32)).T.sum(axis=0, keepdims=True)

    def walk(rel0):
        def row(j, *bounds):
            cols = _tile(j, block_q, n_q)
            rel = rel0 + j * block_q
            q, do = _scaled(q_ref[cols, :], sm_scale, fold), do_ref[cols, :]
            lse, delta = lse_ref[:, cols], delta_of(cols, do)  # (1, block_q)

            def step(c, dqt, masked):
                rows = _tile(c, block_k, n_k)
                s = scores(k_ref[rows, :], q, c, masked=masked, rel=rel)
                p = jnp.exp(s - lse)  # normalized; lse=+inf queries -> 0
                dv_scr[rows, :] += _dot(p.astype(do.dtype), do, _NN)
                dp = _dot(v_ref[rows, :], do, _NT)
                ds = (p * (dp - delta)).astype(q.dtype)
                dk_scr[rows, :] += _dot(ds, q, _NN)
                return dqt + _dot(kt_ref[:, rows], ds, _NN)  # (d, block_q)

            dqt = _walk(
                step, jnp.zeros((kt_ref.shape[0], block_q), jnp.float32),
                *bounds) * sm_scale
            if not sums:
                dqt_ref[:, cols] = dqt.astype(dqt_ref.dtype)
                return
            # the row's piece, its place in it as a column and as the
            # walk comes to it: rows are walked up or down
            c = j // rows_a_copy if copies > 1 else 0
            at = j % rows_a_copy if rows_a_copy > 1 else 0
            nth = at if order[0] == 0 else rows_a_copy - 1 - at
            mine = _tile(at, block_q, rows_a_copy)

            def arrived():
                pl.when(pending[0] == 1)(write(c).wait)
                pl.when(adds)(fetch(c).wait)

            where(nth == 0, arrived)
            sum_scr[c, :, mine] = dqt + jnp.where(adds, had_scr[c, :, mine],
                                                  0.0)
            where(nth == rows_a_copy - 1, lambda: write(c).start())

        the_rows = (rel0, n_q, n_k, block_q, block_k, causal, not static,
                    window, bool(sums))
        order = _rows(*the_rows)[1] or range(n_q)
        if sums:
            @pl.when(adds)
            def _fetch():
                for c in sorted(range(copies), reverse=order[0] != 0):
                    fetch(c).start()

        _walk_rows(row, *the_rows)
        if sums:
            pending[0] = 1

    _walk_by_kind(walk, rel0, res_q, res_k, kinds, window, live)

    @pl.when(step_q == n_steps - 1)
    def _finalize():
        dk = dk_scr[...]
        dk_ref[...] = (dk if fold else dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)
        if sums:
            @pl.when(pending[0] == 1)
            def _land():
                for c in range(copies):
                    write(c).wait()


def _own_lanes(x, h: int, width: int):
    """``x`` (rows, 128) with every lane but head ``h``'s ``width`` zeroed:
    selected, so that whatever lies in the other lanes (a neighbour's
    numbers, or past the array's edge anything at all) adds nothing to a
    contraction over the tile. All of ``x`` where the head is the tile."""
    if width == x.shape[1]:
        return x
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * width) & (lane < (h + 1) * width), x,
                     jnp.zeros_like(x))


def _each_head(head, width: int, phantom: bool):
    """``head(h)`` for the heads of this grid step's lane tile. With
    ``phantom`` (an odd number of 64-wide heads) the last tile's second
    half lies past the arrays' edge: nothing is computed for it, and what
    its rows and lanes of the scratch hold is dropped with the block's
    out-of-bounds part."""
    for h in range(128 // width):
        if phantom and h:
            pl.when(pl.program_id(1) < pl.num_programs(1) - 1)(
                functools.partial(head, h))
        else:
            head(h)


def _fwd_kernel_lanes(q_ref, k_ref, v_ref, o_ref, lse_ref, vt_scr, ot_scr, *,
                      sm_scale: float, block_q: int, block_k: int,
                      window: Optional[int], width: int, phantom: bool):
    """The forward of one lane tile of heads (two 64 wide, one 128 wide),
    each one block of keys, on blocks (T, 128) of the model's own
    [B, T, H x d] arrays. The arithmetic is ``_fwd_kernel``'s, transposed
    scores and all: V^T is made here, once a step, in VMEM; a head's
    scores contract over the tile's 128 lanes with the other head's
    selected to zero (the depth of an MXU pass, which a 64-wide head half
    fills either way); its (width, queries) accumulator lands in its rows
    of ``ot_scr``, which is turned once and leaves as the (T, 128) block
    of O. ``lse_ref`` is (heads a tile, T): a row a head."""
    res = q_ref.shape[0]
    n_q, n_k = res // block_q, res // block_k
    fold = _scale_folds(q_ref.dtype, sm_scale)
    scores = functools.partial(_scores, sm_scale=sm_scale, fold=fold,
                               block_k=block_k, window=window)
    vt_scr[...] = v_ref[...].T

    def head(h):
        ours = pl.ds(h * width, width)
        own = functools.partial(_own_lanes, h=h, width=width)
        edge = own if phantom else (lambda x: x)

        def row(j, *bounds):
            cols = _tile(j, block_q, n_q)
            q = _scaled(own(q_ref[cols, :]), sm_scale, fold)

            def step(c, carry, masked):
                rows = _tile(c, block_k, n_k)
                s = scores(edge(k_ref[rows, :]), q, c, masked=masked,
                           rel=j * block_q)
                return _fold_tile(s, carry, masked,
                                  lambda: vt_scr[ours, rows])

            m, l, acc = _walk(
                step, (jnp.full((1, block_q), NEG_INF, jnp.float32),
                       jnp.zeros((1, block_q), jnp.float32),
                       jnp.zeros((width, block_q), jnp.float32)), *bounds)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            ot_scr[ours, cols] = acc / l_safe
            lse_ref[h:h + 1, cols] = jnp.where(
                l == 0.0, jnp.inf,
                jnp.where(m > NEG_INF / 2, m, 0.0) + jnp.log(l_safe))

        _walk_rows(row, 0, n_q, n_k, block_q, block_k, True, False, window)

    _each_head(head, width, phantom)
    o_ref[...] = ot_scr[...].T.astype(o_ref.dtype)


def _bwd_kernel_lanes(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, kt_scr, dqt_scr, dk_scr, dv_scr,
                      *, sm_scale: float, block_q: int, block_k: int,
                      window: Optional[int], width: int, phantom: bool):
    """The backward of one lane tile of heads on blocks (T, 128) of the
    model's own arrays, as ``_fwd_kernel_lanes`` is the forward:
    ``_bwd_kernel``'s arithmetic with K^T made here, once a step. A head's
    q and dO rows have the other head's lanes selected to zero, so dV = P
    dO and dK = dS Q add to the head's own lanes of the tile's (T, 128)
    sums and to nothing else; its dQ^T lands in its rows of ``dqt_scr``,
    turned once at the end. ``delta``, a query's sum of dO x O over its
    head's lanes, is made here as well, from dO^T and O^T, as the row that
    the transposed scores need: XLA made it by way of a float32 copy of
    the whole product into a layout it could reduce."""
    res = q_ref.shape[0]
    n_q, n_k = res // block_q, res // block_k
    fold = _scale_folds(q_ref.dtype, sm_scale)
    scores = functools.partial(_scores, sm_scale=sm_scale, fold=fold,
                               block_k=block_k, window=window)
    kt_scr[...] = k_ref[...].T
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    # (128, T) float32, until each head's dQ^T takes its rows' place
    dqt_scr[...] = (do_ref[...].T.astype(jnp.float32)
                    * o_ref[...].T.astype(jnp.float32))

    def head(h):
        ours = pl.ds(h * width, width)
        own = functools.partial(_own_lanes, h=h, width=width)
        edge = own if phantom else (lambda x: x)

        def row(j, *bounds):
            cols = _tile(j, block_q, n_q)
            q = _scaled(own(q_ref[cols, :]), sm_scale, fold)
            do = own(do_ref[cols, :])
            lse = lse_ref[h:h + 1, cols]
            delta = dqt_scr[ours, cols].sum(axis=0, keepdims=True)

            def step(c, dqt, masked):
                rows = _tile(c, block_k, n_k)
                s = scores(edge(k_ref[rows, :]), q, c, masked=masked,
                           rel=j * block_q)
                p = jnp.exp(s - lse)  # normalized; lse=+inf queries -> 0
                dv_scr[rows, :] += _dot(p.astype(do.dtype), do, _NN)
                dp = _dot(edge(v_ref[rows, :]), do, _NT)
                ds = (p * (dp - delta)).astype(q.dtype)
                dk_scr[rows, :] += _dot(ds, q, _NN)
                return dqt + _dot(kt_scr[ours, rows], ds, _NN)

            dqt_scr[ours, cols] = _walk(
                step, jnp.zeros((width, block_q), jnp.float32),
                *bounds) * sm_scale

        _walk_rows(row, 0, n_q, n_k, block_q, block_k, True, False, window)

    _each_head(head, width, phantom)
    dq_ref[...] = dqt_scr[...].T.astype(dq_ref.dtype)
    dk = dk_scr[...]
    dk_ref[...] = (dk if fold else dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _largest_block(n: int, target: int, align: int) -> int:
    """The largest multiple of ``align`` up to ``target`` that divides n,
    or n itself (a block may always span its whole dimension)."""
    for b in range(min(target, n) // align * align, 0, -align):
        if n % b == 0:
            return b
    return n


def _block_sizes(q_len: int, k_len: int, block_q: Optional[int],
                 block_k: Optional[int], targets):
    """(block_q, block_k, resident queries, resident keys) for a call.
    Queries and keys both lie along lanes somewhere, so a tile size the
    caller does not give is a multiple of 128 up to the kernel's target,
    or the whole length."""
    block_q = min(block_q or _largest_block(q_len, targets[0], 128), q_len)
    block_k = min(block_k or _largest_block(k_len, targets[1], 128), k_len)
    assert q_len % block_q == 0, (q_len, block_q)
    assert k_len % block_k == 0, (k_len, block_k)
    return (block_q, block_k,
            _largest_block(q_len, max(_MAX_RESIDENT, block_q), block_q),
            _largest_block(k_len, max(_MAX_RESIDENT, block_k), block_k))


def _compiler_params(interpret: bool, width: int, keys_add: bool = False):
    """``width`` is the widest head dimension of the call: up to 128 lanes
    the residents fit the compiler's own 16 MiB of VMEM; past it (keys of
    192 are laid out as 256 lanes) the backward's residents take 16.5 MiB
    at 2048 queries and keys, so the kernel asks for 32 of the chip's 128.
    With ``keys_add`` (the backward over several blocks of keys) the steps
    along the grid's second axis add to one sum in HBM, one after another:
    that axis is no core's to split."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",
                             "arbitrary" if keys_add else "parallel",
                             "arbitrary"),
        vmem_limit_bytes=32 * 2**20 if width > 128 else None)


# The blocks of the other operand that the mask leaves a block anything of,
# first and last, as indices held to the grid: a Python int for a Python int.

def _last_live_k(qi, res_q: int, res_k: int, offset: int, nk: int):
    """Index of the last block of resident keys that any query of block
    ``qi`` sees."""
    return _clamp((qi * res_q + offset + res_q - 1) // res_k, nk - 1)


def _first_live_k(qi, res_q: int, res_k: int, offset: int, nk: int,
                  window: int):
    """Index of the first block of resident keys that the window leaves
    any query of block ``qi``."""
    return _clamp((qi * res_q + offset - window + 1) // res_k, nk - 1)


def _first_live_q(ki, res_q: int, res_k: int, offset: int, nq: int):
    """Index of the first block of resident queries that sees any key of
    block ``ki``."""
    return _clamp((ki * res_k - offset) // res_q, nq - 1)


def _last_live_q(ki, res_q: int, res_k: int, offset: int, nq: int,
                 window: int):
    """Index of the last block of resident queries whose window holds any
    key of block ``ki``."""
    return _clamp((ki * res_k + res_k - 1 + window - 1 - offset) // res_q,
                  nq - 1)


def grid_block_kinds(q_len: int, k_len: int, causal: bool,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None, *,
                     backward: bool = False,
                     window: Optional[int] = None) -> dict:
    """{"whole": n, "diagonal": n, "dead": n, "looped": n}, and under a
    ``window`` "trailing" with them: the grid blocks a head of a call of
    these lengths has, by the rule the kernels branch on (``_grid_kinds``).
    ``looped`` blocks walk their tiles in loops with traced bounds, the
    others with constant ones. The forward's grid unless ``backward``: the
    two kernels' tiles differ, and at some lengths what a grid step holds
    with them."""
    _, _, res_q, res_k = _block_sizes(
        q_len, k_len, block_q, block_k, _BWD_TILES if backward else _FWD_TILES)
    window = _window_of(window, k_len)
    kinds = _grid_kinds(q_len // res_q, k_len // res_k, res_q, res_k,
                        k_len - q_len, causal, window)
    if window is None:
        del kinds["trailing"]
    return kinds


def _window_of(window: Optional[int], k_len: int) -> Optional[int]:
    """A window that holds every key is none."""
    assert window is None or window > 0, window
    return None if window is None or window >= k_len else window


def _kinds_present(nq: int, nk: int, res_q: int, res_k: int, offset: int,
                   causal: bool, backward: bool, window: Optional[int],
                   heads):
    """The kinds of grid block a call holds, for its kernel to branch on,
    and their counts a head written into the runtime's ring: one record a
    traced call (none a step), so a timeline says which walk a model's
    calls took (``looped`` 0: every block in straight-line code), under
    which ``window`` (0: none), with how many heads of queries and of keys
    and values, the batch folded into both (``heads``, ``kv_heads``), and
    how many dQ arrays a backward call leaves for XLA to sum
    (``dq_partials``: 0 for every shape since PR 50, the kernel sums them
    itself; until then one a block of keys that a block of queries
    sees)."""
    counts = _grid_kinds(nq, nk, res_q, res_k, offset, causal, window)
    steptrace.record_counters("attn/grid_blocks", {
        **counts, "queries": nq * res_q, "keys": nk * res_k,
        "backward": int(backward), "window": window or 0,
        "heads": heads[0], "kv_heads": heads[1],
        "dq_partials": 0})
    return tuple(kind for kind in _KINDS if counts[kind])


def _kernel_name(base: str, window: Optional[int]) -> str:
    """``flash_fwd`` / ``flash_bwd``, and of a windowed call
    ``flash_fwd_w<window>``: the benchmark's readers find the kernels, and
    a call's window, by these names."""
    return base if window is None else f"{base}_w{window}"


def _flash_pallas(q, k, v, *, causal: bool, sm_scale: float,
                  block_q: Optional[int], block_k: Optional[int],
                  interpret: bool, window: Optional[int] = None,
                  heads: Optional[int] = None):
    """q: (B, S, D) with batch*heads folded into B; k: (B_kv, S, D) and v:
    (B_kv, S, Dv) with B a multiple of B_kv: query head ``i`` reads
    key-value head ``i // (B // B_kv)``, through the index maps.
    -> (out (B, S, Dv), lse) with lse (B, 1, S) float32. Given ``heads``
    (``results_in_model_arrays``), of which B is a multiple, out is a
    model's own (B / heads, S, heads x Dv): the kernel writes head ``i %
    heads``'s lanes of it, a block of queries at a time."""
    b, q_len, d = q.shape
    k_len, d_v = k.shape[1], v.shape[2]
    group = b // k.shape[0]
    block_q, block_k, res_q, res_k = _block_sizes(q_len, k_len, block_q,
                                                  block_k, _FWD_TILES)
    nq, nk = q_len // res_q, k_len // res_k
    offset = k_len - q_len
    if window is not None and offset:
        # no model sends one, and the blocks' kinds, the index maps' clamps
        # and the backward's first and last live blocks (``_first_live_k``,
        # ``_last_live_q``) were never held to a reference at such lengths
        raise NotImplementedError(
            f"flash_attention: a window over lengths that differ ({q_len} "
            f"queries, {k_len} keys) is the scan's or the reference's")

    if causal and nk > 1 and window is not None:
        # blocks behind the window fetch the first live one, as blocks
        # past the diagonal the last
        kmap = lambda qi, ki: jnp.clip(
            ki, _first_live_k(qi, res_q, res_k, offset, nk, window),
            _last_live_k(qi, res_q, res_k, offset, nk))
    elif causal and nk > 1:
        kmap = lambda qi, ki: jnp.minimum(
            ki, _last_live_k(qi, res_q, res_k, offset, nk))
    else:
        kmap = lambda qi, ki: ki
    kv_head = (lambda bi: bi) if group == 1 else (lambda bi: bi // group)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, offset=offset, static=nq == nk == 1, window=window,
        kinds=_kinds_present(nq, nk, res_q, res_k, offset, causal, False,
                             window, (b, k.shape[0])))
    if heads is None:
        # O^T, which XLA turns
        out_spec = pl.BlockSpec((None, d_v, res_q),
                                lambda bi, qi, ki: (bi, 0, qi))
        out_shape = jax.ShapeDtypeStruct((b, d_v, q_len), q.dtype)
    else:
        kernel = functools.partial(kernel, rows_out=True)
        out_spec = pl.BlockSpec(
            (None, res_q, d_v), lambda bi, qi, ki: (bi // heads, qi,
                                                    bi % heads))
        out_shape = jax.ShapeDtypeStruct((b // heads, q_len, heads * d_v),
                                         q.dtype)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((None, res_q, d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((None, res_k, d),
                         lambda bi, qi, ki: (kv_head(bi), kmap(qi, ki), 0)),
            pl.BlockSpec((None, d_v, res_k),
                         lambda bi, qi, ki: (kv_head(bi), 0, kmap(qi, ki))),
        ],
        out_specs=[
            out_spec,
            pl.BlockSpec((None, 1, res_q), lambda bi, qi, ki: (bi, 0, qi)),
        ],
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((b, 1, q_len), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, res_q), jnp.float32),
            pltpu.VMEM((1, res_q), jnp.float32),
            pltpu.VMEM((d_v, res_q), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret, max(d, d_v)),
        interpret=interpret,
        name=_kernel_name("flash_fwd", window),
    )(q, k, jnp.swapaxes(v, 1, 2))
    return (jnp.swapaxes(out, 1, 2) if heads is None else out), lse


def _flash_pallas_bwd_kernel(q, k, v, do, lse, delta, *, causal: bool,
                             sm_scale: float, block_q: Optional[int],
                             block_k: Optional[int], interpret: bool,
                             window: Optional[int] = None,
                             heads: Optional[int] = None):
    """-> (dq, dk, dv) of ``_flash_pallas``'s call, shaped as q, k and v
    [B x H, T, d] from ``do`` shaped as its output; given ``heads``, ``do``
    is the cotangent of the model's own [B, T, heads x d_v] output, read a
    head's lanes at a time, ``delta`` is that output itself, from which the
    kernel makes the rows, and dk, dv are written as model's arrays
    likewise, [B, T, key-value heads x width]; dq is [B x H, T, d] either
    way."""
    b, q_len, d = q.shape
    b_kv, k_len, d_v = k.shape[0], k.shape[1], v.shape[2]
    group = b // b_kv
    block_q, block_k, res_q, res_k = _block_sizes(q_len, k_len, block_q,
                                                  block_k, _BWD_TILES)
    nq, nk = q_len // res_q, k_len // res_k
    offset = k_len - q_len
    if causal and nk > 1 and window is not None:
        qmap = lambda ki, qi: jnp.clip(
            qi, _first_live_q(ki, res_q, res_k, offset, nq),
            _last_live_q(ki, res_q, res_k, offset, nq, window))
    elif causal and nk > 1:
        qmap = lambda ki, qi: jnp.maximum(
            qi, _first_live_q(ki, res_q, res_k, offset, nq))
    else:
        qmap = lambda ki, qi: qi
    # the grid: key-value heads, their blocks of keys, and for each the
    # blocks of queries of every query head of the group
    if group == 1:
        head, block = (lambda bi, step: bi), (lambda step: step)
    else:
        head = lambda bi, step: bi * group + step // nq
        block = lambda step: step % nq
    qspec = pl.BlockSpec(
        (None, res_q, d),
        lambda bi, ki, step: (head(bi, step), qmap(ki, block(step)), 0))
    kspec = pl.BlockSpec((None, res_k, d), lambda bi, ki, step: (bi, ki, 0))
    vspec = pl.BlockSpec((None, res_k, d_v), lambda bi, ki, step: (bi, ki, 0))
    if heads is None:
        # dO as q, dK and dV as k and v: a head's block of [B x H, T, width]
        dospec = pl.BlockSpec(
            (None, res_q, d_v),
            lambda bi, ki, step: (head(bi, step), qmap(ki, block(step)), 0))
        dkspec, dvspec = kspec, vspec
        dk_dims, dv_dims = k.shape, v.shape
    else:
        # a head's lanes of a model's [B, T, heads x width] array, by
        # (batch row, block, head of the row)
        assert causal and nk > 1 and not offset and res_q == res_k, (
            q.shape, k.shape, causal)
        kv_heads = heads // group
        row = lambda bi: bi // kv_heads
        of_kv = lambda bi, ki, step: (row(bi), ki, bi % kv_heads)
        dospec = pl.BlockSpec(
            (None, res_q, d_v),
            lambda bi, ki, step: (row(bi), qmap(ki, block(step)),
                                  head(bi, step) % heads))
        dkspec = pl.BlockSpec((None, res_k, d), of_kv)
        dvspec = pl.BlockSpec((None, res_k, d_v), of_kv)
        dk_dims, dv_dims = ((b // heads, k_len, kv_heads * width)
                            for width in (d, d_v))
    rowspec = pl.BlockSpec(
        (None, 1, res_q),
        lambda bi, ki, step: (head(bi, step), 0, qmap(ki, block(step))))
    if nk == 1:
        # one block of keys: a block of queries' dQ^T is the whole of it,
        # written where the pipeline takes it from
        dq_shape, sums = jax.ShapeDtypeStruct((b, d, q_len), q.dtype), []
        dqspec = pl.BlockSpec(
            (None, d, res_q),
            lambda bi, ki, step: (head(bi, step), 0, block(step)))
    else:
        # several: the kernel sums them in float32 in HBM, where it fetches
        # and writes a block of queries' sum itself (``_bwd_kernel``), and
        # the swap below rounds once. Whatever the lengths, the group, the
        # widths and the window: the sum takes two (d, res_q) float32
        # buffers of VMEM, as a partial's block took
        dq_shape = jax.ShapeDtypeStruct((b, d, q_len), jnp.float32)
        dqspec = pl.BlockSpec(memory_space=pl.ANY)
        n_q = res_q // block_q
        rows_a_copy = max(
            rows for rows in range(1, n_q + 1) if n_q % rows == 0
            and (rows == 1 or 4 * d * block_q * rows <= _COPY_BYTES))
        pieces = (n_q // rows_a_copy, d, rows_a_copy * block_q)
        sums = [pltpu.VMEM(pieces, jnp.float32),
                pltpu.VMEM(pieces, jnp.float32),
                pltpu.SemaphoreType.DMA((2, pieces[0])),
                pltpu.SMEM((1,), jnp.int32)]
    dq_t, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, offset=offset, static=nq == nk == 1,
            window=window, nq=nq, group=group, o_rows=heads is not None,
            kinds=_kinds_present(nq, nk, res_q, res_k, offset, causal, True,
                                 window, (b, b_kv))),
        grid=(b_kv, nk, group * nq),
        in_specs=[
            qspec, kspec, vspec,
            pl.BlockSpec((None, d, res_k), lambda bi, ki, step: (bi, 0, ki)),
            dospec, rowspec, rowspec if heads is None else dospec,
        ],
        out_specs=[dqspec, dkspec, dvspec],
        out_shape=[dq_shape, jax.ShapeDtypeStruct(dk_dims, k.dtype),
                   jax.ShapeDtypeStruct(dv_dims, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((res_k, d), jnp.float32),
            pltpu.VMEM((res_k, d_v), jnp.float32),
            *sums,
        ],
        compiler_params=_compiler_params(interpret, max(d, d_v),
                                         keys_add=nk > 1),
        interpret=interpret,
        name=_kernel_name("flash_bwd", window),
    )(q, k, v, jnp.swapaxes(k, 1, 2), do, lse, delta)
    return jnp.swapaxes(dq_t.astype(q.dtype), 1, 2), dk, dv


def heads_a_lane_tile(seq_len: int, heads: int, kv_heads: int, d: int,
                      d_v: int) -> int:
    """How many heads one 128-lane tile of a model's [B, T, H x d] arrays
    holds where the kernels address those arrays themselves
    (``_flash_pallas_lanes``), and 0 where XLA turns them into the kernels'
    own [B x H, T, d] (``_flash_pallas``). Read from the call's shapes alone:
    a head is one block of keys (every length up to ``_MAX_RESIDENT``: the
    kernels past it sum over blocks and walk them by kind, a head a grid
    row; what of THEIR boundary is the model's arrays,
    ``results_in_model_arrays`` says), in whole tiles of queries; keys and
    values share a width that divides the lanes, 64 (two heads a tile, an
    odd head out in half a tile past the arrays' edge) or 128 (192 / 128
    and 64 / 128 would need two addresses a head); every query head has its
    own keys and values (a group's would lie in another tile's half); and
    the heads fill a tile."""
    one_block = seq_len <= _MAX_RESIDENT and seq_len % 128 == 0
    if (one_block and heads == kv_heads and d == d_v and d in (64, 128)
            and heads * d >= 128):
        return 128 // d
    return 0


def results_in_model_arrays(seq_len: int, d: int, d_v: int) -> bool:
    """Whether the kernels that walk several blocks of keys a head
    (``_flash_pallas``) write O, dK and dV into, and read O and its
    cotangent from, a model's own [B, T, H x width] arrays, a head's lanes
    a block, where otherwise XLA turns [B x H, d_v, T] and [B x H, T, d]
    arrays round them (at 16,384 tokens and 32 heads of 128 the kernel's
    output was laid out three times and its cotangent twice, 15 ms of a
    474 ms step: PERF.md section 6, PR 55). Read from the call's shapes
    alone, as ``heads_a_lane_tile`` is, which takes the lengths up to
    ``_MAX_RESIDENT``: past it, keys and values of one width that is whole
    lane tiles, so that a head's block is whole tiles of either array (192
    / 128 and 64 / 128 keep XLA's copies, as a width of 64 does: half a
    tile a head); any grouping, a window or none. The operands q, k and V^T
    stay the [B x H, T, d] that XLA makes of the projections' results,
    which it writes in that layout anyway, and dQ leaves as the kernel's
    float32 [B x H, d, T] sum (``_flash_pallas_bwd`` says why)."""
    return seq_len > _MAX_RESIDENT and d == d_v and d % 128 == 0


def _lanes_params(interpret: bool, dtype):
    """Batch rows x lane tiles of heads: no step adds to another's. In
    bfloat16 the backward's residents at 2,048 tokens take 10.5 MiB of the
    compiler's own 16; a four-byte type takes twice the blocks' share."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=32 * 2**20 if dtype.itemsize > 2 else None)


def _lane_tiles(q, heads: int, window: Optional[int], tiles, backward: bool):
    """What both calls on a model's own arrays share, for ``q`` [B, T,
    heads x width] and ``tiles`` (block_q, block_k, the kernel's targets):
    (a (T, 128) block's spec, the spec of a lane tile's rows (heads a tile,
    T), the rows' array [B, lane tiles, heads a tile, T] whose leading two
    are the grid, the kernel's static arguments). Writes the call's
    ``attn/grid_blocks`` record, as ``_kinds_present`` does for the other
    boundary: one block a head, on the diagonal."""
    b, seq, lanes = q.shape
    width = lanes // heads
    a_tile = 128 // width
    n_tiles = -(-heads // a_tile)
    block_q, block_k, res_q, res_k = _block_sizes(seq, seq, *tiles)
    assert res_q == res_k == seq, (seq, res_q, res_k)
    _kinds_present(1, 1, seq, seq, 0, True, backward, window,
                   (b * heads, b * heads))
    return (pl.BlockSpec((None, seq, 128), lambda bi, ti: (bi, 0, ti)),
            pl.BlockSpec((None, None, a_tile, seq),
                         lambda bi, ti: (bi, ti, 0, 0)),
            jax.ShapeDtypeStruct((b, n_tiles, a_tile, seq), jnp.float32),
            dict(block_q=block_q, block_k=block_k, window=window, width=width,
                 phantom=heads % a_tile != 0))


def _flash_pallas_lanes(q, k, v, *, heads: int, sm_scale: float,
                        block_q: Optional[int], block_k: Optional[int],
                        interpret: bool, window: Optional[int] = None):
    """q, k, v: [B, T, heads x d], a model's own arrays (what its
    projection wrote, reshaped), where ``heads_a_lane_tile`` admits the
    call. -> (out [B, T, heads x d], lse [B, lane tiles, heads a tile, T]
    float32): one grid step a batch row and lane tile
    (``_fwd_kernel_lanes``)."""
    seq = q.shape[1]
    spec, rowspec, rows, static = _lane_tiles(
        q, heads, window, (block_q, block_k, _FWD_TILES), False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_lanes, sm_scale=sm_scale, **static),
        grid=rows.shape[:2],
        in_specs=[spec, spec, spec],
        out_specs=[spec, rowspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), rows],
        scratch_shapes=[pltpu.VMEM((128, seq), v.dtype),
                        pltpu.VMEM((128, seq), jnp.float32)],
        compiler_params=_lanes_params(interpret, q.dtype),
        interpret=interpret,
        name=_kernel_name("flash_fwd", window),
    )(q, k, v)


def _flash_pallas_lanes_bwd(q, k, v, do, out, lse, *, heads: int,
                            sm_scale: float, block_q: Optional[int],
                            block_k: Optional[int], interpret: bool,
                            window: Optional[int] = None):
    """-> (dq, dk, dv) [B, T, heads x d] of ``_flash_pallas_lanes``'s call,
    from its output and its ``lse`` in the rows' form it hands out."""
    seq = q.shape[1]
    spec, rowspec, rows, static = _lane_tiles(
        q, heads, window, (block_q, block_k, _BWD_TILES), True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel_lanes, sm_scale=sm_scale, **static),
        grid=rows.shape[:2],
        in_specs=[spec, spec, spec, spec, spec, rowspec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((128, seq), k.dtype),
                        pltpu.VMEM((128, seq), jnp.float32),
                        pltpu.VMEM((seq, 128), jnp.float32),
                        pltpu.VMEM((seq, 128), jnp.float32)],
        compiler_params=_lanes_params(interpret, q.dtype),
        interpret=interpret,
        name=_kernel_name("flash_bwd", window),
    )(q, k, v, do, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_pallas_diff(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, window=None, heads=None):
    """Differentiable Pallas flash attention: both directions are Pallas
    kernels (forward saves the logsumexp; one backward kernel recomputes P
    per tile from q,k,lse — O(seq) memory, no attention matrix ever
    materialized). q, k, v are the kernels' own [B x H, T, d] or, given
    ``heads``, a model's [B, T, heads x d] (``heads_a_lane_tile``, or past
    one block of keys ``results_in_model_arrays``, where k and v may hold
    fewer heads)."""
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, window, heads)[0]


def _folded(x, heads: int):
    """A model's [B, T, heads x d] as the kernels' [B x heads, T, d]."""
    b, seq, lanes = x.shape
    return x.reshape(b, seq, heads, lanes // heads).transpose(
        0, 2, 1, 3).reshape(b * heads, seq, lanes // heads)


def _unfolded(x, heads: int):
    """The kernels' [B x heads, T, d] as a model's [B, T, heads x d]."""
    folded, seq, d = x.shape
    return x.reshape(folded // heads, heads, seq, d).transpose(
        0, 2, 1, 3).reshape(folded // heads, seq, heads * d)


def _folded_operands(q, k, v, heads: int):
    """q, k, v [B, T, H x d] (k and v of as many heads as their widths
    say) as ``_flash_pallas`` takes them."""
    kv_heads = k.shape[2] // (q.shape[2] // heads)
    return _folded(q, heads), _folded(k, kv_heads), _folded(v, kv_heads)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window, heads):
    """(out, lse) by the boundary the operands are in."""
    if heads is None:
        return _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, window=window)
    assert causal, "a model's own arrays are causal self-attention's"
    if q.shape[1] > _MAX_RESIDENT:   # ``results_in_model_arrays``
        return _flash_pallas(*_folded_operands(q, k, v, heads),
                             causal=True, sm_scale=sm_scale, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             window=window, heads=heads)
    return _flash_pallas_lanes(q, k, v, heads=heads, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, window=window)


def _flash_pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                      window, heads):
    out, lse = map(ad_checkpoint.checkpoint_name, _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window,
        heads), _REMAT_NAMES)
    return out, (q, k, v, out, lse)


def _flash_pallas_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                      heads, res, g):
    q, k, v, out, lse = res
    # delta_i = rowsum(dO_i * O_i); tiny elementwise reduce — XLA fuses it.
    # A row (b, 1, q_len), like lse.
    if heads is None:
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, None, :]
        return _flash_pallas_bwd_kernel(
            q, k, v, g, lse, delta, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window)
    if q.shape[1] > _MAX_RESIDENT:   # ``results_in_model_arrays``
        # O goes in as dO does, and the kernel makes ``delta`` of them.
        # dQ stays its float32 [B x H, d, T] sum, which XLA rounds and
        # turns: what reads dQ next (the rotary's and the heads' norm's
        # backward) wants the tokens minor, and a dQ rounded by the kernel
        # into the model's [B, T, H x d] cost the step 14 ms more in XLA's
        # float32 copies than it saved (PERF.md section 6, PR 55)
        dq, dk, dv = _flash_pallas_bwd_kernel(
            *_folded_operands(q, k, v, heads), g, lse, out, causal=True,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            interpret=interpret, window=window, heads=heads)
        return _unfolded(dq, heads), dk, dv
    return _flash_pallas_lanes_bwd(
        q, k, v, g, out, lse, heads=heads, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window)


_flash_pallas_diff.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "impl",
                     "window", "heads"),
)
def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    impl: Optional[str] = None,
                    window: Optional[int] = None,
                    heads: Optional[int] = None) -> jax.Array:
    """Flash attention over (..., seq, head_dim) inputs.

    Accepts (b, h, s, d) or (b, s, d); ``q`` and ``k`` share a width and
    ``v`` may have its own, which is the output's (keys of 192 against
    values of 128: whatever lanes a width is padded to inside the kernel are
    the kernel's business). ``k`` and ``v`` may have fewer heads than ``q``,
    a number that divides its heads (with (b, s, d) inputs, of the folded
    batch x heads): query head ``j`` reads key-value head ``j // group``,
    in the kernel through its index maps, with no copy of ``k`` or ``v``
    a query head, and the backward kernel sums a key-value head's dK and dV
    over its group. Under ``window``, which needs ``causal``, query ``i``
    sees keys ``i - window + 1 .. i``; one that holds every key is none.
    With ``impl=None`` the platform
    decides: the Pallas kernel on "tpu" — where a kernel that fails to
    lower raises, it never falls back — and the scan formulation on any
    other backend. ``impl`` forces a path:
    "pallas" | "pallas_interpret" | "scan" | "reference".

    Given ``heads``, q, k and v are a model's own (b, s, heads x d), every
    head's width side by side along the last axis, and so is the output:
    causal self-attention of a shape ``heads_a_lane_tile`` admits, whose
    kernels address those arrays themselves (two 64-wide heads a lane
    tile), or past one block of keys a head of a shape
    ``results_in_model_arrays`` admits (k and v of fewer heads, as their
    last axis says), whose kernels write the output, dK and dV into such
    arrays and read the output's cotangent from one; the other paths are
    handed the heads as an axis.

    ``block_q`` queries meet ``block_k`` keys at a time; left out, the
    kernel chooses both from the sequence lengths (``_block_sizes``) and
    scan takes 128 keys.

    Under a mesh whose data-like axes split the batch (``_batch_axes``)
    the kernel runs per batch shard inside ``shard_map``: the partitioner
    refuses Mosaic calls, and batch and heads are independent. Any other
    mesh axis of size > 1 (``unmapped_mesh_axes``) still leaves the call
    to the partitioner, and JAX's own error says so.
    """
    assert causal or window is None, "a window is a causal mask's"
    window = _window_of(window, k.shape[-2])
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    if sm_scale is None:
        sm_scale = (q.shape[-1] // (heads or 1)) ** -0.5
    if impl in ("reference", "scan"):
        if heads is not None:   # these take the heads as an axis
            d = q.shape[2] // heads
            q, k, v = (t.reshape(*t.shape[:2], n, -1).transpose(0, 2, 1, 3)
                       for t, n in ((q, heads), (k, k.shape[2] // d),
                                    (v, k.shape[2] // d)))
        if impl == "reference":
            out = attention_reference(q, k, v, causal=causal,
                                      sm_scale=sm_scale, window=window)
        else:
            out = _flash_scan(q, k, v, causal=causal, sm_scale=sm_scale,
                              block_k=block_k or 128, window=window)
        if heads is not None:
            b, _, seq, _ = out.shape
            out = out.transpose(0, 2, 1, 3).reshape(b, seq, -1)
        return out
    interpret = impl == "pallas_interpret"
    if heads is None:
        assert (q.shape[-3] % k.shape[-3] == 0
                and k.shape[:-1] == v.shape[:-1]), (q.shape, k.shape, v.shape)
    else:
        d, seq = q.shape[2] // heads, q.shape[1]
        if seq > _MAX_RESIDENT:
            kv_heads = k.shape[2] // d
            admitted = (heads % kv_heads == 0 and results_in_model_arrays(
                seq, d, v.shape[2] // kv_heads))
        else:
            admitted = q.shape == k.shape == v.shape and heads_a_lane_tile(
                seq, heads, heads, d, d)
        assert admitted and k.shape[1] == v.shape[1] == seq, (
            q.shape, k.shape, v.shape, heads)

    def kernel(q, k, v):
        if heads is not None:
            return _flash_pallas_diff(q, k, v, causal, sm_scale, block_q,
                                      block_k, interpret, window, heads)
        if q.ndim == 4:
            b, h, s, _ = q.shape
            fold = lambda x: x.reshape(b * x.shape[1], *x.shape[-2:])
            out = _flash_pallas_diff(fold(q), fold(k), fold(v), causal,
                                     sm_scale, block_q, block_k, interpret,
                                     window)
            return out.reshape(b, h, s, v.shape[-1])
        return _flash_pallas_diff(q, k, v, causal, sm_scale, block_q,
                                  block_k, interpret, window)

    mesh, axes = _batch_axes(q)
    if not axes:
        return kernel(q, k, v)
    spec = PartitionSpec(axes)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=set(axes),
                         check_vma=False)(q, k, v)


def _batch_axes(x):
    """(mesh, axes): the mesh ``x`` is traced under and those of its axes
    the batch (leading) dim is split over — the repo's data-like axes
    (``mesh_utils.data_sharding``) of size > 1 that an enclosing
    ``shard_map`` has not already split. ``axes`` is empty outside a mesh.
    A leading dim those axes do not divide is an error, not a reason to
    leave the kernel to the partitioner, which refuses it."""
    mesh, axes, _ = traced_mesh_axes(x)
    n = math.prod(mesh.shape[a] for a in axes)
    if x.shape[0] % n:
        raise ValueError(
            f"flash_attention: leading dim {x.shape[0]} is not divisible by "
            f"the mesh's batch axes {axes} (size {n}); the Pallas kernel "
            "runs per batch shard and cannot be partitioned otherwise")
    return mesh, axes


def unmapped_mesh_axes(x) -> tuple:
    """Axes of size > 1 of the mesh ``x`` is traced under that neither
    ``flash_attention`` maps the batch over nor an enclosing ``shard_map``
    has made manual (``model`` under tensor parallelism, ``seq``,
    ``expert``). Under any of them the kernel reaches the partitioner,
    which refuses it ("Mosaic kernels cannot be automatically
    partitioned"): a caller that chooses between paths asks here first."""
    return traced_mesh_axes(x)[2]


# Where "auto" takes the Pallas kernel: where it was measured faster than
# XLA's attention on a v5e, forward plus backward at 16,384 tokens a call
# (PERF.md section 6, PR 25). Head dimension 64: every multiple of 128 tried
# from 512 to 2048 (512, 640, 768, 896, 1024, 1152, 1280, 1536, 2048: 2.4x
# to 4.9x; at 256 and 384 XLA wins), where one grid step holds a whole
# head, and 3072, 4096 and 8192 (3.3x, 5.2x, 47x), where it holds 1536 or
# 2048 queries and keys. Past ``_MAX_RESIDENT`` a length that 1024 does not
# divide can leave the kernel 128-wide grid blocks (2176 = 17 x 128: 20.9 ms
# against XLA's 17.3), so those stay with XLA. Head dimension 128: 512, 768,
# 1024, 2048, 4096 (2.4x to 4.3x). The multiples of 128 between those
# lengths are interpolated, lengths past 8192 extrapolated (XLA's [T, T]
# scores take 718 ms a layer at 8192 and no longer fit at 16,384).
# Keys 192 wide and values 128 (PR 31; 32 heads, 16,384 tokens a call,
# forward plus backward, kernel against the "xla" path written out for two
# widths): 512: 8.10 against 9.57 ms; 1024: 9.51 / 16.75; 2048: 13.53 /
# 30.57; 4096: 28.29 / 59.31 (1.2x to 2.3x); 8192: 50.66 ms, where XLA's
# scores (8.6 GB) were not tried. At 128 / 128 and the same 32 heads the
# kernel read 6.95, 7.86, 10.66, 25.57 and 45.59 ms.
# Since PR 38 (grid blocks walked by kind; ``benches/flash_widths.py``, my
# chip run, same 16,384 tokens and 32 heads), ``flash_fwd`` and ``flash_bwd``
# alone in a trace, then the wall time of forward plus backward with the
# transposes round them and, until PR 50, XLA's sum of dQ's partials (past
# 2048 tokens; what PR 50 reads at the cells' shapes stands below):
#   192 / 128   2048: 3.51 and 6.11 ms, 13.53 (one block a head: as before)
#               4096: 6.91 and 12.14, 24.45 (was 28.27)
#               8192: 13.22 and 23.62, 43.29 (was 50.62; the kernels alone
#                     15.48 and 28.72)
#   128 / 128   2048: 2.65 and 4.22, 10.65; 4096: 5.23 and 8.80, 18.75 (was
#               25.57); 8192: 10.12 and 17.40, 32.99 (was 45.59)
#   64 / 64     1024: 1.27 and 2.60, 5.85; 4096: 4.19 and 8.04, 14.71
# The wider key costs 34% to 40% more kernel time at 2048 to 8192 (less
# than its 3/2 in QK^T, dK and dQ; a 192-wide operand is laid out as 256
# lanes and fills the MXU's depth one and a half times). By block at 192 /
# 128 (the 8192 reading less four diagonal blocks a head at the 2048
# reading's price): backward 23.9 us a diagonal block of 36 tiles and 45.6
# a whole block of 64 (0.66 and 0.71 us a tile against 0.55 at the MXU's
# peak); forward 13.7 us a diagonal block of 10 tiles and 25.3 a whole
# block of 16 (1.37 and 1.58 us a tile against 0.85).
# Since PR 44 (grouped key-value heads, a window; same bench with
# ``--kv-heads 4`` and ``--window``, my chip run, 128 / 128, one sequence of
# 16,384 tokens, 32 query heads, 8 x 8 grid blocks a head), ``flash_fwd`` and
# ``flash_bwd`` alone, then the wall time of forward plus backward:
#   no window, 32 key-value heads (28 whole, 8 diagonal, 28 dead a head):
#               19.47 and 34.46 ms, 60.80
#   no window, 4 key-value heads: 19.18 and 34.15, 58.20 (the keys and values
#               of a group are fetched once a group in the backward and leave
#               as 4 heads' dK and dV: grouping costs the kernels nothing)
#   window 2048, 4 key-value heads (8 diagonal, 7 trailing, 49 dead):
#               6.15 and 8.92, 17.70; the dQ partials 2 x 32 x 128 x 16,384
#               float32 (0.5 GiB) against 8 (2.0 GiB) without a window
#               (until PR 50: below)
# By kind of block, from the 2048 and 8192 readings above: a diagonal block
# 10.35 us forward and 16.5 backward, a whole block 19.45 and 34.3 (the full
# call priced so: 20.08 and 34.96 ms, read 19.18 and 34.15); a trailing
# block with the seven dead grid steps of its row 15.6 us forward (the dead
# steps fetch nothing and still owe the scratch's start and the outputs'
# write) and 21.0 backward. A window that is no whole number of blocks (4096
# tokens under 1024 keys: "looped") reads 4.27 and 7.87 ms against 4.42 and
# 6.63 under 2048.
# Since PR 50 (the backward sums dQ^T over a head's blocks of keys itself,
# ``_bwd_kernel``; same bench, my chip runs, the parent beside the change in
# one call, at the three cells' shapes): ``flash_bwd`` alone, what the wall
# time of forward plus backward holds besides the two kernels
# (``round_kernels_ms``: the V^T, K^T, O^T and dQ^T swaps, ``delta``, dQ's
# rounding; before, XLA's sum of the partials too), that wall time, and the
# bytes of dQ the call writes for XLA; ``flash_fwd`` unmoved throughout:
#   192 / 128 at 8,192, 64 heads:   23.62 -> 23.48 ms, 6.51 -> 4.83, 43.35 ->
#               41.52; 1,610.6 -> 402.7 MB
#   128 / 128 at 16,384, 32 on 4:   34.15 -> 33.51, 4.88 -> 2.22, 58.20 ->
#               54.91; 2,147.5 -> 268.4 MB
#     under a window of 2,048:      8.92 -> 9.10, 2.64 -> 2.22, 17.71 -> 17.48;
#               536.9 -> 268.4 MB (no dead step wrote zeros here before, so
#               the kernel pays for its copies and gains nothing back)
#   64 / 128 at 16,384, 20 on 10:   19.28 -> 19.28, 1.91 -> 1.12, 32.84 ->
#               32.05; 671.1 -> 83.9 MB
#     under a window of 512:        3.73 -> 3.77, 1.19 -> 1.07, 8.02 -> 7.94
#   64 / 64 at 1,024 (one block of keys a head: the kernel's body is the
#               parent's): 0.975 -> 0.975, 2.09 -> 2.09
# Output and the three gradients against ``attention_reference`` in float32
# on the chip: the same five digits as the parent at every shape (dQ within
# 0.00285 to 0.00392 of the largest entry).
_FLASH_MIN_SEQ = 512
# (key width, value width) of a head the kernel was measured at. (192, 128):
# latent attention's per-head form, PERF.md section 6, PR 31. (64, 128): a
# differential layer's map, PR 48; (256, 256): PR 56 (both: this file's end)
_FLASH_HEAD_DIMS = ((64, 64), (64, 128), (128, 128), (192, 128), (256, 256))


def auto_attention(q, v=None) -> str:
    """What ``attention="auto"`` runs for causal self-attention of ``q``
    [B, T, H, d] (and ``v`` [B, T, H_kv, d_v], where values have a width of
    their own; how many heads of keys and values the query heads share, and
    a window, change nothing of the choice: the kernel was measured with
    both, PR 44) on the default backend: "flash" or "xla". Decided from the
    backend and from the operands' types alone: the length, the pair (key
    width, value width) of a head, which has to be one the kernel was
    measured at (``_FLASH_HEAD_DIMS``), and the mesh ``q`` is traced under:
    a mesh axis the kernel's ``shard_map`` wrapper does not map (``model``
    under tensor parallelism, ``seq``, ``expert``) would leave the Mosaic
    call to the partitioner, which refuses it, so there "auto" stays on
    XLA's attention as it was before the kernel was chosen anywhere
    (ROADMAP Speed 9a). A kernel that then fails to lower raises."""
    _, seq_len, _, head_dim = q.shape
    widths = (head_dim, head_dim if v is None else v.shape[-1])
    measured = (widths in _FLASH_HEAD_DIMS and seq_len >= _FLASH_MIN_SEQ
                and seq_len % 128 == 0
                and (seq_len <= _MAX_RESIDENT or seq_len % 1024 == 0))
    if (jax.default_backend() == "tpu" and measured
            and not unmapped_mesh_axes(q)):
        return "flash"
    return "xla"


def causal_self_attention(q, k, v, attention: str = "auto",
                          window: Optional[int] = None):
    """Causal self-attention of ``q`` [B, T, H, d], ``k`` [B, T, H_kv, d]
    and ``v`` [B, T, H_kv, d_v] in a model's own layout (one length; H_kv
    divides H, query head ``j`` reading key-value head ``j // (H / H_kv)``;
    ``v`` may have a width of its own, the output's; under ``window`` a
    query sees its own position and the ``window - 1`` before it) by the
    path ``attention`` names: "flash", this module's kernel; "xla",
    ``jax.nn.dot_product_attention``, on this runtime plain XLA fusions
    that write the [B, H, T, T] scores to HBM (it takes one width and no
    window here, so for values of another width or under a window the same
    program is written out: ``attention_reference``); "auto", whichever
    ``auto_attention`` finds for ``q`` and ``v``.

    The kernel's path has three boundaries, chosen here from the call's
    shapes and written into the runtime's ring, one ``attention/boundary``
    record a traced call: the kernels address the model's own [B, T, H x
    d] arrays throughout (``heads_a_lane_tile``; ``model_arrays`` 1); past
    one block of keys the output, its cotangent, dK and dV alone cross in
    such arrays (``results_in_model_arrays``; ``model_results`` 1) while
    XLA folds q, k and v into [B x H, T, d] and turns dQ back; or XLA turns
    everything (both 0)."""
    if attention == "auto":
        attention = auto_attention(q, v)
    bhsd = lambda t: t.transpose(0, 2, 1, 3)
    if attention == "flash":
        (b, seq, heads, d), kv_heads, d_v = q.shape, k.shape[2], v.shape[3]
        a_tile = heads_a_lane_tile(seq, heads, kv_heads, d, d_v)
        results = results_in_model_arrays(seq, d, d_v)
        steptrace.record_counters("attention/boundary", {
            "tokens": seq, "heads": heads, "kv_heads": kv_heads,
            "d_qk": d, "d_v": d_v, "window": window or 0,
            "heads_a_lane_tile": a_tile, "model_arrays": int(a_tile > 0),
            "model_results": int(results)})
        if a_tile or results:
            lanes = lambda t: t.reshape(b, seq, -1)
            return flash_attention(
                lanes(q), lanes(k), lanes(v), causal=True, window=window,
                heads=heads).reshape(b, seq, heads, d_v)
        return flash_attention(
            bhsd(q), bhsd(k), bhsd(v), causal=True, window=window
        ).transpose(0, 2, 1, 3)
    if attention == "xla":
        if v.shape[-1] != q.shape[-1] or window is not None:
            return attention_reference(
                bhsd(q), bhsd(k), bhsd(v), causal=True,
                window=window).transpose(0, 2, 1, 3)
        return jax.nn.dot_product_attention(q, k, v, is_causal=True)
    raise ValueError(f"attention={attention!r}: expected auto, xla or flash")


# What recomputation keeps of the kernel: the two residuals of the forward
# rule that only the kernel can make (``q``, ``k`` and ``v`` come back from a
# block's projections). ``_flash_pallas_fwd`` names them, and hands the named
# output on, so that what a block computes from it is recomputed from the kept
# copy. Without a policy a name is the identity and lowers to nothing.
# What a layer then holds, where the kernels address the model's arrays
# (``heads_a_lane_tile``: GPT-2's calls), is the output as the kernel wrote
# it, a dense [B, T, H x d_v], and [B, lane tiles, heads a tile, T] float32.
# Where their results alone cross in the model's arrays
# (``results_in_model_arrays``: past one block of keys at one width of whole
# lane tiles) it is again the dense [B, T, H x d_v] that the kernel wrote,
# which the backward kernel reads a second time for ``delta``, and [B x H,
# 1, T] float32.
# On the last boundary it is the [B x H, T, d_v] swap of what the kernel
# wrote and [B x H, 1, T] float32; at a value width of 64 the swap, kept,
# becomes a copy with its 64-wide rows padded to the 128 lanes (until PR 51
# GPT-2 XL's: 93 MiB a layer of plan where the output's bytes are 50, and a
# copy more in the backward pass, PERF.md section 6, PRs 45 and 51).
_REMAT_NAMES = ("flash_out", "flash_lse")
# and of the selective scan (``ops/ssm.py`` names them in its forward rule):
# its output [B, T, channels] in the compute dtype and the state each chunk
# starts from, [B, T / chunk, states, channels] float32. A block without a
# scan has no such name, and its program is the one it was.
SCAN_REMAT_NAMES = ("ssm_scan_out", "ssm_scan_bounds")
# and of the gated delta rule (``ops/delta.py``): its output [B, T, heads,
# d_v] in the compute dtype and the state each group of chunks starts from,
# [B, heads, T / stride, d_k, d_v] float32, no more bytes than the output
DELTA_REMAT_NAMES = ("delta_rule_out", "delta_rule_bounds")
# and of the scalar-decay state-space scan (``ops/ssm.py:ssd_scan``): its
# output [B, T, heads, head_dim] in the compute dtype and the state each
# stride starts from, [B, T / stride, heads, head_dim, states] float32, no
# more bytes than the output
SSD_REMAT_NAMES = ("ssd_out", "ssd_bounds")


def remat_policy():
    """The policy for ``jax.checkpoint`` / ``nn.remat`` round a block that
    may run a kernel of ``ray_tpu/ops``: keep the selective scan's, the
    scalar-decay scan's and the gated delta rule's output and boundary states, and the flash kernel's
    output and log-sum-exp (per layer
    one [B, T, H, d_v] array in the compute dtype and B x H x T float32;
    dense where the kernels write the model's arrays, else at a value
    width of 64 a lane-padded [B x H, T, 64] of nearly twice those bytes:
    the comment above), recompute everything else. The backward pass of such a block then
    reruns the projections and not the forward kernel. Where the block's
    attention is not the kernel (``xla``, the scan) no such name exists,
    nothing is kept and the program is the one without a policy."""
    return jax.checkpoint_policies.save_only_these_names(
        *_REMAT_NAMES, *SCAN_REMAT_NAMES, *DELTA_REMAT_NAMES,
        *SSD_REMAT_NAMES)


# Keys 64 and values 128 wide (PR 48; ``benches/flash_widths.py --widths
# 64x128 --lengths 16384 --heads 20 --kv-heads 10 --check 1``, my chip run:
# one sequence of 16,384 tokens, 20 query heads on 10 key-value heads, a map
# of a differential attention layer), ``flash_fwd`` and ``flash_bwd`` alone,
# then the wall time of forward plus backward; beside it (64, 64) at the same
# heads, of which such a layer would need four calls where it needs two of
# these:
#   no window:   (64, 128) 11.65 and 19.28 ms, 32.84; (64, 64) 9.58 and
#                19.28, 30.65: the wider value costs the forward 22% and the
#                backward nothing that these readings show. Why not is not
#                known: two of its five matmuls (dP, dV) carry the values'
#                width. A guess that fits, untested: the 64-wide keys'
#                passes set its time. (64, 256) and (128, 128) at the same
#                heads would tell; neither was run
#   window 512:  (64, 128) 3.10 and 3.73 ms, 8.01; (64, 64) 1.99 and 3.72,
#                6.80. 512 keys are a quarter of a 2,048-wide grid block, so
#                all 64 blocks a head are "looped" (``grid_block_kinds``):
#                every diagonal block walks its tiles in loops with traced
#                bounds, and the needed pairs (8.26M a head) are 10% of the
#                peak forward and 20% backward. Left as it is: 14 ms of a
#                785 ms step in the one cell that has such a window.
# Output and the three gradients against ``attention_reference`` in float32
# there: within 0.0029 to 0.0055 of the largest entry, with and without the
# window (bfloat16 operands). XLA's scores at these lengths are [20, 16384,
# 16384] a map and were not tried.
#
# Keys and values 256 wide (PR 56; ``benches/flash_widths.py --widths 256x256
# --lengths 8192 --tokens 32768 --heads 16 --kv-heads 2 --check 1``, my chip
# run: four sequences of 8,192 tokens, 16 query heads on 2 key-value heads,
# the widest head and, with (32 on 4 of 128), the widest group so far): the
# tiles of the narrower widths fit VMEM at twice the width, so none changed;
# a head is 4 x 4 grid blocks (6 whole, 4 diagonal, 6 dead, none looped) on
# the ``model_results`` boundary (one width of whole lane tiles past one
# block of keys). ``flash_fwd`` 16.29 ms and ``flash_bwd`` 30.26 alone, 49.80
# forward plus backward by the wall clock (3.25 of it outside the kernels);
# the needed pairs are 69.5% of the MXU's peak forward and 92.1% backward
# (``attn_kernel_roofline_pct``'s count on the cell's traced step, 8.03 and
# 15.15 ms at two sequences): a 256-wide head fills the MXU's depth where a
# 64-wide one fills a quarter. Output and the three gradients against
# ``attention_reference`` in float32 there: within 0.0030 (out), 0.0044
# (dq), 0.0042 (dk), 0.0032 (dv) of the largest entry. XLA's scores at this
# shape are [16, 8192, 8192] float32 a sequence, 4.3 GB, and were not tried.
