"""The flash-attention kernels: the Pallas bodies, the walks over a grid
block's tiles, the tile sizes and the ``pallas_call`` builders.
``ops/attention.py`` holds what a caller reads (the reference, the scan, the
shape rules, the ``custom_vjp`` and the entries) and calls the four builders
here: ``_flash_pallas`` / ``_flash_pallas_bwd_kernel`` on [B x H, T, d]
operands, ``_flash_pallas_lanes`` / ``_flash_pallas_lanes_bwd`` on a model's
own [B, T, H x d]. Nothing here imports from that file.

Scores are computed transposed, s^T = K Q^T of shape (keys, queries): the
queries lie along the 128 lanes. The softmax statistics (running max, sum,
lse, delta) are then row vectors (1, queries) that broadcast along
sublanes, the accumulators are (d, queries) and fill whole registers at
d = 64, and every matmul is NN or NT on operands as they arrive — given
V^T (forward) and K^T (backward), and with O^T and dQ^T turned back.
Operands go to the MXU in the inputs' dtype and accumulate in float32; the
statistics and accumulators are float32.

Who turns them is the call's boundary, chosen at trace time from its shapes
(``heads_a_lane_tile``; one ``attention/boundary`` record a traced call of
``causal_self_attention`` says which). Where a head is one block of keys
and its width divides the 128 lanes (every GPT-2 call: 1024 tokens, heads
of 64), the kernels take (T, 128) blocks of the model's own [B, T, H x d]
arrays, two 64-wide heads a grid step, and turn V, K, dO and O once a step
in VMEM, O^T and dQ^T once back (``_fwd_kernel_lanes``,
``_bwd_kernel_lanes``): XLA copies nothing round them. Elsewhere (several
blocks of keys a head, grouped key-value heads, keys and values of two
widths, ``flash_attention``'s (b, h, s, d) entry) the operands are
[B x H, T, d] and XLA makes V^T and K^T and turns O^T and dQ^T back, in
HBM, through arrays whose 64-wide rows are padded to the lanes: in GPT-2's
step that was 84 copies, 12 asynchronous copies and 48 asynchronous slices
of head-shaped arrays, 0.64 ms a layer beside the kernels' 1.45 (PERF.md
section 6, PR 51).

One grid step holds up to ``_MAX_RESIDENT`` queries and as many keys (a
whole head at GPT-2's 1024) and computes on tiles of ``block_q`` queries
by ``block_k`` keys. Under a causal mask a tile row ends at the diagonal
and only the tiles the diagonal crosses are masked. Where one grid step
holds the whole sequence, which tiles are live is known when the kernel
is traced and the walk over them is straight-line code. Where a head is
several grid blocks, a block's place in the grid says what the mask leaves
of it (``_grid_kinds``): whole, on the diagonal or dead. The kernel
branches on the place and walks each kind with constant bounds
(``_walk_by_kind``): a diagonal block as a lone block is walked, a whole
block's rows as one loop whose body is a row's tiles in straight-line
code (``_walk_rows``), a dead block not at all (in the forward it still
owes the scratch's start and the outputs' write; in the backward nothing:
dQ^T is summed over a head's blocks of keys by the kernel itself, in HBM,
by the live steps alone, ``_bwd_kernel``), and the index maps clamp dead
blocks to the last live one, which the pipeline does not fetch again. Under
a window over several blocks of keys the grid does not hold a head's dead
blocks at all (``_live_span``, PR 66: a dead step cost 0.33-0.49 us, and
49 of a head's 64 were dead under a window of one block of eight): a row of
the forward's grid is the ``span = window / residents + 1`` blocks of keys
up to its diagonal's, the last step of every row live; a block of keys'
steps in the backward's are the ``span`` blocks of queries from its own on,
for each head of its group; the ``span (span - 1) / 2`` steps that pass the
head's edge (the first rows', the last blocks of keys') are the only dead
ones left, and do nothing. The index maps are ``qi - (span - 1) + step``
and ``ki + step``, clamped: the operands are the same arrays in the same
order, which the benchmark's readers count on. Only
a masked call that is no self-attention in square blocks (lengths that
differ, ``res_q != res_k``) keeps loops with bounds computed from the grid
position. A call under the block-diffusion mask (``blocks``: two streams of
one sequence, ``_BY_BLOCK``) is walked the same way, its grid blocks' kinds
told from their place among the two streams' residents
(``_by_block_place``, ``_walk_by_place``) and its edges at a block's start.
"""

from __future__ import annotations

import collections
import functools
import itertools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops import sparse_index
from ray_tpu.ops.mosaic import compiler_params

NEG_INF = -1e30

# The constants below were read on a v5e; the tables are in
# ``benches/flash_widths.py``'s docstring, with the command for each.
# The most queries, and keys, one grid step holds (a whole head at GPT-2's
# 1024); a longer call is several grid blocks a head, walked by kind (PR 38).
_MAX_RESIDENT = 2048
# The narrowest window whose own length a call's grid steps hold instead
# (``_block_sizes``), read at 8,192 tokens, 32 query heads on 4 of 128, under
# 1,024 keys (PR 62: 4.48 and 6.83 ms forward and backward a call of two
# sequences against 5.51 and 9.77 with every block looped). A window of 512
# is one cell's (``phi-4-mini-flash``), still looped and not read here.
_WINDOW_RESIDENT_FROM = 1024
# (block_q, block_k) targets: the fastest measured for each kernel alone at
# (192, 1024, 64) bf16 causal (PR 25); the backward's five matmuls pay more
# for the dead half of a diagonal tile than for smaller tiles' loop steps.
_FWD_TILES, _BWD_TILES = (512, 512), (256, 256)
# The most bytes of a block of queries' float32 dQ^T sum that one DMA moves,
# in a backward step over several blocks of keys (``_bwd_kernel``: a copy is
# whole rows of tiles, at least one), read at the three cells' shapes (PR 50).
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
            block_k: int, rel, window: Optional[int] = None, edge=None):
    """s^T (block_k, block_q) of key tile ``c``, keys along sublanes: scaled
    here unless q came scaled, and masked (``_EDGES``) where the diagonal
    or the window's far edge crosses; given ``edge`` (a block-diffusion
    call's: ``_BY_BLOCK``), where that edge does."""
    s = _dot(k, q, _NT)
    if not fold:
        s = s * sm_scale
    if masked and edge is not None:
        # a key's position and the start of a query's own block of
        # ``blocks`` tokens, both less the q tile's first position, which
        # is itself the start of a block
        blocks, near, far = edge    # the sides in blocks: ``_SIDES``
        key = lax.broadcasted_iota(jnp.int32, s.shape, 0) + (c * block_k - rel)
        own = lax.broadcasted_iota(
            jnp.int32, (1, s.shape[1]), 1) // blocks * blocks
        seen = key < own + far * blocks
        if near is not None:
            seen = seen & (key >= own + near * blocks)
        s = jnp.where(seen, s, NEG_INF)
    elif masked:
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
          longest_first: bool = False, edge=None):
    """(bounds, order) of a grid block's ``n_q`` rows of tiles: row ``j``'s
    bounds from ``_live_tiles``, under a window with those of
    ``_window_tiles`` after them (under an ``edge`` with a near side, a
    block-diffusion call's "own" blocks, the tiles that hold the q tile's
    own positions, every one masked), and the order in which ``_walk_rows``
    walks the rows: None for one loop over ``j``, which with ``alike_loop``
    rows are whose bounds are constants, the same for all and leave no tile
    masked (a whole grid block's, a dead one's); else their indices, with
    ``longest_first`` from the last row up where bounds are constants and
    leave the last row more live tiles than the first (a diagonal
    block's)."""
    def bounds_of(j):
        rel = rel0 + j * block_q
        if edge is not None and edge[1] is not None:
            first = rel // block_k
            return (first, (rel + block_q - 1) // block_k + 1, first, first)
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
               longest_first: bool = False, edge=None):
    """``row(j, n_full, n_live)`` for each of a grid block's ``n_q`` rows of
    tiles, under a window ``row(j, n_full, n_live, n_start, n_clear)``, in
    the order ``_rows`` gives: rows alike as one loop over ``j``, a row a
    step, so that the row's tiles stay straight-line code and the kernel's
    size stays a row's."""
    bounds, order = _rows(rel0, n_q, n_k, block_q, block_k, causal,
                          alike_loop, window, longest_first, edge)
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


def _live_span(nq: int, nk: int, res_q: int, res_k: int, offset: int,
               causal: bool, window: Optional[int]) -> Optional[int]:
    """How many blocks of keys a block of queries' window can leave anything
    of, the diagonal's the last of them (and of queries a block of keys',
    its own the first), where the call's grid holds those alone: under a
    window over several blocks of keys whose kinds are told apart. None
    where the grid holds every block of a head: any other call."""
    if window is None or nk == 1 or not causal or not _kinds_told_apart(
            nq, nk, res_q, res_k, offset, causal, window):
        return None
    return window // res_k + 1


def _grid_steps(nq: int, nk: int, span: Optional[int], dead: int) -> dict:
    """{"steps": the grid steps a head that a call launches, "dead_steps":
    those of them the mask leaves nothing of}: every block of the head and
    its ``dead`` ones, or under ``span`` (``_live_span``) a row's ``span``,
    of which the first ``span - 1`` rows' (the backward's last columns')
    pass the head's edge by 1 + ... + (span - 1)."""
    if span is None:
        return {"steps": nq * nk, "dead_steps": dead}
    return {"steps": nq * span, "dead_steps": span * (span - 1) // 2}


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


# The block-diffusion mask over two streams of one sequence, a noisy copy
# [0, L) and a clean copy [L, 2L), both cut into blocks of ``blocks`` tokens:
# a noisy query sees the noisy keys of its own block and the clean keys of
# the blocks before it; a clean query sees the clean keys of its own block
# and of those before it, and no noisy key. A grid step's residents lie in
# one stream (``_by_block_grid``: L is a whole number of them, ``n`` a
# stream), so a grid block's place says its kind as under a causal mask:
# "whole", "dead", or one of three that an edge crosses, each with the
# (near, far) side of what a query sees, counted from the start of its own
# block: "own" (noisy on noisy: the q tile's own positions' tiles alone),
# "strict" (noisy on the clean copy of its stream's block) and "inclusive"
# (clean on clean).
_BY_BLOCK = ("whole", "own", "strict", "inclusive", "dead")
_SIDES = {"own": (0, 1), "strict": (None, 0), "inclusive": (None, 1)}


def _by_block_place(qi, ki, n: int) -> dict:
    """{kind: whether grid block (qi, ki) of a block-diffusion call is of
    it}, for the grid's own (traced) indices."""
    q_noisy, k_noisy, q_clean, k_clean = qi < n, ki < n, qi >= n, ki >= n
    qc, kc = jnp.where(q_clean, qi - n, qi), jnp.where(k_clean, ki - n, ki)
    same = qc == kc
    place = {"whole": k_clean & (kc < qc), "own": q_noisy & k_noisy & same,
             "strict": q_noisy & k_clean & same,
             "inclusive": q_clean & k_clean & same}
    live = functools.reduce(lambda a, b: a | b, place.values())
    return place | {"dead": jnp.logical_not(live)}


def by_block_counts(n: int) -> dict:
    """{kind: grid blocks of it a head} of a block-diffusion call whose
    streams are ``n`` grid blocks each: of the (2n)^2, n (n - 1) whole (a
    triangle a stream of queries), n of each kind an edge crosses, the rest
    dead (clean queries on noisy keys, noisy on noisy off the diagonal,
    everything past a diagonal)."""
    live = {"whole": n * (n - 1), "own": n, "strict": n, "inclusive": n}
    return live | {"dead": 4 * n * n - sum(live.values())}


def _walk_by_place(walk, place: dict, res: int, blocks: int, kinds,
                   live_only: bool = False):
    """``walk(rel0, edge)`` for this grid block of a block-diffusion call,
    a branch a kind present (``kinds``), each with constant tile bounds:
    ``_walk_by_kind``'s whole and dead blocks, and a block on a diagonal
    with its ``edge`` (blocks, near, far) for ``_scores`` and ``_rows``.
    With ``live_only`` (the backward's) a dead block is not walked."""
    straight = {"whole": (res - 1, None), "dead": (-res, None)} | {
        kind: (0, (blocks, *sides)) for kind, sides in _SIDES.items()}
    for kind in kinds:
        if not (live_only and kind == "dead"):
            pl.when(place[kind])(functools.partial(walk, *straight[kind]))


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


def _selected(s, sel_ref, c, cols):
    """Scores ``s`` (block_k, block_q) of key tile ``c`` with every pair
    that a selection's mask leaves out at NEG_INF. The mask holds the causal
    edge too. ``sel_ref`` holds the pairs as bits, 32 keys a word
    (``sparse_index.pack``): the tile's block_k / 32 rows of words are
    expanded in registers, an ``and`` a register of scores."""
    words = s.shape[0] // sparse_index.KEYS_A_WORD
    rows = _tile(c, words, sel_ref.shape[0] // words)
    return jnp.where(sparse_index.bits(sel_ref[rows, cols]) != 0, s, NEG_INF)


def _with_selection(kernel, at: int):
    """``kernel`` with the operand at place ``at`` of its refs, a
    selection's mask, handed over as ``sel_ref``: the kernels' own operands
    keep their places and their order, which the benchmark's readers count
    on."""
    def selected(*refs):
        return kernel(*refs[:at], *refs[at + 1:], sel_ref=refs[at])
    return selected


def _fwd_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                offset: int, static: bool, kinds, window: Optional[int],
                rows_out: bool = False, blocks: Optional[int] = None,
                span: Optional[int] = None, sel_ref=None):
    """``o_ref`` is this block of queries' O^T (d_v, resident queries), or
    with ``rows_out`` its O (resident queries, d_v): a head's lanes of a
    model's own [B, T, H x d_v] array (``results_in_model_arrays``), for
    which a row of tiles' float32 accumulator is turned here, in VMEM.
    ``blocks``: the block-diffusion mask's block length (``_BY_BLOCK``).
    The grid's last axis is a row's blocks of keys, all ``nk`` of them, or
    with ``span`` (``_live_span``) the last ``span`` up to the diagonal's,
    which is then every row's last step; a step before a head's first
    block of keys does nothing. ``sel_ref`` (``_with_selection``): this
    block's (resident keys / 32, resident queries) of a selection's mask,
    words of 32 keys' bits, under which every live tile is masked by what
    they say."""
    qi, step_k = pl.program_id(1), pl.program_id(2)
    n_steps = pl.num_programs(2)
    res_q, res_k = q_ref.shape[0], k_ref.shape[0]
    n_q, n_k = res_q // block_q, res_k // block_k
    if span is None:
        ki, live = step_k, None
        rel0 = offset if static else qi * res_q + offset - ki * res_k
    else:
        # block of keys qi - (span - 1) + step_k; before the head's first,
        # the place of a dead block
        live = qi + step_k >= span - 1
        rel0 = jnp.where(live, (span - 1 - step_k) * res_k, -res_k)
    fold = _scale_folds(q_ref.dtype, sm_scale)
    scores = functools.partial(_scores, sm_scale=sm_scale, fold=fold,
                               block_k=block_k, window=window)

    @pl.when(step_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def walk(rel0, edge=None):
        def row(j, *bounds):
            cols = _tile(j, block_q, n_q)
            rel = rel0 + j * block_q
            q = _scaled(q_ref[cols, :], sm_scale, fold)

            def step(c, carry, masked):
                rows = _tile(c, block_k, n_k)
                s = scores(k_ref[rows, :], q, c, masked=masked, rel=rel,
                           edge=edge)
                if sel_ref is not None:
                    s, masked = _selected(s, sel_ref, c, cols), True
                return _fold_tile(s, carry, masked, lambda: vt_ref[:, rows])

            m, l, acc = _walk(
                step, (m_scr[:, cols], l_scr[:, cols], acc_scr[:, cols]),
                *bounds)
            m_scr[:, cols], l_scr[:, cols], acc_scr[:, cols] = m, l, acc

            @pl.when(step_k == n_steps - 1)
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
                   window, edge=edge)

    if blocks:
        _walk_by_place(walk, _by_block_place(qi, ki, n_steps // 2), res_k,
                       blocks, kinds)
    else:
        _walk_by_kind(walk, rel0, res_q, res_k, kinds, window, live)


def _bwd_kernel(q_ref, k_ref, v_ref, kt_ref, do_ref, lse_ref, delta_ref,
                dqt_ref, dk_ref, dv_ref, dk_scr, dv_scr, *sums,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                offset: int, static: bool, kinds, window: Optional[int],
                nq: int, group: int, o_rows: bool = False,
                blocks: Optional[int] = None, span: Optional[int] = None,
                sel_ref=None):
    """dQ^T of this (resident keys, resident queries) pair, and dK, dV
    accumulated over the queries: s and p are recomputed once for all
    three. The last grid axis walks the ``nq`` blocks of queries of each of
    the ``group`` query heads that read this key-value head, one head after
    another, so dK and dV gather the whole group in the scratch; with
    ``span`` (``_live_span``) a head's steps are the ``span`` blocks of
    queries from this block of keys' own on, the ones whose window holds any
    of its keys, and a step past a head's last block of queries does
    nothing.

    One block of keys a head: ``dqt_ref`` is this block of queries' place
    in VMEM and a row of tiles writes its dQ^T there. Several: a block of
    queries comes back once for every block of keys that sees it (under
    ``span`` and no group on consecutive steps: the last of one block of
    keys, after which ``_finalize`` waits for its way back, and the first
    of the next), so its float32 sum lives in HBM (``dqt_ref`` is the whole
    array)
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
    changes nothing in here. ``sel_ref``: as ``_fwd_kernel``'s."""
    ki, step_q = pl.program_id(1), pl.program_id(2)
    n_steps = pl.num_programs(2)
    steps = nq if span is None else span    # a query head's
    at = step_q if group == 1 else step_q % steps
    res_q, res_k = q_ref.shape[0], k_ref.shape[0]
    n_q, n_k = res_q // block_q, res_k // block_k
    if span is None:
        qi, live = at, None
        rel0 = offset if static else qi * res_q + offset - ki * res_k
    else:
        # past the head's last block of queries, the place of a dead block
        qi = ki + at
        live = qi < nq
        rel0 = jnp.where(live, at * res_q, -res_k)
    fold = _scale_folds(q_ref.dtype, sm_scale)
    scores = functools.partial(_scores, sm_scale=sm_scale, fold=fold,
                               block_k=block_k, window=window)
    if sums:
        nk = pl.num_programs(1)
        sum_scr, had_scr, sems, pending = sums
        if blocks:
            # the first block of keys a block of queries sees is its own
            # (noisy) or the clean stream's first
            kind_here = _by_block_place(qi, ki, nq // 2)
            adds = ki != jnp.minimum(qi, nq // 2)
        else:
            first_k = (0 if window is None else
                       _first_live_k(qi, res_q, res_k, offset, nk, window))
            if span is None:
                last_k = (_last_live_k(qi, res_q, res_k, offset, nk)
                          if causal else nk - 1)
                live = (ki >= first_k) & (ki <= last_k)
            adds = ki != first_k
        head = pl.program_id(0) * group + step_q // steps
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

    def walk(rel0, edge=None):
        def row(j, *bounds):
            cols = _tile(j, block_q, n_q)
            rel = rel0 + j * block_q
            q, do = _scaled(q_ref[cols, :], sm_scale, fold), do_ref[cols, :]
            lse, delta = lse_ref[:, cols], delta_of(cols, do)  # (1, block_q)

            def step(c, dqt, masked):
                rows = _tile(c, block_k, n_k)
                s = scores(k_ref[rows, :], q, c, masked=masked, rel=rel,
                           edge=edge)
                if sel_ref is not None:
                    s = _selected(s, sel_ref, c, cols)
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
                    window, bool(sums), edge)
        order = _rows(*the_rows)[1] or range(n_q)
        if sums:
            @pl.when(adds)
            def _fetch():
                for c in sorted(range(copies), reverse=order[0] != 0):
                    fetch(c).start()

        _walk_rows(row, *the_rows)
        if sums:
            pending[0] = 1

    if blocks:
        _walk_by_place(walk, kind_here, res_k, blocks, kinds,
                       live_only=True)
    else:
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
                 block_k: Optional[int], targets,
                 window: Optional[int] = None):
    """(block_q, block_k, resident queries, resident keys) for a call.
    Queries and keys both lie along lanes somewhere, so a tile size the
    caller does not give is a multiple of 128 up to the kernel's target,
    or the whole length. A grid step holds up to ``_MAX_RESIDENT`` queries
    and keys; where a head is several grid blocks anyway, under a
    ``window`` from ``_WINDOW_RESIDENT_FROM`` keys up that whole tiles fill
    and that divides both lengths, it holds the window's own length: the
    window is then one whole block, and a block's place says its kind
    (``_grid_kinds``: diagonal, trailing, dead) where half a block's window
    leaves every block ``looped``."""
    block_q = min(block_q or _largest_block(q_len, targets[0], 128), q_len)
    block_k = min(block_k or _largest_block(k_len, targets[1], 128), k_len)
    assert q_len % block_q == 0, (q_len, block_q)
    assert k_len % block_k == 0, (k_len, block_k)
    residents = lambda most: (
        _largest_block(q_len, max(most, block_q), block_q),
        _largest_block(k_len, max(most, block_k), block_k))
    if (window is not None and min(q_len, k_len) > _MAX_RESIDENT
            and _WINDOW_RESIDENT_FROM <= window < _MAX_RESIDENT
            and residents(window) == (window, window)):
        return block_q, block_k, window, window
    return (block_q, block_k, *residents(_MAX_RESIDENT))


def _compiler_params(interpret: bool, width: int, keys_add: bool = False,
                     selected: bool = False):
    """``width`` is the widest head dimension of the call: up to 128 lanes
    the residents fit the compiler's own 16 MiB of VMEM; past it (keys of
    192 are laid out as 256 lanes) the backward's residents take 16.5 MiB
    at 2048 queries and keys, so the kernel asks for 32 of the chip's 128.
    With ``keys_add`` (the backward over several blocks of keys) the steps
    along the grid's second axis add to one sum in HBM, one after another:
    that axis is no core's to split. Under a selection (``selected``) a
    step also holds a (resident keys / 32, resident queries) block of the
    mask's words, 512 KiB at 2048 each way and twice that in flight, and a
    tile's expanded bits beside its scores: 32 MiB."""
    return compiler_params(
        interpret,
        ("parallel", "arbitrary" if keys_add else "parallel", "arbitrary"),
        32 * 2**20 if selected or width > 128 else None)


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
    others with constant ones. With them "steps" and "dead_steps": the
    grid steps a head that the call launches and how many of them the mask
    leaves nothing of (``_grid_steps``). The forward's grid unless
    ``backward``: the two kernels' tiles differ, and at some lengths what a
    grid step holds with them."""
    window = _window_of(window, k_len)
    _, _, res_q, res_k = _block_sizes(
        q_len, k_len, block_q, block_k,
        _BWD_TILES if backward else _FWD_TILES, window)
    grid = (q_len // res_q, k_len // res_k, res_q, res_k, k_len - q_len,
            causal, window)
    kinds = _grid_kinds(*grid)
    kinds.update(_grid_steps(*grid[:2], _live_span(*grid), kinds["dead"]))
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
    and values, the batch folded into both (``heads``, ``kv_heads``), how
    many dQ arrays a backward call leaves for XLA to sum (``dq_partials``:
    0 for every shape since PR 50, the kernel sums them itself; until then
    one a block of keys that a block of queries sees), and what the call's
    grid launches of the head's blocks (``steps``, ``dead_steps``:
    ``_grid_steps`` under the call's ``_live_span``)."""
    grid = (nq, nk, res_q, res_k, offset, causal, window)
    counts = _grid_kinds(*grid)
    steptrace.record_counters("attn/grid_blocks", {
        **counts, "queries": nq * res_q, "keys": nk * res_k,
        "backward": int(backward), "window": window or 0,
        "heads": heads[0], "kv_heads": heads[1],
        "dq_partials": 0,
        **_grid_steps(nq, nk, _live_span(*grid), counts["dead"])})
    return tuple(kind for kind in _KINDS if counts[kind])


def _kernel_name(base: str, window: Optional[int],
                 blocks: Optional[int] = None,
                 topk: Optional[int] = None) -> str:
    """``flash_fwd`` / ``flash_bwd``, of a windowed call
    ``flash_fwd_w<window>``, of a block-diffusion call
    ``flash_fwd_bd<blocks>`` and of a call under a selection of ``topk``
    keys a query ``flash_fwd_sel<topk>``: the benchmark's readers find the
    kernels, and a call's mask, by these names."""
    if topk:
        return f"{base}_sel{topk}"
    if blocks:
        return f"{base}_bd{blocks}"
    return base if window is None else f"{base}_w{window}"


def by_block_fits(length: int, blocks: int, block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> bool:
    """Whether the kernels take a block-diffusion call over ``length``
    positions (two streams of half of it) in blocks of ``blocks``: each
    stream a whole number of blocks and of the residents both kernels
    choose for it, which queries and keys share, in tiles that are whole
    blocks (an edge then starts at a q tile's first position) and, where
    the kernels choose them, whole lane tiles."""
    half = length // 2
    if length % 2 or half % blocks:
        return False
    for targets in (_FWD_TILES, _BWD_TILES):
        try:
            bq, bk, res_q, res_k = _block_sizes(half, half, block_q, block_k,
                                                targets)
        except AssertionError:
            return False
        if res_q != res_k or bq % blocks or bk % blocks:
            return False
        # a tile the kernels choose is whole lane tiles, never an odd
        # length whole
        if (block_q is None and bq % 128) or (block_k is None and bk % 128):
            return False
    return True


def by_block_kinds(length: int, blocks: int, block_q: Optional[int] = None,
                   block_k: Optional[int] = None, *,
                   backward: bool = False) -> dict:
    """{kind: grid blocks of it a head} of a block-diffusion call over
    ``length`` positions, as ``grid_block_kinds`` says it of the other
    masks: ``by_block_counts`` of the residents the kernel chooses."""
    res = _block_sizes(length // 2, length // 2, block_q, block_k,
                       _BWD_TILES if backward else _FWD_TILES)[2]
    return by_block_counts(length // 2 // res)


def _by_block_grid(length: int, blocks: int, block_q, block_k,
                   backward: bool, heads):
    """(block_q, block_k, residents, blocks of residents a stream, the
    kinds of grid block the call holds) of a block-diffusion call, whose
    residents are chosen for ONE stream, so that no grid step holds both;
    writes the call's ``attn/grid_blocks`` record, as ``_kinds_present``
    does under the other masks."""
    assert by_block_fits(length, blocks, block_q, block_k), (length, blocks)
    block_q, block_k, res, _ = _block_sizes(
        length // 2, length // 2, block_q, block_k,
        _BWD_TILES if backward else _FWD_TILES)
    n = length // 2 // res
    counts = by_block_counts(n)
    steptrace.record_counters("attn/grid_blocks", {
        **counts, "queries": length, "keys": length,
        "backward": int(backward), "window": 0, "blocks": blocks,
        "heads": heads[0], "kv_heads": heads[1], "dq_partials": 0,
        **_grid_steps(2 * n, 2 * n, None, counts["dead"])})
    return (block_q, block_k, res, n,
            tuple(kind for kind in _BY_BLOCK if counts[kind]))


def _flash_pallas(q, k, v, *, causal: bool, sm_scale: float,
                  block_q: Optional[int], block_k: Optional[int],
                  interpret: bool, window: Optional[int] = None,
                  heads: Optional[int] = None,
                  blocks: Optional[int] = None, selected=None,
                  topk: Optional[int] = None):
    """q: (B, S, D) with batch*heads folded into B; k: (B_kv, S, D) and v:
    (B_kv, S, Dv) with B a multiple of B_kv: query head ``i`` reads
    key-value head ``i // (B // B_kv)``, through the index maps.
    -> (out (B, S, Dv), lse) with lse (B, 1, S) float32. Given ``heads``
    (``results_in_model_arrays``), of which B is a multiple, out is a
    model's own (B / heads, S, heads x Dv): the kernel writes head ``i %
    heads``'s lanes of it, a block of queries at a time. Given ``blocks``,
    S is two streams under the block-diffusion mask (``_BY_BLOCK``). Given
    ``selected`` (with ``heads`` and ``causal``; a mask the step computed,
    ``ops/sparse_index.py``: int32 [B / heads, S / 32, S queries], a bit a
    pair, set where the query sees the key, nothing past the diagonal),
    every head of a batch row reads that row's mask, a grid block's
    (resident keys / 32, resident queries) words a step; ``topk`` names the
    kernel."""
    b, q_len, d = q.shape
    k_len, d_v = k.shape[1], v.shape[2]
    group = b // k.shape[0]
    if selected is not None:
        assert (causal and heads and window is None and not blocks
                and selected.shape == (
                    b // heads, k_len // sparse_index.KEYS_A_WORD, q_len)), (
                    selected.shape, q.shape, heads)
    if blocks:
        assert causal and window is None and q_len == k_len, (q.shape, k.shape)
        block_q, block_k, res_q, n, kinds = _by_block_grid(
            q_len, blocks, block_q, block_k, False, (b, k.shape[0]))
        res_k = res_q
    else:
        block_q, block_k, res_q, res_k = _block_sizes(
            q_len, k_len, block_q, block_k, _FWD_TILES, window)
    nq, nk = q_len // res_q, k_len // res_k
    offset = k_len - q_len
    span = _live_span(nq, nk, res_q, res_k, offset, causal, window)
    if window is not None and offset:
        # no model sends one, and the blocks' kinds, the index maps' clamps
        # and the backward's first and last live blocks (``_first_live_k``,
        # ``_last_live_q``) were never held to a reference at such lengths
        raise NotImplementedError(
            f"flash_attention: a window over lengths that differ ({q_len} "
            f"queries, {k_len} keys) is the scan's or the reference's")

    if blocks:
        # a dead block fetches what the next live one will: a noisy query's
        # own block, the clean stream's first, and past a diagonal the last
        kmap = lambda qi, ki: jnp.where(
            ki < n, jnp.where(qi < n, qi, n), jnp.minimum(ki, n + qi % n))
    elif span:
        # a row's last ``span`` blocks of keys up to its diagonal's; a step
        # before the head's first block fetches that block, the next live
        kmap = lambda qi, step: jnp.maximum(qi - (span - 1) + step, 0)
    elif causal and nk > 1 and window is not None:
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
        blocks=blocks, span=span,
        kinds=kinds if blocks else _kinds_present(
            nq, nk, res_q, res_k, offset, causal, False, window,
            (b, k.shape[0])))
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
    masks, mask_specs = (), []
    if selected is not None:
        kernel, masks = _with_selection(kernel, 3), (selected,)
        mask_specs = [pl.BlockSpec(
            (None, res_k // sparse_index.KEYS_A_WORD, res_q),
            lambda bi, qi, ki: (bi // heads, kmap(qi, ki), qi))]
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, nq, span or nk),
        in_specs=[
            pl.BlockSpec((None, res_q, d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((None, res_k, d),
                         lambda bi, qi, ki: (kv_head(bi), kmap(qi, ki), 0)),
            pl.BlockSpec((None, d_v, res_k),
                         lambda bi, qi, ki: (kv_head(bi), 0, kmap(qi, ki))),
            *mask_specs,
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
        compiler_params=_compiler_params(interpret, max(d, d_v),
                                         selected=bool(masks)),
        interpret=interpret,
        name=_kernel_name("flash_fwd", window, blocks, topk),
    )(q, k, jnp.swapaxes(v, 1, 2), *masks)
    return (jnp.swapaxes(out, 1, 2) if heads is None else out), lse


def _flash_pallas_bwd_kernel(q, k, v, do, lse, delta, *, causal: bool,
                             sm_scale: float, block_q: Optional[int],
                             block_k: Optional[int], interpret: bool,
                             window: Optional[int] = None,
                             heads: Optional[int] = None,
                             dq_turned: bool = True,
                             blocks: Optional[int] = None, selected=None,
                             topk: Optional[int] = None):
    """-> (dq, dk, dv) of ``_flash_pallas``'s call, shaped as q, k and v
    [B x H, T, d] from ``do`` shaped as its output; given ``heads``, ``do``
    is the cotangent of the model's own [B, T, heads x d_v] output, read a
    head's lanes at a time, ``delta`` is that output itself, from which the
    kernel makes the rows, and dk, dv are written as model's arrays
    likewise, [B, T, key-value heads x width]; dq is [B x H, T, d] either
    way, or without ``dq_turned`` dQ^T as the kernel leaves it, [B x H, d,
    T] (float32 where a head has several blocks of keys), for a caller
    whose own kernel reads it next (``ops/rotary.py``). ``selected``,
    ``topk``: ``_flash_pallas``'s."""
    b, q_len, d = q.shape
    b_kv, k_len, d_v = k.shape[0], k.shape[1], v.shape[2]
    group = b // b_kv
    if blocks:
        block_q, block_k, res_q, n, kinds = _by_block_grid(
            q_len, blocks, block_q, block_k, True, (b, b_kv))
        res_k = res_q
    else:
        block_q, block_k, res_q, res_k = _block_sizes(
            q_len, k_len, block_q, block_k, _BWD_TILES, window)
    nq, nk = q_len // res_q, k_len // res_k
    offset = k_len - q_len
    span = _live_span(nq, nk, res_q, res_k, offset, causal, window)
    if blocks:
        # a noisy block of keys is seen by its own block of queries alone;
        # a clean one by its stream's blocks from its own on, in each stream
        qmap = lambda ki, qi: jnp.where(
            ki < n, ki, jnp.maximum(qi, ki - n + jnp.where(qi < n, 0, n)))
    elif span:
        # a block of keys' own block of queries and the ``span - 1`` after
        # it; a step past the head's last block fetches that block again
        qmap = lambda ki, step: jnp.minimum(ki + step, nq - 1)
    elif causal and nk > 1 and window is not None:
        qmap = lambda ki, qi: jnp.clip(
            qi, _first_live_q(ki, res_q, res_k, offset, nq),
            _last_live_q(ki, res_q, res_k, offset, nq, window))
    elif causal and nk > 1:
        qmap = lambda ki, qi: jnp.maximum(
            qi, _first_live_q(ki, res_q, res_k, offset, nq))
    else:
        qmap = lambda ki, qi: qi
    # the grid: key-value heads, their blocks of keys, and for each the
    # blocks of queries of every query head of the group: all ``nq`` of a
    # head, or the ``span`` that see the block of keys
    steps = span or nq
    if group == 1:
        head, block = (lambda bi, step: bi), (lambda step: step)
    else:
        head = lambda bi, step: bi * group + step // steps
        block = lambda step: step % steps
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
    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, offset=offset, static=nq == nk == 1,
        window=window, nq=nq, group=group, o_rows=heads is not None,
        blocks=blocks, span=span,
        kinds=kinds if blocks else _kinds_present(
            nq, nk, res_q, res_k, offset, causal, True, window,
            (b, b_kv)))
    masks, mask_specs = (), []
    if selected is not None:
        assert heads and selected.shape == (
            b // heads, k_len // sparse_index.KEYS_A_WORD, q_len), (
                selected.shape, q.shape, heads)
        kernel, masks = _with_selection(kernel, 7), (selected,)
        mask_specs = [pl.BlockSpec(
            (None, res_k // sparse_index.KEYS_A_WORD, res_q),
            lambda bi, ki, step: (bi // (heads // group), ki,
                                  qmap(ki, block(step))))]
    dq_t, dk, dv = pl.pallas_call(
        kernel,
        grid=(b_kv, nk, group * steps),
        in_specs=[
            qspec, kspec, vspec,
            pl.BlockSpec((None, d, res_k), lambda bi, ki, step: (bi, 0, ki)),
            dospec, rowspec, rowspec if heads is None else dospec,
            *mask_specs,
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
                                         keys_add=nk > 1,
                                         selected=bool(masks)),
        interpret=interpret,
        name=_kernel_name("flash_bwd", window, blocks, topk),
    )(q, k, v, jnp.swapaxes(k, 1, 2), do, lse, delta, *masks)
    if not dq_turned:
        return dq_t, dk, dv
    return jnp.swapaxes(dq_t.astype(q.dtype), 1, 2), dk, dv


def _lanes_params(interpret: bool, dtype):
    """Batch rows x lane tiles of heads: no step adds to another's. In
    bfloat16 the backward's residents at 2,048 tokens take 10.5 MiB of the
    compiler's own 16; a four-byte type takes twice the blocks' share."""
    return compiler_params(interpret, ("parallel", "parallel"),
                           32 * 2**20 if dtype.itemsize > 2 else None)


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
