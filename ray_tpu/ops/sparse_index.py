"""A learned selection of keys for each query (DeepSeek-V3.2-Exp's sparse
attention, "DSA"): a small indexer scores every earlier key of a query, the
``topk`` highest are the keys the query's attention sees, and the indexer
learns from a KL divergence against the main attention's own probabilities.

For a query ``t`` and a key ``s <= t``, with ``q_idx`` [B, T, J, W] (J index
heads of width W), ``k_idx`` [B, T, W] (ONE index key a token) and ``w`` [B,
T, J] float32:

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])

- ``select``: ``tau[t]``, the ``topk``-th largest of ``I[t, :t + 1]`` (``-inf``
  while the query has fewer than ``topk`` keys), and the set as a MASK of
  BITS, a bit a pair, set where ``s <= t`` and ``I[t, s] >= tau[t]``: int32
  words [B, T / 32, T queries], keys packed along the key axis, queries
  along the lanes, which is how the flash kernels hold a tile of scores
  (``ops/flash_kernels.py:_selected``). A chunk of 256 keys is 8 rows of
  words, the sublanes of one register: bit ``j`` of row ``8 c + i`` is key
  ``256 c + 8 j + i``, so that a reader has the 8 keys of an (8, 128)
  register of scores by ONE ``and`` of the chunk's own register with a
  constant, no shift and no move along the sublanes (``pack``, ``bits``;
  ``unpack`` gives the dense int8 [B, T keys, T queries] back to the twins,
  the dense reference and a side run that wants to look). Exact: a
  bisection over the float32 scores' own bits, 32 counts a row, no
  approximation; keys that tie with the ``topk``-th are all kept, as ``I >=
  lax.top_k(...)[-1]`` keeps them. The mask is made ONCE a layer and every
  head's tiles read it, forward and backward, a recomputed block's too (32
  MiB a sequence of 16,384, which ``remat_policy`` keeps: ``REMAT_NAMES``):
  membership is decided by one piece of arithmetic, and no score is made a
  second time, in another tiling or another pass, to be held against
  ``tau``.
- ``index_kl``: ``sum_t KL(p[t, S_t] || softmax(I[t, S_t]))`` with ``p`` the
  mean over the query heads of the main attention's softmax over the
  selected set, taken as a constant: the gradient reaches ``q_idx``,
  ``k_idx`` and ``w`` alone (``dI = softmax(I) - p`` on the set).

Each has a Pallas kernel and a ``jnp`` twin over blocks of rows: the twin
where the kernels do not run (off a TPU, under a mesh axis that is not the
batch's, at a shape ``fits`` refuses) and their reference. ``auto_impl``
chooses as every op of ``ray_tpu/ops`` does (``mosaic.takes_kernels``).

The kernels. ``select``: a grid step holds 128 queries along the lanes and
makes their scores against every key up to their own, 512 keys at a time,
16 small matmuls a tile, into a [T, 128] scratch of the scores' bits as
sortable integers; 32 passes over the scratch find each query's threshold
bit by bit; a last pass packs the mask's block, 512 keys into 16 rows of
words, and makes the set's log-sum-exp.
``index_kl``: a grid step holds a tile of 512 keys by 512 queries: every
query head's scores against its keys again (what the flash forward made and
could not keep), ``exp(s - lse)`` summed over the heads, the index scores
again, the tile's part of the KL and of ``dI``, and from ``dI`` the tile's
part of the three gradients; its forward rule keeps the gradients
(``REMAT_NAMES``), so a recomputed block does not run it again. Neither
does it run ``select`` again: the flash backward finds the mask among the
kept values, and ``tau`` and the set's log-sum-exp feed the KL alone.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops.mosaic import compiler_params, takes_unmapped_kernel

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
# Queries a grid step of ``select`` (the lanes of one tile: its scratch is
# [T, 128] int32, 8 MiB at 16,384 keys), keys a tile of its walks, and the
# tile of ``index_kl`` (keys x queries); the twin's rows a block.
_SELECT_QUERIES, _SELECT_KEYS = 128, 512
_KL_TILE = 512
_TWIN_ROWS = 256
# the key of a pair past the diagonal: under every float's, -inf's too
_NO_PAIR = jnp.iinfo(jnp.int32).min
# The mask's words: 32 keys each, a chunk of keys the 8 rows of words that
# one register holds along its sublanes (this file's docstring)
KEYS_A_WORD, _CHUNK_ROWS = 32, 8
_CHUNK = KEYS_A_WORD * _CHUNK_ROWS
_BIT = tuple(np.uint32(1 << j).astype(np.int32)
             for j in range(KEYS_A_WORD))
# What recomputation keeps (``ops.remat.remat_policy``): the gradients that
# ``index_kl``'s forward rule made beside the loss and the mask that
# ``select`` packed, so that a recomputed block runs neither kernel a second
# time
_KL_NAMES = ("index_kl_dq", "index_kl_dk", "index_kl_dw")
_MASK_NAME = "index_mask"
REMAT_NAMES = (*_KL_NAMES, _MASK_NAME)


class Selection(NamedTuple):
    """What ``select`` found. ``mask``: int32 [B, T / 32 (whole chunks of
    256 keys), T queries], a bit a pair (``pack``); ``tau``: float32 [B, T],
    a query's threshold; ``lse``: float32 [B, T], the log-sum-exp of a
    query's selected index scores."""
    mask: jax.Array
    tau: jax.Array
    lse: jax.Array


def pairs_selected(seq: int, topk: int) -> int:
    """Query-key pairs a sequence that a selection of ``topk`` leaves:
    every earlier key while there are no more than ``topk``."""
    short = min(seq, topk)
    return short * (short + 1) // 2 + (seq - short) * topk


def fits(q_idx, topk: int) -> bool:
    """Whether the kernels take ``q_idx`` [B, T, J, W]: whole tiles of
    queries and keys, an index head no wider than the lanes, and more keys
    than one tile (below, the twin is as fast)."""
    _, seq, _, width = q_idx.shape
    return (seq % _KL_TILE == 0 and seq >= 2 * _KL_TILE and width <= 128
            and width % 8 == 0 and 0 < topk)


def auto_impl(q_idx, topk: int) -> str:
    """What ``impl=None`` runs for ``q_idx``: "pallas" where ``fits`` admits
    the shapes and a Mosaic call may run where it is traced, under no live
    mesh axis at all (``mosaic.takes_unmapped_kernel``: the KL is one sum
    over the batch, which no batch shard has), else "jnp"."""
    return ("pallas" if fits(q_idx, topk) and takes_unmapped_kernel(q_idx)
            else "jnp")


def precision_of(dtype):
    """The precision of a matmul on index operands of ``dtype``: float32
    operands are multiplied as float32 (on a TPU the default would round
    them to bfloat16 on their way into the MXU), so the caller chooses the
    precision by the type it hands over."""
    return lax.Precision.HIGHEST if dtype == _F32 else None


# ----------------------------------------------------------------------
# the mask's bits
# ----------------------------------------------------------------------

def pack(seen):
    """Membership ``seen`` [..., keys, queries] (bool; ``keys`` whole
    chunks of 256) as words, int32 [..., keys / 32, queries]: bit ``j`` of
    row ``8 c + i`` is key ``256 c + 8 j + i``. Traced in the kernel and in
    the twin alike."""
    *lead, keys, queries = seen.shape
    assert keys % _CHUNK == 0, seen.shape
    by_bit = seen.astype(jnp.int32).reshape(
        *lead, keys // _CHUNK, KEYS_A_WORD, _CHUNK_ROWS, queries)
    place = lax.broadcasted_iota(jnp.int32, by_bit.shape, len(lead) + 1)
    # distinct bits: the sum is their union, the sign bit's wrap included
    return (by_bit << place).sum(axis=-3).reshape(
        *lead, keys // KEYS_A_WORD, queries)


def bits(words):
    """``pack``'s words [rows, queries] (whole chunks of 8 rows) as int32
    [32 x rows, queries], nonzero where the pair's bit is set: a chunk's
    rows ``and`` one constant for each 8 keys, so a register of the result
    is one operation on a register that is already there. For a kernel's
    tile; ``unpack`` is the arrays'."""
    rows = words.shape[0]
    assert rows % _CHUNK_ROWS == 0, words.shape
    return jnp.concatenate([
        words[at:at + _CHUNK_ROWS] & bit
        for at in range(0, rows, _CHUNK_ROWS) for bit in _BIT], axis=0)


def unpack(words):
    """``pack``'s words [B, rows, T queries] as the dense mask, int8 [B, T
    keys, T queries], 1 where the query sees the key: for the twins, the
    dense reference and whoever wants to look at a set. 8 times the bytes:
    nothing on a step's path at a real length reads it."""
    b, rows, seq = words.shape
    by_bit = lax.shift_right_logical(
        words.reshape(b, rows // _CHUNK_ROWS, 1, _CHUNK_ROWS, seq),
        jnp.arange(KEYS_A_WORD, dtype=jnp.int32).reshape(1, 1, -1, 1, 1)) & 1
    return by_bit.reshape(b, rows * KEYS_A_WORD, seq)[:, :seq].astype(
        jnp.int8)


# ----------------------------------------------------------------------
# the twin
# ----------------------------------------------------------------------

def index_scores(q_idx, k_idx, w):
    """I [B, R, T] float32 of the queries ``q_idx`` [B, R, J, W] with ``w``
    [B, R, J] against the keys ``k_idx`` [B, T, W]: the heads summed one
    after another, as the kernels sum them. No mask."""
    total = None
    for j in range(q_idx.shape[2]):
        r = jnp.einsum("brd,bsd->brs", q_idx[:, :, j], k_idx,
                       preferred_element_type=_F32,
                       precision=precision_of(k_idx.dtype))
        part = w[:, :, j, None].astype(_F32) * jnp.maximum(r, 0.0)
        total = part if total is None else total + part
    return total


def _row_blocks(seq: int, rows: Optional[int]) -> int:
    rows = min(rows or _TWIN_ROWS, seq)
    assert seq % rows == 0, (seq, rows)
    return rows


def _by_rows(x, rows: int):
    """[B, T, ...] as [T / rows, B, rows, ...], for ``lax.map``."""
    b, seq = x.shape[:2]
    return x.reshape(b, seq // rows, rows, *x.shape[2:]).swapaxes(0, 1)


def _select_twin(q_idx, k_idx, w, topk: int, rows: Optional[int]):
    b, seq = q_idx.shape[:2]
    rows = _row_blocks(seq, rows)
    keys = jnp.arange(seq)

    def one(args):
        q_blk, w_blk, first = args
        scores = index_scores(q_blk, k_idx, w_blk)
        at = first + jnp.arange(rows)
        seen = keys[None, :] <= at[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        if topk < seq:
            tau = lax.top_k(scores, topk)[0][..., -1]
        else:
            tau = jnp.full(scores.shape[:2], -jnp.inf)
        mask = seen & (scores >= tau[..., None])
        lse = jax.nn.logsumexp(jnp.where(mask, scores, -jnp.inf), axis=-1)
        # keys major, whole chunks of them: no key past the last is seen
        words = pack(jnp.pad(mask.swapaxes(1, 2),
                             ((0, 0), (0, -seq % _CHUNK), (0, 0))))
        return words, tau, lse

    words, tau, lse = lax.map(one, (
        _by_rows(q_idx, rows), _by_rows(w, rows),
        jnp.arange(0, seq, rows)))
    joined = lambda x: x.swapaxes(0, 1).reshape(b, seq, *x.shape[3:])
    # [blocks, B, words' rows, queries a block] -> queries along the last
    return (words.transpose(1, 2, 0, 3).reshape(b, -1, seq), joined(tau),
            joined(lse))


def _heads_of(folded, b: int):
    """[B x H, T, D] as [B, H, T, D]."""
    return folded.reshape(b, folded.shape[0] // b, *folded.shape[1:])


def _kl_twin(q_idx, k_idx, w, mask, qf, kf, sm_scale: float,
             rows: Optional[int]):
    """The KL in plain ``jnp`` under the dense ``mask`` (``unpack``'s), rows
    a block at a time against every key; differentiable in ``q_idx``,
    ``k_idx`` and ``w`` by JAX's own rules."""
    b, seq = q_idx.shape[:2]
    rows = _row_blocks(seq, rows)
    qh, kh = _heads_of(qf, b), _heads_of(kf, b)
    heads, kv_heads = qh.shape[1], kh.shape[1]
    seen_rows = _by_rows(mask.swapaxes(1, 2), rows)        # queries major
    q_rows = qh.reshape(b, kv_heads, heads // kv_heads, seq // rows, rows,
                        qh.shape[-1]).transpose(3, 0, 1, 2, 4, 5)

    @jax.checkpoint
    def one(k_idx, args):
        q_blk, w_blk, seen, q_main = args
        seen = seen != 0
        scores = index_scores(q_blk, k_idx, w_blk)
        log_q = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), -1)
        s = jnp.einsum("bgjrd,bgsd->bgjrs", q_main, kh,
                       preferred_element_type=_F32) * sm_scale
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        p = lax.stop_gradient(jax.nn.softmax(s, -1).mean(axis=(1, 2)))
        live = seen & (p > 0)
        return jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                    - jnp.where(live, log_q, 0.0)), 0.0).sum()

    parts = lax.map(functools.partial(one, k_idx), (
        _by_rows(q_idx, rows), _by_rows(w, rows), seen_rows, q_rows))
    return parts.sum()


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------

def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=_F32,
                           precision=precision_of(a.dtype))


def _sortable(x):
    """float32 -> int32 that orders as the floats do."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _unsortable(keys):
    return lax.bitcast_convert_type(
        jnp.where(keys < 0, keys ^ jnp.int32(0x7FFFFFFF), keys), _F32)


def _tile_scores(k_tile, qt_ref, wt_ref, cols=slice(None)):
    """I^T (keys, queries) float32 of a tile: ``k_tile`` (keys, W),
    ``qt_ref`` (J, W, queries), ``wt_ref`` (J, queries)."""
    total = None
    for j in range(qt_ref.shape[0]):
        r = _dot(k_tile, qt_ref[j, :, cols], _NN)
        part = wt_ref[j:j + 1, cols] * jnp.maximum(r, 0.0)
        total = part if total is None else total + part
    return total


def _select_kernel(k_ref, qt_ref, wt_ref, mask_ref, tau_ref, lse_ref,
                   keys_scr, *, topk: int, block_k: int):
    """One block of queries (along the lanes) against every key up to the
    block's last: ``k_ref`` (T, W) the index keys, ``qt_ref`` (J, W,
    queries) the index queries turned, ``wt_ref`` (J, queries); ->
    ``mask_ref`` (T / 32, queries) int32, the set's words (``pack``),
    ``tau_ref`` and ``lse_ref`` (1, queries). ``keys_scr`` (T, queries)
    int32 holds the scores' bits as sortable integers, ``_NO_PAIR`` past the
    diagonal."""
    qi = pl.program_id(1)
    n_q = qt_ref.shape[2]
    seq = k_ref.shape[0]
    first = qi * n_q
    # tiles of keys that hold any key a query of the block sees
    n_live = (first + n_q - 1) // block_k + 1
    at = first + lax.broadcasted_iota(jnp.int32, (1, n_q), 1)

    def rows_of(c, size=block_k):
        return pl.ds(pl.multiple_of(c * size, size), size)

    def fill(c, _):
        rows = rows_of(c)
        scores = _tile_scores(k_ref[rows, :], qt_ref, wt_ref)
        key = c * block_k + lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        keys_scr[rows, :] = jnp.where(key <= at, _sortable(scores), _NO_PAIR)
        return 0

    lax.fori_loop(0, n_live, fill, 0)

    def count_from(least):
        """How many of a query's pairs have a key >= ``least`` (1, queries)."""
        def add(c, n):
            return n + (keys_scr[rows_of(c), :] >= least).astype(
                jnp.int32).sum(axis=0, keepdims=True)
        return lax.fori_loop(0, n_live, add, jnp.zeros((1, n_q), jnp.int32))

    # the largest integer that at least ``topk`` of a query's keys reach,
    # bit by bit from the sign down; with fewer pairs than ``topk`` it stays
    # the least integer there is, and every pair is kept
    least = jnp.full((1, n_q), _NO_PAIR, jnp.int32)
    least = jnp.where(count_from(jnp.zeros_like(least)) >= topk, 0, least)

    def a_bit(i, least):
        tried = least | (jnp.int32(1) << (30 - i))
        return jnp.where(count_from(tried) >= topk, tried, least)

    least = lax.fori_loop(0, 31, a_bit, least)
    tau_ref[...] = jnp.where(least == _NO_PAIR, -jnp.inf,
                             _unsortable(jnp.maximum(least, _NO_PAIR + 1)))

    def kept(c):
        keys = keys_scr[rows_of(c), :]
        return keys, (keys >= least) & (keys != _NO_PAIR)

    def highest(c, m):
        keys, seen = kept(c)
        return jnp.maximum(m, jnp.where(seen, _unsortable(keys), -jnp.inf).max(
            axis=0, keepdims=True))

    m = lax.fori_loop(0, n_live, highest, jnp.full((1, n_q), -jnp.inf, _F32))

    def write(c, l):
        keys, seen = kept(c)
        mask_ref[rows_of(c, block_k // KEYS_A_WORD), :] = pack(seen)
        return l + jnp.where(seen, jnp.exp(_unsortable(keys) - m), 0.0).sum(
            axis=0, keepdims=True)

    l = lax.fori_loop(0, n_live, write, jnp.zeros((1, n_q), _F32))
    lse_ref[...] = m + jnp.log(l)

    def blank(c, _):
        mask_ref[rows_of(c, block_k // KEYS_A_WORD), :] = jnp.zeros(
            (block_k // KEYS_A_WORD, n_q), jnp.int32)
        return 0

    lax.fori_loop(n_live, seq // block_k, blank, 0)


def _select_pallas(q_idx, k_idx, w, topk: int, interpret: bool,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None):
    b, seq, heads, width = q_idx.shape
    block_q = block_q or _SELECT_QUERIES
    block_k = min(block_k or _SELECT_KEYS, seq)
    assert (seq % block_q == 0 and seq % block_k == 0
            and block_k % _CHUNK == 0), (seq, block_q, block_k)
    qt = q_idx.transpose(0, 2, 3, 1)                      # [B, J, W, T]
    wt = w.astype(_F32).transpose(0, 2, 1)                # [B, J, T]
    row = pl.BlockSpec((None, 1, block_q), lambda bi, qi: (bi, 0, qi))
    mask, tau, lse = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k),
        grid=(b, seq // block_q),
        in_specs=[
            pl.BlockSpec((None, seq, width), lambda bi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, heads, width, block_q),
                         lambda bi, qi: (bi, 0, 0, qi)),
            pl.BlockSpec((None, heads, block_q), lambda bi, qi: (bi, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((None, seq // KEYS_A_WORD, block_q),
                         lambda bi, qi: (bi, 0, qi)),
            row, row],
        out_shape=[jax.ShapeDtypeStruct((b, seq // KEYS_A_WORD, seq),
                                        jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, seq), _F32),
                   jax.ShapeDtypeStruct((b, 1, seq), _F32)],
        scratch_shapes=[pltpu.VMEM((seq, block_q), jnp.int32)],
        compiler_params=compiler_params(
            interpret, ("parallel", "parallel"), 64 * 2**20),
        interpret=interpret,
        name=f"index_select_top{topk}",
    )(k_idx, qt, wt)
    return mask, tau[:, 0], lse[:, 0]


def _kl_kernel(qf_ref, kf_ref, lse_ref, mask_ref, k_ref, kt_ref, q_ref,
               qt_ref, wt_ref, lsei_ref, kl_ref, dwt_ref, dqt_ref, dk_ref, *,
               sm_scale: float, group: int):
    """One tile of (keys, queries) of one batch row. Main attention:
    ``qf_ref`` (H, queries, D), ``kf_ref`` (G, keys, D), ``lse_ref`` (H, 1,
    queries). The selection's words: ``mask_ref`` (keys / 32, queries). The
    indexer: ``k_ref`` (keys, W), ``kt_ref`` (W, keys), ``q_ref`` (J,
    queries, W), ``qt_ref`` (J, W, queries), ``wt_ref`` (J, queries),
    ``lsei_ref`` (1, queries). -> summed over a row of the grid's tiles of
    keys, ``kl_ref`` (1, queries), ``dwt_ref`` (J, queries), ``dqt_ref``
    (J, W, queries); this tile's own ``dk_ref`` (keys, W)."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    heads, index_heads = qf_ref.shape[0], q_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dwt_ref[...] = jnp.zeros_like(dwt_ref)
        dqt_ref[...] = jnp.zeros_like(dqt_ref)

    @pl.when(ki > qi)
    def _dead():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(ki <= qi)
    def _live():
        seen = bits(mask_ref[...]) != 0

        def a_head(h, total):
            s = _dot(kf_ref[h // group], qf_ref[h], _NT) * sm_scale
            return total + jnp.exp(s - lse_ref[h])

        total = lax.fori_loop(0, heads, a_head,
                              jnp.zeros(seen.shape, _F32))
        p = jnp.where(seen, total * (1.0 / heads), 0.0)
        k_tile = k_ref[...]
        log_q = _tile_scores(k_tile, qt_ref, wt_ref) - lsei_ref[...]
        live = seen & (p > 0.0)
        kl_ref[...] += jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_q), 0.0).sum(
                axis=0, keepdims=True)
        d_scores = jnp.where(seen, jnp.exp(log_q), 0.0) - p
        d_keys = jnp.zeros(dk_ref.shape, _F32)
        for j in range(index_heads):
            r = _dot(k_tile, qt_ref[j], _NN)
            dwt_ref[j:j + 1, :] += (d_scores * jnp.maximum(r, 0.0)).sum(
                axis=0, keepdims=True)
            through = jnp.where(r > 0.0, d_scores * wt_ref[j:j + 1, :],
                                0.0).astype(k_tile.dtype)
            dqt_ref[j] += _dot(kt_ref[...], through, _NN)
            d_keys = d_keys + _dot(through, q_ref[j], _NN)
        dk_ref[...] = d_keys


def _kl_pallas(q_idx, k_idx, w, selection: Selection, qf, kf, lse,
               sm_scale: float, interpret: bool,
               tile: Optional[int] = None):
    """-> (kl, dq_idx, dk_idx, dw): the sum over the batch's queries and
    its gradient (float32, shaped as the three operands)."""
    b, seq, index_heads, width = q_idx.shape
    heads, kv_heads, d = qf.shape[0] // b, kf.shape[0] // b, qf.shape[-1]
    tile = min(tile or _KL_TILE, seq)
    assert seq % tile == 0 and tile % _CHUNK == 0, (seq, tile)
    n = seq // tile
    live_k = lambda qi, ki: jnp.minimum(ki, qi)
    q_heads = q_idx.transpose(0, 2, 1, 3)                 # [B, J, T, W]
    qt = q_idx.transpose(0, 2, 3, 1)                      # [B, J, W, T]
    wt = w.astype(_F32).transpose(0, 2, 1)                # [B, J, T]
    of_q = lambda *block: pl.BlockSpec(
        (None, *block, tile), lambda bi, qi, ki: (bi,) + (0,) * len(block)
        + (qi,))
    kl, dwt, dqt, dk_parts = pl.pallas_call(
        functools.partial(_kl_kernel, sm_scale=sm_scale,
                          group=heads // kv_heads),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((None, heads, tile, d),
                         lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((None, kv_heads, tile, d),
                         lambda bi, qi, ki: (bi, 0, live_k(qi, ki), 0)),
            pl.BlockSpec((None, heads, 1, tile),
                         lambda bi, qi, ki: (bi, 0, 0, qi)),
            pl.BlockSpec((None, tile // KEYS_A_WORD, tile),
                         lambda bi, qi, ki: (bi, live_k(qi, ki), qi)),
            pl.BlockSpec((None, tile, width),
                         lambda bi, qi, ki: (bi, live_k(qi, ki), 0)),
            pl.BlockSpec((None, width, tile),
                         lambda bi, qi, ki: (bi, 0, live_k(qi, ki))),
            pl.BlockSpec((None, index_heads, tile, width),
                         lambda bi, qi, ki: (bi, 0, qi, 0)),
            of_q(index_heads, width), of_q(index_heads), of_q(1),
        ],
        out_specs=[
            of_q(1), of_q(index_heads), of_q(index_heads, width),
            pl.BlockSpec((None, None, tile, width),
                         lambda bi, qi, ki: (bi, qi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, seq), _F32),
            jax.ShapeDtypeStruct((b, index_heads, seq), _F32),
            jax.ShapeDtypeStruct((b, index_heads, width, seq), _F32),
            jax.ShapeDtypeStruct((b, n, seq, width), _F32),
        ],
        compiler_params=compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), 64 * 2**20),
        interpret=interpret,
        name="index_kl",
    )(_heads_of(qf, b), _heads_of(kf, b), _heads_of(lse, b), selection.mask,
      k_idx, k_idx.swapaxes(1, 2), q_heads, qt, wt, selection.lse[:, None])
    return (kl.sum(), dqt.transpose(0, 3, 1, 2), dk_parts.sum(axis=1),
            dwt.transpose(0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _kl_diff(q_idx, k_idx, w, selection, qf, kf, lse, sm_scale, interpret,
             tile):
    return _kl_pallas(q_idx, k_idx, w, selection, qf, kf, lse, sm_scale,
                      interpret, tile)[0]


def _kl_fwd(q_idx, k_idx, w, selection, qf, kf, lse, sm_scale, interpret,
            tile):
    """The residuals are the three gradients, float32, and an EMPTY array
    in each operand's type for the backward rule to round to: nothing of
    the operands themselves, so what a recomputed block would have to make
    again to hand them over (the selection, the indexer's projections) is
    made for the forward pass alone."""
    kl, *grads = _kl_pallas(q_idx, k_idx, w, selection, qf, kf, lse,
                            sm_scale, interpret, tile)
    grads = tuple(map(ad_checkpoint.checkpoint_name, grads, _KL_NAMES))
    return kl, (grads, tuple(jnp.zeros((0,), x.dtype)
                             for x in (q_idx, k_idx, w)))


def _kl_bwd(sm_scale, interpret, tile, res, g):
    # the selection and the main attention's operands are constants here
    return (*((g * grad).astype(as_.dtype) for grad, as_ in zip(*res)),
            None, None, None, None)


_kl_diff.defvjp(_kl_fwd, _kl_bwd)


# ----------------------------------------------------------------------
# the entries
# ----------------------------------------------------------------------

def _said(q_idx, topk: int, impl: str) -> dict:
    b, seq, heads, width = q_idx.shape
    causal = seq * (seq + 1) // 2
    return {"heads": heads, "width": width, "rows": b * seq,
            "pairs": b * causal, "topk": topk, "kernel": int(impl != "jnp")}


def select(q_idx, k_idx, w, topk: int, *, impl: Optional[str] = None,
           rows: Optional[int] = None, block_q: Optional[int] = None,
           block_k: Optional[int] = None) -> Selection:
    """The ``topk`` highest-scored earlier keys of every query (this file's
    docstring), from ``q_idx`` [B, T, J, W], ``k_idx`` [B, T, W] and ``w``
    [B, T, J]. Nothing here is differentiated: the operands are taken as
    constants. ``impl``: None (``auto_impl``), "pallas",
    "pallas_interpret" or "jnp" (``rows`` a block). The matmuls run in the
    operands' type, float32 accumulated: the caller chooses the precision
    by the type it hands over. The mask is bits on every path (``pack``) and
    is named for recomputation (``REMAT_NAMES``). Records ``index/scores``,
    ``index/threshold`` and ``index/kept`` (the mask as it is kept: a bit a
    pair, its bytes), one each a traced call."""
    q_idx, k_idx, w = map(lax.stop_gradient, (q_idx, k_idx, w))
    impl = impl or auto_impl(q_idx, topk)
    b, seq, heads, width = q_idx.shape
    said = _said(q_idx, topk, impl)
    steptrace.record_counters("index/scores", {
        **said, "flops_needed": 2 * said["pairs"] * heads * width,
        "bytes_needed": b * seq * ((heads + 1) * width
                                   * q_idx.dtype.itemsize + 4 * heads),
        "operand_bits": 8 * q_idx.dtype.itemsize})
    steptrace.record_counters("index/threshold", {
        **said, "bisection": int(impl != "jnp"),
        "passes": 32 if impl != "jnp" else 1})
    if impl == "jnp":
        with jax.named_scope("index_select_twin"):
            mask, tau, lse = _select_twin(q_idx, k_idx, w, topk, rows)
    else:
        mask, tau, lse = _select_pallas(
            q_idx, k_idx, w, topk, impl == "pallas_interpret", block_q,
            block_k)
    steptrace.record_counters("index/kept", {
        "bits_a_pair": 1, "bytes": mask.size * mask.dtype.itemsize,
        "kernel": said["kernel"]})
    return Selection(ad_checkpoint.checkpoint_name(mask, _MASK_NAME), tau,
                     lse)


def index_kl(q_idx, k_idx, w, selection: Selection, qf, kf, lse=None, *,
             topk: int, sm_scale: float, impl: Optional[str] = None,
             rows: Optional[int] = None, tile: Optional[int] = None):
    """``sum_t KL(p[t, S_t] || softmax(I[t, S_t]))`` over the batch's
    queries (a float32 scalar; the caller divides), differentiable in
    ``q_idx``, ``k_idx`` and ``w`` alone. ``qf`` [B x H, T, D] and ``kf`` [B
    x G, T, D] are the main attention's queries and keys as its scores are
    made of them (normed, rotated), ``lse`` [B x H, 1, T] its log-sum-exp
    over the selected set (the flash kernel's; the twin makes its own and
    takes None, and where ``lse`` is None ``impl=None`` is the twin: the
    attention ran without the kernels): all three constants here. One
    ``index/loss`` record a traced call."""
    selection = jax.tree.map(lax.stop_gradient, selection)
    qf, kf = lax.stop_gradient(qf), lax.stop_gradient(kf)
    impl = impl or ("jnp" if lse is None else auto_impl(q_idx, topk))
    steptrace.record_counters("index/loss", {
        **_said(q_idx, topk, impl),
        "main_heads": qf.shape[0] // q_idx.shape[0]})
    if impl == "jnp":
        with jax.named_scope("index_kl_twin"):
            return _kl_twin(q_idx, k_idx, w, unpack(selection.mask), qf, kf,
                            sm_scale, rows)
    assert lse is not None, "the kernel reads the flash kernel's log-sum-exp"
    return _kl_diff(q_idx, k_idx, w, selection, qf, kf, lax.stop_gradient(lse),
                    sm_scale, impl == "pallas_interpret", tile)
