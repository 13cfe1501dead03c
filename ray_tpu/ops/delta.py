"""The gated delta rule of a linear-attention layer, forward and backward: a
Pallas TPU kernel pair and a chunked ``lax.scan`` that computes the same.

A head's state ``S`` is a [d_k, d_v] matrix, zero at a sequence's start:

    S' = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

The decay is one scalar a head and position, so a chunk of C positions is a
handful of matmuls (Yang et al., arXiv:2412.06464 and 2406.06484). With
``gamma`` the log-decay summed from the chunk's start (inclusive),

    L  = tril(beta_i (k_i . k_j) exp(gamma_i - gamma_j), -1)
    T  = (I + L)^-1
    W  = T (beta exp(gamma) k),   U = T (beta v),   V' = U - W S
    O  = (exp(gamma) q) S + tril((q_i . k_j) exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) k)^T V'

A decay only ever appears as ``exp`` of a difference that is <= 0. The
state, the decays, the triangular solve and every accumulation are float32;
the other matmuls take their operands in the inputs' dtype (bfloat16 in a
training step) and accumulate in float32.

Both paths keep one boundary state every ``stride`` positions (the state a
group of ``stride / chunk`` chunks starts from), ``stride`` chosen so that
the boundaries weigh no more than the output (256 positions for bfloat16 at
d_k 128: a boundary a chunk of 64 would be 1 GiB a layer at 32,768 tokens of
32 heads). The backward pass walks the groups from the last, makes a group's
inner states again from its boundary, and walks its chunks in reverse with
the state's gradient as the carry. One ``custom_vjp`` holds both paths: the
forward rule's outputs are named ``delta_rule_out`` / ``delta_rule_bounds``
(``REMAT_NAMES``: ``ops.remat.remat_policy`` keeps them, so a recomputed
block does not run the forward rule again).

The kernels (``gated_delta_fwd`` / ``gated_delta_bwd``: the benchmark's
readers find them by these names) address the model's own arrays, [B, T,
heads x width], one lane tile a head at width 128. The grid is (batch, key
heads / keys, groups): a grid step is the whole groups of ``keys`` adjacent
key heads, their q and k [stride, keys x d_k], the v, o and their
cotangents of ALL the value heads that share them (adjacent lane tiles,
[stride, keys x rep x d_v]), their gates [keys x rep, chunks, chunk] and
boundary states [keys x rep, d_k, d_v]; every value head's state (backward:
its gradient) is carried from group to group in VMEM scratch. A step's
problems (a value head's chunk each: 16 at 32 heads on 16 and a stride of
four chunks, two key heads a step; ``_step_width`` settles the key heads a
step from the shapes alone, and cuts the value heads into passes where the
VMEM limit would not hold them) are worked on in stages, each stage written
for every problem before the next, because the compiler keeps the source's
order: a problem written start to end waits on its own chain of products.

- what needs no state (``_step_parts``): the decays, ``k k^T`` and ``q
  k^T`` once a CHUNK (one product, beside itself for the value heads), L,
  ``T = (I + L)^-1``, W, U and the masked ``q k^T``. The [C, C] matrices of
  ``pack`` value heads lie side by side along the lanes (``[C, pack x C]``:
  two heads fill a lane tile at C 64), so the vector unit works on full
  registers, and a product of packed matrices takes its right factor on a
  block diagonal (``[a0 | a1] @ diag(b0, b1) = [a0 b0 | a1 b1]``: 128 deep
  and 128 wide, exact zeros beside the blocks).
- the state's walk, chunk after chunk with the value heads side by side
  (forward: ``V' = U - W S``, O, the state's update; backward: the states
  again from the boundary, then the reverse walk, which holds only the two
  products a chunk that stand in the state's gradient's chain).
- backward, every chunk's gradients from what the two walks left, as wide
  as the first stage; the value heads' dq and dk are summed in a product
  (a packed left factor against k or q stacked down the rows) and written
  once.

``(I + L)^-1`` is made from matmuls alone, link by link over the step's
problems (``_unit_lower_inverses``): the diagonal 16 x 16 blocks by the
finite series ``(I - X)(I + X^2)(I + X^4)(I + X^8)`` (X nilpotent of index
16), then the blocks below them by the same series over the strictly
block-lower rest; float32 in and out, each product at full float32
precision for float32 operands and, for bfloat16 operands (whose T is
rounded to bfloat16 before the matmuls that use it), with both factors
held to 16 bits of mantissa: ``_split_dot``'s three terms in its order,
made by ``_packed_products`` in two stacked passes (``[a_hi; a_lo] @ b_hi``
and ``a_hi @ b_lo``; a link's two products that share their right factor
ride the same passes).
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops.chunks import (NT, TN, dot, folded, gates, grouped, iota,
                                ungated, ungrouped)
from ray_tpu.ops.mosaic import (compiler_params, per_batch_shard,
                                takes_kernels)

CHUNK = 64           # positions a chunk: one triangular solve each
_SOLVE_BLOCK = 16    # the diagonal blocks the solve's first series inverts
_LANES = 128
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def stride_of(chunk: int, d_k: int, itemsize: int) -> int:
    """Positions between two kept boundary states: whole chunks, and enough
    of them that a boundary ([d_k, d_v] float32) weighs no more than the
    output of the positions it covers ([stride, d_v] of ``itemsize``)."""
    return chunk * -(-(d_k * 4) // (itemsize * chunk))


def bytes_needed(q, v, backward: bool) -> int:
    """What a call has to move whatever its form: forward q, k, v, g, beta
    in and o out; backward those and o's cotangent in, the five gradients
    out."""
    batch, length, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    tokens, size = batch * length, q.dtype.itemsize
    qk, vo, gb = (2 * tokens * key_heads * d_k * size,
                  tokens * heads * d_v * size, 2 * tokens * heads * 4)
    return (2 * qk + 3 * vo + 2 * gb) if backward else qk + 2 * vo + gb


def _record(q, v, chunk, stride, backward: bool, kernels: bool):
    """One ``counters`` record a traced pass (none a step): what the rule
    walks, what its boundary states weigh, what it has to move and how wide
    the kernels' grid steps are (value heads x chunks: the problems a step
    makes the state-free part of together; 0 for the ``lax.scan``, which
    has no grid)."""
    batch, length, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    groups, chunks = length // stride, stride // chunk
    keys = _step(q, v, chunk, stride)[1] if kernels else 0
    steptrace.record_counters("delta/rule", {
        "heads": heads, "key_heads": key_heads, "d_k": d_k, "d_v": d_v,
        "tokens": batch * length, "sequences": batch, "chunk": chunk,
        "boundary_bytes": batch * groups * heads * d_k * d_v * 4,
        "bytes_needed": bytes_needed(q, v, backward),
        "backward": int(backward),
        "problems_a_step": keys * heads // key_heads * chunks,
        "grid_steps": batch * key_heads // keys * groups if kernels else 0})


# ---------------------------------------------------------------------------
# the chunked lax.scan: any backend
# ---------------------------------------------------------------------------

def _scan_chunk(state, inputs):
    """One chunk from the state [B, H, d_k, d_v] it starts in: q, k [B, C,
    H, d_k] (already one a value head), v [B, C, H, d_v], g, beta [B, C, H],
    all float32 -> (end state, o [B, C, H, d_v])."""
    q, k, v, g, beta = inputs
    chunk = q.shape[1]
    gamma = jnp.cumsum(g, axis=1)                               # [B, C, H]
    by_head = lambda t: jnp.moveaxis(t, 1, 2)                   # [B, H, C..]
    q, k, v, gamma, beta = map(by_head, (q, k, v, gamma, beta))
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, diff, 0.0)),
                      0.0)
    kk = jnp.einsum("bhid,bhjd->bhij", k, k)
    lower = jnp.where(row > col, beta[..., None] * kk * decay, 0.0)
    eye = jnp.eye(chunk, dtype=_F32)
    solve = functools.partial(jax.scipy.linalg.solve_triangular, lower=True,
                              unit_diagonal=True)
    rhs = jnp.concatenate(
        [(beta * jnp.exp(gamma))[..., None] * k, beta[..., None] * v], -1)
    wu = solve(eye + lower, rhs)
    w, u = wu[..., :k.shape[-1]], wu[..., k.shape[-1]:]
    new_v = u - jnp.einsum("bhck,bhkv->bhcv", w, state)
    attend = jnp.einsum("bhid,bhjd->bhij", q, k) * decay
    o = (jnp.einsum("bhck,bhkv->bhcv", jnp.exp(gamma)[..., None] * q, state)
         + jnp.einsum("bhij,bhjv->bhiv", attend, new_v))
    last = gamma[..., -1:]
    state = (jnp.exp(last)[..., None] * state
             + jnp.einsum("bhck,bhcv->bhkv",
                          jnp.exp(last - gamma)[..., None] * k, new_v))
    return state, jnp.moveaxis(o, 2, 1)


def _scan_group(state, inputs, rep):
    q, k, v, g, beta = inputs
    wide = lambda t: jnp.repeat(t, rep, axis=3) if rep > 1 else t
    return lax.scan(_scan_chunk, state, (wide(q), wide(k), v, g, beta))


def _scan_operands(q, k, v, g, beta, chunk, stride):
    return tuple(grouped(t.astype(_F32), stride, chunk)
                 for t in (q, k, v, g, beta))


def _scan_fwd(q, k, v, g, beta, chunk, stride):
    """-> (o [B, T, H, d_v] float32, bounds [B, H, T / stride, d_k, d_v]
    float32: the state each group starts from)."""
    batch, _, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    group = functools.partial(_scan_group, rep=heads // key_heads)

    def one(state, inputs):
        end, o = group(state, inputs)
        return end, (o, state)

    zero = jnp.zeros((batch, heads, d_k, d_v), _F32)
    _, (o, bounds) = lax.scan(
        one, zero, _scan_operands(q, k, v, g, beta, chunk, stride))
    return ungrouped(o), jnp.moveaxis(bounds, 0, 2)


def _scan_bwd(q, k, v, g, beta, bounds, do, chunk, stride):
    """The gradients of ``_scan_fwd``'s o, a group at a time from the last:
    each group's states are made again from its boundary (``jax.vjp`` of the
    group), the gradient of the state handed to the group before."""
    heads, key_heads = v.shape[2], q.shape[2]
    group = functools.partial(_scan_group, rep=heads // key_heads)

    def one(dstate, inputs):
        *operands, start, do_g = inputs
        _, pull = jax.vjp(group, start, tuple(operands))
        dstate, grads = pull((dstate, do_g))
        return dstate, grads

    _, grads = lax.scan(
        one, jnp.zeros(bounds.shape[:2] + bounds.shape[3:], _F32),
        (*_scan_operands(q, k, v, g, beta, chunk, stride),
         jnp.moveaxis(bounds, 2, 0), grouped(do.astype(_F32), stride, chunk)),
        reverse=True)
    return tuple(map(ungrouped, grads))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _halves(t):
    """A float32 as two bfloat16s: its 8 leading bits of mantissa and the 8
    that follow."""
    hi = t.astype(jnp.bfloat16)
    return hi, (t - hi.astype(_F32)).astype(jnp.bfloat16)


def _split_dot(a, b):
    """``a @ b`` of float32 operands held to 16 bits of mantissa: each split
    into a bfloat16 and what that leaves, three products where full float32
    precision takes six. What ``_packed_products`` makes in one and a half
    passes of the MXU; this plain form is its meaning."""
    (a_hi, a_lo), (b_hi, b_lo) = _halves(a), _halves(b)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _beside(parts):
    """[n, a] each -> [n, sum of a]: along the lanes."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _stacked(parts):
    """[a, n] each -> [sum of a, n]: down the sublanes."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


class _Packing:
    """A step's [C, C] matrices lie ``pack`` side by side along the lanes,
    one a value head ([C, pack x C]: a lane tile at C 64 and two heads), so
    the vector unit works on full registers and a product fills the MXU's
    columns. This holds the masks of that form, made once a kernel (a mask
    made where it is used would be half of the kernels' text, which every
    run's set-up lowers), and what is done with them."""

    def __init__(self, chunk: int, pack: int):
        self.chunk, self.pack = chunk, pack
        along = lambda shape, axis: lax.broadcasted_iota(jnp.int32, shape,
                                                         axis)
        packed, square = (chunk, pack * chunk), (pack * chunk, pack * chunk)
        row, lane = along(packed, 0), along(packed, 1)
        block = lax.div(lane, chunk)
        col = lane - block * chunk               # inside its block
        self.in_block = [block == u for u in range(pack)]
        self.strict, self.seen, self.diagonal = (row > col, row >= col,
                                                 row == col)
        self.eye = self.diagonal.astype(_F32)
        self.same = (lax.div(row, _SOLVE_BLOCK)
                     == lax.div(col, _SOLVE_BLOCK))
        self.last_col = col[:1] == chunk - 1                # [1, pack x C]
        self.on_blocks = (lax.div(along(square, 0), chunk)
                          == lax.div(along(square, 1), chunk))
        unit_row, unit_col = iota(chunk)
        self.unit = unit_row == unit_col                    # [C, C]
        self.final = along((1, chunk), 1) == chunk - 1

    def as_col(self, row_vec):
        """[1, C] -> [C, 1]: the numbers a position each, down the
        sublanes."""
        return jnp.sum(jnp.where(self.unit, row_vec, 0.0), axis=1,
                       keepdims=True)

    def by_block(self, per_block):
        """One [C, 1] or [C, C] a block -> the packed array that holds each
        over its block's lanes; of [1, 1]s, its one row."""
        out = per_block[-1]
        for u in reversed(range(self.pack - 1)):
            mask = self.in_block[u]
            out = jnp.where(mask if out.shape[0] > 1 else mask[:1],
                            per_block[u], out)
        return out

    def block_diagonal(self, packed):
        """Packed [C, pack x C] -> [pack x C, pack x C] with block u at (u,
        u) and zeros elsewhere: as a right factor it multiplies each block
        of a packed left factor by its own block (``[a0 | a1] @ diag(b0,
        b1) = [a0 b0 | a1 b1]``); as a left factor it gives the blocks'
        products down the rows (``diag(a0, a1) @ [b0; b1] = [a0 b0; a1
        b1]``)."""
        if self.pack == 1:
            return packed
        return jnp.where(self.on_blocks, _stacked([packed] * self.pack),
                         jnp.zeros((), packed.dtype))

    def rowsums(self, packed):
        """[C, pack x C] -> its blocks' row sums, [C, 1] each."""
        if self.pack == 1:
            return [jnp.sum(packed, axis=1, keepdims=True)]
        return [jnp.sum(jnp.where(mask, packed, 0.0), axis=1, keepdims=True)
                for mask in self.in_block]


def _rows_of(stacked, chunk: int):
    return [stacked[u * chunk:(u + 1) * chunk]
            for u in range(stacked.shape[0] // chunk)]


def _packed_products(lefts, right, packing: _Packing, exact: bool):
    """``[l @ right for l in lefts]``, block by block, of packed float32
    [C, pack x C]s. ``exact``: at full float32 precision. Else
    ``_split_dot``'s three terms summed in its order, from two passes for
    all of ``lefts``: the left factors' leading and trailing halves stacked
    down the rows against ``right``'s leading half (``[l_hi; l_lo] @ r_hi``:
    at C 64 and two blocks a 128-deep, 128-wide pass whose rows hold ``l_hi
    r_hi`` and ``l_lo r_hi``), and their leading halves against its trailing
    half."""
    chunk = packing.chunk
    if exact:
        return _rows_of(dot(_stacked(lefts), packing.block_diagonal(right),
                            precision=_HIGHEST), chunk)
    halves = _halves(right)
    r_hi, r_lo = map(packing.block_diagonal, halves)
    split = [halves if l is right else _halves(l) for l in lefts]
    his, los = [h for h, _ in split], [l for _, l in split]
    top = _rows_of(dot(_stacked(his + los), r_hi), chunk)
    low = _rows_of(dot(_stacked(his), r_lo), chunk)
    n = len(lefts)
    return [top[i] + (low[i] + top[n + i]) for i in range(n)]


def _series(products, powers, links: int, times):
    """``(I + X)`` -> ``(I + X)(I + X^2) ... (I + X^(2^links))`` of every
    problem, one link for all the problems before the next: a link's wait
    is paid once. The two products of a link that share their right factor
    (into the running product, and the next square) are one stacked pass."""
    if links:
        powers = [times([p], p)[0] for p in powers]
    for n in range(links):
        made = [times([s, p] if n + 1 < links else [s], p)
                for s, p in zip(products, powers)]
        products = [s + m[0] for s, m in zip(products, made)]
        powers = [m[-1] for m in made]
    return products


def _unit_lower_inverses(lowers, packing: _Packing, exact: bool):
    """``(I + lower)^-1`` of every strictly lower-triangular packed [C,
    pack x C] float32 in ``lowers``, by matmuls alone and link by link over
    all of them: first the diagonal ``_SOLVE_BLOCK``-wide blocks (X = minus
    those blocks is nilpotent of index 16, so the inverse is the finite
    product of ``I + X^(2^n)``), then ``(I + M)^-1`` of what is left, M =
    (diagonal inverse) x (the blocks below the diagonal), nilpotent of
    index C / 16. ``exact``: every product at full float32 precision
    (float32 operands: the result is used as it is); else split
    (``_packed_products``: the result is rounded to the operands' 8 bits
    before its first use, and these ten products are most of a chunk's MXU
    passes)."""
    times = functools.partial(_packed_products, packing=packing,
                              exact=exact)
    eye, same = packing.eye, packing.same
    powers = [-jnp.where(same, l, 0.0) for l in lowers]
    inverses = _series([eye + p for p in powers], powers, 3, times)
    blocks = packing.chunk // _SOLVE_BLOCK
    if blocks == 1:
        return inverses
    powers = [-times([i], jnp.where(same, 0.0, l))[0]
              for i, l in zip(inverses, lowers)]
    rests = _series([eye + p for p in powers], powers,
                    (blocks - 1).bit_length() - 1, times)
    return [times([r], i)[0] for r, i in zip(rests, inverses)]


def _blocks_nt(lefts, rights):
    """``[l @ r.T for l, r in zip(lefts, rights)]`` packed side by side: the
    left factors beside one another against the right factors on a block
    diagonal, one product whose result is born packed."""
    if len(lefts) == 1:
        return dot(lefts[0], rights[0], NT)
    zero = jnp.zeros_like(rights[0])
    diagonal = _stacked([_beside([r if v == u else zero
                                  for v in range(len(rights))])
                         for u, r in enumerate(rights)])
    return dot(_beside(lefts), diagonal, NT)


def _step_parts(q_ref, k_ref, v_ref, g_ref, b_ref, heads, rep: int,
                chunks: int, packing: _Packing):
    """What both kernels make of a grid step before a state enters, for the
    value heads ``heads`` of its key heads (value head ``j`` of the step is
    key head ``j // rep``'s): ``parts[r][t]`` of chunk ``r`` and the ``t``-th
    ``pack`` of heads holds the operands, the decays in the forms the
    matmuls want them, ``T = (I + L)^-1`` and what it is applied to: ``W``,
    ``U`` and the masked ``q k^T``. Written stage by stage over all of the
    step's problems; ``k k^T`` and ``q k^T`` are made once a chunk and key
    head."""
    dt = q_ref.dtype
    d_v = v_ref.shape[1] // g_ref.shape[0]
    d_k = q_ref.shape[1] * rep // g_ref.shape[0]
    chunk, pack = packing.chunk, packing.pack
    strict, seen = packing.strict, packing.seen

    @functools.lru_cache(maxsize=None)
    def of_keys(r, h):
        """Chunk ``r`` of key head ``h``: q, k, their float32s and ``k
        k^T``, ``q k^T`` [C, pack x C] (beside themselves for the pack)."""
        rows, lanes = slice(r * chunk, (r + 1) * chunk), slice(h * d_k,
                                                               (h + 1) * d_k)
        q, k = q_ref[rows, lanes], k_ref[rows, lanes]
        scores = dot(_stacked([k, q]), _stacked([k] * pack), NT)
        return (q, k, q.astype(_F32), k.astype(_F32), scores[:chunk],
                scores[chunk:])

    parts = []
    for r in range(chunks):
        rows = slice(r * chunk, (r + 1) * chunk)
        packs = []
        for lo in range(0, len(heads), pack):
            js = heads[lo:lo + pack]
            q, k, q32, k32, kk, qk = of_keys(r, js[0] // rep)
            gamma_rows = [g_ref[j, r:r + 1, :] for j in js]          # [1, C]
            gammas = [packing.as_col(g) for g in gamma_rows]         # [C, 1]
            betas = [packing.as_col(b_ref[j, r:r + 1, :]) for j in js]
            lasts = [jnp.sum(jnp.where(packing.final, g, 0.0), axis=1,
                             keepdims=True) for g in gamma_rows]     # [1, 1]
            gamma, beta = packing.by_block(gammas), packing.by_block(betas)
            decay = jnp.where(seen, jnp.exp(jnp.where(
                seen, gamma - _beside(gamma_rows), 0.0)), 0.0)
            grows = [jnp.exp(g) for g in gammas]
            to_ends = [jnp.exp(l - g) for l, g in zip(lasts, gammas)]
            v32s = [v_ref[rows, j * d_v:(j + 1) * d_v].astype(_F32)
                    for j in js]
            packs.append(dict(
                js=js, key=js[0] // rep, q=q, k=k, k32=k32, kk=kk,
                decay=decay, beta=beta, betas=betas, grows=grows,
                to_ends=to_ends, v32s=v32s, attend32=qk * decay,
                lower=jnp.where(strict, beta * kk * decay, 0.0),
                kb32s=[k32 * (b * g) for b, g in zip(betas, grows)],
                vb32s=[v * b for v, b in zip(v32s, betas)],
                qg32s=[q32 * g for g in grows],
                kd32s=[k32 * e for e in to_ends],
                lasts=[jnp.exp(l) for l in lasts],
                # the same over a row of the state: Mosaic broadcasts one
                # way a time
                last_rows=[jnp.broadcast_to(jnp.exp(l), (1, d_v))
                           for l in lasts]))
        parts.append(packs)
    every = [p for packs in parts for p in packs]
    solved = _unit_lower_inverses([p["lower"] for p in every], packing,
                                  exact=dt == _F32)
    for p, t in zip(every, solved):
        # T on its block diagonal: one product gives W (and one U) of the
        # pack's heads down the rows
        p["solved"] = packing.block_diagonal(t.astype(dt))
        p["attend"] = packing.block_diagonal(p["attend32"].astype(dt))
    for p in every:
        p["ws"] = _rows_of(dot(p["solved"], _stacked(
            [kb.astype(dt) for kb in p["kb32s"]])), chunk)
        p["us"] = _rows_of(dot(p["solved"], _stacked(
            [vb.astype(dt) for vb in p["vb32s"]])), chunk)
    return parts


def _heads_of(packs):
    """[(a pack's parts, a value head's place in the pack, the head)] over
    the packs of a chunk."""
    return [(p, u, j) for p in packs for u, j in enumerate(p["js"])]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, bound_ref, s_scr,
                *, rep: int, chunk: int, chunks: int, pack: int, width: int):
    heads = s_scr.shape[0]                       # the step's value heads
    d_v = v_ref.shape[1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, _F32)

    bound_ref[...] = s_scr[...]
    packing = _Packing(chunk, pack)
    for first in range(0, heads, width):
        parts = _step_parts(q_ref, k_ref, v_ref, g_ref, b_ref,
                            list(range(first, first + width)), rep, chunks,
                            packing)
        states = {j: s_scr[j] for j in range(first, first + width)}
        # the state's walk: chunk after chunk, the heads side by side, each
        # product for every head before the next
        for r, packs in enumerate(parts):
            rows, dt = slice(r * chunk, (r + 1) * chunk), q_ref.dtype
            mine = _heads_of(packs)
            # W S and (exp(gamma) q) S: one pass over the state
            over = {j: dot(_stacked([p["ws"][u].astype(dt),
                                     p["qg32s"][u].astype(dt)]),
                           states[j].astype(dt)) for p, u, j in mine}
            new_vs = {j: (p["us"][u] - over[j][:chunk]).astype(dt)
                      for p, u, j in mine}
            within = {j: rows for p in packs for j, rows in zip(
                p["js"], _rows_of(dot(p["attend"], _stacked(
                    [new_vs[j] for j in p["js"]])), chunk))}
            for p, u, j in mine:
                o = over[j][chunk:] + within[j]
                o_ref[rows, j * d_v:(j + 1) * d_v] = o.astype(o_ref.dtype)
            states.update({j: p["last_rows"][u] * states[j] + dot(
                p["kd32s"][u].astype(dt), new_vs[j], TN)
                for p, u, j in mine})
        for j, state in states.items():
            s_scr[j] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, bound_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, rep: int,
                chunk: int, chunks: int, pack: int, width: int):
    heads_a_step = ds_scr.shape[0]
    d_v = v_ref.shape[1] // heads_a_step
    d_k = q_ref.shape[1] * rep // heads_a_step

    @pl.when(pl.program_id(2) == 0)              # a sequence's last group
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    packing = _Packing(chunk, pack)
    rowsum = lambda t: jnp.sum(t, axis=1, keepdims=True)
    dqs, dks = collections.defaultdict(float), collections.defaultdict(float)
    for first in range(0, heads_a_step, width):
        heads = list(range(first, first + width))
        parts = _step_parts(q_ref, k_ref, v_ref, g_ref, b_ref, heads, rep,
                            chunks, packing)
        dt = q_ref.dtype
        cast = lambda t: t.astype(dt)
        lanes = lambda j: slice(j * d_v, (j + 1) * d_v)
        dos = {(r, j): do_ref[r * chunk:(r + 1) * chunk, lanes(j)]
               for r in range(chunks) for j in heads}
        # what of the reverse walk needs no state's gradient:
        # attend^T do, down the rows a head, and (exp(gamma) q)^T do
        into_new_v = {(r, j): rows for r, packs in enumerate(parts)
                      for p in packs for j, rows in zip(p["js"], _rows_of(
                          dot(p["attend"], _stacked(
                              [dos[r, j] for j in p["js"]]), TN), chunk))}
        into_state = {(r, j): dot(cast(p["qg32s"][u]), dos[r, j], TN)
                      for r, packs in enumerate(parts)
                      for p, u, j in _heads_of(packs)}

        # the group's states again, from its boundary: starts[r, j] is the
        # state chunk r of head j starts from; the heads side by side
        starts, new_vs = {}, {}
        states = {j: bound_ref[j] for j in heads}
        for r, packs in enumerate(parts):
            mine = _heads_of(packs)
            starts.update({(r, j): states[j] for j in heads})
            new_vs.update({(r, j): p["us"][u] - dot(
                cast(p["ws"][u]), cast(states[j])) for p, u, j in mine})
            if r + 1 < chunks:
                states = {j: p["last_rows"][u] * states[j] + dot(
                    cast(p["kd32s"][u]), cast(new_vs[r, j]), TN)
                    for p, u, j in mine}

        # the reverse walk, the state's gradient as the carry: two
        # products a chunk and head stand in its chain
        dstates, d_new_vs = {}, {}
        carried = {j: ds_scr[j] for j in heads}
        for r in reversed(range(chunks)):
            mine = _heads_of(parts[r])
            dstates.update({(r, j): carried[j] for j in heads})
            d_new_vs.update({(r, j): cast(into_new_v[r, j] + dot(
                cast(p["kd32s"][u]), cast(carried[j]))) for p, u, j in mine})
            carried = {j: (into_state[r, j] + p["last_rows"][u] * carried[j]
                           - dot(cast(p["ws"][u]), d_new_vs[r, j], TN))
                       for p, u, j in mine}
        for j in heads:
            ds_scr[j] = carried[j]

        # every chunk's and head's gradients from what the walks left,
        # stage by stage over all of them like the first stage: the chain
        # from the state's products to dk is six products long
        every = [(r, p) for r, packs in enumerate(parts) for p in packs]
        for r, p in every:
            js = p["js"]
            p["new_v"] = [cast(new_vs[r, j]) for j in js]
            p["d_new_v"] = [d_new_vs[r, j] for j in js]
            # do S^T and d(V') S^T: one pass over the state
            by_start = [dot(_stacked([dos[r, j], dnv]), cast(starts[r, j]),
                            NT) for j, dnv in zip(js, p["d_new_v"])]
            p["dqgs"] = [b[:chunk] for b in by_start]
            p["dws"] = [cast(-b[chunk:]) for b in by_start]
            p["dkds"] = [dot(nv, cast(dstates[r, j]), NT)
                         for j, nv in zip(js, p["new_v"])]
            p["d_attend"] = jnp.where(packing.seen, _blocks_nt(
                [dos[r, j] for j in js], p["new_v"]), 0.0)
        for _, p in every:
            p["dsolved"] = packing.block_diagonal(cast(
                _blocks_nt(p["d_new_v"], [cast(vb) for vb in p["vb32s"]])
                + _blocks_nt(p["dws"], [cast(kb) for kb in p["kb32s"]])))
            p["dvbs"] = _rows_of(dot(p["solved"], _stacked(p["d_new_v"]),
                                     TN), chunk)
            p["dkbs"] = _rows_of(dot(p["solved"], _stacked(p["dws"]), TN),
                                 chunk)
        # d(I + L)^-1 = -T^T dT T^T, on the strictly lower part
        for _, p in every:
            p["inner"] = cast(dot(p["solved"], p["dsolved"], TN))
        for _, p in every:
            p["dlower"] = jnp.where(packing.strict, -packing.by_block(
                _rows_of(dot(p["inner"], p["solved"], NT), chunk)), 0.0)
        for r, p in every:
            rows, js = slice(r * chunk, (r + 1) * chunk), p["js"]
            dqgs, dkds, dvbs, dkbs, d_attend, dlower = (p[n] for n in (
                "dqgs", "dkds", "dvbs", "dkbs", "d_attend", "dlower"))
            dkk = cast(dlower * (p["beta"] * p["decay"]))
            dqk = cast(d_attend * p["decay"])
            # d/d(decay's log)
            through = dlower * p["lower"] + d_attend * p["attend32"]
            through_rows = packing.rowsums(through)
            dbeta_rows = packing.rowsums(dlower * p["kk"] * p["decay"])
            dgammas, dlasts, dbetas = [], [], []
            for u, j in enumerate(js):
                dkd_kd = rowsum(dkds[u] * p["kd32s"][u])
                dgammas.append(
                    rowsum(dqgs[u] * p["qg32s"][u])
                    + rowsum(dkbs[u] * p["kb32s"][u]) - dkd_kd
                    + through_rows[u])
                dlasts.append(
                    jnp.sum(dkd_kd, axis=0, keepdims=True)
                    + p["lasts"][u] * jnp.sum(
                        rowsum(dstates[r, j] * starts[r, j]), axis=0,
                        keepdims=True))                       # [1, 1]
                dbetas.append(
                    dbeta_rows[u]
                    + p["grows"][u] * rowsum(dkbs[u] * p["k32"])
                    + rowsum(dvbs[u] * p["v32s"][u]))
                dv_ref[rows, lanes(j)] = (p["betas"][u] * dvbs[u]
                                          ).astype(dv_ref.dtype)
            as_rows = lambda cols: jnp.sum(jnp.where(
                packing.diagonal, packing.by_block(cols), 0.0), axis=0,
                keepdims=True)                           # [1, pack x C]
            dg = (as_rows(dgammas)
                  - jnp.sum(through, axis=0, keepdims=True)
                  + jnp.where(packing.last_col, packing.by_block(dlasts),
                              0.0))
            db = as_rows(dbetas)
            for u, j in enumerate(js):
                dg_ref[j, r:r + 1, :] = dg[:, u * chunk:(u + 1) * chunk]
                db_ref[j, r:r + 1, :] = db[:, u * chunk:(u + 1) * chunk]
            # the pack's heads' dq and dk: a packed left factor sums
            # them in the product
            k_rows, q_rows = _stacked([p["k"]] * pack), p["q"]
            at = r, p["key"]
            dqs[at] = dqs[at] + dot(dqk, k_rows) + sum(
                g * d for g, d in zip(p["grows"], dqgs))
            dks[at] = (dks[at] + dot(dkk, k_rows)
                       + sum(_rows_of(dot(dqk, q_rows, TN)
                                      + dot(dkk, p["k"], TN), chunk))
                       + sum((b * g) * d for b, g, d in zip(
                           p["betas"], p["grows"], dkbs))
                       + sum(e * d for e, d in zip(p["to_ends"], dkds)))
    for (r, h), dq in dqs.items():
        rows, mine = slice(r * chunk, (r + 1) * chunk), slice(h * d_k,
                                                              (h + 1) * d_k)
        dq_ref[rows, mine] = dq.astype(dq_ref.dtype)
        dk_ref[rows, mine] = dks[r, h].astype(dk_ref.dtype)


def _geometry(q, v, chunk, stride):
    batch, length, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    assert length % stride == 0 and stride % chunk == 0 \
        and chunk % _SOLVE_BLOCK == 0 and heads % key_heads == 0, (
            q.shape, v.shape, chunk, stride)
    return (batch, key_heads, length // stride, heads // key_heads,
            stride // chunk, d_k, d_v)


_VMEM_LIMIT = 64 * 2**20
# value heads x chunks a grid step at most where a step may take more than one
# key head: on a v5e at 32 heads on 16 (benches/delta_rule.py, PR 59) 8 -> 16
# problems took a fifth off the forward kernel and a quarter off the backward
# for 4 s more of lowering the cell's step, 16 -> 32 another 14% and 9% for 12
# s more, which every run's set-up would pay
_STEP_PROBLEMS = 16


def _step_width(rep: int, key_heads: int, chunk: int, chunks: int, d_k: int,
                d_v: int):
    """(pack, keys, width) of a grid step, from the shapes alone. ``pack``:
    the value heads whose [C, C] matrices share a lane tile, a power of two
    that divides ``rep``. ``keys``: the adjacent key heads a step takes,
    each with its ``rep`` value heads (4, 2 or 1, a divisor of
    ``key_heads``), up to ``_STEP_PROBLEMS`` problems a step: their states'
    walks are independent chains of products and stand side by side, and
    the solve's links are paid once for all of them. ``width``: the value
    heads a step works on together (all ``keys x rep`` where that fits).
    What fits: a chunk and head holds some ten [C, d] and eight [C, C]
    float32 arrays and two states between the kernels' stages, and a
    step's may take half the VMEM limit (the blocks' two buffers and the
    compiler's own take the rest)."""
    pack = 1
    while rep % (2 * pack) == 0 and 2 * pack * chunk <= _LANES:
        pack *= 2
    wide = max(d_k, d_v)
    held = chunks * 4 * (10 * chunk * wide + 8 * chunk * chunk
                         + 2 * d_k * d_v)
    fits = max(pack, _VMEM_LIMIT // 2 // held)           # value heads
    keys = max(n for n in (4, 2, 1) if key_heads % n == 0 and (n == 1 or (
        n * rep <= fits and n * rep * chunks <= _STEP_PROBLEMS)))
    width = max(w for w in range(pack, keys * rep + 1, pack)
                if keys * rep % w == 0 and w <= fits)
    return pack, keys, width


def _log_decay(g, chunk, stride):
    """``gamma``: g summed from each chunk's start, in the kernels' form."""
    return jnp.cumsum(gates(g.astype(_F32), chunk, stride), axis=-1)


def _params(interpret: bool):
    return compiler_params(interpret, ("parallel", "parallel", "arbitrary"),
                           _VMEM_LIMIT)


def _step(q, v, chunk, stride):
    """(pack, keys, width) of the kernels' grid steps for these operands."""
    _, key_heads, _, rep, chunks, d_k, d_v = _geometry(q, v, chunk, stride)
    return _step_width(rep, key_heads, chunk, chunks, d_k, d_v)


def _specs(q, v, chunk, stride, group_of):
    """The block specs both kernels share: (q or k, v or o, a gate, a
    boundary state) of a group of ``keys`` adjacent key heads: their q and k
    adjacent lane tiles, their value heads adjacent lane tiles of v and o
    and adjacent rows of the gates and the states; the group a grid step
    works on by ``group_of``."""
    _, _, _, rep, chunks, d_k, d_v = _geometry(q, v, chunk, stride)
    keys = _step(q, v, chunk, stride)[1]
    return (
        pl.BlockSpec((None, stride, keys * d_k),
                     lambda b, h, i: (b, group_of(i), h)),
        pl.BlockSpec((None, stride, keys * rep * d_v),
                     lambda b, h, i: (b, group_of(i), h)),
        pl.BlockSpec((None, keys * rep, None, chunks, chunk),
                     lambda b, h, i: (b, h, group_of(i), 0, 0)),
        pl.BlockSpec((None, keys * rep, None, d_k, d_v),
                     lambda b, h, i: (b, h, group_of(i), 0, 0)))


def _kernel(body, q, v, chunk, stride):
    _, _, _, rep, chunks, _, _ = _geometry(q, v, chunk, stride)
    pack, _, width = _step(q, v, chunk, stride)
    return functools.partial(body, rep=rep, chunk=chunk, chunks=chunks,
                             pack=pack, width=width)


def _traced_once(call):
    """``call(*arrays, chunk, stride, interpret)`` traced once a signature
    and replayed from its jaxpr after: a kernel's body is thousands of
    equations, a program traces the rule for the function, for its forward
    rule and again under recomputation, every compilation of its step does
    so anew, and that tracing is every run's set-up."""

    @functools.lru_cache(maxsize=None)
    def traced(avals, statics):
        return jax.make_jaxpr(lambda *xs: call(*xs, *statics))(*avals)

    @functools.wraps(call)
    def replay(*args):
        *xs, chunk, stride, interpret = args
        closed = traced(tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                              for x in xs), (chunk, stride, interpret))
        return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *xs)

    return replay


@_traced_once
def _pallas_fwd(q, k, v, g, beta, chunk, stride, interpret):
    batch, key_heads, groups, rep, chunks, d_k, d_v = _geometry(
        q, v, chunk, stride)
    heads, keys = key_heads * rep, _step(q, v, chunk, stride)[1]
    qk, vo, gate, bound = _specs(q, v, chunk, stride, lambda i: i)
    o, bounds = pl.pallas_call(
        _kernel(_fwd_kernel, q, v, chunk, stride),
        grid=(batch, key_heads // keys, groups),
        in_specs=[qk, qk, vo, gate, gate],
        out_specs=[vo, bound],
        out_shape=[
            jax.ShapeDtypeStruct(folded(v).shape, v.dtype),
            jax.ShapeDtypeStruct((batch, heads, groups, d_k, d_v), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((keys * rep, d_k, d_v), _F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gated_delta_fwd",
    )(folded(q), folded(k), folded(v), _log_decay(g, chunk, stride),
      gates(beta.astype(_F32), chunk, stride))
    return o.reshape(v.shape), bounds


@_traced_once
def _pallas_bwd(q, k, v, g, beta, bounds, do, chunk, stride, interpret):
    batch, key_heads, groups, rep, chunks, d_k, d_v = _geometry(
        q, v, chunk, stride)
    heads, keys = key_heads * rep, _step(q, v, chunk, stride)[1]
    qk, vo, gate, bound = _specs(q, v, chunk, stride,
                                 lambda i: groups - 1 - i)
    dgate = jax.ShapeDtypeStruct((batch, heads, groups, chunks, chunk), _F32)
    dq, dk, dv, dgamma, dbeta = pl.pallas_call(
        _kernel(_bwd_kernel, q, v, chunk, stride),
        grid=(batch, key_heads // keys, groups),
        in_specs=[qk, qk, vo, gate, gate, vo, bound],
        out_specs=[qk, qk, vo, gate, gate],
        out_shape=[
            jax.ShapeDtypeStruct(folded(q).shape, q.dtype),
            jax.ShapeDtypeStruct(folded(k).shape, k.dtype),
            jax.ShapeDtypeStruct(folded(v).shape, v.dtype),
            dgate, dgate,
        ],
        scratch_shapes=[pltpu.VMEM((keys * rep, d_k, d_v), _F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gated_delta_bwd",
    )(folded(q), folded(k), folded(v), _log_decay(g, chunk, stride),
      gates(beta.astype(_F32), chunk, stride), folded(do), bounds)
    # g_t enters every gamma from t to its chunk's end
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgamma, -1), axis=-1), -1)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            ungated(dg), ungated(dbeta))


# ---------------------------------------------------------------------------
# one differentiable function over both
# ---------------------------------------------------------------------------

def _forward(q, k, v, g, beta, chunk, stride, impl):
    _record(q, v, chunk, stride, False, impl != "scan")
    if impl == "scan":
        o, bounds = _scan_fwd(q, k, v, g, beta, chunk, stride)
        return o.astype(v.dtype), bounds
    return _pallas_fwd(q, k, v, g, beta, chunk, stride,
                       impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule_diff(q, k, v, g, beta, chunk, stride, impl):
    return _forward(q, k, v, g, beta, chunk, stride, impl)[0]


# What recomputation keeps of the rule (``ops.remat.remat_policy``): its
# output [B, T, heads, d_v] in the compute dtype and the state each group of
# chunks starts from, [B, heads, T / stride, d_k, d_v] float32, no more bytes
# than the output
REMAT_NAMES = ("delta_rule_out", "delta_rule_bounds")


def _rule_diff_fwd(q, k, v, g, beta, chunk, stride, impl):
    o, bounds = map(ad_checkpoint.checkpoint_name,
                    _forward(q, k, v, g, beta, chunk, stride, impl),
                    REMAT_NAMES)
    return o, (q, k, v, g, beta, bounds)


def _rule_diff_bwd(chunk, stride, impl, res, do):
    q, k, v, g, beta, bounds = res
    _record(q, v, chunk, stride, True, impl != "scan")
    if impl == "scan":
        grads = _scan_bwd(q, k, v, g, beta, bounds, do, chunk, stride)
    else:
        grads = _pallas_bwd(q, k, v, g, beta, bounds, do, chunk, stride,
                            impl == "pallas_interpret")
    return tuple(d.astype(r.dtype) for d, r in zip(grads, res))


_rule_diff.defvjp(_rule_diff_fwd, _rule_diff_bwd)


def auto_impl(q, v) -> str:
    """What ``impl=None`` runs: the kernels where the layout fits them (a
    head's key and value widths whole lane tiles) and ``q`` is traced where
    a kernel may run (``mosaic.takes_kernels``); the chunked ``lax.scan``
    elsewhere."""
    fits = q.shape[3] % _LANES == 0 and v.shape[3] % _LANES == 0
    return "pallas" if fits and takes_kernels(q) else "scan"


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def gated_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                     impl: Optional[str] = None) -> jax.Array:
    """``o`` [B, T, heads, d_v] of the recurrence in the module's docstring,
    each of the B sequences from a zero state: ``q``, ``k`` [B, T, key
    heads, d_k] as the recurrence takes them (a layer normalises and scales
    them first; key head ``h // (heads / key heads)`` serves value head
    ``h``), ``v`` [B, T, heads, d_v], ``g`` [B, T, heads] the log of the
    decay (<= 0), ``beta`` [B, T, heads]. ``o`` has ``v``'s dtype; the state,
    the decays and every accumulation are float32. ``chunk`` positions share
    a triangular solve (a multiple of 16; ``CHUNK`` if left out); a boundary
    state is kept every ``stride_of(chunk, d_k, itemsize)`` positions, and a
    length that is no multiple of that is padded with positions that leave
    the state as it is (``g`` 0, ``beta`` 0) and whose output is dropped.
    ``impl``: "pallas" | "pallas_interpret" | "scan"; None: ``auto_impl``.
    """
    length = q.shape[1]
    impl = impl or auto_impl(q, v)
    chunk = chunk or CHUNK
    assert chunk % _SOLVE_BLOCK == 0, chunk
    stride = stride_of(chunk, q.shape[3], v.dtype.itemsize)
    pad = -length % stride
    if pad:
        widths = lambda t: ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)
        q, k, v, g, beta = (jnp.pad(t, widths(t)) for t in (q, k, v, g, beta))

    def rule(q, k, v, g, beta):
        return _rule_diff(q, k, v, g, beta, chunk, stride, impl)

    if impl != "scan":
        rule = per_batch_shard(rule, q, (True,) * 5, "gated_delta_rule")
    o = rule(q, k, v, g, beta)
    return o[:, :length] if pad else o
