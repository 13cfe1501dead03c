"""Mixture-of-experts with expert parallelism (EP).

SURVEY §2.9: EP = "mesh axis + ragged_all_to_all style dispatch" — absent
in the reference (Ray delegates to external stacks); TPU-native it is a
first-class parallelism axis. This implements Switch-style top-1 routing
(Fedus et al.) with GShard's dense dispatch/combine einsums, which map
onto the MXU, and an expert-parallel execution mode where experts shard
over a mesh axis and tokens travel by `lax.all_to_all` over ICI.

Two execution modes with identical math:
- ``moe_ffn``: all experts local (single chip / replicated).
- ``moe_ffn_ep``: inside ``shard_map`` with experts sharded over
  ``axis`` — dispatch (E, C, d) splits over the expert dim, an
  all_to_all sends each expert its tokens from every data shard, local
  experts run, and the inverse all_to_all returns outputs for combine.

Capacity is static (compile-friendly): C = ceil(capacity_factor * T / E);
overflow tokens are dropped by the dispatch mask (their combine weight is
zero, so the residual path carries them — standard Switch behavior).

Beside them, the layer that drops nothing (PR 31): ``topk_routing`` (sigmoid
or softmax scores, selection by score + bias, k experts a token, weights
normalised over the k) and ``held_expert_ffn``, which is told which slice of
the experts it holds, sorts the token-expert pairs that fall on them and runs
grouped SwiGLU matmuls over the sorted rows (on a TPU under no mesh the
Pallas kernels of ``ops/grouped_matmul.py``, whose grids walk the tiles that
hold a group's rows, PR 61; elsewhere ``jax.lax.ragged_dot``, which the TPU
compiler lowers to a grouped-matmul kernel of its own, in tiles of 512 x 128
x 128), what is no matmul over as much of the row buffer as holds the pairs,
chunk by chunk (``row_buffer_rungs``, PR 32), and takes the rows back to their
tokens with a Pallas kernel that reads the rows that hold a pair
(``to_tokens``, PR 53: on a TPU under no mesh; a gather of tokens x k rows
elsewhere). Every
pair of a held expert is computed, whatever the routing; what absent experts
would add is left out and nothing stands in for them or for their exchange.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import steptrace
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.mosaic import takes_unmapped_kernel


def switch_gating(logits: jnp.ndarray, capacity: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 routing.

    Args: logits (T, E); capacity C per expert.
    Returns (dispatch, combine, aux_loss):
      dispatch (T, E, C) one-hot token->slot assignment (bool as float),
      combine (T, E, C) = dispatch * router prob,
      aux_loss: Switch load-balance loss (scalar).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                      # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=logits.dtype)   # (T, E)
    # Queue positions in int32: a low-precision (bf16) cumsum silently
    # collides slots past 256 tokens per expert (8-bit mantissa).
    onehot_i = jax.nn.one_hot(expert, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot_i, axis=0) * onehot_i - onehot_i  # (T, E)
    keep = (pos < capacity).astype(logits.dtype) * onehot    # (T, E)
    slot = jax.nn.one_hot(
        jnp.sum(pos, axis=-1), capacity, dtype=logits.dtype
    )                                                        # (T, C)
    dispatch = keep[:, :, None] * slot[:, None, :]           # (T, E, C)
    gate = jnp.sum(probs * onehot, axis=-1)                  # (T,)
    combine = dispatch * gate[:, None, None]
    # load-balance loss: E * sum_e f_e * P_e (Switch eq. 4)
    f = jnp.mean(onehot, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)
    return dispatch, combine, aux


def init_moe_params(key, d_model: int, d_hidden: int, num_experts: int,
                    dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Router + stacked expert FFN parameters (experts stacked on dim 0 so
    an EP shard slices contiguously)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": (jax.random.normal(k1, (d_model, num_experts)) * scale_in
                   ).astype(dtype),
        "wi": (jax.random.normal(k2, (num_experts, d_model, d_hidden))
               * scale_in).astype(dtype),
        "wo": (jax.random.normal(k3, (num_experts, d_hidden, d_model))
               * scale_out).astype(dtype),
    }


def _expert_ffn(wi, wo, x):
    """Per-expert FFN over (E, C, d) inputs; einsums ride the MXU."""
    h = jnp.einsum("ecd,edh->ech", x, wi)
    h = jax.nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, wo)


def moe_ffn(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
            capacity_factor: float = 1.25
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense-dispatch MoE with all experts local.

    x: (T, d). Returns (out (T, d), aux_loss)."""
    E = params["router"].shape[1]
    T = x.shape[0]
    capacity = max(1, -(-int(capacity_factor * T) // E))  # ceil, as documented
    logits = x @ params["router"]
    dispatch, combine, aux = switch_gating(logits, capacity)
    expert_in = jnp.einsum("td,tec->ecd", x, dispatch)
    expert_out = _expert_ffn(params["wi"], params["wo"], expert_in)
    out = jnp.einsum("ecd,tec->td", expert_out, combine)
    return out, aux


def ep_loss_and_grads(loss_fn, params: Dict[str, jnp.ndarray],
                      data_axis: str, ep_axis: str):
    """The verified EP training-step pattern (call inside ``shard_map``
    with tokens sharded over BOTH mesh axes — no shard may duplicate
    another's tokens, or collective transposes double-count):

    - differentiate the LOCAL loss scaled by 1/N_shards,
    - global loss = psum over both axes (the global token mean),
    - router grads psum over both axes; expert grads (ep-sharded) psum
      over the data axis only.

    Gradient parity with the dense path is exact (tests/test_moe.py).
    ``loss_fn(params) -> local scalar`` (unscaled)."""
    n = jax.lax.psum(1, data_axis) * jax.lax.psum(1, ep_axis)
    scaled, grads = jax.value_and_grad(
        lambda p: loss_fn(p) / n
    )(params)
    loss = jax.lax.psum(jax.lax.psum(scaled, data_axis), ep_axis)
    grads = dict(grads)
    for k in grads:
        grads[k] = jax.lax.psum(grads[k], data_axis)
        if k == "router":  # replicated over ep too
            grads[k] = jax.lax.psum(grads[k], ep_axis)
    return loss, grads


def moe_ffn_ep(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
               axis: str, capacity_factor: float = 1.25
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE: call inside ``shard_map`` with ``params["wi"]/
    ["wo"]`` sharded over ``axis`` on the expert dim and ``x`` sharded over
    the data axis. Tokens travel to their experts and back via
    ``lax.all_to_all`` on the ``axis`` ring (ICI on TPU pods).

    Router weights are replicated; gating runs on local tokens. The global
    expert count is n * E_local."""
    n = jax.lax.psum(1, axis)
    E_local = params["wi"].shape[0]
    E = n * E_local
    T = x.shape[0]
    capacity = max(1, -(-int(capacity_factor * T) // E))  # ceil, as documented
    logits = x @ params["router"]
    dispatch, combine, aux = switch_gating(logits, capacity)
    # local dispatch to ALL global experts: (E, C, d)
    expert_in = jnp.einsum("td,tec->ecd", x, dispatch)
    # exchange: split the expert dim across shards, concat the sender dim —
    # each shard ends with (E_local, n*C, d): its experts' tokens from
    # every data shard.
    expert_in = jax.lax.all_to_all(
        expert_in, axis, split_axis=0, concat_axis=1, tiled=True
    )
    expert_out = _expert_ffn(params["wi"], params["wo"], expert_in)
    # inverse exchange: send each sender's slice back, restore (E, C, d)
    expert_out = jax.lax.all_to_all(
        expert_out, axis, split_axis=1, concat_axis=0, tiled=True
    )
    out = jnp.einsum("ecd,tec->td", expert_out, combine)
    # aux loss is computed on local tokens; average over the data shards
    # happens in the caller's loss pmean.
    return out, aux


# ----------------------------------------------------------------------
# top-k routing with no dropped pair, over the experts held
# ----------------------------------------------------------------------

def topk_routing(x, router, bias, k: int, scale: float = 1.0,
                 normalize: bool = True, eps: float = 1e-20,
                 score: str = "sigmoid"):
    """Route each token of ``x`` (T, d) to ``k`` of the E experts.

    Scores are ``sigmoid(x @ router)``, or with ``score="softmax"`` the
    softmax of ``x @ router`` over all E, in float32 (``router`` (d, E));
    the k experts with the largest ``score + bias`` are chosen (``bias``
    (E,) moves the selection only and takes no gradient; None: the scores
    alone choose); a chosen expert's weight is its score, over the sum of
    the k chosen scores plus ``eps`` where ``normalize``, times ``scale``.
    -> (experts (T, k) int32, weights f32)."""
    f32 = jnp.float32
    squash = {"sigmoid": jax.nn.sigmoid,
              "softmax": functools.partial(jax.nn.softmax, axis=-1)}[score]
    scores = squash(jnp.dot(
        x.astype(f32), router.astype(f32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(
        scores if bias is None
        else scores + jax.lax.stop_gradient(bias.astype(f32)), k)
    # each chosen expert's own score, picked by comparison, one choice at a
    # time (no array of tokens x k x experts is formed): a gather of T * k
    # scalars, and the scatter-add that is its transpose, take a v5e four to
    # six times as long (1.40 and 1.29 ms against 0.35 and 0.22 at 16,384 x
    # 8 of 256: PERF.md section 6, PR 32)
    columns = jnp.arange(scores.shape[-1])
    weights = jnp.stack(
        [jnp.where(experts[:, j:j + 1] == columns, scores, 0.0).sum(axis=-1)
         for j in range(k)], axis=-1)
    if normalize:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + eps)
    return experts.astype(jnp.int32), weights * scale


def _take_rows(x, index):
    """``x[index]`` along axis 0 for an index known to be in bounds: no
    clamp and no fill, which cost a pass each over a gather's result."""
    return x.at[index].get(mode="promise_in_bounds")


def row_buffer_rungs(pairs: int) -> Tuple[int, ...]:
    """How much of its row buffer ``held_expert_ffn`` can walk in a step of
    ``pairs`` = tokens x k token-expert pairs, ascending: one chunk of rows,
    two, ... up to ``pairs`` itself, the most that can fall on held experts.
    A chunk is a thirty-second of ``pairs`` in whole tiles of 8 rows."""
    chunk = -(-pairs // 256) * 8
    return tuple(range(chunk, pairs, chunk)) + (pairs,)


def row_buffer_rung(present, pairs: int):
    """Which of ``row_buffer_rungs(pairs)`` a layer walks in a step where
    ``present`` pairs fall on held experts: the index of the first that holds
    them. ``present`` is a number or an array (numpy's or jax's, traced or
    not), and so is the result."""
    return sum((present > rung for rung in row_buffer_rungs(pairs)[:-1]),
               0 * present)


def _unwritten(shape, dtype):
    """A buffer that no operation has written: the result of a TPU kernel
    that writes nothing, left in HBM as the allocator found it (NaN, the
    last step's rows, anything), so that no pass over it is paid for."""
    return pl.pallas_call(
        lambda out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="unwritten")()


def _fresh_buffer(x):
    """What ``_walk`` starts its buffers from in the layer of input ``x``:
    ``_unwritten`` on a TPU under no mesh axis of more than one device
    (``mosaic.takes_unmapped_kernel``: stricter than the other kernels'
    rule, this call has no batch to be mapped over), else ``jnp.zeros``,
    which the CPU's grouped matmul, reading what it likes, needs. Asked
    when a pass of the layer is traced, of the backend and ``x``'s type
    alone."""
    return _unwritten if takes_unmapped_kernel(x) else jnp.zeros


def _walk(plan, fresh, of_chunk):
    """``of_chunk(start, pair)`` -> arrays a chunk long, over each chunk of
    the row buffer up to the one that holds the last pair present, written
    side by side into buffers as long as the whole chunks that hold tokens
    x k rows, each made by ``fresh(shape, dtype)``. ``pair`` is the chunk's
    pairs in the order of the sort. One loop whose trip count follows the
    count: the code stands once in the executable, the cost is the rows'.

    Beyond what was walked a buffer is UNWRITTEN (``_fresh_buffer``: zeros
    off a TPU, whatever the memory held on one). A walked chunk is written
    whole, rows past the count inside it from the padded ``order``: real,
    finite values. So a reader may take the walked chunks as they are and
    nothing of the rest: a grouped matmul, which reads its groups' rows a
    tile at a time (512 rows ``ragged_dot``'s, 256 the kernels' of
    ``ops/grouped_matmul.py``: a chunk at the cells' sizes is whole tiles,
    so a tile that holds a pair lies inside a walked chunk; the kernels
    mask by rows what a tile holds outside a group besides); another walk over
    the same plan, a chunk at a time; the kernel ``to_tokens``, which sets to
    zero what a chunk of its own holds outside an expert's range; a gather
    whose places past the count are clamped and whose values there a select
    sets to zero (``_sum_of_pairs``, ``d_weights``)."""
    pairs = plan["order"].shape[0]
    rungs = row_buffer_rungs(pairs)
    chunk, length = rungs[0], len(rungs) * rungs[0]
    order = jnp.pad(plan["order"], (0, length - pairs))

    def at(i):
        start = i * chunk
        return of_chunk(start, jax.lax.dynamic_slice(order, (start,), (chunk,)))

    def body(i, buffers):
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(buffer, part, i * chunk, 0)
            for buffer, part in zip(buffers, at(i)))

    return jax.lax.fori_loop(
        0, row_buffer_rung(plan["present"], pairs) + 1, body,
        tuple(fresh((length,) + part.shape[1:], part.dtype)
              for part in jax.eval_shape(at, 0)))


def _count_row_buffers(fresh, buffers, backward: bool):
    """One ``counters`` record ``moe/row_buffers`` a traced pass of the
    layer (none a step; a model's layers of one shape share it): how many
    buffers ``_walk`` filled for it, their rows (tokens x k in whole
    chunks) and bytes, and how many of them nobody initialised."""
    steptrace.record_counters("moe/row_buffers", {
        "buffers": len(buffers), "rows": buffers[0].shape[0],
        "bytes": sum(b.size * b.dtype.itemsize for b in buffers),
        "unwritten": len(buffers) * (fresh is _unwritten),
        "backward": int(backward)})


def _chunk(rows, start, like):
    return jax.lax.dynamic_slice_in_dim(rows, start, like.shape[0])


# The way back to the tokens where no kernel runs (off a TPU, under a mesh, at
# shapes ``_token_blocks`` refuses): ``_gathered``, a gather of tokens x k
# rows and a sum over a token's pairs, and the kernel's reference.
#
# A gather of rows whose source is at most this large ran at the speed of its
# writes on a v5e, a larger one at a fifth of it, whatever part of the source
# its indices touch (benches/moe_gather_source.py, PR 32: 131,072 rows of
# 2,048 from 49,152 rows: 6.0 ms whole, 2.6 in two halves of the columns;
# from 131,072 rows no split helped).
_FAST_GATHER_SOURCE_BYTES = 96 * 2**20


def _column_parts(rows) -> int:
    """Into how many column blocks ``_sum_of_pairs`` cuts ``rows`` (R, d) so
    that each block is a fast gather's source: 1, 2 or 4 blocks of whole
    lane tiles, else 1."""
    source = rows.size * rows.dtype.itemsize
    for parts in (1, 2, 4):
        if (source <= parts * _FAST_GATHER_SOURCE_BYTES
                and rows.shape[1] % (parts * 128) == 0):
            return parts
    return 1


def _sum_of_pairs(rows, plan, weights=None):
    """``y[t] = sum over t's pairs that are mine of weight x rows[place]``
    -> (T, d) float32, for ``rows`` (R, d) in the order of the sort and
    ``weights`` (T, k), or None for ones. A pair that is ``mine`` lies
    before the count and so inside ``rows``; the others' places are clamped
    into it and what is read there, whatever a TPU's grouped matmul left,
    counts as zero."""
    mine = plan["mine"]
    at = jnp.minimum(plan["inverse"], rows.shape[0] - 1)
    sums = []
    for block in jnp.split(rows, _column_parts(rows), axis=1):
        pairs = _take_rows(block, at).reshape(*mine.shape, block.shape[1])
        pairs = jnp.where(mine[..., None], pairs.astype(jnp.float32), 0.0)
        if weights is not None:
            # a product and a sum, not an einsum: as a matmul its float32
            # operand [T, k, d] was written out whole, a GiB a pass
            pairs = pairs * weights[..., None]
        sums.append(pairs.sum(axis=1))
    return jnp.concatenate(sums, axis=1)


def _gather_sources(rows) -> Tuple[int, ...]:
    """The lengths at which ``_to_tokens`` cuts ``rows`` (R, d) for its
    gather, ascending: what two and four fast column blocks hold, then R."""
    fast = _FAST_GATHER_SOURCE_BYTES // (rows.shape[1] * rows.dtype.itemsize)
    return tuple(n for n in (2 * fast, 4 * fast) if n < rows.shape[0]) + (
        rows.shape[0],)


def _gathered(rows, plan, weights=None):
    """``_sum_of_pairs`` over the first of ``_gather_sources(rows)`` that
    holds the pairs present: a gather's cost follows its source's length,
    not the rows it touches, and the length has to be static."""
    sources = _gather_sources(rows)

    def from_the_first(n):
        return lambda rows, plan, weights: _sum_of_pairs(
            rows[:n], plan, weights)

    return jax.lax.switch(
        sum(plan["present"] > n for n in sources[:-1]) + 0 * plan["present"],
        [from_the_first(n) for n in sources], rows, plan, weights)


# What a block of tokens may keep in VMEM of the compiler's own 16 MiB: its
# float32 sum, two blocks of the result and two chunks of rows (9 MiB at
# blocks of 512 tokens of 2,048 bfloat16), beside the tables' blocks and a
# column block's product.
_TOKEN_BLOCK_BYTES = 10 * 2**20


def _token_blocks(rows, plan) -> Optional[Tuple[int, int]]:
    """(tokens a block, rows a chunk) of the kernel ``to_tokens`` over
    ``rows`` (R, d), or None where it does not run: rows that are not whole
    lane tiles of bfloat16 or float32, a buffer that is not whole chunks of
    128 rows, tokens that no block of 128 or more divides."""
    (tokens, _), (length, d) = plan["mine"].shape, rows.shape
    size, chunk = rows.dtype.itemsize, 128
    if (d % 128 or length % chunk
            or rows.dtype not in (jnp.bfloat16, jnp.float32)):
        return None
    return next(((block, chunk) for block in (512, 256, 128)
                 if tokens % block == 0
                 and (block * (4 + 2 * size) + 2 * chunk * size) * d
                 <= _TOKEN_BLOCK_BYTES), None)


def _to_tokens_kernel(starts, counts, place_ref, *refs, held: int,
                      weighted: bool):
    """One block of tokens of ``_placed``. ``starts`` / ``counts`` (blocks x
    held, in SMEM): where in ``rows_ref`` (HBM) each held expert's rows of
    this block's tokens begin, and how many they are; ``place_ref`` (block,
    held): the row of (token, expert), -1 where the token has none.

    An expert's range is read in chunks that start at a whole tile of rows
    at or before it, two in flight (the next expert's first among them).
    What a chunk holds outside the range (another expert's or another
    block's rows, what nobody wrote) is set to zero before the MXU places
    the rows at their tokens: ``S @ rows`` with ``S`` the 0/1 matrix of
    ``place``, which moves bits (a token has at most one row an expert) but
    would make NaN of 0 x NaN."""
    refs = list(refs)
    weight_ref = refs.pop(0) if weighted else None
    rows_ref, out_ref, sum_ref, chunk_ref, sems = refs
    (block, d), (_, chunk, _) = sum_ref.shape, chunk_ref.shape
    tile = 32 // rows_ref.dtype.itemsize        # rows of a tile in HBM
    last = rows_ref.shape[0] - chunk
    exact = (jax.lax.Precision.HIGHEST if rows_ref.dtype == jnp.float32
             else None)
    cols = min(d, 512)
    at = pl.program_id(0) * held

    def span(e):
        start, count = starts[at + e], counts[at + e]
        first = start // tile * tile
        chunks = jnp.where(count > 0, pl.cdiv(start + count - first, chunk), 0)
        return start, start + count, first, chunks

    def base(first, c):
        # the buffer's last chunk at the latest: its rows before this
        # chunk's own are the one before's, and count as outside
        return pl.multiple_of(jnp.minimum(first + c * chunk, last), tile)

    def copy(first, c, slot):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(base(first, c), chunk)], chunk_ref.at[slot],
            sems.at[slot])

    def an_expert(e, done, span, following, begun):
        start, end, first, chunks = span

        @pl.when((chunks > 0) & jnp.logical_not(begun))
        def _():
            copy(first, 0, done % 2).start()

        place = place_ref[:, e:e + 1]
        weight = weight_ref[:, e:e + 1] if weighted else None

        def a_chunk(c, carry):
            slot = (done + c) % 2
            copy(first, c, slot).wait()

            @pl.when(c + 1 < chunks)
            def _():
                copy(first, c + 1, 1 - slot).start()

            if following is not None:
                @pl.when((c + 1 == chunks) & (following[3] > 0))
                def _():
                    copy(following[2], 0, 1 - slot).start()

            here = base(first, c)
            row = here + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            inside = (row >= jnp.maximum(start, first + c * chunk)) & (
                row < end)
            places = (place - here == jax.lax.broadcasted_iota(
                jnp.int32, (block, chunk), 1)).astype(rows_ref.dtype)
            for c0 in range(0, d, cols):
                rows = chunk_ref[slot, :, c0:c0 + cols]
                placed = jnp.dot(
                    places, jnp.where(inside, rows, jnp.zeros_like(rows)),
                    preferred_element_type=jnp.float32, precision=exact)
                sum_ref[:, c0:c0 + cols] += (
                    placed * weight if weighted else placed)
            return carry

        jax.lax.fori_loop(0, chunks, a_chunk, 0)
        return done + chunks

    sum_ref[...] = jnp.zeros_like(sum_ref)
    spans = [span(e) for e in range(held)]
    done = 0
    for e in range(held):
        done = an_expert(
            e, done, spans[e], spans[e + 1] if e + 1 < held else None,
            spans[e - 1][3] > 0 if e else False)
    out_ref[...] = sum_ref[...].astype(out_ref.dtype)


def _block_ranges(plan, block: int):
    """(starts, counts), each (T / block, held): where in the sorted rows
    each held expert's pairs on a block of tokens begin (its group's start
    plus its pairs on earlier blocks), and how many they are."""
    (tokens, k), held = plan["mine"].shape, plan["tokens"].shape[0]
    counts = (plan["local"].reshape(tokens // block, block * k, 1)
              == jnp.arange(held)).sum(axis=1, dtype=jnp.int32)
    return (jnp.cumsum(plan["tokens"]) - plan["tokens"]
            + jnp.cumsum(counts, axis=0) - counts), counts


def _placed(rows, plan, weights, block: int, chunk: int,
            interpret: bool = False):
    """``_sum_of_pairs`` rounded to ``rows``' type, by the kernel
    ``to_tokens``: its cost follows the rows that hold a pair, not tokens x
    k. The sort is stable over the pairs in the tokens' order and a token
    has at most one pair an expert (``topk_routing``'s k experts differ), so
    the rows that held expert ``e`` owes a block of tokens are one range of
    ``rows``, at most a block long: the group's start plus ``e``'s pairs on
    earlier blocks. The kernel reads each range in chunks of ``chunk`` rows
    and sums in float32, a token's pairs in the order of their experts
    (``_sum_of_pairs``: in the order of the k choices)."""
    (tokens, k), held = plan["mine"].shape, plan["tokens"].shape[0]
    local, experts = plan["local"], jnp.arange(held)
    starts, counts = _block_ranges(plan, block)

    def by_expert(of_pairs):
        """(T, k) -> (T, held), by comparison as ``topk_routing``'s weights"""
        return sum(jnp.where(local[:, j:j + 1] == experts,
                             of_pairs[:, j:j + 1], 0) for j in range(k))

    tables = [by_expert(plan["inverse"].reshape(tokens, k) + 1) - 1]
    if weights is not None:
        tables.append(by_expert(weights.astype(jnp.float32)))
    d = rows.shape[1]
    return pl.pallas_call(
        functools.partial(_to_tokens_kernel, held=held,
                          weighted=weights is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tokens // block,),
            in_specs=[pl.BlockSpec((block, held), lambda b, *_: (b, 0))
                      for _ in tables] + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((2, chunk, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        interpret=interpret, name="to_tokens",
    )(starts.reshape(-1), counts.reshape(-1), *tables, rows)


def _to_tokens(rows, plan, weights, fresh, backward: bool):
    """The way from the sorted ``rows`` back to the tokens: the kernel
    (``_placed``) where ``_fresh_buffer`` chose ``_unwritten`` and
    ``_token_blocks`` finds its blocks, else the gathers (``_gathered``).
    One ``counters`` record ``moe/to_tokens`` a traced pass says which, and
    the ``slots`` (tokens x k) a gather's result holds."""
    blocks = _token_blocks(rows, plan) if fresh is _unwritten else None
    block, chunk = blocks or (0, 0)
    steptrace.record_counters("moe/to_tokens", {
        "kernel": int(blocks is not None), "slots": plan["mine"].size,
        "tokens": plan["mine"].shape[0], "block": block, "chunk": chunk,
        "held": plan["tokens"].shape[0], "backward": int(backward)})
    if blocks is None:
        return _gathered(rows, plan, weights)
    return _placed(rows, plan, weights, block, chunk)


# Who multiplies a held expert's rows by its matrices: the Pallas kernels of
# ``ops/grouped_matmul.py`` where ``_fresh_buffer`` chose ``_unwritten`` (a TPU
# under no mesh) and the shapes find their tiles in the VMEM the device's kind
# is known to have (``mosaic.vmem_bytes``), else ``jax.lax.ragged_dot``
# and its cotangent (the compiler's ``ragged-dot-none`` on a TPU). Either
# leaves a result's rows past the groups as nobody's. One ``counters`` record
# ``moe/grouped_matmul`` a traced matmul says which: ``form`` 0 (rows x w),
# 1 (rows x w^T) or 2 (rows^T x d_out, a group's matrix), the rows' buffer,
# the contraction ``k`` and the result's ``n`` columns, the rows a ``tile``
# and the blocks of K and N (0: no kernel).
def _count_grouped_matmul(form: int, rows, k: int, n: int, sizes, tiles,
                          backward: bool):
    tile, block_k, block_n = tiles or (0, 0, 0)
    steptrace.record_counters("moe/grouped_matmul", {
        "kernel": int(tiles is not None), "form": form,
        "rows": rows.shape[0], "k": k, "n": n, "held": sizes.shape[0],
        "tile": tile, "block_k": block_k, "block_n": block_n,
        "backward": int(backward)})


def _by_group(rows, w, sizes, fresh, backward: bool, transposed: bool = False):
    """``rows [R, K] x w [held, K, N] -> [R, N]`` by group; ``transposed``:
    against ``w [held, N, K]``, read in place by the kernel."""
    tiles = (grouped_matmul.tiles_by_group(rows, w, transposed)
             if fresh is _unwritten else None)
    _count_grouped_matmul(
        int(transposed), rows, rows.shape[1], w.shape[1 if transposed else 2],
        sizes, tiles and (tiles[0], rows.shape[1], tiles[1]), backward)
    if tiles is None:
        return jax.lax.ragged_dot(
            rows, w.swapaxes(1, 2) if transposed else w, sizes)
    return grouped_matmul.by_group(rows, w, sizes, transposed=transposed,
                                   tiles=tiles)


def _per_group(rows, d_out, w, sizes, fresh):
    """``rows^T [K, R] x d_out [R, N] -> [held, K, N]`` by group, of the
    rows' type: the cotangent of ``_by_group(rows, w, ...)``'s ``w``."""
    tiles = (grouped_matmul.tiles_per_group(rows, d_out)
             if fresh is _unwritten else None)
    _count_grouped_matmul(2, rows, rows.shape[1], d_out.shape[1], sizes,
                          tiles, backward=True)
    if tiles is None:
        return jax.vjp(lambda m: jax.lax.ragged_dot(rows, m, sizes),
                       w)[1](d_out)[0]
    return grouped_matmul.per_group(rows, d_out, sizes, tiles=tiles)


def _swiglu(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _relu2(hidden):
    """Un-gated: ``relu(h)^2``, whose derivative is ``2 relu(h)``."""
    return jnp.square(jax.nn.relu(hidden))


# what an expert does between its two matrices, by ``activation``: "swiglu"
# (``wi`` [held, d, 2 x width], gate and up side by side) or "relu2" (``wi``
# [held, d, width])
_ACTIVATIONS = {"swiglu": _swiglu, "relu2": _relu2}


@functools.partial(jax.jit, static_argnames="activation")
def _rows_forward(x, weights, wi, wo, plan, activation="swiglu"):
    act_fn = _ACTIVATIONS[activation]
    k, sizes = plan["mine"].shape[1], plan["tokens"]
    fresh = _fresh_buffer(x)
    rows, = _walk(plan, fresh, lambda start, pair: (
        _take_rows(x, pair // k),))
    hidden = _by_group(rows, wi.astype(x.dtype), sizes, fresh, backward=False)
    act, = _walk(plan, fresh, lambda start, pair: (
        act_fn(_chunk(hidden, start, pair)),))
    out = _by_group(act, wo.astype(x.dtype), sizes, fresh, backward=False)
    _count_row_buffers(fresh, (rows, act), backward=False)
    return _to_tokens(out, plan, weights, fresh,
                      backward=False).astype(x.dtype)


@functools.partial(jax.jit, static_argnames="activation")
def _rows_backward(x, weights, wi, wo, plan, g, activation="swiglu"):
    act_fn = _ACTIVATIONS[activation]
    f32, k, sizes = jnp.float32, plan["mine"].shape[1], plan["tokens"]
    wi_x, wo_x = wi.astype(x.dtype), wo.astype(x.dtype)
    fresh = _fresh_buffer(x)
    rows, g_rows = _walk(plan, fresh, lambda start, pair: (
        _take_rows(x, pair // k), _take_rows(g, pair // k)))
    hidden = _by_group(rows, wi_x, sizes, fresh, backward=True)
    # with y = sum of weight x (act @ wo): d_act = weight x (g @ wo^T), and
    # d_weight = g . (act @ wo) = (g @ wo^T) . act: no second matmul again
    g_act = _by_group(g_rows, wo_x, sizes, fresh, backward=True,
                      transposed=True)

    def of_chunk(start, pair):
        scale = _take_rows(weights.reshape(-1), pair)[:, None]
        act, pull = jax.vjp(act_fn, _chunk(hidden, start, pair))
        g_chunk = _chunk(g_act, start, pair).astype(f32)
        d_hidden, = pull((scale * g_chunk).astype(act.dtype))
        return (d_hidden, (scale * act.astype(f32)).astype(act.dtype),
                (g_chunk * act.astype(f32)).sum(axis=-1))

    d_hidden, act_scaled, d_scale = _walk(plan, fresh, of_chunk)
    _count_row_buffers(fresh, (rows, g_rows, d_hidden, act_scaled, d_scale),
                       backward=True)

    d_rows = _by_group(d_hidden, wi_x, sizes, fresh, backward=True,
                       transposed=True)
    d_weights = jnp.where(plan["mine"], _take_rows(d_scale, jnp.minimum(
        plan["inverse"], d_scale.shape[0] - 1)).reshape(weights.shape), 0.0)
    return (_to_tokens(d_rows, plan, None, fresh,
                       backward=True).astype(x.dtype),
            d_weights.astype(weights.dtype),
            _per_group(rows, d_hidden, wi_x, sizes, fresh).astype(wi.dtype),
            _per_group(act_scaled, g_rows, wo_x, sizes, fresh
                       ).astype(wo.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_rows(x, weights, wi, wo, plan, activation="swiglu"):
    """``held_expert_ffn``'s row work: gather, the grouped experts (a SwiGLU
    or an un-gated ``relu^2`` by ``activation``), back to the tokens. The
    grouped matmuls (``_by_group``, ``_per_group``) run over whole buffers
    and follow the rows present by themselves; what is not a matmul walks
    the buffer only as far
    as the pairs present (``_walk``), or reads the rows that hold a pair
    (``_to_tokens``).
    Past the last walked chunk a row buffer holds what nobody wrote, of the
    walked ones (``rows``, ``act``; backward ``rows``, ``g_rows``,
    ``d_hidden``, ``act_scaled``, ``d_scale``) as of the grouped matmuls'
    results, and everything here that reads one stops before it or sets
    what it read to zero (``_walk`` says who may read what). It is
    differentiated by hand: the backward pass takes the same count
    again and recomputes rows and hidden; its residuals are the arguments,
    whose shapes the count does not change; and every cotangent of a gather
    is a gather. Both passes are jitted so that a model's layers of one
    shape trace their loops and branches once: traced a layer at a time
    they cost the cell 4 to 8 s of every start."""
    return _rows_forward(x, weights, wi, wo, plan, activation)


_held_rows.defvjp(
    lambda *a: (_rows_forward(*a[:5], activation=a[5]), a[:5]),
    lambda activation, res, g: _rows_backward(
        *res, g, activation=activation) + (None,))


def held_expert_ffn(x, experts, weights, wi, wo, *, index: int, of: int,
                    activation: str = "swiglu"):
    """The held experts' part of a routed layer of SwiGLU experts, or with
    ``activation="relu2"`` of un-gated ``relu(x W_i)^2 W_o`` experts (``wi``
    then [held, d, width]); everything below holds for both.

    ``x`` (T, d); ``experts`` / ``weights`` (T, k) from ``topk_routing``
    over all E experts (a token's k experts differ: the kernel back to the
    tokens counts on at most one row a token and expert); ``wi`` (held, d,
    2 x width) holds gate and up side by side and ``wo`` (held, width, d)
    the way down, of the experts ``index * held ... (index + 1) * held -
    1``: shard ``index`` of ``of``.
    -> (y (T, d), tokens (held,) int32): ``y = sum over the token's pairs
    on held experts of weight x expert(x)``, and how many tokens each held
    expert received.

    The T * k pairs are sorted by held expert (pairs of absent experts last,
    under a key of their own), each sorted row gathers its token, grouped
    matmuls (the kernels of ``ops/grouped_matmul.py`` on a TPU under no
    mesh, else ``jax.lax.ragged_dot``) run over the rows present, and the rows
    go back to their tokens. The row buffer is T * k long, the most that
    can fall on held experts, so no pair is dropped at any routing, and the
    work follows the pairs present, ``tokens.sum()``: the matmuls' kernel by
    itself, the gathers into the buffer and the passes over the hidden rows
    in loops that stop after the chunk that holds the last pair
    (``_walk``), the way back to the tokens by reading, a block of tokens
    at a time, each held expert's range of rows (the kernel ``to_tokens``
    on a TPU under no mesh; elsewhere gathers of tokens x k rows from a
    source cut to the pairs present: ``_to_tokens``), forward and again
    backward. On a TPU the rest of every buffer is never written (PR 46:
    ``_walk`` starts from ``_unwritten`` memory where it filled 2.25 GiB a
    layer a step with zeros) and never read: no result and no gradient
    depends on it.

    What that buys (``benches/moe_row_buffer.py`` on a v5e, re-read by PR
    53: the layer alone, forward plus backward, T = 16,384, k = 8, d =
    2,048, experts 768 wide, 16 held; ms; PR 31's function, every pass over
    the whole buffer, beside PR 46's, whose loops start from unwritten
    memory and whose way back to the tokens is the gathers', and this one,
    whose way back is the kernel ``to_tokens``, and the rows it walks)::

        pairs present    PR 31's    PR 46's    this function
                8,200       28.3       14.1     10.9   (12,288)
               16,384       30.2       16.8     13.3   (16,384)
               25,000       32.3       20.5     17.1   (28,672)
               45,000       36.6       27.5     23.9   (45,056)
               65,536       41.2       38.4     32.4   (65,536)
              131,072       55.6       66.2     57.6  (131,072)

    The bench hands back the layer's result beside the gradients, so both
    passes are in it (until PR 53 its loss was linear in a result nobody
    read, and the forward pass was dead: 9.6 where this table has 14.1).

    The grouped matmuls are the kernels of ``ops/grouped_matmul.py`` where
    they run (``_by_group``, ``_per_group``; PR 61): XLA's own kernel for
    ``ragged_dot`` works in tiles of 512 x 128 x 128 and took 2.7-4.0 ms a
    call at the nemotron cell's widths (2,688 and 1,856, 768 rows an expert:
    8-11% of the MXU's peak; 0.8-1.0 ms at 3,072 and 2,048, 10-14 ms at
    4,096 rows an expert: the widths, not the rows), the kernels 0.47-0.51
    (61-67%); 45-72% against 85-89% at the lfm2 cell's 4,096 rows an expert
    (``benches/grouped_matmul.py`` holds the table). The nemotron cell's
    traced step spent 89.3 ms in its 28 grouped matmuls and spends 13.4.

    The way back to the tokens alone, a pass (the same bench): 1.2-1.3 ms by
    the kernel from 8,200 to 45,000 pairs (512 ranges of rows, one chunk of
    128 each, whatever the load) and 2.5-2.7 at all 131,072, where the
    gathers took 2.6 up to 49,152 pairs, 4.1 up to 98,304 and 5.5 beyond
    (``_gather_sources``); the rest is the rows'."""
    assert 0 <= index < of and activation in _ACTIVATIONS, (
        index, of, activation)
    plan = _plan(experts, wi.shape[0], index)
    return _held_rows(x, weights, wi, wo, plan, activation), plan["tokens"]


def _plan(experts, held: int, index: int):
    """What ``_held_rows`` needs of a routing ``experts`` (T, k) on a chip
    that holds experts ``index * held ...``: the sort of the pairs by held
    expert, its inverse, which pairs are ``mine``, their ``local`` expert
    (``held`` for the others), each held expert's ``tokens`` and how many
    pairs are ``present``."""
    local = experts - index * held
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(-1)          # (T * k,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    tokens = (key[:, None] == jnp.arange(held)).sum(axis=0, dtype=jnp.int32)
    # The TPU's grouped matmul writes the tiles that hold a group's rows and
    # leaves the rest of its result as it finds it (the CPU's writes zeros),
    # forward and in its operand's cotangent alike; and since PR 46 the
    # buffers the loops fill (``_walk``) are left as found past the last
    # walked chunk too. Rows past the count belong to no pair that is
    # ``mine``: they are set to zero where they would reach a token
    # (``_to_tokens_kernel`` and ``_sum_of_pairs``, forward and backward;
    # ``d_weights``) and are read nowhere else (a group's matmul reads its
    # own rows only). Left in, they gave a toy configuration NaN and the
    # full one a finite loss that fell a tenth as fast (PERF.md section 6,
    # PR 31).
    return {"order": order, "inverse": inverse, "mine": mine,
            "local": key.reshape(mine.shape), "tokens": tokens,
            "present": tokens.sum()}
