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
scores, selection by score + bias, k experts a token, weights normalised
over the k) and ``held_expert_ffn``, which is told which slice of the
experts it holds, sorts the token-expert pairs that fall on them and runs
grouped SwiGLU matmuls over the sorted rows (``jax.lax.ragged_dot``, which
the TPU compiler lowers to its own grouped-matmul kernel whose grid follows
the rows present), over as much of the row buffer as holds them, chunk by
chunk (``row_buffer_rungs``, PR 32). Every pair of a held expert is computed,
whatever the routing; what absent experts would add is left out and nothing
stands in for them or for their exchange.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu._private import steptrace
from ray_tpu.parallel.mesh_utils import traced_mesh_axes


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
                 normalize: bool = True, eps: float = 1e-20):
    """Route each token of ``x`` (T, d) to ``k`` of the E experts.

    Scores are ``sigmoid(x @ router)`` in float32 (``router`` (d, E)); the k
    experts with the largest ``score + bias`` are chosen (``bias`` (E,) moves
    the selection only and takes no gradient); a chosen expert's weight is
    its score, over the sum of the k chosen scores plus ``eps`` where
    ``normalize``, times ``scale``. -> (experts (T, k) int32, weights f32)."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(f32), router.astype(f32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(f32)), k)
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
    (the partitioner refuses a Mosaic call, and this one has no batch to
    be mapped over), else ``jnp.zeros``, which the CPU's grouped matmul,
    reading what it likes, needs. Asked when a pass of the layer is
    traced, of the backend and ``x``'s type alone, as
    ``ops.attention.auto_attention`` asks for its kernel."""
    _, batch_axes, other_axes = traced_mesh_axes(x)
    if jax.default_backend() == "tpu" and not (batch_axes or other_axes):
        return _unwritten
    return jnp.zeros


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
    tile of 512 at a time (a chunk at the cells' sizes is 8 such tiles, so
    a tile that holds a pair lies inside a walked chunk); another walk over
    the same plan, a chunk at a time; a gather whose places past the count
    are clamped and whose values there a select sets to zero
    (``_sum_of_pairs``, ``d_weights``)."""
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


def _to_tokens(rows, plan, weights=None):
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


def _swiglu(hidden):
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


@jax.jit
def _rows_forward(x, weights, wi, wo, plan):
    k, sizes = plan["mine"].shape[1], plan["tokens"]
    fresh = _fresh_buffer(x)
    rows, = _walk(plan, fresh, lambda start, pair: (
        _take_rows(x, pair // k),))
    hidden = jax.lax.ragged_dot(rows, wi.astype(x.dtype), sizes)
    act, = _walk(plan, fresh, lambda start, pair: (
        _swiglu(_chunk(hidden, start, pair)),))
    out = jax.lax.ragged_dot(act, wo.astype(x.dtype), sizes)
    _count_row_buffers(fresh, (rows, act), backward=False)
    return _to_tokens(out, plan, weights).astype(x.dtype)


@jax.jit
def _rows_backward(x, weights, wi, wo, plan, g):
    f32, k, sizes = jnp.float32, plan["mine"].shape[1], plan["tokens"]
    wi_x, wo_x = wi.astype(x.dtype), wo.astype(x.dtype)
    fresh = _fresh_buffer(x)
    rows, g_rows = _walk(plan, fresh, lambda start, pair: (
        _take_rows(x, pair // k), _take_rows(g, pair // k)))
    hidden = jax.lax.ragged_dot(rows, wi_x, sizes)
    # with y = sum of weight x (act @ wo): d_act = weight x (g @ wo^T), and
    # d_weight = g . (act @ wo) = (g @ wo^T) . act: no second matmul again
    g_act = jax.lax.ragged_dot(g_rows, wo_x.swapaxes(1, 2), sizes)

    def of_chunk(start, pair):
        scale = _take_rows(weights.reshape(-1), pair)[:, None]
        act, pull = jax.vjp(_swiglu, _chunk(hidden, start, pair))
        g_chunk = _chunk(g_act, start, pair).astype(f32)
        d_hidden, = pull((scale * g_chunk).astype(act.dtype))
        return (d_hidden, (scale * act.astype(f32)).astype(act.dtype),
                (g_chunk * act.astype(f32)).sum(axis=-1))

    d_hidden, act_scaled, d_scale = _walk(plan, fresh, of_chunk)
    _count_row_buffers(fresh, (rows, g_rows, d_hidden, act_scaled, d_scale),
                       backward=True)

    def for_the_matrices(rows, matrices, d_out):
        return jax.vjp(lambda m: jax.lax.ragged_dot(rows, m, sizes),
                       matrices)[1](d_out)[0]

    d_rows = jax.lax.ragged_dot(d_hidden, wi_x.swapaxes(1, 2), sizes)
    d_weights = jnp.where(plan["mine"], _take_rows(d_scale, jnp.minimum(
        plan["inverse"], d_scale.shape[0] - 1)).reshape(weights.shape), 0.0)
    return (_to_tokens(d_rows, plan).astype(x.dtype),
            d_weights.astype(weights.dtype),
            for_the_matrices(rows, wi_x, d_hidden).astype(wi.dtype),
            for_the_matrices(act_scaled, wo_x, g_rows).astype(wo.dtype))


@jax.custom_vjp
def _held_rows(x, weights, wi, wo, plan):
    """``held_expert_ffn``'s row work: gather, grouped SwiGLU, back to the
    tokens. The grouped matmuls run over whole buffers and follow the rows
    present by themselves; what is not a matmul walks the buffer only as far
    as the pairs present (``_walk``), or reads that far (``_to_tokens``).
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
    return _rows_forward(x, weights, wi, wo, plan)


_held_rows.defvjp(lambda *a: (_rows_forward(*a), a),
                  lambda res, g: _rows_backward(*res, g) + (None,))


def held_expert_ffn(x, experts, weights, wi, wo, *, index: int, of: int):
    """The held experts' part of a routed SwiGLU layer.

    ``x`` (T, d); ``experts`` / ``weights`` (T, k) from ``topk_routing``
    over all E experts; ``wi`` (held, d, 2 x width) holds gate and up side
    by side and ``wo`` (held, width, d) the way down, of the experts
    ``index * held ... (index + 1) * held - 1``: shard ``index`` of ``of``.
    -> (y (T, d), tokens (held,) int32): ``y = sum over the token's pairs
    on held experts of weight x expert(x)``, and how many tokens each held
    expert received.

    The T * k pairs are sorted by held expert (pairs of absent experts last,
    under a key of their own), each sorted row gathers its token, grouped
    matmuls (``jax.lax.ragged_dot``) run over the rows present, and the rows
    go back to their tokens. The row buffer is T * k long, the most that
    can fall on held experts, so no pair is dropped at any routing, and the
    work follows the pairs present, ``tokens.sum()``: the matmuls' kernel by
    itself, the gathers into the buffer and the passes over the hidden rows
    in loops that stop after the chunk that holds the last pair
    (``_walk``), the gathers back to the tokens by reading a source cut to
    that length (``_to_tokens``), forward and again backward. On a TPU the
    rest of every buffer is never written (PR 46: ``_walk`` starts from
    ``_unwritten`` memory where it filled 2.25 GiB a layer a step with
    zeros) and never read: no result and no gradient depends on it.

    What that buys (``benches/moe_row_buffer.py`` on a v5e, PRs 32 and 46:
    the layer alone, forward plus backward, T = 16,384, k = 8, d = 2,048,
    experts 768 wide, 16 held; ms; PR 31's function, every pass over the
    whole buffer, beside PR 32's, whose loops started from zeros, and this
    one, whose loops start from unwritten memory, and the rows it walks)::

        pairs present    PR 31's    PR 32's    this function
                8,200       27.3       12.2      9.6   (12,288)
               16,384       29.0       13.9     11.2   (16,384)
               25,000       31.1       16.5     14.0   (28,672)
               45,000       35.5       21.5     19.0   (45,056)
               65,536       40.0       28.4     26.0   (65,536)
               90,000       45.5       34.8     32.6   (90,112)
              131,072       54.4       46.7     44.7  (131,072)

    The bench differentiates a loss that is linear in the result, so only
    the backward pass's five buffers are in it (1.56 GiB of zeros, 1.7 ms a
    GiB); a training step fills the forward's two as well.

    The gathers back to the tokens step at 49,152 and 98,304 pairs (about
    1.5 ms each way: ``_gather_sources``); the rest is the rows'."""
    held = wi.shape[0]
    assert 0 <= index < of, (index, of)
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
    # (``_sum_of_pairs``, forward and backward; ``d_weights``) and are
    # read nowhere else (a group's matmul reads its own rows only).
    # Left in, they gave a toy configuration NaN and the full one a finite
    # loss that fell a tenth as fast (PERF.md section 6, PR 31).
    plan = {"order": order, "inverse": inverse, "mine": mine,
            "tokens": tokens, "present": tokens.sum()}
    return _held_rows(x, weights, wi, wo, plan), tokens
