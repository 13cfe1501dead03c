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
the rows present). Every pair of a held expert is computed, whatever the
routing; what absent experts would add is left out and nothing stands in
for them or for their exchange.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


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
                 normalize: bool = True):
    """Route each token of ``x`` (T, d) to ``k`` of the E experts.

    Scores are ``sigmoid(x @ router)`` in float32 (``router`` (d, E)); the k
    experts with the largest ``score + bias`` are chosen (``bias`` (E,) moves
    the selection only and takes no gradient); a chosen expert's weight is
    its score, over the sum of the k chosen scores where ``normalize``,
    times ``scale``. -> (experts (T, k) int32, weights (T, k) float32)."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(f32), router.astype(f32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(f32)), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalize:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scale


def _take_rows(x, index):
    """``x[index]`` along axis 0 for an index known to be in bounds: no
    clamp and no fill, which cost a pass each over a gather's result."""
    return x.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_of_sorted_pairs(x, order, inverse, mine, k: int):
    """``x[order // k]``: the token row of each sorted pair. ``order`` is a
    permutation of the T * k pairs and ``inverse`` its inverse, so the
    cotangent is a gather too (un-sort, then sum a token's k pairs), where
    autodiff's transpose of a gather is a scatter-add. Only the pairs that
    are ``mine`` (T, k) give a token anything back: the cotangent's rows of
    the others are whatever the grouped matmul left there."""
    return _take_rows(x, order // k)


def _rows_fwd(x, order, inverse, mine, k):
    return _take_rows(x, order // k), (inverse, mine)


def _rows_bwd(k, res, g):
    inverse, mine = res
    pairs = _take_rows(g, inverse).reshape(-1, k, g.shape[-1])
    dx = jnp.where(mine[..., None], pairs.astype(jnp.float32), 0.0).sum(axis=1)
    return dx.astype(g.dtype), None, None, None


_rows_of_sorted_pairs.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """``y[inverse]``: sorted pairs back in the order of the tokens; its
    cotangent is the gather ``g[order]``."""
    return _take_rows(y, inverse)


_unsort.defvjp(lambda y, order, inverse: (_unsort(y, order, inverse), order),
               lambda order, g: (_take_rows(g, order), None, None))


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
    under a key of their own), each sorted row gathers its token, two
    grouped matmuls (``jax.lax.ragged_dot``) run over the rows present, and
    the rows go back to their tokens by the inverse permutation. The row
    buffer is T * k long, the most that can fall on held experts, so no pair
    is dropped at any routing; the grouped matmuls' cost follows the rows
    present, the gathers' and the elementwise passes' the buffer."""
    T, d = x.shape
    k, held = experts.shape[1], wi.shape[0]
    assert 0 <= index < of, (index, of)
    local = experts - index * held
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(-1)          # (T * k,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    tokens = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    rows = _rows_of_sorted_pairs(x, order, inverse, mine, k)  # (T * k, d)
    hidden = jax.lax.ragged_dot(rows, wi.astype(x.dtype), tokens)
    gate, up = jnp.split(hidden, 2, axis=-1)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, wo.astype(x.dtype),
                             tokens)                          # (T * k, d)
    # The TPU's grouped matmul writes the tiles that hold a group's rows and
    # leaves the rest of its result as it finds it (the CPU's writes zeros),
    # forward and in its operand's cotangent alike. Rows past the last group
    # belong to pairs that are not ``mine``: they are set to zero where they
    # would reach a token, here and in ``_rows_of_sorted_pairs``' cotangent,
    # and are read nowhere else (a group's matmul reads its own rows only).
    # Left in, they gave a toy configuration NaN and the full one a finite
    # loss that fell a tenth as fast (PERF.md section 6, PR 31).
    pairs = _unsort(out, order, inverse).reshape(T, k, d)
    pairs = jnp.where(mine[..., None], pairs.astype(jnp.float32), 0.0)
    y = jnp.einsum("tkd,tk->td", pairs, jnp.where(mine, weights, 0.0))
    return y.astype(x.dtype), tokens
