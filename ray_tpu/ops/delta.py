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
(``ops.attention.remat_policy`` keeps them, so a recomputed block does not
run the forward rule again).

The kernels (``gated_delta_fwd`` / ``gated_delta_bwd``: the benchmark's
readers find them by these names) address the model's own arrays, [B, T,
heads x width], one lane tile a head at width 128. The grid is (batch, key
heads, groups, value heads a key head), the last innermost: a key head's
q and k are fetched once for the value heads that share them, whose dq and
dk are summed in VMEM; each value head's state is carried from group to
group in VMEM scratch. ``(I + L)^-1`` is made from matmuls alone: the
diagonal 16 x 16 blocks by the finite series ``(I - X)(I + X^2)(I + X^4)(I
+ X^8)`` (X nilpotent of index 16), then the blocks below them by the same
series over the strictly block-lower rest; float32 in and out, each product
at full float32 precision for float32 operands and, for bfloat16 operands
(whose T is rounded to bfloat16 before the matmuls that use it), with both
factors held to 16 bits of mantissa (``_split_dot``: half the MXU passes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu.ops.attention import (DELTA_REMAT_NAMES, _batch_axes,
                                   unmapped_mesh_axes)

CHUNK = 64           # positions a chunk: one triangular solve each
_SOLVE_BLOCK = 16    # the diagonal blocks the solve's first series inverts
_LANES = 128
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


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
    qk, vo, gates = (2 * tokens * key_heads * d_k * size,
                     tokens * heads * d_v * size, 2 * tokens * heads * 4)
    return (2 * qk + 3 * vo + 2 * gates) if backward else qk + 2 * vo + gates


def _record(q, v, chunk, stride, backward: bool):
    """One ``counters`` record a traced pass (none a step): what the rule
    walks, what its boundary states weigh and what it has to move."""
    batch, length, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    steptrace.record_counters("delta/rule", {
        "heads": heads, "key_heads": key_heads, "d_k": d_k, "d_v": d_v,
        "tokens": batch * length, "sequences": batch, "chunk": chunk,
        "boundary_bytes": batch * (length // stride) * heads * d_k * d_v * 4,
        "bytes_needed": bytes_needed(q, v, backward),
        "backward": int(backward)})


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


def _grouped(t, stride, chunk):
    """[B, T, ...] -> [T / stride, stride / chunk, B, chunk, ...]."""
    b, length = t.shape[:2]
    t = t.reshape(b, length // stride, stride // chunk, chunk, *t.shape[2:])
    return jnp.moveaxis(t, 0, 2)


def _ungrouped(t):
    t = jnp.moveaxis(t, 2, 0)
    return t.reshape(t.shape[0], -1, *t.shape[4:])


def _scan_group(state, inputs, rep):
    q, k, v, g, beta = inputs
    wide = lambda t: jnp.repeat(t, rep, axis=3) if rep > 1 else t
    return lax.scan(_scan_chunk, state, (wide(q), wide(k), v, g, beta))


def _scan_operands(q, k, v, g, beta, chunk, stride):
    return tuple(_grouped(t.astype(_F32), stride, chunk)
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
    return _ungrouped(o), jnp.moveaxis(bounds, 0, 2)


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
         jnp.moveaxis(bounds, 2, 0), _grouped(do.astype(_F32), stride, chunk)),
        reverse=True)
    return tuple(map(_ungrouped, grads))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _dot(a, b, dims=_NN, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=_F32)


def _iota(chunk: int):
    return (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0),
            lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _as_col(row_vec, chunk: int):
    """[1, C] -> [C, 1]: the numbers a position each, down the sublanes."""
    row, col = _iota(chunk)
    return jnp.sum(jnp.where(row == col, row_vec, 0.0), axis=1, keepdims=True)


def _as_row(col_vec, chunk: int):
    """[C, 1] -> [1, C]."""
    row, col = _iota(chunk)
    return jnp.sum(jnp.where(row == col, col_vec, 0.0), axis=0, keepdims=True)


def _split_dot(a, b):
    """``a @ b`` of float32 operands held to 16 bits of mantissa: each split
    into a bfloat16 and what that leaves, three passes of the MXU where full
    float32 precision takes six."""
    halves = lambda t: (t.astype(jnp.bfloat16),
                        (t - t.astype(jnp.bfloat16).astype(_F32)).astype(
                            jnp.bfloat16))
    (a_hi, a_lo), (b_hi, b_lo) = halves(a), halves(b)
    return _dot(a_hi, b_hi) + (_dot(a_hi, b_lo) + _dot(a_lo, b_hi))


def _unit_lower_inverse(lower, chunk: int, exact: bool):
    """``(I + lower)^-1`` of a strictly lower-triangular [C, C] float32, by
    matmuls alone: first the diagonal ``_SOLVE_BLOCK``-wide blocks (X =
    minus those blocks is nilpotent of index 16, so the inverse is the
    finite product of ``I + X^(2^n)``), then ``(I + M)^-1`` of what is
    left, M = (diagonal inverse) x (the blocks below the diagonal),
    nilpotent of index C / 16. ``exact``: every product at full float32
    precision (float32 operands: the result is used as it is); else by
    ``_split_dot`` (the result is rounded to the operands' 8 bits before its
    first use, and these ten products are most of a chunk's MXU passes)."""
    hi = functools.partial(_dot, precision=_HIGHEST) if exact else _split_dot
    row, col = _iota(chunk)
    eye = (row == col).astype(_F32)
    same = (row // _SOLVE_BLOCK) == (col // _SOLVE_BLOCK)
    power = -jnp.where(same, lower, 0.0)
    inverse = eye + power
    for _ in range(3):                       # X^2, X^4, X^8
        power = hi(power, power)
        inverse = inverse + hi(inverse, power)
    blocks = chunk // _SOLVE_BLOCK
    if blocks == 1:
        return inverse
    power = -hi(inverse, jnp.where(same, 0.0, lower))
    rest = eye + power
    for _ in range((blocks - 1).bit_length() - 1):
        power = hi(power, power)
        rest = rest + hi(rest, power)
    return hi(rest, inverse)


def _chunk_parts(q_ref, k_ref, v_ref, g_ref, b_ref, r: int, chunk: int):
    """What both kernels make of chunk ``r`` of a group before the state
    enters: the operands, the decays in the forms the matmuls want them and
    ``T = (I + L)^-1`` with the right-hand sides it is applied to."""
    rows = slice(r * chunk, (r + 1) * chunk)
    q, k, v = q_ref[rows, :], k_ref[rows, :], v_ref[rows, :]
    dt = q.dtype
    gamma_row = g_ref[r:r + 1, :]                                # [1, C]
    gamma = _as_col(gamma_row, chunk)                            # [C, 1]
    beta = _as_col(b_ref[r:r + 1, :], chunk)
    row, col = _iota(chunk)
    last = jnp.sum(jnp.where(col[:1] == chunk - 1, gamma_row, 0.0), axis=1,
                   keepdims=True)                                # [1, 1]
    decay = jnp.where(row >= col,
                      jnp.exp(jnp.where(row >= col, gamma - gamma_row, 0.0)),
                      0.0)
    kk = _dot(k, k, _NT)
    lower = jnp.where(row > col, beta * kk * decay, 0.0)
    solved = _unit_lower_inverse(lower, chunk, exact=dt == _F32)
    grow, to_end = jnp.exp(gamma), jnp.exp(last - gamma)         # [C, 1]
    k32, q32, v32 = k.astype(_F32), q.astype(_F32), v.astype(_F32)
    return dict(
        q=q, k=k, dt=dt, k32=k32, v32=v32, beta=beta, grow=grow,
        decay=decay, kk=kk, lower=lower, solved=solved, strict=row > col,
        seen=row >= col, to_end=to_end, kb32=k32 * (beta * grow),
        vb32=v32 * beta, qg32=q32 * grow, kd32=k32 * to_end,
        last=jnp.exp(last),
        # the same over a row of the state: Mosaic broadcasts one way a time
        last_row=jnp.broadcast_to(jnp.exp(last), (1, v.shape[1])))


def _chunk_new_values(p, state):
    """-> (W [C, d_k] float32, V' [C, d_v] float32) of a chunk that starts
    in ``state`` (float32)."""
    dt = p["dt"]
    solved = p["solved"].astype(dt)
    w = _dot(solved, p["kb32"].astype(dt))
    u = _dot(solved, p["vb32"].astype(dt))
    return w, u - _dot(w.astype(dt), state.astype(dt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, bound_ref, s_scr,
                *, chunk: int, chunks: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _():
        s_scr[j] = jnp.zeros(s_scr.shape[1:], _F32)

    state = s_scr[j]
    bound_ref[...] = state
    for r in range(chunks):
        p = _chunk_parts(q_ref, k_ref, v_ref, g_ref, b_ref, r, chunk)
        dt = p["dt"]
        _, new_v = _chunk_new_values(p, state)
        attend = (_dot(p["q"], p["k"], _NT) * p["decay"]).astype(dt)
        o = (_dot(p["qg32"].astype(dt), state.astype(dt))
             + _dot(attend, new_v.astype(dt)))
        o_ref[r * chunk:(r + 1) * chunk, :] = o.astype(o_ref.dtype)
        state = p["last_row"] * state + _dot(p["kd32"].astype(dt),
                                             new_v.astype(dt), _TN)
    s_scr[j] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, bound_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                ds_scr, starts_scr, dq_scr, dk_scr, *, chunk: int,
                chunks: int, rep: int):
    i, j = pl.program_id(2), pl.program_id(3)    # group i from the last

    @pl.when(i == 0)
    def _():
        ds_scr[j] = jnp.zeros(ds_scr.shape[1:], _F32)

    # the group's states again, from its boundary: starts[r] is the state
    # chunk r starts from
    parts, state = [], bound_ref[...]
    for r in range(chunks):
        p = _chunk_parts(q_ref, k_ref, v_ref, g_ref, b_ref, r, chunk)
        parts.append(p)
        starts_scr[r] = state
        if r + 1 < chunks:
            _, new_v = _chunk_new_values(p, state)
            state = p["last_row"] * state + _dot(
                p["kd32"].astype(p["dt"]), new_v.astype(p["dt"]), _TN)

    dstate = ds_scr[j]
    for r in reversed(range(chunks)):
        p, rows = parts[r], slice(r * chunk, (r + 1) * chunk)
        dt = p["dt"]
        cast = lambda t: t.astype(dt)
        start = starts_scr[r]
        start_dt, dstate_dt = cast(start), cast(dstate)
        solved = cast(p["solved"])
        kb, vb, kd, qg = map(cast, (p["kb32"], p["vb32"], p["kd32"],
                                    p["qg32"]))
        w, new_v = _chunk_new_values(p, start)
        do = do_ref[rows, :]
        attend = _dot(p["q"], p["k"], _NT) * p["decay"]
        d_new_v = _dot(cast(attend), do, _TN) + _dot(kd, dstate_dt)
        d_attend = jnp.where(p["seen"], _dot(do, cast(new_v), _NT), 0.0)
        dqg = _dot(do, start_dt, _NT)
        dkd = _dot(cast(new_v), dstate_dt, _NT)
        d_new_v_dt = cast(d_new_v)
        dw = -_dot(d_new_v_dt, start_dt, _NT)
        dw_dt = cast(dw)
        dsolved = _dot(d_new_v_dt, vb, _NT) + _dot(dw_dt, kb, _NT)
        dvb = _dot(solved, d_new_v_dt, _TN)
        dkb = _dot(solved, dw_dt, _TN)
        # d(I + L)^-1 = -T^T dT T^T, on the strictly lower part
        dlower = jnp.where(p["strict"], -_dot(
            cast(_dot(solved, cast(dsolved), _TN)), solved, _NT), 0.0)
        dkk = cast(dlower * (p["beta"] * p["decay"]))
        dqk = cast(d_attend * p["decay"])
        through = dlower * p["lower"] + d_attend * attend   # d/d(decay's log)
        rowsum = lambda t: jnp.sum(t, axis=1, keepdims=True)
        dkd_kd = rowsum(dkd * p["kd32"])
        dgamma = (rowsum(dqg * p["qg32"]) + rowsum(dkb * p["kb32"]) - dkd_kd
                  + rowsum(through))
        dlast = jnp.sum(dkd_kd, axis=0, keepdims=True) + p["last"] * jnp.sum(
            rowsum(dstate * start), axis=0, keepdims=True)       # [1, 1]
        lane = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        dg_ref[r:r + 1, :] = (
            _as_row(dgamma, chunk) - jnp.sum(through, axis=0, keepdims=True)
            + jnp.where(lane == chunk - 1, dlast, 0.0))
        dbeta = (rowsum(dlower * p["kk"] * p["decay"])
                 + p["grow"] * rowsum(dkb * p["k32"])
                 + rowsum(dvb * p["v32"]))
        db_ref[r:r + 1, :] = _as_row(dbeta, chunk)
        dv_ref[rows, :] = (p["beta"] * dvb).astype(dv_ref.dtype)
        dq = _dot(dqk, p["k"]) + p["grow"] * dqg
        dk = (_dot(dqk, p["q"], _TN) + _dot(dkk, p["k"])
              + _dot(dkk, p["k"], _TN) + (p["beta"] * p["grow"]) * dkb
              + p["to_end"] * dkd)

        @pl.when(j == 0)
        def _():
            dq_scr[rows, :] = dq
            dk_scr[rows, :] = dk

        @pl.when(j > 0)
        def _():
            dq_scr[rows, :] += dq
            dk_scr[rows, :] += dk

        dstate = (_dot(qg, do, _TN) + p["last_row"] * dstate
                  - _dot(cast(w), d_new_v_dt, _TN))
    ds_scr[j] = dstate

    @pl.when(j == rep - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)


def _geometry(q, v, chunk, stride):
    batch, length, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    assert length % stride == 0 and stride % chunk == 0 \
        and chunk % _SOLVE_BLOCK == 0 and heads % key_heads == 0, (
            q.shape, v.shape, chunk, stride)
    return (batch, key_heads, length // stride, heads // key_heads,
            stride // chunk, d_k, d_v)


def _folded(t):
    """[B, T, H, d] -> [B, T, H x d]: the model's own array."""
    return t.reshape(*t.shape[:2], -1)


def _gates(t, chunk, stride):
    """[B, T, H] float32 -> [B, H, T / stride, stride / chunk, chunk]: a
    chunk's numbers along the lanes."""
    batch, length, heads = t.shape
    return jnp.transpose(
        t.reshape(batch, length // stride, stride // chunk, chunk, heads),
        (0, 4, 1, 2, 3))


def _ungated(t):
    batch, heads = t.shape[:2]
    return jnp.transpose(t, (0, 2, 3, 4, 1)).reshape(batch, -1, heads)


def _log_decay(g, chunk, stride):
    """``gamma``: g summed from each chunk's start, in the kernels' form."""
    return jnp.cumsum(_gates(g.astype(_F32), chunk, stride), axis=-1)


def _params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=64 * 2**20)


def _specs(q, v, chunk, stride, group_of):
    """The block specs both kernels share: (q or k, v or o, a gate, a
    boundary state), the group a grid step works on by ``group_of``."""
    _, _, _, rep, chunks, d_k, d_v = _geometry(q, v, chunk, stride)
    head = lambda h, j: h * rep + j
    return (
        pl.BlockSpec((None, stride, d_k),
                     lambda b, h, i, j: (b, group_of(i), h)),
        pl.BlockSpec((None, stride, d_v),
                     lambda b, h, i, j: (b, group_of(i), head(h, j))),
        pl.BlockSpec((None, None, None, chunks, chunk),
                     lambda b, h, i, j: (b, head(h, j), group_of(i), 0, 0)),
        pl.BlockSpec((None, None, None, d_k, d_v),
                     lambda b, h, i, j: (b, head(h, j), group_of(i), 0, 0)))


def _pallas_fwd(q, k, v, g, beta, chunk, stride, interpret):
    batch, key_heads, groups, rep, chunks, d_k, d_v = _geometry(
        q, v, chunk, stride)
    heads = key_heads * rep
    qk, vo, gate, bound = _specs(q, v, chunk, stride, lambda i: i)
    o, bounds = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=chunks),
        grid=(batch, key_heads, groups, rep),
        in_specs=[qk, qk, vo, gate, gate],
        out_specs=[vo, bound],
        out_shape=[
            jax.ShapeDtypeStruct(_folded(v).shape, v.dtype),
            jax.ShapeDtypeStruct((batch, heads, groups, d_k, d_v), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((rep, d_k, d_v), _F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gated_delta_fwd",
    )(_folded(q), _folded(k), _folded(v), _log_decay(g, chunk, stride),
      _gates(beta.astype(_F32), chunk, stride))
    return o.reshape(v.shape), bounds


def _pallas_bwd(q, k, v, g, beta, bounds, do, chunk, stride, interpret):
    batch, key_heads, groups, rep, chunks, d_k, d_v = _geometry(
        q, v, chunk, stride)
    heads = key_heads * rep
    qk, vo, gate, bound = _specs(q, v, chunk, stride,
                                 lambda i: groups - 1 - i)
    gates = jax.ShapeDtypeStruct((batch, heads, groups, chunks, chunk), _F32)
    dq, dk, dv, dgamma, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=chunks, rep=rep),
        grid=(batch, key_heads, groups, rep),
        in_specs=[qk, qk, vo, gate, gate, vo, bound],
        out_specs=[qk, qk, vo, gate, gate],
        out_shape=[
            jax.ShapeDtypeStruct(_folded(q).shape, q.dtype),
            jax.ShapeDtypeStruct(_folded(k).shape, k.dtype),
            jax.ShapeDtypeStruct(_folded(v).shape, v.dtype),
            gates, gates,
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, d_k, d_v), _F32),
            pltpu.VMEM((chunks, d_k, d_v), _F32),
            pltpu.VMEM((stride, d_k), _F32),
            pltpu.VMEM((stride, d_k), _F32),
        ],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gated_delta_bwd",
    )(_folded(q), _folded(k), _folded(v), _log_decay(g, chunk, stride),
      _gates(beta.astype(_F32), chunk, stride), _folded(do), bounds)
    # g_t enters every gamma from t to its chunk's end
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgamma, -1), axis=-1), -1)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            _ungated(dg), _ungated(dbeta))


# ---------------------------------------------------------------------------
# one differentiable function over both
# ---------------------------------------------------------------------------

def _forward(q, k, v, g, beta, chunk, stride, impl):
    _record(q, v, chunk, stride, False)
    if impl == "scan":
        o, bounds = _scan_fwd(q, k, v, g, beta, chunk, stride)
        return o.astype(v.dtype), bounds
    return _pallas_fwd(q, k, v, g, beta, chunk, stride,
                       impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule_diff(q, k, v, g, beta, chunk, stride, impl):
    return _forward(q, k, v, g, beta, chunk, stride, impl)[0]


def _rule_diff_fwd(q, k, v, g, beta, chunk, stride, impl):
    o, bounds = map(ad_checkpoint.checkpoint_name,
                    _forward(q, k, v, g, beta, chunk, stride, impl),
                    DELTA_REMAT_NAMES)
    return o, (q, k, v, g, beta, bounds)


def _rule_diff_bwd(chunk, stride, impl, res, do):
    q, k, v, g, beta, bounds = res
    _record(q, v, chunk, stride, True)
    if impl == "scan":
        grads = _scan_bwd(q, k, v, g, beta, bounds, do, chunk, stride)
    else:
        grads = _pallas_bwd(q, k, v, g, beta, bounds, do, chunk, stride,
                            impl == "pallas_interpret")
    return tuple(d.astype(r.dtype) for d, r in zip(grads, res))


_rule_diff.defvjp(_rule_diff_fwd, _rule_diff_bwd)


def auto_impl(q, v) -> str:
    """What ``impl=None`` runs: the kernels on a TPU where the layout fits
    them (a head's key and value widths whole lane tiles) and the mesh ``q``
    is traced under has no axis of more than one device but the batch's
    (the kernel then runs per batch shard, as the flash kernel does); the
    chunked ``lax.scan`` elsewhere."""
    fits = q.shape[3] % _LANES == 0 and v.shape[3] % _LANES == 0
    if jax.default_backend() == "tpu" and fits and not unmapped_mesh_axes(q):
        return "pallas"
    return "scan"


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

    mesh, axes = _batch_axes(q) if impl != "scan" else (None, ())
    if axes:
        rows = PartitionSpec(axes)
        rule = jax.shard_map(rule, mesh=mesh, in_specs=(rows,) * 5,
                             out_specs=rows, axis_names=set(axes),
                             check_vma=False)
    o = rule(q, k, v, g, beta)
    return o[:, :length] if pad else o
