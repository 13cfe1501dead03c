"""Attention ops: Pallas TPU flash-attention kernel + chunked JAX fallback.

TPU-native replacement for the attention math the reference delegates to
torch/CUDA ecosystems (ray SURVEY §5: sequence-parallel/long-context paths
are absent in-repo and arrive via external stacks run on Ray). Here they are
first-class ops:

- ``flash_attention``: O(seq) memory online-softmax attention. On TPU it runs
  a Pallas kernel tiled for the MXU (q blocks x kv blocks, accumulators in
  VMEM); elsewhere it runs a numerically identical ``lax.scan`` formulation,
  so tests validate the same math on CPU.
- ``attention_reference``: naive full-matrix attention for numerics tests.

All paths are differentiable: the fallback natively, the Pallas path via
custom VJP (recompute-based backward using the same online-softmax blocks).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

NEG_INF = -1e30
# lse/delta side tensors are stored lane-broadcast (last dim = one 128-lane
# register row) so their Pallas blocks satisfy the TPU (8, 128) tiling rule
_LANES = 128


def attention_reference(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """Naive softmax(QK^T)V. Shapes: (..., s, d)."""
    sm_scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k) * sm_scale
    if causal:
        q_len, k_len = s.shape[-2], s.shape[-1]
        qi = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        ki = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(qi + (k_len - q_len) >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v).astype(q.dtype)


# ----------------------------------------------------------------------
# online-softmax block update (shared by fallback + ring attention)
# ----------------------------------------------------------------------

def online_block_update(q, k, v, m, l, acc, *, sm_scale: float,
                        q_offset=0, k_offset=0, causal: bool = False,
                        k_total: Optional[int] = None):
    """Fold one KV block into flash accumulators.

    q: (..., bq, d); k/v: (..., bk, d); m,l: (..., bq); acc: (..., bq, d).
    Offsets are the blocks' global sequence positions (for causal masks in
    blockwise/ring execution). ``k_total`` masks padding columns whose
    global position is past the true sequence end.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * sm_scale
    bq, bk = s.shape[-2], s.shape[-1]
    qi = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    ki = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k_offset
    if causal:
        s = jnp.where(qi >= ki, s, NEG_INF)
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

def _flash_scan(q, k, v, *, causal: bool, sm_scale: float, block_k: int):
    *lead, q_len, d = q.shape
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
    vb = vp.reshape(*lead, nk, block_k, d)

    m0 = jnp.full((*lead, q_len), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((*lead, q_len), jnp.float32)
    a0 = jnp.zeros((*lead, q_len, d), jnp.float32)

    def body(carry, ib):
        m, l, acc = carry
        kk, vv, i = ib
        m2, l2, a2 = online_block_update(
            q, kk, vv, m, l, acc, sm_scale=sm_scale,
            q_offset=k_len - q_len, k_offset=i * block_k, causal=causal,
            k_total=k_len if pad else None,
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
# Grid-streamed K/V: the kv-block axis is the innermost ("arbitrary") grid
# dimension, so only one (block_k, d) K/V tile is resident in VMEM at a
# time — sequence length is bounded by HBM, not VMEM (the r1 kernel loaded
# the full K/V per q-block, capping seq length). The forward also emits the
# per-row logsumexp so the backward is real Pallas kernels (dq and dk/dv)
# instead of a scan-recompute VJP.

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, causal: bool, block_q: int, block_k: int,
                q_len: int, k_len: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_offset = qi * block_q + (k_len - q_len)
    k_offset = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # a causal block is live unless every row is above the diagonal
    live = (q_offset + block_q - 1 >= k_offset) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[...].astype(jnp.float32) * sm_scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + q_offset >= cols + k_offset, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        safe_m = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - safe_m)
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - safe_m), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        m = m_scr[...]
        # rows with no live columns get lse=+inf => p == 0 in the backward.
        # lse is stored lane-broadcast as (block_q, LANES): a (block_q,)
        # vector output would need a (1, block_q) block, which violates the
        # TPU (8, 128) tiling rule once the batch dim is squeezed.
        lse = jnp.where(
            l == 0.0, jnp.inf,
            jnp.where(m > NEG_INF / 2, m, 0.0) + jnp.log(l_safe),
        )
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale: float, causal: bool, block_q: int,
                   block_k: int, q_len: int, k_len: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_offset = qi * block_q + (k_len - q_len)
    k_offset = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    live = (q_offset + block_q - 1 >= k_offset) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, 0:1]
        delta = delta_ref[...][:, 0:1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + q_offset >= cols + k_offset, s, NEG_INF)
        p = jnp.exp(s - lse)  # normalized probs; lse=+inf rows -> 0
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += sm_scale * jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                    causal: bool, block_q: int, block_k: int, q_len: int,
                    k_len: int):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    q_offset = qi * block_q + (k_len - q_len)
    k_offset = ki * block_k

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    live = (q_offset + block_q - 1 >= k_offset) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, 0:1]
        delta = delta_ref[...][:, 0:1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + q_offset >= cols + k_offset, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += sm_scale * jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _compiler_params(interpret: bool, n_arbitrary: int = 1):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel")
        + ("arbitrary",) * n_arbitrary
    )


def _flash_pallas(q, k, v, *, causal: bool, sm_scale: float,
                  block_q: int, block_k: int, interpret: bool):
    """q,k,v: (B, S, D) with batch*heads folded into B. -> (out, lse)."""
    b, q_len, d = q.shape
    k_len = k.shape[1]
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    assert q_len % block_q == 0, (q_len, block_q)
    assert k_len % block_k == 0, (k_len, block_k)

    grid = (b, q_len // block_q, k_len // block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, q_len=q_len, k_len=k_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda bi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda bi, qi, ki: (bi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda bi, qi, ki: (bi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, q_len, d), q.dtype),
            jax.ShapeDtypeStruct((b, q_len, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)


def _flash_pallas_bwd_kernels(q, k, v, do, lse, delta, *, causal: bool,
                              sm_scale: float, block_q: int, block_k: int,
                              interpret: bool):
    b, q_len, d = q.shape
    k_len = k.shape[1]
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)

    qspec = lambda f: pl.BlockSpec((None, block_q, d), f)
    kspec = lambda f: pl.BlockSpec((None, block_k, d), f)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, k_len=k_len,
        ),
        grid=(b, q_len // block_q, k_len // block_k),
        in_specs=[
            qspec(lambda bi, qi, ki: (bi, qi, 0)),
            kspec(lambda bi, qi, ki: (bi, ki, 0)),
            kspec(lambda bi, qi, ki: (bi, ki, 0)),
            qspec(lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda bi, qi, ki: (bi, qi, 0)),
        ],
        out_specs=qspec(lambda bi, qi, ki: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, q_len, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, q_len=q_len, k_len=k_len,
        ),
        grid=(b, k_len // block_k, q_len // block_q),
        in_specs=[
            qspec(lambda bi, ki, qi: (bi, qi, 0)),
            kspec(lambda bi, ki, qi: (bi, ki, 0)),
            kspec(lambda bi, ki, qi: (bi, ki, 0)),
            qspec(lambda bi, ki, qi: (bi, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda bi, ki, qi: (bi, qi, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda bi, ki, qi: (bi, qi, 0)),
        ],
        out_specs=[
            kspec(lambda bi, ki, qi: (bi, ki, 0)),
            kspec(lambda bi, ki, qi: (bi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k_len, d), k.dtype),
            jax.ShapeDtypeStruct((b, k_len, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_pallas_diff(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret):
    """Differentiable Pallas flash attention: both directions are Pallas
    kernels (forward saves the logsumexp; backward recomputes P per block
    from q,k,lse — O(seq) memory, no attention matrix ever materialized)."""
    out, _ = _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return out


def _flash_pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_pallas_bwd(causal, sm_scale, block_q, block_k, interpret,
                      res, g):
    q, k, v, out, lse = res
    # delta_i = rowsum(dO_i * O_i); tiny elementwise reduce — XLA fuses it.
    # Lane-broadcast to (b, q_len, _LANES) to match the lse layout.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    dq, dk, dv = _flash_pallas_bwd_kernels(
        q, k, v, g, lse, delta, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq, dk, dv


_flash_pallas_diff.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "impl"),
)
def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    impl: Optional[str] = None) -> jax.Array:
    """Flash attention over (..., seq, head_dim) inputs.

    Accepts (b, h, s, d) or (b, s, d). With ``impl=None`` the platform
    decides: the Pallas kernel on "tpu" — where a kernel that fails to
    lower raises, it never falls back — and the scan formulation on any
    other backend. ``impl`` forces a path:
    "pallas" | "pallas_interpret" | "scan" | "reference".

    Under a mesh whose data-like axes split the batch (``_batch_axes``)
    the kernel runs per batch shard inside ``shard_map``: the partitioner
    refuses Mosaic calls, and batch and heads are independent.
    """
    sm_scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    if impl == "reference":
        return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    if impl == "scan":
        return _flash_scan(q, k, v, causal=causal, sm_scale=sm_scale,
                           block_k=block_k)
    interpret = impl == "pallas_interpret"

    def kernel(q, k, v):
        if q.ndim == 4:
            b, h, s, d = q.shape
            fold = lambda x: x.reshape(b * h, x.shape[-2], d)
            out = _flash_pallas_diff(fold(q), fold(k), fold(v), causal,
                                     sm_scale, block_q, block_k, interpret)
            return out.reshape(b, h, s, d)
        return _flash_pallas_diff(q, k, v, causal, sm_scale, block_q,
                                  block_k, interpret)

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
    mesh = jax.typeof(x).sharding.mesh
    axes = tuple(a for a in ("data", "fsdp")
                 if a in mesh.axis_names and mesh.shape[a] > 1
                 and a not in mesh.manual_axes)
    n = math.prod(mesh.shape[a] for a in axes)
    if x.shape[0] % n:
        raise ValueError(
            f"flash_attention: leading dim {x.shape[0]} is not divisible by "
            f"the mesh's batch axes {axes} (size {n}); the Pallas kernel "
            "runs per batch shard and cannot be partitioned otherwise")
    return mesh, axes
