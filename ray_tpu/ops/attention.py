"""Attention ops: Pallas TPU flash-attention kernel + chunked JAX fallback.

TPU-native replacement for the attention math the reference delegates to
torch/CUDA ecosystems (ray SURVEY §5: sequence-parallel/long-context paths
are absent in-repo and arrive via external stacks run on Ray). Here they are
first-class ops:

- ``flash_attention``: O(seq) memory online-softmax attention. On TPU it runs
  a Pallas kernel tiled for the MXU (``ops/flash_kernels.py``: tiles of
  queries x keys, accumulators in VMEM, tile sizes chosen from the sequence
  lengths); elsewhere it runs a numerically identical ``lax.scan``
  formulation, so tests validate the same math on CPU.
- ``attention_reference``: naive full-matrix attention for numerics tests.
- ``causal_self_attention``: a model's entry, ``attention="auto"`` chosen by
  ``auto_attention``.

All paths are differentiable: the fallback natively, the Pallas path via
custom VJP (one backward kernel that recomputes the probabilities from
q, k and the saved logsumexp).

This file holds what a caller reads: the reference and the scan, the two
shape rules that choose a call's boundary, the ``custom_vjp`` and the
entries. The kernels' bodies and their ``pallas_call`` builders are
``ops/flash_kernels.py``; where a Mosaic call may run and how it is handed to
a mesh is ``ops/mosaic.py``. ``normed_rotary_self_attention``, at the end, is
the entry of a layer whose q and k pass a head norm and a rotation first:
their kernels are ``ops/rotary.py``.

Four kinds of mask: causal, a causal window, block diffusion's over two
streams (``blocks``), each a function of the two positions alone that the
kernels work out from a tile's place, and since PR 67 a SELECTION
(``selected``): a mask the step computed (``ops/sparse_index.py``: the keys
a learned indexer picks for each query), an operand that every head of a
batch row reads a tile at a time: a bit a pair, int32 words [B, T / 32, T
queries] (``sparse_index.pack``), to ``normed_rotary_self_attention``,
whose kernels expand a tile's words in registers; the dense int8 [B, T
keys, T queries] that ``sparse_index.unpack`` makes of them to
``attention_reference`` and ``causal_self_attention``. It excludes
``window`` and ``blocks``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import ad_checkpoint, lax

from ray_tpu._private import steptrace
from ray_tpu.ops import flash_kernels as kernels, rotary
from ray_tpu.ops.sparse_index import pairs_selected, unpack
from ray_tpu.ops.flash_kernels import NEG_INF
from ray_tpu.ops.mosaic import per_batch_shard, takes_kernels


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


def seen_by_block(qi, ki, blocks: int, half: int):
    """Whether, under the block-diffusion mask over two streams of ``half``
    positions, a noisy copy [0, half) and a clean copy [half, 2 half), both
    cut into blocks of ``blocks`` tokens, the query at ``qi`` sees the key
    at ``ki``: a noisy query the noisy keys of its own block and the clean
    keys of the blocks before it; a clean query the clean keys of its own
    block and of those before it, and no noisy key."""
    q_block, k_block = (qi % half) // blocks, (ki % half) // blocks
    return jnp.where(
        qi < half,
        jnp.where(ki < half, k_block == q_block, k_block < q_block),
        (ki >= half) & (k_block <= q_block))


def attention_reference(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None,
                        blocks: Optional[int] = None,
                        selected=None) -> jax.Array:
    """Naive softmax(QK^T)V. Shapes: (..., h, s, d); ``k`` and ``v`` may
    have fewer heads, each read by a group of query heads. Under ``window``
    a query sees its own position and the ``window - 1`` before it; given
    ``blocks``, the mask is ``seen_by_block``'s over two streams of s / 2;
    given ``selected`` (int8 [b, s keys, s queries], a mask the step
    computed: ``ops/sparse_index.py``; no window, no blocks), a query sees
    the keys it says, which hold the causal edge."""
    sm_scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    assert causal or window is None, "a window is a causal mask's"
    k, v = _per_query_head(q, k), _per_query_head(q, v)
    s = jnp.einsum("...qd,...kd->...qk", q, k) * sm_scale
    if selected is not None:
        assert causal and window is None and not blocks, (window, blocks)
        s = jnp.where(jnp.swapaxes(selected, -1, -2)[:, None] != 0, s,
                      NEG_INF)
    elif blocks:
        q_len, k_len = s.shape[-2], s.shape[-1]
        assert q_len == k_len and window is None, (q_len, k_len, window)
        qi = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        ki = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        s = jnp.where(seen_by_block(qi, ki, blocks, q_len // 2), s, NEG_INF)
    elif causal:
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
# the Pallas path: which boundary a call's shapes admit, and one
# differentiable function over the kernels (``ops/flash_kernels.py``)
# ----------------------------------------------------------------------

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
    one_block = seq_len <= kernels._MAX_RESIDENT and seq_len % 128 == 0
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
    return seq_len > kernels._MAX_RESIDENT and d == d_v and d % 128 == 0


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_pallas_diff(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, window=None, heads=None, blocks=None):
    """Differentiable Pallas flash attention: both directions are Pallas
    kernels (forward saves the logsumexp; one backward kernel recomputes P
    per tile from q,k,lse — O(seq) memory, no attention matrix ever
    materialized). q, k, v are the kernels' own [B x H, T, d] or, given
    ``heads``, a model's [B, T, heads x d] (``heads_a_lane_tile``, or past
    one block of keys ``results_in_model_arrays``, where k and v may hold
    fewer heads). ``blocks``: the block-diffusion mask's block length."""
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, window, heads, blocks)[0]


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
                   window, heads, blocks=None):
    """(out, lse) by the boundary the operands are in."""
    tiles = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                 interpret=interpret, window=window)
    if heads is None:
        return kernels._flash_pallas(q, k, v, causal=causal, blocks=blocks,
                                     **tiles)
    assert causal, "a model's own arrays are causal self-attention's"
    if q.shape[1] > kernels._MAX_RESIDENT:   # ``results_in_model_arrays``
        return kernels._flash_pallas(*_folded_operands(q, k, v, heads),
                                     causal=True, heads=heads, blocks=blocks,
                                     **tiles)
    # never under ``blocks``: ``_boundary`` keeps the lanes' kernels off
    return kernels._flash_pallas_lanes(q, k, v, heads=heads, **tiles)


# What recomputation keeps of the kernel (``ops.remat.remat_policy``): the
# two residuals of the forward rule that only the kernel can make (``q``,
# ``k`` and ``v`` come back from a block's projections). ``_flash_pallas_fwd``
# names them, and hands the named output on, so that what a block computes
# from it is recomputed from the kept copy. Without a policy a name is the
# identity and lowers to nothing.
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
REMAT_NAMES = ("flash_out", "flash_lse")


def _flash_pallas_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                      window, heads, blocks):
    out, lse = map(ad_checkpoint.checkpoint_name, _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window,
        heads, blocks), REMAT_NAMES)
    return out, (q, k, v, out, lse)


def _flash_pallas_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                      heads, blocks, res, g):
    q, k, v, out, lse = res
    # delta_i = rowsum(dO_i * O_i); tiny elementwise reduce — XLA fuses it.
    # A row (b, 1, q_len), like lse.
    if heads is None:
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, None, :]
        return kernels._flash_pallas_bwd_kernel(
            q, k, v, g, lse, delta, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window, blocks=blocks)
    if q.shape[1] > kernels._MAX_RESIDENT:   # ``results_in_model_arrays``
        # O goes in as dO does, and the kernel makes ``delta`` of them.
        # dQ stays its float32 [B x H, d, T] sum, which XLA rounds and
        # turns: what reads dQ next (the rotary's and the heads' norm's
        # backward) wants the tokens minor, and a dQ rounded by the kernel
        # into the model's [B, T, H x d] cost the step 14 ms more in XLA's
        # float32 copies than it saved (PERF.md section 6, PR 55)
        dq, dk, dv = kernels._flash_pallas_bwd_kernel(
            *_folded_operands(q, k, v, heads), g, lse, out, causal=True,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            interpret=interpret, window=window, heads=heads, blocks=blocks)
        return _unfolded(dq, heads), dk, dv
    return kernels._flash_pallas_lanes_bwd(
        q, k, v, g, out, lse, heads=heads, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window)


_flash_pallas_diff.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "impl",
                     "window", "heads", "blocks"),
)
def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    impl: Optional[str] = None,
                    window: Optional[int] = None,
                    heads: Optional[int] = None,
                    blocks: Optional[int] = None) -> jax.Array:
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
    Given ``blocks`` (with ``causal``, no window), the sequence is two
    streams under the block-diffusion mask (``seen_by_block``): the kernels
    where ``flash_kernels.by_block_fits`` admits the lengths, or the
    reference's dense mask (the scan has no such mask).
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

    Under a mesh whose data-like axes split the batch the kernel runs per
    batch shard (``mosaic.per_batch_shard``): the partitioner refuses Mosaic
    calls, and batch and heads are independent. Any other mesh axis of size
    > 1 (``mosaic.unmapped_mesh_axes``) still leaves the call to the
    partitioner, and JAX's own error says so.
    """
    assert causal or window is None, "a window is a causal mask's"
    window = kernels._window_of(window, k.shape[-2])
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    if sm_scale is None:
        sm_scale = (q.shape[-1] // (heads or 1)) ** -0.5
    assert not blocks or (causal and window is None
                          and impl != "scan"), (blocks, causal, window, impl)
    if impl in ("reference", "scan"):
        if heads is not None:   # these take the heads as an axis
            d = q.shape[2] // heads
            q, k, v = (t.reshape(*t.shape[:2], n, -1).transpose(0, 2, 1, 3)
                       for t, n in ((q, heads), (k, k.shape[2] // d),
                                    (v, k.shape[2] // d)))
        if impl == "reference":
            out = attention_reference(q, k, v, causal=causal,
                                      sm_scale=sm_scale, window=window,
                                      blocks=blocks)
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
        if seq > kernels._MAX_RESIDENT:
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
                                      block_k, interpret, window, heads,
                                      blocks)
        if q.ndim == 4:
            b, h, s, _ = q.shape
            fold = lambda x: x.reshape(b * x.shape[1], *x.shape[-2:])
            out = _flash_pallas_diff(fold(q), fold(k), fold(v), causal,
                                     sm_scale, block_q, block_k, interpret,
                                     window, None, blocks)
            return out.reshape(b, h, s, v.shape[-1])
        return _flash_pallas_diff(q, k, v, causal, sm_scale, block_q,
                                  block_k, interpret, window, None, blocks)

    return per_batch_shard(kernel, q, (True,) * 3, "flash_attention")(q, k, v)


# Where "auto" takes the Pallas kernel: from this length on it was measured
# faster than XLA's attention on a v5e, forward plus backward (PR 25; XLA
# wins at 256 and 384). The tables are in ``benches/flash_widths.py``'s
# docstring.
_FLASH_MIN_SEQ = 512
# (key width, value width) of a head the kernel was measured at on a v5e:
# (192, 128) latent attention's per-head form (PR 31), (64, 128) a
# differential layer's map (PR 48), (256, 256) PR 56. The tables are in
# ``benches/flash_widths.py``'s docstring.
_FLASH_HEAD_DIMS = ((64, 64), (64, 128), (128, 128), (192, 128), (256, 256))


def auto_attention(q, v=None) -> str:
    """What ``attention="auto"`` runs for causal self-attention of ``q``
    [B, T, H, d] (and ``v`` [B, T, H_kv, d_v], where values have a width of
    their own; how many heads of keys and values the query heads share, and
    a window, change nothing of the choice: the kernel was measured with
    both, PR 44) on the default backend: "flash" or "xla". Decided from the
    backend and from the operands' types alone: the length, the pair (key
    width, value width) of a head, which has to be one the kernel was
    measured at (``_FLASH_HEAD_DIMS``), and the mesh ``q`` is traced under
    (``mosaic.takes_kernels``: under ``model``, ``seq`` or ``expert`` "auto"
    stays on XLA's attention, as it was before the kernel was chosen
    anywhere, ROADMAP Speed 9a). A kernel that then fails to lower raises."""
    _, seq_len, _, head_dim = q.shape
    widths = (head_dim, head_dim if v is None else v.shape[-1])
    measured = (widths in _FLASH_HEAD_DIMS and seq_len >= _FLASH_MIN_SEQ
                and seq_len % 128 == 0
                and (seq_len <= kernels._MAX_RESIDENT or seq_len % 1024 == 0))
    return "flash" if measured and takes_kernels(q) else "xla"


def _boundary(q, k, v, window: Optional[int], blocks: Optional[int] = None,
              kernel: bool = True) -> bool:
    """Whether a "flash" call of q [B, T, H, d], k and v crosses in the
    model's own arrays, all of it (``heads_a_lane_tile``) or its results
    (``results_in_model_arrays``); writes the call's ``attention/boundary``
    record. Under the block-diffusion mask (``blocks``; never the lanes'
    kernels, whose grid step would hold both streams) the record also says
    the mask's block length, whether the kernels ran (``kernel``; 0: the
    dense mask in ``jnp``) and the forward grid's live and skipped blocks a
    call, over all heads."""
    (b, seq, heads, d), kv_heads, d_v = q.shape, k.shape[2], v.shape[3]
    a_tile = 0 if blocks else heads_a_lane_tile(seq, heads, kv_heads, d, d_v)
    results = kernel and results_in_model_arrays(seq, d, d_v)
    said = {"tokens": seq, "heads": heads, "kv_heads": kv_heads,
            "d_qk": d, "d_v": d_v, "window": window or 0,
            "heads_a_lane_tile": a_tile, "model_arrays": int(a_tile > 0),
            "model_results": int(results)}
    if blocks:
        live = skipped = 0
        if kernel:
            counts = kernels.by_block_kinds(seq, blocks)
            skipped = b * heads * counts.pop("dead")
            live = b * heads * sum(counts.values())
        said |= {"blocks": blocks, "kernel": int(kernel),
                 "live_blocks": live, "skipped_blocks": skipped}
    steptrace.record_counters("attention/boundary", said)
    return bool(a_tile or results)


def causal_self_attention(q, k, v, attention: str = "auto",
                          window: Optional[int] = None,
                          blocks: Optional[int] = None, selected=None):
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
    everything (both 0).

    Given ``blocks`` the T positions are two streams of T / 2, a noisy and
    a clean copy of one sequence, and the mask is block diffusion's
    (``seen_by_block``) in place of the causal one: the kernels under
    "flash" (under "auto" where ``flash_kernels.by_block_fits`` admits the
    length too), else the dense mask in ``jnp`` (``attention_reference``);
    the record says which ran (``kernel``).

    Given ``selected`` (``attention_reference``'s: a mask the step
    computed) this entry has the dense mask in ``jnp`` alone: the kernels
    under a selection are ``normed_rotary_self_attention``'s."""
    if selected is not None:
        assert window is None and not blocks, (window, blocks)
        return attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True,
            selected=selected).transpose(0, 2, 1, 3)
    if attention == "auto":
        attention = auto_attention(q, v)
        if blocks and not kernels.by_block_fits(q.shape[1], blocks):
            attention = "xla"
    bhsd = lambda t: t.transpose(0, 2, 1, 3)
    if attention == "flash":
        (b, seq, heads, d), d_v = q.shape, v.shape[3]
        if _boundary(q, k, v, window, blocks):
            lanes = lambda t: t.reshape(b, seq, -1)
            return flash_attention(
                lanes(q), lanes(k), lanes(v), causal=True, window=window,
                heads=heads, blocks=blocks).reshape(b, seq, heads, d_v)
        return flash_attention(
            bhsd(q), bhsd(k), bhsd(v), causal=True, window=window,
            blocks=blocks).transpose(0, 2, 1, 3)
    if attention == "xla":
        if blocks:
            _boundary(q, k, v, window, blocks, kernel=False)
        if v.shape[-1] != q.shape[-1] or window is not None or blocks:
            return attention_reference(
                bhsd(q), bhsd(k), bhsd(v), causal=True,
                window=window, blocks=blocks).transpose(0, 2, 1, 3)
        return jax.nn.dot_product_attention(q, k, v, is_causal=True)
    raise ValueError(f"attention={attention!r}: expected auto, xla or flash")


# ----------------------------------------------------------------------
# a layer whose q and k pass a head norm and a rotation first: the
# prologue's kernels (``ops/rotary.py``) and the flash kernels under one
# ``custom_vjp``, so that XLA has nothing to lay out between two custom
# calls
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _normed_rotary_flash(q, k, v, q_scale, k_scale, cos, sin, heads, eps,
                         sm_scale, block_q, block_k, interpret, window,
                         blocks=None):
    """Causal self-attention of ``head_rotary(q)`` on ``head_rotary(k)`` and
    v, the three as the projections wrote them, [B, T, heads x 128] (k and v
    of as many heads as their widths say), on the ``model_results`` boundary
    (``results_in_model_arrays``): -> [B, T, heads x 128]. ``cos``, ``sin``:
    [T, 64] float32 or None. ``blocks``: the block-diffusion mask's block
    length, the T positions then two streams (``cos``, ``sin`` say which
    positions: a stream's, twice)."""
    return _normed_rotary_flash_fwd(
        q, k, v, q_scale, k_scale, cos, sin, heads, eps, sm_scale, block_q,
        block_k, interpret, window, blocks)[0]


def _normed_rotary_flash_fwd(q, k, v, q_scale, k_scale, cos, sin, heads, eps,
                             sm_scale, block_q, block_k, interpret, window,
                             blocks):
    kv_heads = k.shape[2] // (q.shape[2] // heads)
    prologue = functools.partial(rotary.head_rotary_fwd, cos=cos, sin=sin,
                                 eps=eps, interpret=interpret)
    # the flash kernels' own operands, straight from the prologue's kernel;
    # nothing of them is named for ``remat_policy``: a recomputed block
    # runs the prologue again, 0.4 ms a layer, and the plan keeps its room
    qf = prologue(q, q_scale, heads=heads)
    kf = prologue(k, k_scale, heads=kv_heads)
    out, lse = map(ad_checkpoint.checkpoint_name, kernels._flash_pallas(
        qf, kf, _folded(v, kv_heads), causal=True, heads=heads,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, blocks=blocks), REMAT_NAMES)
    return out, (q, k, v, q_scale, k_scale, cos, sin, qf, kf, out, lse)


def _normed_rotary_flash_bwd(heads, eps, sm_scale, block_q, block_k,
                             interpret, window, blocks, res, g):
    q, k, v, q_scale, k_scale, cos, sin, qf, kf, out, lse = res
    kv_heads = k.shape[2] // (q.shape[2] // heads)
    # dQ^T stays the flash kernel's float32 [B x H, 128, T] sum: the
    # prologue's backward kernel turns it tile by tile in VMEM; dK and dV
    # arrive in the model's arrays
    dq_t, dk, dv = kernels._flash_pallas_bwd_kernel(
        qf, kf, _folded(v, kv_heads), g, lse, out, causal=True,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, heads=heads, dq_turned=False,
        blocks=blocks)
    back = functools.partial(rotary.head_rotary_bwd, cos=cos, sin=sin,
                             eps=eps, interpret=interpret)
    dq, dq_scale = back(dq_t, q, q_scale, heads=heads, turned=True)
    dk, dk_scale = back(dk, k, k_scale, heads=kv_heads, turned=False)
    return (dq, dk, dv, dq_scale.astype(q_scale.dtype),
            dk_scale.astype(k_scale.dtype),
            *jax.tree.map(jnp.zeros_like, (cos, sin)))


_normed_rotary_flash.defvjp(_normed_rotary_flash_fwd,
                            _normed_rotary_flash_bwd)


# The same under a selection (``ops/sparse_index.py``): the mask is an
# operand, every head of a batch row reads that row's, and the call hands
# back what the indexer's loss reads of the main attention beside the output:
# the flash kernels' own operands and their log-sum-exp over the selected
# set. A function of its own: the calls above keep their equations.

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(8, 9, 10, 11, 12, 13, 14))
def _normed_rotary_flash_selected(q, k, v, q_scale, k_scale, cos, sin, mask,
                                  heads, eps, sm_scale, block_q, block_k,
                                  interpret, topk):
    """``_normed_rotary_flash`` under ``mask`` (int32 [B, T / 32, T
    queries], a bit a pair) -> (out [B, T, heads x 128], (qf [B x H, T,
    128], kf [B x G, T, 128], lse [B x H, 1, T])); the three are constants to whoever reads
    them: their cotangents are dropped."""
    return _normed_rotary_flash_selected_fwd(
        q, k, v, q_scale, k_scale, cos, sin, mask, heads, eps, sm_scale,
        block_q, block_k, interpret, topk)[0]


def _normed_rotary_flash_selected_fwd(q, k, v, q_scale, k_scale, cos, sin,
                                      mask, heads, eps, sm_scale, block_q,
                                      block_k, interpret, topk):
    kv_heads = k.shape[2] // (q.shape[2] // heads)
    prologue = functools.partial(rotary.head_rotary_fwd, cos=cos, sin=sin,
                                 eps=eps, interpret=interpret)
    qf = prologue(q, q_scale, heads=heads)
    kf = prologue(k, k_scale, heads=kv_heads)
    out, lse = map(ad_checkpoint.checkpoint_name, kernels._flash_pallas(
        qf, kf, _folded(v, kv_heads), causal=True, heads=heads,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret, selected=mask, topk=topk), REMAT_NAMES)
    return (out, (qf, kf, lse)), (q, k, v, q_scale, k_scale, cos, sin, mask,
                                  qf, kf, out, lse)


def _normed_rotary_flash_selected_bwd(heads, eps, sm_scale, block_q, block_k,
                                      interpret, topk, res, g):
    q, k, v, q_scale, k_scale, cos, sin, mask, qf, kf, out, lse = res
    kv_heads = k.shape[2] // (q.shape[2] // heads)
    dq_t, dk, dv = kernels._flash_pallas_bwd_kernel(
        qf, kf, _folded(v, kv_heads), g[0], lse, out, causal=True,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret, heads=heads, dq_turned=False, selected=mask,
        topk=topk)
    back = functools.partial(rotary.head_rotary_bwd, cos=cos, sin=sin,
                             eps=eps, interpret=interpret)
    dq, dq_scale = back(dq_t, q, q_scale, heads=heads, turned=True)
    dk, dk_scale = back(dk, k, k_scale, heads=kv_heads, turned=False)
    return (dq, dk, dv, dq_scale.astype(q_scale.dtype),
            dk_scale.astype(k_scale.dtype),
            *jax.tree.map(jnp.zeros_like, (cos, sin)),
            np.zeros(mask.shape, jax.dtypes.float0))


_normed_rotary_flash_selected.defvjp(_normed_rotary_flash_selected_fwd,
                                     _normed_rotary_flash_selected_bwd)


def auto_head_rotary(q, v, cos, attention: str = "auto") -> str:
    """What ``normed_rotary_self_attention(..., impl=None)`` runs for q [B,
    T, H, d], v [B, T, H_kv, d_v] and the table ``cos`` (None: no rotation)
    under ``attention``: "pallas", the prologue's kernel pair under one
    ``custom_vjp`` with the flash kernels, or "jnp". Read from the call
    alone, as ``auto_attention`` is: the attention comes to "flash" on the
    ``model_results`` boundary (``results_in_model_arrays``), a Mosaic call
    may run where ``q`` is traced (``mosaic.takes_kernels``), and
    ``rotary.fits`` admits the head's width and the table."""
    if attention == "auto":
        attention = auto_attention(q, v)
    return ("pallas" if _prologue_fits(q, v, cos, attention)
            and takes_kernels(q) else "jnp")


def _prologue_fits(q, v, cos, attention: str) -> bool:
    """Whether the shapes admit the prologue's kernels under ``attention``
    (``auto_head_rotary`` says what each part asks)."""
    return (attention == "flash" and rotary.fits(q, cos)
            and results_in_model_arrays(q.shape[1], q.shape[3], v.shape[3]))


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "interpret", "block_q", "block_k", "topk"))
def _normed_rotary_kernels_selected(q, k, v, q_scale, k_scale, cos, sin, mask,
                                    *, heads, eps, interpret, block_q,
                                    block_k, topk):
    """``_normed_rotary_kernels`` under a selection's ``mask``, outside any
    mesh (``sparse_index.auto_impl`` keeps the kernels there): -> (out,
    (qf, kf, lse))."""
    tables = (None, None) if cos is None else rotary.tables(cos, sin)
    return _normed_rotary_flash_selected(
        q, k, v, q_scale, k_scale, *tables, mask, heads, eps,
        (q.shape[2] // heads) ** -0.5, block_q, block_k, interpret, topk)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "window", "interpret", "block_q", "block_k", "blocks"))
def _normed_rotary_kernels(q, k, v, q_scale, k_scale, cos, sin, *, heads,
                           eps, window, interpret, block_q, block_k,
                           blocks=None):
    """``normed_rotary_self_attention``'s kernels for q [B, T, heads x 128],
    k and v as the projections wrote them, handed to a mesh a batch shard
    each. Jitted, as ``flash_attention`` is: layers of one shape share one
    trace of the kernels' bodies (thousands of equations a flash kernel)
    and one lowering, where a trace a layer cost the mellum2 cell's step
    9 s of set-up (PERF.md section 6, PR 63)."""
    seq = q.shape[1]
    tables = () if cos is None else rotary.tables(cos, sin)

    def kernel(q, k, v, q_scale, k_scale, *tables):
        return _normed_rotary_flash(
            q, k, v, q_scale, k_scale, *(tables or (None, None)), heads, eps,
            (q.shape[2] // heads) ** -0.5, block_q, block_k, interpret,
            kernels._window_of(window, seq), blocks)

    return per_batch_shard(
        kernel, q, (True,) * 3 + (False,) * (2 + len(tables)),
        "normed_rotary_self_attention")(q, k, v, q_scale, k_scale, *tables)


def normed_rotary_self_attention(q, k, v, q_scale, k_scale, cos, sin, *,
                                 eps: float, attention: str = "auto",
                                 window: Optional[int] = None,
                                 impl: Optional[str] = None,
                                 block_q: Optional[int] = None,
                                 block_k: Optional[int] = None,
                                 blocks: Optional[int] = None,
                                 selected=None, topk: Optional[int] = None):
    """``causal_self_attention`` of a layer whose q [B, T, H, d] and k [B,
    T, H_kv, d], as the projections wrote them, first pass an RMSNorm over
    a head's width (``q_scale``, ``k_scale`` [d]; ``eps``) and, given a
    layer kind's table ``cos``, ``sin`` [B or 1, T, d / 2] (None: normed and
    not rotated), the rotation of the head's halves:
    ``rotary.head_rotary``'s arithmetic, float32 with one rounding.

    With ``impl=None`` the call's own shapes and surroundings choose
    (``auto_head_rotary``): "pallas", ``ops/rotary.py``'s kernel pair under
    one ``custom_vjp`` with the flash kernels (the forward kernel writes
    their [B x H, T, d] operands from the projections' results, the backward
    kernel reads their float32 dQ^T sum and their dK, and XLA lays out
    nothing between), or "jnp" (off a TPU, under another mesh axis, heads 64
    wide, a partial rotation, one block of keys a head, XLA's attention):
    ``rotary.head_rotary`` and ``causal_self_attention``. ``impl`` forces
    "pallas", "pallas_interpret" (the kernels, the flash pair included, with
    ``block_q`` / ``block_k`` if given) or "jnp". One ``counters`` record
    ``attention/head_rotary`` a traced call says which ran. ``blocks`` is
    ``causal_self_attention``'s: the T positions are two streams under the
    block-diffusion mask, and the table holds a stream's positions twice.

    Given ``selected`` (int32 [B, T / 32, T queries], the bits of a mask
    the step computed, of ``topk`` keys a query: ``sparse_index.select``'s;
    no window, no blocks) a query sees the keys it says (the twin through
    ``sparse_index.unpack``), and the call returns (y, (qf
    [B x H, T, d], kf [B x H_kv, T, d], lse)): beside the output, what the
    indexer's loss reads of the main attention, the normed and rotated
    queries and keys and, from the kernels, the log-sum-exp of the scores
    over the selected set [B x H, 1, T] (None from the twin, whose reader
    makes its own). One ``attn/selected`` record a traced call."""
    if attention == "auto":
        attention = auto_attention(q, v)
        if blocks and not kernels.by_block_fits(q.shape[1], blocks):
            attention = "xla"
    if impl is None:
        impl = auto_head_rotary(q, v, cos, attention)
    (b, seq, heads, d), d_v = q.shape, v.shape[3]
    if selected is not None:
        assert window is None and not blocks and topk, (window, blocks, topk)
        steptrace.record_counters("attn/selected", {
            "topk": topk, "rows": b * seq, "heads": heads,
            "pairs_selected": b * pairs_selected(seq, topk),
            "pairs_causal": b * seq * (seq + 1) // 2,
            "dead_tiles": 0, "kernel": int(impl != "jnp")})
    steptrace.record_counters("attention/head_rotary", {
        "tokens": seq, "heads": heads, "kv_heads": k.shape[2], "head_dim": d,
        "rotated": int(cos is not None), "kernel": int(impl != "jnp")})
    if impl == "jnp":
        prologue = functools.partial(rotary.head_rotary, cos=cos, sin=sin,
                                     eps=eps)
        if selected is not None:
            qr, kr = prologue(q, q_scale), prologue(k, k_scale)
            heads_first = lambda t: t.transpose(0, 2, 1, 3).reshape(
                -1, seq, t.shape[3])
            return (causal_self_attention(qr, kr, v,
                                          selected=unpack(selected)),
                    (heads_first(qr), heads_first(kr), None))
        # ``blocks`` only where there is one: a test's stand-in for the
        # twin's call takes the five arguments it always did
        return causal_self_attention(
            prologue(q, q_scale), prologue(k, k_scale), v, attention, window,
            **({"blocks": blocks} if blocks else {}))
    assert _prologue_fits(q, v, cos, attention), (q.shape, attention)
    _boundary(q, k, v, window, blocks)
    lanes = lambda t: t.reshape(b, seq, -1)
    if selected is not None:
        y, read = _normed_rotary_kernels_selected(
            lanes(q), lanes(k), lanes(v), q_scale, k_scale, cos, sin,
            selected, heads=heads, eps=eps, block_q=block_q, block_k=block_k,
            interpret=impl == "pallas_interpret", topk=topk)
        return y.reshape(b, seq, heads, d_v), read
    return _normed_rotary_kernels(
        lanes(q), lanes(k), lanes(v), q_scale, k_scale, cos, sin,
        heads=heads, eps=eps, window=window,
        interpret=impl == "pallas_interpret", block_q=block_q,
        block_k=block_k, blocks=blocks).reshape(b, seq, heads, d_v)
