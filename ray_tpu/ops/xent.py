"""The vocabulary's loss: fused cross-entropy over logits, and the walk over
sequence chunks against a head's ``[V, d]`` matrix that never holds the
logits whole and forms the head's gradient beside its loss.

The walk takes the matrix as an argument: the embedding of a model whose
head is tied to it (GPT-2), the head's own of one whose head is not. A loss
of several terms through one head (a next-token term and a
multi-token-prediction term) calls it once a term; autodiff sums the
head's gradients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu._private.steptrace import device_scope
from ray_tpu.parallel.mesh_utils import on_batch_axes


def _shifted_lse(logits):
    """-> (row maximum, logits less it, log of the row sum of their
    exponentials), float32 with a trailing unit axis on the statistics: the
    max/sum reductions fuse into a single read of the bf16 logits."""
    lmax = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    # upcast BEFORE subtracting: the bf16→f32 cast is free next to the
    # reduction, and the f32 subtraction is exact (bf16 would round the
    # shifted logits to 8 mantissa bits)
    lmax = lmax.astype(jnp.float32)
    shifted = logits.astype(jnp.float32) - lmax
    return lmax, shifted, jnp.log(
        jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))


def token_log_likelihood(logits, labels):
    """Per-token ll = logit[label] - logsumexp(logits), fused: never
    materializes log_softmax over the vocab (a B*T*50257 f32 tensor is
    ~1.6GB at batch 8 — pure HBM-bandwidth waste)."""
    _, shifted, lse = _shifted_lse(logits)
    label_logit = jnp.take_along_axis(shifted, labels[..., None], axis=-1)
    return (label_logit - lse)[..., 0]


def fused_xent(logits, labels, mask=None):
    """Masked-mean fused cross-entropy (see token_log_likelihood)."""
    with device_scope("vocab"):
        ll = token_log_likelihood(logits, labels)
        if mask is None:
            return -ll.mean()
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)


def chunked_xent(hidden, embedding, labels, mask=None, n_chunks=8,
                 denom=None):
    """LM loss of ``hidden`` [B, T, d] through the head ``embedding``
    [V, d] (the embedding itself where the head is tied to it), computed in
    sequence chunks, with its own derivative rule.

    The full [B, T, vocab] logits tensor never exists: one ``lax.scan``
    walks the chunks, and a chunk's logits (one MXU matmul against the
    head's matrix, bf16 with float32 statistics: ``_shifted_lse``)
    live only inside its iteration. The cross-entropy is the last thing
    the forward pass does and its cotangent is a scalar, so the
    differentiated call forms the head's gradient in the same walk, while
    the chunk's logits are there: three vocabulary matmuls a chunk (logits,
    ``dh``, ``dE``), nothing recomputed and nothing of vocabulary width
    kept for a backward pass, which only scales ``dh`` and ``dE`` by the
    cotangent. The label's logit is the row dot ``h . E[label]`` (float32
    accumulation of the unrounded products), not a pass over the chunk's
    logits. Called without differentiation it computes the loss alone.

    A function with a ``custom_vjp`` has no forward-mode derivative:
    ``jax.jvp`` / ``jacfwd`` over this loss raise (``loss_chunks=0`` keeps
    them). Labels and mask get no gradient.

    ``mask`` may be any per-position weights; the loss is their weighted
    sum over ``denom``, left out the weights' own sum (a mean). A given
    ``denom`` is an objective's own (a diffusion loss weights the masked
    positions by their inverse rate and divides by the positions there
    are, not by the weights)."""
    B, T, C = hidden.shape
    assert T % n_chunks == 0, (T, n_chunks)
    t = T // n_chunks

    def chunks(x):
        return x.reshape(B, n_chunks, t, *x.shape[2:]).swapaxes(0, 1)

    with device_scope("vocab"):
        hid = on_batch_axes(chunks(hidden), batch_dim=1)
        if mask is None:
            # unmasked: the denominator is statically B*T, no ones to scan
            weights, denom = None, jnp.float32(B * T)
        else:
            weights = chunks(mask.astype(jnp.float32))
            denom = (jnp.maximum(weights.sum(), 1.0) if denom is None
                     else jnp.float32(denom))
        return _chunked_xent(hid, embedding, chunks(labels), weights, denom)


def _scan_xent_chunks(hid, embedding, lab, weights, denom, with_grads):
    """-> loss, and with ``with_grads`` its gradients for ``hid`` (stacked
    as ``hid``) and ``embedding`` (float32, accumulated over the chunks in
    the scan's carry; under a mesh the partitioner lays the carry out as
    the step constrains the gradient, which is as the embedding lies at
    rest: tests/test_tpu_compile.py compiles it, ``f32[50257, 400]``)."""
    table = embedding.astype(hid.dtype)
    f32 = jnp.float32

    def chunk(carry, hlw):
        h, l, w = hlw
        logits = on_batch_axes(h @ table.T)
        lmax, shifted, lse = _shifted_lse(logits)
        # the label's logit as a row dot: 768 multiply-adds a token, the
        # unrounded value of what a pass over the bf16 logits would pick
        rows = on_batch_axes(jnp.take(table, l, axis=0))
        label_logit = jnp.sum(h.astype(f32) * rows.astype(f32), axis=-1)
        ll = label_logit - (lmax + lse)[..., 0]
        numer, d_emb = carry
        numer = numer + (ll.sum() if w is None else (ll * w).sum())
        if not with_grads:
            return (numer, d_emb), None
        scale = 1.0 / denom if w is None else w[..., None] / denom
        onehot = l[..., None] == jnp.arange(logits.shape[-1])
        # rounded to bf16 only as a matmul operand, where autodiff rounds
        dlogits = ((jnp.exp(shifted - lse) - onehot) * scale).astype(h.dtype)
        dh = on_batch_axes(dlogits @ table)
        # a chunk's product in the operands' dtype, as autodiff's cotangent
        # of the cast table is, summed over the chunks in float32. Under
        # fsdp the chips' partial sums then go round the ring in bf16
        # beside the matmul's parts; a float32 product is reduce-scattered
        # whole and synchronously, [vocab, C] a chunk
        d_emb = d_emb + jnp.einsum("btv,btc->vc", dlogits, h).astype(f32)
        return (numer, d_emb), dh

    d_emb = jnp.zeros(embedding.shape, f32) if with_grads else None
    (numer, d_emb), dh = jax.lax.scan(
        chunk, (f32(0.0), d_emb), (hid, lab, weights))
    return -numer / denom, dh, d_emb


@jax.custom_vjp
def _chunked_xent(hid, embedding, lab, weights, denom):
    return _scan_xent_chunks(hid, embedding, lab, weights, denom, False)[0]


def _chunked_xent_fwd(hid, embedding, lab, weights, denom):
    loss, dh, d_emb = _scan_xent_chunks(hid, embedding, lab, weights, denom,
                                        True)
    return loss, (dh, d_emb.astype(embedding.dtype))


def _chunked_xent_bwd(grads, g):
    dh, d_emb = grads
    return (dh * g.astype(dh.dtype), d_emb * g.astype(d_emb.dtype),
            None, None, None)


_chunked_xent.defvjp(_chunked_xent_fwd, _chunked_xent_bwd)
