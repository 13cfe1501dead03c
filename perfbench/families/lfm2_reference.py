"""The plain reference of the ``lfm2`` family (gated short-convolution
layers, a few grouped-attention layers with normed heads, routed experts
with no shared one, a tied head): the forward pass, the loss and its
gradient in straightforward float32 ``jax.numpy``.

Independent of ``ray_tpu/models/lfm2.py`` and of ``ray_tpu/ops/conv.py``: it
imports nothing from the program and shares only the names of the parameter
tree it is handed. No kernel, no sort, no grouped matmul, no chunked loss
walk, no bfloat16. It follows the layer equations the configuration file
states (its published keys and what it lists under ``assumed``):

- embedding ``h = E[t]``; a block ``h = h + Op(N1(h))``, ``h = h + F(N2(h))``,
  the N RMSNorms with a learned scale and ``norm_eps``;
- ``Op`` in a ``conv`` layer: ``[B | C | x] = u W_in``; ``z = B * x``;
  ``c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t`` per channel (for
  ``conv_L_cache`` = 3 taps; zeros before the sequence's first position):
  three shifted products; ``y = (C * c) W_out``;
- ``Op`` in a ``full_attention`` layer: ``q = u Wq`` [T, H, D], ``k = u Wk``,
  ``v = u Wv`` [T, G, D]; q and k pass an RMSNorm over D (one scale vector
  each); both are rotated by position (dimension i against i + D/2, angle
  position x theta^(-2i / D)); query head j reads key-value head ``j // (H /
  G)``; causal softmax of ``q.k / sqrt(D)``; ``Wo``;
- ``F``: a SwiGLU of ``intermediate_size`` in the first ``num_dense_layers``
  of the layers run; in the others ``s = sigmoid(x W_r)``, the
  ``num_experts_per_tok`` experts with the largest ``s + b``, weights ``s_i /
  (sum of the chosen s + route_eps) x routed_scaling_factor``, each expert a
  SwiGLU of ``moe_intermediate_size``; no shared expert;
- final RMSNorm, the head tied to the embedding, mean next-token
  cross-entropy.

Departures, each of which changes no value that is compared:

- it is given the same share of the deployment as the program: the experts
  ``expert_shard.index`` of ``expert_shard.of`` (the routed result is the
  sum over the held experts only), the sliced vocabulary, the cut depth
  (``kept_layers`` of ``layer_types``);
- every held expert is applied to all tokens and weighted by the token's
  weight for it, zero where the token did not choose it;
- attention runs in blocks of queries against all keys and the loss in
  blocks of positions; with a gradient asked for, layers, blocks of queries,
  experts and blocks of the loss are recomputed in the backward pass
  (``jax.checkpoint``), so that a sequence's gradient fits beside the state
  it is compared with.

On a TPU a float32 matrix multiplication runs in lower precision unless the
precision is raised, so every entry point runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
CONV = "conv"


def _rms_norm(x, p, eps):
    return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
            * p["scale"])


def _swiglu(x, p):
    gate, up = x @ p["gate_proj"]["kernel"], x @ p["up_proj"]["kernel"]
    return (jax.nn.silu(gate) * up) @ p["down_proj"]["kernel"]


def _short_conv(u, p):
    """The gated short convolution of u [b, t, d]: the taps' products
    written out, position t's from z at t - 2, t - 1 and t."""
    t = u.shape[1]
    gate_in, gate_out, x = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    z = gate_in * x
    taps = p["conv_weight"]                                  # [K, d]
    last = taps.shape[0] - 1
    padded = jnp.pad(z, ((0, 0), (last, 0), (0, 0)))
    conv = sum(taps[k] * padded[:, k:k + t] for k in range(last + 1))
    return (gate_out * conv) @ p["out_proj"]["kernel"]


def _rotate(x, theta):
    """x [b, t, h, D]: dimension i against i + D/2, by position x
    theta^(-2i / D)."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [t, D/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _attend(q, k, v, remat):
    """Causal softmax attention of q [b, t, h, D] over k, v [b, t, g, D],
    query head j on key-value head j // (h / g), one block of queries at a
    time against every key."""
    b, t, h, dk = q.shape
    g = k.shape[2]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0 and h % g == 0, (t, block, h, g)
    key_pos = jnp.arange(t)

    def one(args):
        qb, start = args                                   # [b, block, h, dk]
        grouped = qb.reshape(b, block, g, h // g, dk)
        scores = jnp.einsum("bqgjd,bkgd->bgjqk", grouped, k) * dk ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        out = jnp.einsum("bgjqk,bkgd->bqgjd", jax.nn.softmax(scores, -1), v)
        return out.reshape(b, block, h, v.shape[-1])

    if remat:
        one = jax.checkpoint(one)
    blocks = q.reshape(b, t // block, block, h, dk).swapaxes(0, 1)
    out = jax.lax.map(one, (blocks, jnp.arange(0, t, block)))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1])


def _attention(x, p, m, remat):
    b, t, d = x.shape
    h, g = m["num_attention_heads"], m["num_key_value_heads"]
    dim, eps, theta = d // h, m["norm_eps"], float(m["rope_theta"])
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, h, dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, g, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, g, dim)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), theta)
    return _attend(q, k, v, remat).reshape(b, t, d) @ p["o_proj"]["kernel"]


def _experts(x, p, m, remat=False):
    """The held experts' part of the routed result; there is no other."""
    shard = m["expert_shard"]
    held = p["experts_wi"].shape[0]
    first = shard["index"] * held
    scores = jax.nn.sigmoid(x @ p["router"])                # [b, t, E]
    k = m["num_experts_per_tok"]
    # the k-th largest biased score decides who is chosen; no sort of pairs
    biased = scores + p["router_bias"]
    chosen = biased >= jax.lax.top_k(biased, k)[0][..., -1:]
    picked = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + m["route_eps"])
    weights = picked * m["routed_scaling_factor"]

    def expert(x, wi, wo):
        gate, up = jnp.split(x @ wi, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wo

    if remat:
        expert = jax.checkpoint(expert)
    y = jnp.zeros_like(x)
    for e in range(held):
        y = y + weights[..., first + e, None] * expert(
            x, p["experts_wi"][e], p["experts_wo"][e])
    return y


def _block(x, p, *, m, kind, dense, remat):
    eps = m["norm_eps"]
    u = _rms_norm(x, p["operator_norm"], eps)
    if kind == CONV:
        x = x + _short_conv(u, p["conv"])
    else:
        x = x + _attention(u, p["attn"], m, remat)
    h = _rms_norm(x, p["ffn_norm"], eps)
    return x + (_swiglu(h, p["mlp"]) if dense
                else _experts(h, p["moe"], m, remat))


def layers_run(m):
    """(published index, kind, dense feed-forward) of the layers run."""
    kept = m.get("kept_layers") or range(len(m["layer_types"]))
    return [(i, m["layer_types"][i], n < m["num_dense_layers"])
            for n, i in enumerate(kept)]


def hidden_states(params, input_ids, *, m, remat=False):
    """[b, t, d] after the final norm."""
    x = params["embed"]["embedding"][input_ids]
    for i, kind, dense in layers_run(m):
        fn = functools.partial(_block, m=m, kind=kind, dense=dense,
                               remat=remat)
        x = (jax.checkpoint(fn) if remat else fn)(x, params[f"layers_{i}"])
    return _rms_norm(x, params["norm"], m["norm_eps"])


def logits(params, input_ids, *, m):
    """[b, t, vocab_size]: for the tests, at sizes where they fit."""
    return (hidden_states(params, input_ids, m=m)
            @ params["embed"]["embedding"].T)


def _log_likelihood(hidden, head, targets):
    """Sum of log p(target) over the positions of hidden [n, d]."""
    log_p = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    return jnp.take_along_axis(log_p, targets[:, None], axis=-1).sum()


def loss(params, input_ids, labels, *, m, remat=False):
    """The mean next-token cross-entropy over one batch [b, t], the
    positions taken ``LOSS_BLOCK`` at a time."""
    hidden = hidden_states(params, input_ids, m=m, remat=remat)
    flat, targets = hidden.reshape(-1, hidden.shape[-1]), labels.reshape(-1)
    n = flat.shape[0]
    block = min(LOSS_BLOCK, n)
    assert n % block == 0, (n, block)
    part = functools.partial(_log_likelihood,
                             head=params["embed"]["embedding"])
    if remat:
        part = jax.checkpoint(part)
    sums = jax.lax.map(lambda args: part(args[0], targets=args[1]),
                       (flat.reshape(n // block, block, -1),
                        targets.reshape(n // block, block)))
    return -sums.sum() / n


def make(model: dict, with_grad: bool):
    """Jitted (params, input_ids, labels) -> float32 loss, or with
    ``with_grad`` -> (loss, gradient tree)."""

    def fn(params, input_ids, labels):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            if with_grad:
                return jax.value_and_grad(loss)(params, input_ids, labels,
                                                m=model, remat=True)
            return loss(params, input_ids, labels, m=model)

    return jax.jit(fn)


def over_microbatches(model: dict, params, tokens, microbatch: int,
                      with_grad: bool, place):
    """The reference over the whole step batch, ``microbatch`` sequences at
    a time (equal parts, so the mean of the parts is the batch's mean).
    ``tokens`` is the host's [n, seq + 1] array and ``place`` puts one
    part's array on the device(s). -> (loss, gradient tree or None). The
    loss is on the device. The gradient is summed on the HOST, one part's
    leaves brought over and freed on the device before the next part runs:
    at the timed size one float32 gradient (2.03 GB) fits beside the state
    it is compared with and the backward pass's working set."""
    n = tokens.shape[0]
    if n % microbatch:
        raise ValueError(f"batch {n} is not a multiple of the reference's "
                         f"microbatch {microbatch}")
    parts = n // microbatch
    fn = make(model, with_grad)
    total, grads = None, None
    for i in range(parts):
        rows = tokens[i * microbatch:(i + 1) * microbatch]
        out = fn(params, place(rows[:, :-1]), place(rows[:, 1:]))
        part = out[0] if with_grad else out
        total = part if total is None else total + part
        if with_grad:
            if grads is None:
                grads = jax.tree.map(np.array, out[1])   # host copies
            else:
                jax.tree.map(lambda acc, x: np.add(acc, x, out=acc),
                             grads, out[1])
            del out                       # the device's copy goes here
    if with_grad and parts > 1:
        jax.tree.map(lambda acc: np.divide(acc, parts, out=acc), grads)
    return total / parts, grads
