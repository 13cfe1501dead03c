"""The plain reference of the ``mellum`` family (window layers three to one
over full layers, rotary positions in both from two tables, every
feed-forward part routed to experts): the forward pass, the loss and its
gradient in straightforward float32 ``jax.numpy``.

Independent of ``ray_tpu/models/mellum.py``: it imports nothing from the
program and shares only the names of the parameter tree it is handed. No
kernel, no sort, no grouped matmul, no chunked loss walk, no bfloat16. It
follows the layer equations the configuration file states (its published
keys and what it lists under ``assumed``):

- embedding ``h = E[t]``;
- a block ``h = h + Attn(N1(h))``, ``h = h + F(N2(h))``, both N RMSNorms
  with a learned scale and ``rms_norm_eps``;
- ``Attn(x)``: ``q = x Wq`` [T, H, D], ``k = x Wk``, ``v = x Wv`` [T, G, D],
  no biases; q and k pass an RMSNorm over D (one scale vector each), then
  both are rotated by position in EVERY layer (dimension i against i + D/2,
  angle ``position x inv_i``) by the layer kind's entry of
  ``rope_parameters``: a ``default`` entry gives ``inv_i = theta^(-2i / D)``;
  a ``yarn`` entry ``inv_i = (1 - ramp_i) theta^(-2i / D) + ramp_i
  theta^(-2i / D) / factor`` with ``ramp_i = clip((i - low) / (high - low),
  0, 1)``, ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``,
  ``c(r) = D ln(original / (2 pi r)) / (2 ln theta)``, and cos and sin both
  times ``attention_factor``; query head j reads key-value head ``j // (H /
  G)``; scores ``q.k / sqrt(D)``; query i sees keys j with ``0 <= i - j``
  and in a ``sliding_attention`` layer ``i - j < sliding_window``; then
  ``Wo``, with no gate;
- ``F``, in every layer: ``p = softmax(x W_r)`` over all experts, the
  ``num_experts_per_tok`` experts with the largest ``p + b``, weights ``p_i
  / sum of the chosen p`` (``norm_topk_prob``), each expert a SwiGLU of
  ``moe_intermediate_size``; no shared expert;
- final RMSNorm, untied head, mean next-token cross-entropy.

Departures, each of which changes no value that is compared:

- it is given the same share of the deployment as the program: the experts
  ``expert_shard.index`` of ``expert_shard.of`` (the routed result is the
  sum over the held experts only), the sliced vocabulary, the cut depth
  (the first ``num_hidden_layers`` of ``layer_types``);
- every held expert is applied to all tokens, one expert after another,
  and weighted by the token's weight for it, zero where the token did not
  choose it;
- attention runs in blocks of queries against all keys, a window layer's
  too (the mask alone tells the kinds apart), and the loss in blocks of
  positions; with a gradient asked for, layers, blocks of queries, experts
  and blocks of the loss are recomputed in the backward pass
  (``jax.checkpoint``), so that a sequence's gradient fits beside the state
  it is compared with.

On a TPU a float32 matrix multiplication runs in lower precision unless the
precision is raised, so every entry point runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
WINDOW = "sliding_attention"


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def inverse_frequencies(dim: int, rope: dict):
    """(inv [dim / 2] float32, the factor on cos and sin) of one
    ``rope_parameters`` entry, from the equations in this file's
    docstring."""
    theta, half = float(rope["rope_theta"]), dim // 2
    i = np.arange(half, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    if rope.get("rope_type", "default") == "default":
        return jnp.asarray(plain, jnp.float32), 1.0
    assert rope["rope_type"] == "yarn", rope
    original, factor = rope["original_max_position_embeddings"], rope["factor"]
    turns = lambda r: (dim * math.log(original / (2 * math.pi * r))
                       / (2 * math.log(theta)))
    low = max(math.floor(turns(rope["beta_fast"])), 0)
    high = min(math.ceil(turns(rope["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return (jnp.asarray((1.0 - ramp) * plain + ramp * plain / factor,
                        jnp.float32), float(scale))


def _rotate(x, rope: dict):
    """x [b, t, h, D]: dimension i against i + D/2, by position x inv_i."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv, scale = inverse_frequencies(x.shape[-1], rope)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [t, D/2]
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _attend(q, k, v, window, remat):
    """Causal softmax attention of q [b, t, h, D] over k, v [b, t, g, D],
    query head j on key-value head j // (h / g), one block of queries at a
    time against every key; under ``window`` a query sees its own position
    and the ``window - 1`` before it."""
    b, t, h, dk = q.shape
    g = k.shape[2]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0 and h % g == 0, (t, block, h, g)
    key_pos = jnp.arange(t)

    def one(args):
        qb, start = args                                   # [b, block, h, dk]
        grouped = qb.reshape(b, block, g, h // g, dk)
        scores = jnp.einsum("bqgjd,bkgd->bgjqk", grouped, k) * dk ** -0.5
        ahead = (start + jnp.arange(block))[:, None] - key_pos[None, :]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        out = jnp.einsum("bgjqk,bkgd->bqgjd", jax.nn.softmax(scores, -1), v)
        return out.reshape(b, block, h, v.shape[-1])

    if remat:
        one = jax.checkpoint(one)
    blocks = q.reshape(b, t // block, block, h, dk).swapaxes(0, 1)
    out = jax.lax.map(one, (blocks, jnp.arange(0, t, block)))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1])


def _attention(x, p, m, kind, remat):
    b, t, _ = x.shape
    h, g, dim = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps, rope = m["rms_norm_eps"], m["rope_parameters"][kind]
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, h, dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, g, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, g, dim)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), rope)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), rope)
    window = m["sliding_window"] if kind == WINDOW else None
    out = _attend(q, k, v, window, remat).reshape(b, t, h * dim)
    return out @ p["o_proj"]["kernel"]


def routing_weights(x, p, m):
    """[b, t, E]: a token's weight for each expert, zero where it did not
    choose it."""
    scores = jax.nn.softmax(x @ p["router"], axis=-1)
    # the k-th largest biased score decides who is chosen; no sort of pairs
    biased = scores + p["router_bias"]
    chosen = biased >= jax.lax.top_k(
        biased, m["num_experts_per_tok"])[0][..., -1:]
    picked = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return picked


def _experts(x, p, m, remat=False):
    """The held experts' part of the routed result: every held expert in
    turn over all tokens."""
    held = p["experts_wi"].shape[0]
    first = m["expert_shard"]["index"] * held
    weights = routing_weights(x, p, m)

    def expert(x, wi, wo, weight):
        gate, up = jnp.split(x @ wi, 2, axis=-1)
        return weight[..., None] * ((jax.nn.silu(gate) * up) @ wo)

    if remat:
        expert = jax.checkpoint(expert)

    def add(y, one):
        return y + expert(x, *one), None

    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    return jax.lax.scan(add, jnp.zeros_like(x),
                        (p["experts_wi"], p["experts_wo"], mine))[0]


def _block(x, p, *, m, kind, remat):
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_norm"], eps), p["attn"], m,
                       kind, remat)
    return x + _experts(_rms_norm(x, p["post_attn_norm"], eps), p["moe"], m,
                        remat)


def _log_likelihood(hidden, head, targets):
    """Sum of log p(target) over the positions of hidden [n, d]."""
    log_p = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    return jnp.take_along_axis(log_p, targets[:, None], axis=-1).sum()


def hidden_states(params, input_ids, *, m, remat=False):
    """[b, t, d] after the final norm."""
    x = params["embed"]["embedding"][input_ids]
    for i, kind in enumerate(m["layer_types"][:m["num_hidden_layers"]]):
        fn = functools.partial(_block, m=m, kind=kind, remat=remat)
        x = (jax.checkpoint(fn) if remat else fn)(x, params[f"layers_{i}"])
    return _rms_norm(x, params["norm"], m["rms_norm_eps"])


def logits(params, input_ids, *, m):
    """[b, t, vocab_size]: for the tests, at sizes where they fit."""
    return hidden_states(params, input_ids, m=m) @ params["lm_head"].T


def loss(params, input_ids, labels, *, m, remat=False):
    """The mean next-token cross-entropy over one batch [b, t], the
    positions taken ``LOSS_BLOCK`` at a time."""
    hidden = hidden_states(params, input_ids, m=m, remat=remat)
    flat, targets = hidden.reshape(-1, hidden.shape[-1]), labels.reshape(-1)
    n = flat.shape[0]
    block = min(LOSS_BLOCK, n)
    assert n % block == 0, (n, block)
    part = functools.partial(_log_likelihood, head=params["lm_head"])
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
    at the timed size one float32 gradient (2.38 GB) fits beside the state
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
