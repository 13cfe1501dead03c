"""The plain reference of the ``sdar`` family (block diffusion over two
streams, every feed-forward part routed to experts): the noise, the forward
pass, the loss and its gradient in straightforward float32 ``jax.numpy``.

Independent of ``ray_tpu/models/sdar.py``: it imports nothing from the
program and shares only the names of the parameter tree it is handed. No
kernel, no sort, no grouped matmul, no chunked loss walk, no bfloat16. It
follows the equations the configuration file states (its published keys
and what it lists under ``assumed``):

- the noise of step ``count`` (0: the step that is compared): the key
  ``fold_in(PRNGKey(noise_seed), count)`` is split in three; the first
  gives one uniform offset ``u``, the second a permutation of the step's
  ``n = B L / block_length`` blocks, the third one uniform number a
  position. Block ``k`` has the rate ``t = 1 - (u + perm[k] / n) mod 1``
  and ``p = (1 - noise_eps) t + noise_eps``; a position is masked where its
  uniform number is under its block's ``p``: ``x_t[i] = mask_token_id``
  there, ``x_0[i]`` elsewhere;
- the trunk reads ``[x_t ; x_0]`` (2 L ids) at positions ``[0..L-1 ;
  0..L-1]``: embedding ``h = E[id]``; a block ``h = h + Attn(N1(h))``, ``h
  = h + F(N2(h))``, both N RMSNorms with a learned scale and
  ``rms_norm_eps``;
- ``Attn(x)``: ``q = x Wq`` [2L, H, D], ``k = x Wk``, ``v = x Wv`` [2L, G,
  D], no biases; q and k pass an RMSNorm over D (one scale vector each),
  then both are rotated by position (dimension i against i + D/2, angle
  ``position x rope_theta^(-2i / D)``); query head j reads key-value head
  ``j // (H / G)``; scores ``q.k / sqrt(D)`` under the ``2L x 2L`` mask
  ``seen`` below; then ``Wo``;
- the mask, with ``b(i) = i // block_length`` within a stream: a query of
  the noisy stream at ``i`` sees the noisy stream's keys ``j`` with ``b(j)
  == b(i)`` and the clean stream's keys ``j`` with ``b(j) < b(i)``; a query
  of the clean stream at ``i`` sees the clean stream's keys ``j`` with
  ``b(j) <= b(i)``, and no key of the noisy stream;
- ``F``, in every layer: ``p = softmax(x W_r)`` over all experts, the
  ``num_experts_per_tok`` experts with the largest ``p + b``, weights ``p_i
  / sum of the chosen p`` (``norm_topk_prob``), each expert a SwiGLU of
  ``moe_intermediate_size``; no shared expert;
- the final RMSNorm and the untied head over the noisy stream's L
  positions; the loss ``(1 / (B L)) sum_i m_i (1 / p_i) (-log softmax(W
  h_i)[x_0[i]])``.

Departures, each of which changes no value that is compared:

- it is given the same share of the deployment as the program: the experts
  ``expert_shard.index`` of ``expert_shard.of`` (the routed result is the
  sum over the held experts only), the sliced vocabulary, the cut depth;
- every held expert is applied to all positions, one expert after another,
  and weighted by the position's weight for it, zero where it did not
  choose it;
- attention runs in blocks of queries against all keys under the rows of
  the mask, and the loss in blocks of positions; with a gradient asked for,
  layers, blocks of queries, experts and blocks of the loss are recomputed
  in the backward pass (``jax.checkpoint``), so that a sequence's gradient
  fits beside the state it is compared with.

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


def noised(m: dict, clean, count: int = 0):
    """-> (x_t, weights m / p float32) for ``clean`` [B, L], the whole
    step's batch, at step ``count``."""
    rows, length = clean.shape
    size, eps = m["block_length"], m["noise_eps"]
    blocks = rows * (length // size)
    key = jax.random.fold_in(jax.random.PRNGKey(m["noise_seed"]), count)
    for_offset, for_order, for_mask = jax.random.split(key, 3)
    offset = jax.random.uniform(for_offset, ())
    order = jax.random.permutation(for_order, blocks)
    t = 1.0 - jnp.mod(offset + order / blocks, 1.0)
    p = ((1.0 - eps) * t + eps).reshape(rows, length // size)
    p = jnp.repeat(p, size, axis=1)
    masked = jax.random.uniform(for_mask, (rows, length)) < p
    return (jnp.where(masked, m["mask_token_id"], clean),
            jnp.where(masked, 1.0 / p, 0.0).astype(jnp.float32))


def seen(length: int, size: int):
    """The [2 length, 2 length] mask, queries along rows: the four
    sentences of this file's docstring."""
    at = jnp.arange(2 * length)
    noisy, block = at < length, (at % length) // size
    q_noisy, q_block = noisy[:, None], block[:, None]
    k_noisy, k_block = noisy[None, :], block[None, :]
    return ((q_noisy & k_noisy & (k_block == q_block))
            | (q_noisy & ~k_noisy & (k_block < q_block))
            | (~q_noisy & ~k_noisy & (k_block <= q_block)))


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rotate(x, theta: float):
    """x [b, 2 L, h, D], the two streams' positions 0..L-1 each: dimension
    i against i + D/2, by position x theta^(-2i / D)."""
    t, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    inv = jnp.asarray(float(theta) ** (-2.0 * np.arange(half) / dim),
                      jnp.float32)
    position = jnp.tile(jnp.arange(t // 2, dtype=jnp.float32), 2)
    angle = position[:, None] * inv                              # [t, D/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _attend(q, k, v, mask, remat):
    """Softmax attention of q [b, t, h, D] over k, v [b, t, g, D] under
    ``mask`` [t, t], query head j on key-value head j // (h / g), one block
    of queries at a time against every key."""
    b, t, h, dk = q.shape
    g = k.shape[2]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0 and h % g == 0, (t, block, h, g)

    def one(args):
        qb, rows = args                       # [b, block, h, dk], [block, t]
        grouped = qb.reshape(b, block, g, h // g, dk)
        scores = jnp.einsum("bqgjd,bkgd->bgjqk", grouped, k) * dk ** -0.5
        scores = jnp.where(rows, scores, -jnp.inf)
        out = jnp.einsum("bgjqk,bkgd->bqgjd", jax.nn.softmax(scores, -1), v)
        return out.reshape(b, block, h, v.shape[-1])

    if remat:
        one = jax.checkpoint(one)
    blocks = q.reshape(b, t // block, block, h, dk).swapaxes(0, 1)
    out = jax.lax.map(one, (blocks, mask.reshape(t // block, block, t)))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1])


def _attention(x, p, m, mask, remat):
    b, t, _ = x.shape
    h, g, dim = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, h, dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, g, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, g, dim)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), theta)
    out = _attend(q, k, v, mask, remat).reshape(b, t, h * dim)
    return out @ p["o_proj"]["kernel"]


def routing_weights(x, p, m):
    """[b, t, E]: a position's weight for each expert, zero where it did
    not choose it."""
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
    turn over all positions."""
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


def _block(x, p, mask, *, m, remat):
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_norm"], eps), p["attn"], m,
                       mask, remat)
    return x + _experts(_rms_norm(x, p["post_attn_norm"], eps), p["moe"], m,
                        remat)


def hidden_states(params, both_ids, *, m, remat=False):
    """[b, L, d]: the noisy stream after the final norm, for ``both_ids``
    [b, 2 L], the noisy copy and then the clean one."""
    length = both_ids.shape[1] // 2
    mask = seen(length, m["block_length"])
    x = params["embed"]["embedding"][both_ids]
    for i in range(m["num_hidden_layers"]):
        fn = functools.partial(_block, m=m, remat=remat)
        x = (jax.checkpoint(fn) if remat else fn)(x, params[f"layers_{i}"],
                                                  mask)
    return _rms_norm(x[:, :length], params["norm"], m["rms_norm_eps"])


def logits(params, both_ids, *, m):
    """[b, L, vocab_size]: for the tests, at sizes where they fit."""
    return hidden_states(params, both_ids, m=m) @ params["lm_head"].T


def _weighted_log_likelihood(hidden, head, targets, weights):
    """Sum of weight x log p(target) over the positions of hidden [n, d]."""
    log_p = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    return (weights * jnp.take_along_axis(
        log_p, targets[:, None], axis=-1)[:, 0]).sum()


def loss(params, noisy, clean, weights, *, m, remat=False):
    """The weighted cross-entropy of the rows given, over their every
    position, the positions taken ``LOSS_BLOCK`` at a time."""
    hidden = hidden_states(params, jnp.concatenate([noisy, clean], axis=1),
                           m=m, remat=remat)
    flat = hidden.reshape(-1, hidden.shape[-1])
    n = flat.shape[0]
    block = min(LOSS_BLOCK, n)
    assert n % block == 0, (n, block)
    part = functools.partial(_weighted_log_likelihood,
                             head=params["lm_head"])
    if remat:
        part = jax.checkpoint(part)
    cut = lambda a: a.reshape(n // block, block, *a.shape[2:])
    sums = jax.lax.map(
        lambda args: part(args[0], targets=args[1], weights=args[2]),
        (flat.reshape(n // block, block, -1), cut(clean), cut(weights)))
    return -sums.sum() / n


def make(model: dict, with_grad: bool):
    """Jitted (params, x_t, x_0, weights) -> float32 loss, or with
    ``with_grad`` -> (loss, gradient tree)."""

    def fn(params, noisy, clean, weights):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            if with_grad:
                return jax.value_and_grad(loss)(params, noisy, clean,
                                                weights, m=model, remat=True)
            return loss(params, noisy, clean, weights, m=model)

    return jax.jit(fn)


def over_microbatches(model: dict, params, tokens, microbatch: int,
                      with_grad: bool, place):
    """The reference over the whole step batch at the first step's noise,
    ``microbatch`` sequences at a time (equal parts, so the mean of the
    parts is the batch's mean). ``tokens`` is the host's [n, seq + 1]
    array, whose first ``seq`` columns are the data (the objective has no
    shift), and ``place`` puts one part's array on the device(s). ->
    (loss, gradient tree or None). The loss is on the device. The gradient
    is summed on the HOST, one part's leaves brought over and freed on the
    device before the next part runs."""
    n = tokens.shape[0]
    if n % microbatch:
        raise ValueError(f"batch {n} is not a multiple of the reference's "
                         f"microbatch {microbatch}")
    parts = n // microbatch
    clean = np.asarray(tokens[:, :-1])
    noisy, weights = (np.asarray(a) for a in noised(model, clean))
    fn = make(model, with_grad)
    total, grads = None, None
    for i in range(parts):
        rows = slice(i * microbatch, (i + 1) * microbatch)
        out = fn(params, place(noisy[rows]), place(clean[rows]),
                 place(weights[rows]))
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
