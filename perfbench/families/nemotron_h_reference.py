"""The plain reference of the ``nemotron_h`` family (one mixer a block by a
pattern string: Mamba-2 state-space mixers, grouped attention without a
positional term, sigmoid-routed un-gated relu^2 experts beside a shared
expert, an untied head): the forward pass, the loss and its gradient in
straightforward float32 ``jax.numpy``.

Independent of ``ray_tpu/models/nemotron_h.py`` and of ``ray_tpu/ops``: it
imports nothing from the program and shares only the names of the parameter
tree it is handed. No kernel, no chunked (matmul) form of the recurrence, no
sort, no grouped matmul, no bfloat16. It follows the layer equations the
configuration file states (its published keys and what it lists under
``assumed``), d the hidden size, eps ``layer_norm_epsilon``:

- ``N(x) = x / rms(x) * w`` for the block norms and the final norm;
- a block ``h = h + Mixer_i(N_i(h))``; block i's kind is character i of
  ``hybrid_override_pattern`` (``M``, ``*``, ``E``);
- ``M``: ``[z | xBC | dt] = u W_in`` (widths heads x head_dim | that plus 2
  x groups x states | heads); ``xBC = silu(conv(xBC) + b_conv)``, the
  depthwise causal convolution as ``conv_kernel`` shifted products with zeros
  before the sequence's start, plus its bias; ``[x | B | C] = xBC``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence, ONE
  POSITION A STEP (``ssd_recurrence``): ``S = exp(dt_t A) S + (dt_t x_t)
  B_t^T``, ``y_t = S C_t + D x_t``, head h reading group ``h // (heads /
  groups)``; ``y = GroupRMSNorm(y * silu(z))`` over each of ``n_groups``
  groups of channels with one weight a channel; ``y W_out``;
- ``*``: ``num_attention_heads`` query heads on ``num_key_value_heads``
  key-value heads, query head j reading key-value head ``j // (H / G)``,
  causal softmax of ``q.k / sqrt(head_dim)``, no positional term; ``W_o``;
- ``E``: ``s = sigmoid(u W_r)`` over all experts; the
  ``num_experts_per_tok`` with the largest ``s + b`` (b the selection bias);
  weights ``s_i / (sum of the chosen s + 1e-20)`` times
  ``routed_scaling_factor``; each expert ``relu(u W_i)^2 W_o``; plus the
  shared expert ``relu(u W_up)^2 W_down`` on every token;
- final N, the untied head, mean next-token cross-entropy.

Departures, each of which changes no value that is compared: the same share
of the deployment as the program (held experts, sliced vocabulary, cut
depth); every held expert applied to all tokens and weighted by the token's
weight for it; attention in blocks of queries, the loss in blocks of
positions, the recurrence in blocks of positions; with a gradient asked for,
blocks are recomputed in the backward pass (``jax.checkpoint``).

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
SCAN_BLOCK = 64
KINDS = {"M": "mamba", "*": "attention", "E": "expert"}


def _norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p[
        "scale"]


def _relu2(x, p):
    up = jax.nn.relu(x @ p["up_proj"]["kernel"])
    return (up * up) @ p["down_proj"]["kernel"]


def _causal_conv(x, taps, bias):
    """x [b, t, c], taps [K, c], bias [c]: position t's sum over x at t - K
    + 1 .. t, the products written out, plus the bias."""
    t, last = x.shape[1], taps.shape[0] - 1
    padded = jnp.pad(x, ((0, 0), (last, 0), (0, 0)))
    return sum(taps[k] * padded[:, k:k + t] for k in range(last + 1)) + bias


def ssd_recurrence(x, dt, a, b, c, skip, remat=False):
    """The scalar-decay recurrence one position a step: x [n, t, heads, p],
    dt [n, t, heads], a, skip [heads], b, c [n, t, groups, states] -> y [n,
    t, heads, p]. The positions are walked ``SCAN_BLOCK`` at a time so that,
    with ``remat``, the backward pass keeps one state a block and makes a
    block's states again."""
    n, t, heads, p = x.shape
    groups, states = b.shape[2:]
    rep = heads // groups
    b, c = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)
    block = math.gcd(t, SCAN_BLOCK)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + jnp.einsum("nhp,nhs->nhps", dt_t[..., None] * x_t, b_t))
        return state, (jnp.einsum("nhps,nhs->nhp", state, c_t)
                       + skip[:, None] * x_t)

    def one(state, inputs):
        return jax.lax.scan(step, state, inputs)

    if remat:
        one = jax.checkpoint(one)
    blocks = lambda v: jnp.moveaxis(
        v.reshape(n, t // block, block, *v.shape[2:]), (1, 2), (0, 1))
    _, y = jax.lax.scan(one, jnp.zeros((n, heads, p, states), jnp.float32),
                        tuple(map(blocks, (x, dt, b, c))))
    return jnp.moveaxis(y, (0, 1), (1, 2)).reshape(n, t, heads, p)


def _mamba(u, p, m, remat):
    n, t, _ = u.shape
    heads, dim = m["mamba_num_heads"], m["mamba_head_dim"]
    groups, states = m["n_groups"], m["ssm_state_size"]
    inner, bc = heads * dim, groups * states
    zxbcdt = u @ p["in_proj"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_weight"], p["conv_bias"]))
    x = xbc[..., :inner].reshape(n, t, heads, dim)
    b = xbc[..., inner:inner + bc].reshape(n, t, groups, states)
    c = xbc[..., inner + bc:].reshape(n, t, groups, states)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, p["D"], remat)
    gated = (y.reshape(n, t, inner) * jax.nn.silu(z)).reshape(
        n, t, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        (gated * gated).mean(-1, keepdims=True) + m["layer_norm_epsilon"])
    return (normed.reshape(n, t, inner) * p["norm"]["scale"]) @ p[
        "out_proj"]["kernel"]


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
        qb, start = args
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


def _attention(u, p, m, remat):
    b, t, _ = u.shape
    h, g, dim = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    q = (u @ p["q_proj"]["kernel"]).reshape(b, t, h, dim)
    k = (u @ p["k_proj"]["kernel"]).reshape(b, t, g, dim)
    v = (u @ p["v_proj"]["kernel"]).reshape(b, t, g, dim)
    return _attend(q, k, v, remat).reshape(b, t, h * dim) @ p["o_proj"][
        "kernel"]


def routing_weights(x, p, m):
    """[b, t, E]: each token's weight for every expert, zero where it did
    not choose it."""
    scores = jax.nn.sigmoid(x @ p["router"])
    # the k-th largest biased score decides who is chosen; no sort of pairs
    biased = scores + p["router_bias"]
    chosen = biased >= jax.lax.top_k(biased, m["num_experts_per_tok"])[0][
        ..., -1:]
    picked = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return picked * m["routed_scaling_factor"]


def _experts(x, p, m, remat=False):
    """The held experts' part of the routed result and the shared expert:
    every held expert in turn applied to all tokens and weighted by each
    token's weight for it."""
    held = p["experts_wi"].shape[0]
    first = m["expert_shard"]["index"] * held
    weights = routing_weights(x, p, m)

    def expert(x, wi, wo, weight):
        up = jax.nn.relu(x @ wi)
        return weight[..., None] * ((up * up) @ wo)

    if remat:
        expert = jax.checkpoint(expert)

    def add(y, one):
        return y + expert(x, *one), None

    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    return jax.lax.scan(add, _relu2(x, p["shared_experts"]),
                        (p["experts_wi"], p["experts_wo"], mine))[0]


_MIXERS = {"mamba": _mamba, "attention": _attention, "expert": _experts}


def _block(x, p, *, m, kind, remat):
    u = _norm(x, p["norm"], m["layer_norm_epsilon"])
    return x + _MIXERS[kind](u, p["mixer"], m, remat)


def layers_run(m):
    """(published index, kind) of the blocks run."""
    kinds = [KINDS[c] for c in m["hybrid_override_pattern"]]
    kept = m.get("kept_layers") or range(len(kinds))
    return [(i, kinds[i]) for i in kept]


def hidden_states(params, input_ids, *, m, remat=False):
    """[b, t, d] after the final norm."""
    x = params["embed"]["embedding"][input_ids]
    for i, kind in layers_run(m):
        fn = functools.partial(_block, m=m, kind=kind, remat=remat)
        x = (jax.checkpoint(fn) if remat else fn)(x, params[f"layers_{i}"])
    return _norm(x, params["norm"], m["layer_norm_epsilon"])


def logits(params, input_ids, *, m):
    """[b, t, vocab_size]: for the tests, at sizes where they fit."""
    return hidden_states(params, input_ids, m=m) @ params["lm_head"].T


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
    at the timed size one float32 gradient (2.7 GB) fits beside the state it
    is compared with and the backward pass's working set."""
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
