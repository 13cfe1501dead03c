"""The plain reference of the ``qwen3_next`` family (gated delta-rule
linear-attention layers three to one over gated full attention, softmax-
routed experts beside a gated shared expert, an untied head): the forward
pass, the loss and its gradient in straightforward float32 ``jax.numpy``.

Independent of ``ray_tpu/models/qwen3_next.py`` and of ``ray_tpu/ops``: it
imports nothing from the program and shares only the names of the parameter
tree it is handed. No kernel, no chunked form of the rule, no triangular
solve, no sort, no grouped matmul, no bfloat16. It follows the layer
equations the configuration file states (its published keys and what it
lists under ``assumed``), d the hidden size, eps ``rms_norm_eps``:

- ``N(x) = x / rms(x) * (1 + w)`` (zero-centred) for the block norms, the
  final norm and the full layer's head norms; the linear layer's output norm
  is the plain ``x / rms(x) * w``;
- a block ``h = h + Mixer(N1(h))``, ``h = h + F(N2(h))``; layer i is
  ``full_attention`` or ``linear_attention`` by ``layer_types``;
- the linear mixer: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``;
  ``[q | k | v]`` pass a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps (shifted products, zeros before the
  sequence's start, no bias), then SiLU; q and k are L2-normalised over each
  head's width (eps 1e-6), q scaled by width^-0.5, key head ``h // rep``
  serves value head ``h``; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; the recurrence, ONE POSITION A STEP
  (``delta_rule``):
  ``S' = exp(g_t) S``, ``S = S' + k_t (beta_t (v_t - S'^T k_t))^T``, ``o_t =
  S^T q_t``; ``y = (o / rms(o) * w) * silu(z)`` over each head; ``y W_out``;
- the full mixer: ``q_proj`` gives [T, H, 2 D], a head's first D the query
  and its second D the gate; N over D on q and on k; rotary positions on the
  first ``partial_rotary_factor x D`` dimensions (halves of those rotated);
  query head j reads key-value head ``j // (H / G)``; causal softmax of
  ``q.k / sqrt(D)``; ``y = attn * sigmoid(gate)``; ``W_o``;
- ``F``: ``p = softmax(x W_r)`` over all experts; the
  ``num_experts_per_tok`` with the largest ``p + b`` (b the selection bias:
  zero is the published router); weights ``p_i / sum of the chosen p``; each
  expert a SwiGLU; plus ``sigmoid(x w_g) * SwiGLU(x)``, the shared expert;
- final N, the untied head, mean next-token cross-entropy.

Departures, each of which changes no value that is compared: the same share
of the deployment as the program (held experts, sliced vocabulary, cut
depth); every held expert applied to all tokens and weighted by the token's
weight for it; attention in blocks of queries, the loss in blocks of
positions, the rule in blocks of positions; with a gradient asked for,
layers and blocks are recomputed in the backward pass (``jax.checkpoint``).

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
RULE_BLOCK = 64
LINEAR = "linear_attention"


def _rms(x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def _norm(x, p, eps):
    """The zero-centred norm."""
    return _rms(x, eps) * (1.0 + p["scale"])


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _swiglu(x, p):
    gate, up = x @ p["gate_proj"]["kernel"], x @ p["up_proj"]["kernel"]
    return (jax.nn.silu(gate) * up) @ p["down_proj"]["kernel"]


def _causal_conv(x, taps):
    """x [b, t, c], taps [K, c]: position t's sum over x at t - K + 1 .. t,
    the products written out."""
    t, last = x.shape[1], taps.shape[0] - 1
    padded = jnp.pad(x, ((0, 0), (last, 0), (0, 0)))
    return sum(taps[k] * padded[:, k:k + t] for k in range(last + 1))


def delta_rule(q, k, v, g, beta, remat=False):
    """The gated delta rule one position a step: q, k [b, t, key heads,
    d_k], v [b, t, heads, d_v], g, beta [b, t, heads] -> o [b, t, heads,
    d_v]. The positions are walked ``RULE_BLOCK`` at a time so that, with
    ``remat``, the backward pass keeps one state a block and makes a block's
    states again."""
    b, t, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    rep = heads // key_heads
    q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    block = math.gcd(t, RULE_BLOCK)

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[..., None, None] * state
        delta = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, delta)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    def one(state, inputs):
        return jax.lax.scan(step, state, inputs)

    if remat:
        one = jax.checkpoint(one)
    blocks = lambda a: jnp.moveaxis(
        a.reshape(b, t // block, block, *a.shape[2:]), (1, 2), (0, 1))
    _, o = jax.lax.scan(one, jnp.zeros((b, heads, d_k, d_v), jnp.float32),
                        tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o, (0, 1), (1, 2)).reshape(b, t, heads, d_v)


def _linear_attention(u, p, m, remat):
    b, t, _ = u.shape
    key_heads, heads = m["linear_num_key_heads"], m["linear_num_value_heads"]
    d_k, d_v = m["linear_key_head_dim"], m["linear_value_head_dim"]
    keys, values = key_heads * d_k, heads * d_v
    qkvz = u @ p["in_proj_qkvz"]["kernel"]
    ba = u @ p["in_proj_ba"]["kernel"]
    mixed = jax.nn.silu(_causal_conv(qkvz[..., :2 * keys + values],
                                     p["conv_weight"]))
    z = qkvz[..., 2 * keys + values:].reshape(b, t, heads, d_v)
    q = _l2(mixed[..., :keys].reshape(b, t, key_heads, d_k)) * d_k ** -0.5
    k = _l2(mixed[..., keys:2 * keys].reshape(b, t, key_heads, d_k))
    v = mixed[..., 2 * keys:].reshape(b, t, heads, d_v)
    beta = jax.nn.sigmoid(ba[..., :heads])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., heads:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta, remat)
    y = _rms(o, m["rms_norm_eps"]) * p["norm"]["scale"] * jax.nn.silu(z)
    return y.reshape(b, t, values) @ p["out_proj"]["kernel"]


def _rotate_part(x, theta, rotary):
    """x [b, t, h, D]: the first ``rotary`` dimensions turned by position,
    dimension i against i + rotary / 2 by position x theta^(-2i / rotary);
    the rest left as they are."""
    t, half = x.shape[1], rotary // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, x[..., rotary:]], -1)


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


def _attention(x, p, m, remat):
    b, t, _ = x.shape
    h, g, dim = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    rotary = int(dim * m["partial_rotary_factor"])
    both = (x @ p["q_proj"]["kernel"]).reshape(b, t, h, 2 * dim)
    q, gate = both[..., :dim], both[..., dim:]
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, g, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, g, dim)
    q = _rotate_part(_norm(q, p["q_norm"], eps), theta, rotary)
    k = _rotate_part(_norm(k, p["k_norm"], eps), theta, rotary)
    y = _attend(q, k, v, remat) * jax.nn.sigmoid(gate)
    return y.reshape(b, t, h * dim) @ p["o_proj"]["kernel"]


def routing_weights(x, p, m):
    """[b, t, E]: each token's weight for every expert, zero where it did
    not choose it."""
    scores = jax.nn.softmax(x @ p["router"], axis=-1)
    # the k-th largest biased score decides who is chosen; no sort of pairs
    biased = scores + p["router_bias"]
    chosen = biased >= jax.lax.top_k(biased, m["num_experts_per_tok"])[0][
        ..., -1:]
    picked = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return picked


def _experts(x, p, m, remat=False):
    """The held experts' part of the routed result and the shared expert
    behind its gate: every held expert in turn applied to all tokens and
    weighted by each token's weight for it."""
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

    shared = (jax.nn.sigmoid(x @ p["shared_gate"])
              * _swiglu(x, p["shared_experts"]))
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    return jax.lax.scan(add, shared,
                        (p["experts_wi"], p["experts_wo"], mine))[0]


def _block(x, p, *, m, kind, remat):
    eps = m["rms_norm_eps"]
    u = _norm(x, p["input_norm"], eps)
    if kind == LINEAR:
        x = x + _linear_attention(u, p["linear_attn"], m, remat)
    else:
        x = x + _attention(u, p["attn"], m, remat)
    return x + _experts(_norm(x, p["post_attn_norm"], eps), p["moe"], m,
                        remat)


def layers_run(m):
    """(published index, kind) of the layers run."""
    kept = m.get("kept_layers") or range(len(m["layer_types"]))
    return [(i, m["layer_types"][i]) for i in kept]


def hidden_states(params, input_ids, *, m, remat=False):
    """[b, t, d] after the final norm."""
    x = params["embed"]["embedding"][input_ids]
    for i, kind in layers_run(m):
        fn = functools.partial(_block, m=m, kind=kind, remat=remat)
        x = (jax.checkpoint(fn) if remat else fn)(x, params[f"layers_{i}"])
    return _norm(x, params["norm"], m["rms_norm_eps"])


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
    at the timed size one float32 gradient (2.5 GB) fits beside the state it
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
