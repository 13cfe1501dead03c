"""The plain reference of the ``keye`` family (attention over the keys a
learned indexer picks for each query, positions of three components, every
feed-forward part routed to experts): the positions, the forward pass, both
terms of the loss and their gradient in straightforward float32
``jax.numpy``.

Independent of ``ray_tpu/models/keye.py`` and ``ray_tpu/ops``: it imports
nothing from the program and shares only the names of the parameter tree it
is handed. No kernel, no bisection, no mask array shared between passes, no
grouped matmul, no chunked loss walk, no bfloat16. It follows the equations
the configuration file states (its published keys and what it lists under
``assumed``):

- the layout is the traffic file's that the configuration names
  (``reference.layout``): ``images`` spans of ``image_grid`` tokens at
  ``image_offsets``, text elsewhere. A text token takes the position triple
  ``(p, p, p)`` and ``p += 1``; a span of grid ``gh x gw`` that starts at
  ``p0`` gives its token ``(r, c)`` the triple ``(p0, p0 + r, p0 + c)``,
  after which ``p = p0 + max(gh, gw)`` (``positions``);
- the trunk: embedding ``h = E[id]``; a block ``h = h + Attn(N1(h))``, ``h =
  h + F(N2(h))``, both N RMSNorms with a learned scale and ``rms_norm_eps``;
- ``Attn(x)``: ``q = x Wq`` [T, H, D], ``k = x Wk``, ``v = x Wv`` [T, G, D],
  no biases; q and k pass an RMSNorm over D (one scale vector each), then
  both are rotated (dimension i against i + D/2) by the angle
  ``component(i) x rope_theta^(-2i / D)``, where pair ``i`` of the D / 2
  reads the temporal component for ``i < s0``, the height's for ``s0 <= i <
  s0 + s1`` and the width's after (``mrope_section = [s0, s1, s2]``);
- the indexer reads ``xd = stop_gradient(x)``: ``qI = xd Wq'`` [T, J, W],
  ``kI = LayerNorm_W(xd Wk')`` [T, W] (scale and bias, eps
  ``rms_norm_eps``), ``w = (xd Ww) J^-0.5 W^-0.5`` [T, J]; ``I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])``, no rotation;
- the selection: ``S_t`` = the keys ``s <= t`` whose ``I[t, s]`` is at
  least the ``topk``-th largest of ``I[t, :t + 1]`` (``lax.top_k``), all of
  them while ``t < topk``: a boolean mask, in sequence order, one set for
  all heads;
- each head's softmax over ``S_t`` alone at scale ``D^-0.5`` (query head j
  reads key-value head ``j // (H / G)``), then ``Wo``;
- ``F``, in every layer: ``p = softmax(x W_r)`` over all experts, the
  ``num_experts_per_tok`` experts with the largest ``p + b``, weights ``p_i
  / sum of the chosen p`` (``norm_topk_prob``), each expert a SwiGLU of
  ``moe_intermediate_size``; no shared expert;
- the loss ``L_lm + L_I``: ``L_lm`` the next-token cross-entropy through the
  final RMSNorm and the untied head over the targets that are text (the
  target of position t is text where position t + 1 is no image's), their
  mean; ``L_I = (1 / (layers B T)) sum_layers sum_t KL(p[t, S_t] ||
  softmax(I[t, S_t]))`` with ``p = stop_gradient`` of the mean over the H
  heads of the layer's attention probabilities.

Departures, each of which changes no value that is compared:

- it is given the same share of the deployment as the program: the experts
  ``expert_shard.index`` of ``expert_shard.of`` (the routed result is the
  sum over the held experts only), the sliced vocabulary, the cut depth;
- every held expert is applied to all positions, one expert after another,
  and weighted by the position's weight for it, zero where it did not
  choose it;
- attention, selection and KL run in blocks of queries against all keys, and
  the loss in blocks of positions; with a gradient asked for, layers,
  blocks of queries, experts and blocks of the loss are recomputed in the
  backward pass (``jax.checkpoint``), so that a sequence's gradient fits
  beside the state it is compared with.

On a TPU a float32 matrix multiplication runs in lower precision unless the
precision is raised, so every entry point runs under
``jax.default_matmul_precision("highest")``. ``selections`` hands out the
layers' boolean masks, for a builder's count of the memberships on which
program and reference differ.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def layout(m: dict) -> dict:
    with open(os.path.join(_ROOT, m["reference"]["layout"])) as f:
        return json.load(f)


def positions(said: dict, seq: int):
    """(triples int32 [3, seq], whether a position is an image's [seq])."""
    height, width = said.get("image_grid", (1, 1))
    starts = set(said.get("image_offsets", ()))
    triples, image = np.zeros((3, seq), np.int32), np.zeros(seq, bool)
    t = p = 0
    while t < seq:
        if t in starts:
            for r in range(height):
                for c in range(width):
                    triples[:, t] = (p, p + r, p + c)
                    image[t] = True
                    t += 1
            p += max(height, width)
        else:
            triples[:, t] = (p, p, p)
            t, p = t + 1, p + 1
    return triples, image


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def angles(triples, dim: int, theta: float, section):
    """[seq, dim / 2]: pair i's angle, from the component its section
    names."""
    half = dim // 2
    inv = np.asarray(float(theta) ** (-2.0 * np.arange(half) / dim),
                     np.float32)
    component = np.repeat(np.arange(3), section)            # [half]
    chosen = jnp.asarray(triples, jnp.float32)[component]   # [half, seq]
    return chosen.T * inv


def _rotate(x, angle):
    """x [b, T, h, D]: dimension i against i + D/2."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _index_scores(q_index, k_index, w):
    """[b, block, T]: sum_j w[.., j] relu(qI[.., j] . kI[s])."""
    dots = jnp.einsum("bqjw,bkw->bqjk", q_index, k_index)
    return jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(dots), w)


def chosen_keys(scores, first, topk: int):
    """The boolean mask [b, block, T] of the queries ``first ..`` from
    their index scores against every key."""
    block, t = scores.shape[1:]
    earlier = jnp.arange(t)[None, :] <= (first + jnp.arange(block))[:, None]
    scores = jnp.where(earlier, scores, -jnp.inf)
    if topk >= t:
        return jnp.broadcast_to(earlier, scores.shape)
    least = jax.lax.top_k(scores, topk)[0][..., -1:]
    return earlier & (scores >= least)


def _attend(q, k, v, q_index, k_index, w, topk: int, remat):
    """-> (out [b, T, h, D], the layer's sum over its queries of the KL): a
    block of queries at a time against every key."""
    b, t, h, dk = q.shape
    g = k.shape[2]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0 and h % g == 0, (t, block, h, g)

    def one(args):
        qb, qi, wb, first = args
        scores = _index_scores(qi, k_index, wb)
        mask = jax.lax.stop_gradient(chosen_keys(scores, first, topk))
        grouped = qb.reshape(b, block, g, h // g, dk)
        s = jnp.einsum("bqgjd,bkgd->bgjqk", grouped, k) * dk ** -0.5
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        attn = jax.nn.softmax(s, -1)
        out = jnp.einsum("bgjqk,bkgd->bqgjd", attn, v).reshape(
            b, block, h, v.shape[-1])
        p = jax.lax.stop_gradient(attn.mean(axis=(1, 2)))      # [b, q, k]
        log_q = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), -1)
        live = mask & (p > 0)
        kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                  - jnp.where(live, log_q, 0.0)), 0.0).sum()
        return out, kl

    if remat:
        one = jax.checkpoint(one)
    cut = lambda a: a.reshape(b, t // block, block, *a.shape[2:]).swapaxes(0, 1)
    out, kl = jax.lax.map(one, (cut(q), cut(q_index), cut(w),
                                jnp.arange(0, t, block)))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1]), kl.sum()


def _indexer(x, p, m):
    sa = m["sa_config"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    xd = jax.lax.stop_gradient(x)
    b, t, _ = x.shape
    q_index = (xd @ p["index_q"]["kernel"]).reshape(b, t, heads, width)
    k_index = _layer_norm(xd @ p["index_k"]["kernel"], p["index_k_norm"],
                          m["rms_norm_eps"])
    w = (xd @ p["index_w"]["kernel"]) * (heads * width) ** -0.5
    return q_index, k_index, w


def _attention(x, p, m, angle, remat):
    b, t, _ = x.shape
    h, g, dim = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, h, dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, g, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, g, dim)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), angle)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), angle)
    out, kl = _attend(q, k, v, *_indexer(x, p, m), m["sa_config"]["topk"],
                      remat)
    return out.reshape(b, t, h * dim) @ p["o_proj"]["kernel"], kl


def routing_weights(x, p, m):
    """[b, t, E]: a position's weight for each expert, zero where it did
    not choose it."""
    scores = jax.nn.softmax(x @ p["router"], axis=-1)
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


def _block(x, p, angle, *, m, remat):
    eps = m["rms_norm_eps"]
    y, kl = _attention(_rms_norm(x, p["input_norm"], eps), p["attn"], m,
                       angle, remat)
    x = x + y
    return x + _experts(_rms_norm(x, p["post_attn_norm"], eps), p["moe"], m,
                        remat), kl


def hidden_states(params, input_ids, triples, *, m, remat=False):
    """-> (hidden [b, T, d] after the final norm, the KL summed over the
    layers and their queries)."""
    angle = angles(triples, m["head_dim"], m["rope_theta"],
                   m["rope_scaling"]["mrope_section"])
    x, total = params["embed"]["embedding"][input_ids], 0.0
    for i in range(m["num_hidden_layers"]):
        fn = functools.partial(_block, m=m, remat=remat)
        x, kl = (jax.checkpoint(fn) if remat else fn)(
            x, params[f"layers_{i}"], angle)
        total = total + kl
    return _rms_norm(x, params["norm"], m["rms_norm_eps"]), total


def selections(params, input_ids, triples, *, m):
    """Yields the layers' boolean masks [b, T queries, T keys] one after
    another, each made a block of queries at a time: for the tests and a
    builder's count of the memberships on which program and reference
    differ."""
    angle = angles(triples, m["head_dim"], m["rope_theta"],
                   m["rope_scaling"]["mrope_section"])
    topk = m["sa_config"]["topk"]

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            normed = _rms_norm(x, p["input_norm"], m["rms_norm_eps"])
            q_index, k_index, w = _indexer(normed, p["attn"], m)
            b, t = x.shape[:2]
            block = min(QUERY_BLOCK, t)
            cut = lambda a: a.reshape(
                b, t // block, block, *a.shape[2:]).swapaxes(0, 1)
            rows = jax.lax.map(
                lambda args: chosen_keys(
                    _index_scores(args[0], k_index, args[1]), args[2], topk),
                (cut(q_index), cut(w), jnp.arange(0, t, block)))
            return (rows.swapaxes(0, 1).reshape(b, t, t),
                    _block(x, p, angle, m=m, remat=False)[0])

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["embed"]["embedding"][input_ids]
    for i in range(m["num_hidden_layers"]):
        mask, x = layer(x, params[f"layers_{i}"])
        yield mask


def _weighted_log_likelihood(hidden, head, targets, weights):
    """Sum of weight x log p(target) over the positions of hidden [n, d]."""
    log_p = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    return (weights * jnp.take_along_axis(
        log_p, targets[:, None], axis=-1)[:, 0]).sum()


def loss_terms(params, input_ids, labels, weights, triples, *, m,
               remat=False):
    """-> (L_lm, L_I) of the rows given."""
    hidden, kl = hidden_states(params, input_ids, triples, m=m, remat=remat)
    flat = hidden.reshape(-1, hidden.shape[-1])
    n = flat.shape[0]
    block = min(LOSS_BLOCK, n)
    assert n % block == 0, (n, block)
    part = functools.partial(_weighted_log_likelihood,
                             head=params["lm_head"])
    if remat:
        part = jax.checkpoint(part)
    cut = lambda a: a.reshape(n // block, block)
    sums = jax.lax.map(
        lambda args: part(args[0], targets=args[1], weights=args[2]),
        (flat.reshape(n // block, block, -1), cut(labels), cut(weights)))
    return (-sums.sum() / weights.sum(),
            kl / (m["num_hidden_layers"] * input_ids.size))


def loss(params, *batch, m, remat=False):
    lm, index = loss_terms(params, *batch, m=m, remat=remat)
    return lm + index, (lm, index)


def make(model: dict, with_grad: bool):
    """Jitted (params, input_ids, labels, weights, triples) -> (loss, (L_lm,
    L_I)), or with ``with_grad`` -> ((loss, (L_lm, L_I)), gradient tree)."""

    def fn(params, *batch):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            if with_grad:
                return jax.value_and_grad(loss, has_aux=True)(
                    params, *batch, m=model, remat=True)
            return loss(params, *batch, m=model)

    return jax.jit(fn)


def over_microbatches(model: dict, params, tokens, microbatch: int,
                      with_grad: bool, place):
    """The reference over the whole step batch, ``microbatch`` sequences at
    a time (equal parts of one layout, so the mean of the parts is the
    batch's mean, in both terms). ``tokens`` is the host's [n, seq + 1]
    array and ``place`` puts one part's array on the device(s). -> (loss,
    gradient tree or None). The loss is on the device. The gradient is
    summed on the HOST, one part's leaves brought over and freed on the
    device before the next part runs."""
    n, seq = tokens.shape[0], tokens.shape[1] - 1
    if n % microbatch:
        raise ValueError(f"batch {n} is not a multiple of the reference's "
                         f"microbatch {microbatch}")
    parts = n // microbatch
    triples, image = positions(layout(model), seq)
    text = np.append(~image[1:], True).astype(np.float32)
    weights = np.broadcast_to(text, (microbatch, seq))
    fn = make(model, with_grad)
    total, grads = None, None
    for i in range(parts):
        rows = np.asarray(tokens[i * microbatch:(i + 1) * microbatch])
        out = fn(params, place(np.ascontiguousarray(rows[:, :-1])),
                 place(np.ascontiguousarray(rows[:, 1:])),
                 place(np.ascontiguousarray(weights)), triples)
        part = out[0][0] if with_grad else out[0]
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
