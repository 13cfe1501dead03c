"""The plain reference of the latent-attention, routed-expert family: the
forward pass, the two-term loss and its gradient in straightforward float32
``jax.numpy``.

Independent of ``ray_tpu/models/mla_moe.py``: it imports nothing from the
program and shares only the names of the parameter tree it is handed. No
kernel, no sort, no grouped matmul, no chunked loss, no bfloat16. It follows
the published description (DeepSeek-V3 technical report, sections 2.1.1,
2.1.2 and 2.2, at the sizes the configuration file gives):

- latent attention per head: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``;
  ``[c_kv, k_r] = x W_kva``, ``[k_nope, v] = RMSNorm(c_kv) W_kvb``; rotary
  embedding (interleaved pairs) on the last ``qk_rope_head_dim`` dimensions
  of each query head and on ``k_r``, which all heads share; causal softmax
  at scale ``(qk_nope + qk_rope)^-0.5``;
- layers below ``first_k_dense_replace``: a SwiGLU of ``intermediate_size``;
  the others: ``s = sigmoid(x W_r)``, the ``num_experts_per_tok`` experts
  with the largest ``s + b``, weights ``s_i / (sum of the chosen s + 1e-20)
  x routed_scaling_factor``, each expert a SwiGLU of
  ``moe_intermediate_size``, and the shared experts on every token;
- one prediction module: ``[RMSNorm(Emb(t_{i+1})); RMSNorm(h_i)] W_eh``, one
  more expert layer, a norm and the shared head predict ``t_{i+2}``;
  ``loss = main + mtp_loss_weight x mtp``, each a mean over its positions.

Departures, each of which changes no value that is compared:

- it is given the same share of the deployment as the program: the experts
  ``expert_shard.index`` of ``expert_shard.of`` (the routed result is the
  sum over the held experts only), the sliced vocabulary, the cut depth;
- every held expert is applied to all tokens and weighted by the token's
  weight for it, zero where the token did not choose it;
- attention runs in blocks of queries against all keys, and with a gradient
  asked for, layers, blocks of queries, experts and the two loss terms are
  recomputed in the backward pass (``jax.checkpoint``), so that an 8k
  sequence fits beside the state it is compared with;
- ``h_i`` is the trunk's output after its final norm, and the order of the
  two halves of ``W_eh``'s input is as above: the config gives neither
  (listed under ``assumed`` in the configuration file).

On a TPU a float32 matrix multiplication runs in lower precision unless the
precision is raised, so every entry point runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _swiglu(x, p):
    gate, up = x @ p["gate_proj"]["kernel"], x @ p["up_proj"]["kernel"]
    return (jax.nn.silu(gate) * up) @ p["down_proj"]["kernel"]


def _rope(x, theta):
    """x [b, t, h, r]: rotate the pairs (2i, 2i + 1) by position x
    theta^(-2i / r)."""
    t, r = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [t, r/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., ::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, remat):
    """Causal softmax attention, q / k [b, t, h, dk], v [b, t, h, dv], one
    block of queries at a time against every key."""
    b, t, h, dk = q.shape
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    key_pos = jnp.arange(t)

    def one(args):
        qb, start = args                                   # [b, block, h, dk]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * dk ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    if remat:
        one = jax.checkpoint(one)
    blocks = q.reshape(b, t // block, block, h, dk).swapaxes(0, 1)
    out = jax.lax.map(one, (blocks, jnp.arange(0, t, block)))
    return out.swapaxes(0, 1).reshape(b, t, h, v.shape[-1])


def _attention(x, p, m, remat):
    b, t, _ = x.shape
    h, nope, rope = (m["num_attention_heads"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"])
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    c_q = _rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_norm"], eps)
    q = (c_q @ p["q_b_proj"]["kernel"]).reshape(b, t, h, nope + rope)
    latent = x @ p["kv_a_proj"]["kernel"]
    c_kv, k_r = latent[..., :m["kv_lora_rank"]], latent[..., m["kv_lora_rank"]:]
    kv = (_rms_norm(c_kv, p["kv_a_norm"], eps) @ p["kv_b_proj"]["kernel"]
          ).reshape(b, t, h, nope + m["v_head_dim"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k_r = jnp.broadcast_to(_rope(k_r[:, :, None, :], theta), (b, t, h, rope))
    out = _attend(q, jnp.concatenate([k_nope, k_r], -1), v, remat)
    return out.reshape(b, t, h * m["v_head_dim"]) @ p["o_proj"]["kernel"]


def _experts(x, p, m, remat=False):
    """The held experts' part of the routed result and the shared experts."""
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
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    weights = picked * m["routed_scaling_factor"]
    def expert(x, wi, wo):
        gate, up = jnp.split(x @ wi, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wo

    if remat:
        expert = jax.checkpoint(expert)
    y = _swiglu(x, p["shared_experts"])
    for e in range(held):
        y = y + weights[..., first + e, None] * expert(
            x, p["experts_wi"][e], p["experts_wo"][e])
    return y


def _block(x, p, *, m, dense, remat):
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_norm"], eps), p["attn"], m, remat)
    h = _rms_norm(x, p["post_attn_norm"], eps)
    return x + (_swiglu(h, p["mlp"]) if dense
                else _experts(h, p["moe"], m, remat))


def _xent(hidden, head, targets, weights=None):
    log_p = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    ll = jnp.take_along_axis(log_p, targets[..., None], axis=-1)[..., 0]
    if weights is None:
        return -ll.mean()
    return -(ll * weights).sum() / weights.sum()


def loss(params, input_ids, labels, *, m, remat=False):
    """main + mtp_loss_weight x mtp over one batch [b, t]."""
    eps = m["rms_norm_eps"]

    def block(x, p, dense):
        fn = functools.partial(_block, m=m, dense=dense, remat=remat)
        return (jax.checkpoint(fn) if remat else fn)(x, p)

    xent = jax.checkpoint(_xent) if remat else _xent
    embed = params["embed"]["embedding"]
    x = embed[input_ids]
    for i in range(m["num_hidden_layers"]):
        x = block(x, params[f"layers_{i}"], i < m["first_k_dense_replace"])
    hidden = _rms_norm(x, params["norm"], eps)
    total = xent(hidden, params["lm_head"], labels)
    if m["num_nextn_predict_layers"]:
        joined = jnp.concatenate(
            [_rms_norm(embed[labels], params["mtp_enorm"], eps),
             _rms_norm(hidden, params["mtp_hnorm"], eps)], axis=-1)
        z = block(joined @ params["mtp_eh_proj"]["kernel"],
                  params["mtp_block"], False)
        z = _rms_norm(z, params["mtp_norm"], eps)
        # position i predicts token i + 2; the last position has no target
        targets = jnp.concatenate(
            [labels[:, 1:], jnp.zeros_like(labels[:, :1])], axis=1)
        keep = jnp.ones(labels.shape, jnp.float32).at[:, -1].set(0.0)
        total = total + m["mtp_loss_weight"] * xent(
            z, params["lm_head"], targets, keep)
    return total


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
    a time (equal parts, so the mean of the parts is the batch's mean: every
    sequence has as many positions in each term). ``tokens`` is the host's
    [n, seq + 1] array and ``place`` puts one part's array on the device(s).
    -> (loss, gradient tree or None). The loss is on the device. The
    gradient is summed on the HOST, one part's leaves brought over and freed
    on the device before the next part runs: at the timed size one part's
    float32 gradient (2.53 GiB) fits beside the state it is compared with
    and the backward pass's working set, two do not."""
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
