"""The plain reference of the ``phi4flash`` family (state-space layers,
window, full and cross differential attention, a gated memory unit): the
forward pass, the loss and its gradient in straightforward float32
``jax.numpy``.

Independent of ``ray_tpu/models/phi4flash.py`` and ``ray_tpu/ops``: it
imports nothing from the program and shares only the names of the parameter
tree it is handed. No kernel, no chunked scan with boundary states of its
own derivative rule, no chunked loss walk, no bfloat16. It follows the layer
equations the configuration file states (its published keys and what it
lists under ``assumed``):

- embedding ``h = E[t]``; a block ``h = h + Mix(LN1(h)); h = h + MLP(LN2(h))``
  with LayerNorms of scale and bias; ``MLP(x) = (silu(g) * u) W_down``,
  ``[g | u] = x W_up``;
- the kind of layer ``i`` of ``L`` published layers: ``i >= L/2 + 2``: a
  cross layer if odd, a gated memory unit if even; else state-space if
  even, and if odd the full layer at ``i = L/2 + 1``, a window layer
  before;
- state-space: ``[x | z] = u W_in``; ``x = silu(conv(x) + b)``, causal,
  depthwise, ``d_conv`` taps; ``[dt | B | C] = x W_x``; ``delta =
  softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(delta_t A)
  h_{t-1} + (delta_t x_t) B_t^T``; ``y_t = h_t C_t + D x_t``; out ``(y *
  silu(z)) W_out``; layer ``L/2`` hands ``y`` down as the memory ``M``;
- differential attention: ``q = x Wq + b`` [T, H, d], ``k``, ``v`` [T, G,
  d]; query heads (2j, 2j + 1) are (q1_j, q2_j), key-value heads (2m, 2m +
  1) give (k1_m, k2_m) and ``V_m = [v1_m | v2_m]``, query pair j reads pair
  ``j // (H / G)``; ``A1 = softmax(mask(q1 k1^T / sqrt(d)))``, ``A2``
  likewise; ``o_j = RMSNorm((A1 - lam A2) V_m) (1 - lam_init)`` with a
  learned scale over the 2 d; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 i)``; then ``Wo + b``. Causal;
  in a window layer query i sees keys j with ``0 <= i - j <
  sliding_window``. The full layer (``L/2 + 1``) hands k and v down; a cross
  layer has ``Wq`` and ``Wo`` only and reads them;
- gated memory unit: ``(M * silu(x W1)) W2``;
- final LayerNorm, the head tied to the embedding, mean next-token
  cross-entropy.

Departures, each of which changes no value that is compared:

- it is given the same share of the deployment as the program: the layers
  ``kept_layers`` of the published stack and the sliced vocabulary;
- the recurrence is one ``lax.scan`` over the positions, in chunks of
  ``SCAN_CHUNK`` whose steps are recomputed in the backward pass
  (``jax.checkpoint``: a [T, channels, states] float32 array of every state
  is 5.4 GB a layer at the timed size); attention runs in blocks of queries
  against all keys, a window layer's too (the mask alone tells the kinds
  apart); the feed-forward part and the loss run in blocks of positions;
  with a gradient asked for, layers and blocks are recomputed in the
  backward pass, so that one 16,384-token sequence's gradient fits beside
  the state it is compared with.

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
ROW_BLOCK = 2048
SCAN_CHUNK = 128


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _linear(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _by_rows(fn, x, remat):
    """``fn`` over x [b, t, d], ``ROW_BLOCK`` positions at a time."""
    b, t, d = x.shape
    block = min(ROW_BLOCK, t)
    if t % block:
        return fn(x)
    if remat:
        fn = jax.checkpoint(fn)
    out = jax.lax.map(fn, x.reshape(b, t // block, block, d).swapaxes(0, 1))
    return out.swapaxes(0, 1).reshape(b, t, -1)


def _mlp(x, p):
    gate, up = jnp.split(_linear(x, p["up_proj"]), 2, axis=-1)
    return _linear(jax.nn.silu(gate) * up, p["down_proj"])


def layer_kind(i: int, layers: int) -> str:
    half = layers // 2
    if i >= half + 2:
        return "cross" if i % 2 else "gmu"
    if i % 2 == 0:
        return "ssm"
    return "full" if i == half + 1 else "window"


def _recurrence(x, delta, a, b, c, remat):
    """y [bt, t, D] of the recurrence without the skip term: x, delta
    [bt, t, D], a [D, N], b, c [bt, t, N]; one position a step."""

    def step(h, inputs):
        x_t, d_t, b_t, c_t = inputs
        h = (jnp.exp(d_t[:, :, None] * a) * h
             + (d_t * x_t)[:, :, None] * b_t[:, None, :])
        return h, (h * c_t[:, None, :]).sum(-1)

    def chunk(h, inputs):
        return jax.lax.scan(step, h, inputs)

    if remat:
        chunk = jax.checkpoint(chunk)
    bt, t, d = x.shape
    size = SCAN_CHUNK if t % SCAN_CHUNK == 0 else t
    by_chunk = lambda v: v.swapaxes(0, 1).reshape(
        t // size, size, bt, v.shape[-1])
    h0 = jnp.zeros((bt, d, a.shape[1]), jnp.float32)
    _, y = jax.lax.scan(chunk, h0, tuple(map(by_chunk, (x, delta, b, c))))
    return y.reshape(t, bt, d).swapaxes(0, 1)


def _state_space(u, p, m, remat):
    """-> (the layer's output, y before the gate)."""
    t, n, rank = u.shape[1], m["d_state"], m["dt_rank"]
    x, z = jnp.split(_linear(u, p["in_proj"]), 2, axis=-1)
    taps = p["conv_weight"]                                  # [d_conv, D]
    padded = jnp.pad(x, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[:, k:k + t] * taps[k]
                        for k in range(taps.shape[0])) + p["conv_bias"])
    proj = _linear(x, p["x_proj"])
    dt, b, c = proj[..., :rank], proj[..., rank:rank + n], proj[..., rank + n:]
    delta = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), b, c, remat) + p["D"] * x
    return _linear(y * jax.nn.silu(z), p["out_proj"]), y


def _attend(q, k, v, window, remat):
    """Causal softmax attention of q [b, t, h, d] over k [b, t, g, d] and v
    [b, t, g, dv], query head j on key-value head j // (h / g), one block
    of queries at a time against every key; under ``window`` a query sees
    its own position and the ``window - 1`` before it."""
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


def _diff_attention(x, p, m, index, window, kv, remat):
    """-> (output, (k, v)): ``kv`` given, a cross layer reads it."""
    b, t, _ = x.shape
    h, g = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // h
    q = _linear(x, p["q_proj"]).reshape(b, t, h // 2, 2, d)
    if kv is None:
        kv = (_linear(x, p["k_proj"]), _linear(x, p["v_proj"]))
    keys = kv[0].reshape(b, t, g // 2, 2, d)
    values = kv[1].reshape(b, t, g // 2, 2 * d)
    signal = _attend(q[:, :, :, 0], keys[:, :, :, 0], values, window, remat)
    noise = _attend(q[:, :, :, 1], keys[:, :, :, 1], values, window, remat)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp((p["lambda_q1"] * p["lambda_k1"]).sum())
           - jnp.exp((p["lambda_q2"] * p["lambda_k2"]).sum()) + lam_init)
    mixed = signal - lam * noise                             # [b, t, h/2, 2d]
    normed = mixed * jax.lax.rsqrt(
        (mixed * mixed).mean(-1, keepdims=True) + m["layer_norm_eps"])
    out = normed * p["subln"]["scale"] * (1.0 - lam_init)
    return _linear(out.reshape(b, t, h * d), p["o_proj"]), kv


def _block(h, side, p, *, m, index, kind, remat):
    """-> (h, what the layer hands down or None)."""
    eps = m["layer_norm_eps"]
    u, handed = _layer_norm(h, p["ln1"], eps), None
    if kind == "ssm":
        mixed, handed = _state_space(u, p["mixer"], m, remat)
    elif kind == "gmu":
        gate = jax.nn.silu(_linear(u, p["mixer"]["in_proj"]))
        mixed = _linear(side * gate, p["mixer"]["out_proj"])
    else:
        mixed, kv = _diff_attention(
            u, p["mixer"], m, index,
            m["sliding_window"] if kind == "window" else None,
            side if kind == "cross" else None, remat)
        handed = kv if kind == "full" else None
    h = h + mixed
    mlp = functools.partial(_mlp, p=p["mlp"])
    return h + _by_rows(mlp, _layer_norm(h, p["ln2"], eps), remat), handed


def hidden_states(params, input_ids, *, m, remat=False):
    """[b, t, d] after the final norm."""
    layers = m["published"]["num_hidden_layers"]
    half = layers // 2
    h = params["embed"]["embedding"][input_ids]
    memory = keys_values = None
    for i in m["kept_layers"]:
        kind = layer_kind(i, layers)
        fn = functools.partial(_block, m=m, index=i, kind=kind, remat=remat)
        side = {"gmu": memory, "cross": keys_values}.get(kind)
        h, handed = (jax.checkpoint(fn) if remat else fn)(
            h, side, params[f"layers_{i}"])
        if i == half:
            memory = handed
        elif kind == "full":
            keys_values = handed
    return _layer_norm(h, params["norm"], m["layer_norm_eps"])


def logits(params, input_ids, *, m):
    """[b, t, vocab_size]: for the tests, at sizes where they fit."""
    return hidden_states(params, input_ids, m=m) \
        @ params["embed"]["embedding"].T


def _log_likelihood(hidden, head, targets):
    """Sum of log p(target) over the positions of hidden [n, d]."""
    log_p = jax.nn.log_softmax(hidden @ head.T, axis=-1)
    return jnp.take_along_axis(log_p, targets[:, None], axis=-1).sum()


def loss(params, input_ids, labels, *, m, remat=False):
    """The mean next-token cross-entropy over one batch [b, t], the
    positions taken ``ROW_BLOCK`` at a time."""
    hidden = hidden_states(params, input_ids, m=m, remat=remat)
    flat, targets = hidden.reshape(-1, hidden.shape[-1]), labels.reshape(-1)
    n = flat.shape[0]
    block = min(ROW_BLOCK, n)
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
    at the timed size one float32 gradient (2.79 GB) fits beside the state
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
