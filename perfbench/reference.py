"""The plain reference: GPT-2's forward pass, next-token cross-entropy and
its gradient in straightforward float32 ``jax.numpy``.

Independent of ``ray_tpu/models/gpt2.py``: it imports nothing from the
program and shares only the names of the parameter tree it is handed
(``wte``/``wpe``/``h_<i>``/``ln_f``, flax's ``kernel``/``bias``/``scale``/
``embedding``). No kernels, no chunked loss, no recomputation, no bfloat16.
It follows the published model (Radford et al. 2019; the released
``model.py``): learned position embeddings, pre-LayerNorm blocks, fused
``c_attn`` projection split into heads, causal softmax attention scaled by
1/sqrt(head size), a 4x MLP with the tanh approximation of GELU
("gelu_new"), a final LayerNorm and an output head tied to the token
embedding. One departure, noted in the configuration files: LayerNorm's
epsilon is whatever the configuration states it runs with.

On a TPU a float32 matrix multiplication runs in lower precision unless the
precision is raised, so every entry point runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, n_head):
    b, t, d = x.shape
    q, k, v = jnp.split(_dense(x, p["c_attn"]), 3, axis=-1)
    heads = lambda a: a.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return _dense(out.transpose(0, 2, 1, 3).reshape(b, t, d), p["c_proj"])


def logits(params, input_ids, *, n_layer, n_head, eps):
    t = input_ids.shape[1]
    x = params["wte"]["embedding"][input_ids] + params["wpe"]["embedding"][:t]
    for i in range(n_layer):
        p = params[f"h_{i}"]
        x = x + _attention(_layer_norm(x, p["ln_1"], eps), p["attn"], n_head)
        h = _gelu_new(_dense(_layer_norm(x, p["ln_2"], eps), p["mlp"]["c_fc"]))
        x = x + _dense(h, p["mlp"]["c_proj"])
    x = _layer_norm(x, params["ln_f"], eps)
    return x @ params["wte"]["embedding"].T


def loss(params, input_ids, labels, *, n_layer, n_head, eps):
    """Mean next-token cross-entropy over every position of the batch."""
    z = logits(params, input_ids, n_layer=n_layer, n_head=n_head, eps=eps)
    log_p = jax.nn.log_softmax(z, axis=-1)
    return -jnp.take_along_axis(log_p, labels[..., None], axis=-1).mean()


def _sizes(model: dict) -> dict:
    return {"n_layer": model["n_layer"], "n_head": model["n_head"],
            "eps": model["layer_norm_epsilon"]}


def _float32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def make_loss(model: dict):
    """Jitted (params, input_ids, labels) -> float32 loss."""
    sizes = _sizes(model)

    def fn(params, input_ids, labels):
        with jax.default_matmul_precision("highest"):
            return loss(_float32(params), input_ids, labels, **sizes)

    return jax.jit(fn)


def make_loss_and_grad(model: dict):
    """Jitted (params, input_ids, labels) -> (loss, gradient tree)."""
    sizes = _sizes(model)

    def fn(params, input_ids, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss)(_float32(params), input_ids,
                                            labels, **sizes)

    return jax.jit(fn)


def over_microbatches(model: dict, params, tokens, microbatch: int,
                      with_grad: bool, place):
    """The reference over the whole step batch, ``microbatch`` sequences at
    a time (equal parts, so the mean of the parts is the batch's mean).
    ``tokens`` is the host's [n, seq + 1] array and ``place`` puts one
    part's array on the device(s).
    -> (loss, gradient tree or None), both on the device."""
    n = tokens.shape[0]
    if n % microbatch:
        raise ValueError(f"batch {n} is not a multiple of the reference's "
                         f"microbatch {microbatch}")
    parts = n // microbatch
    fn = make_loss_and_grad(model) if with_grad else make_loss(model)
    total, grads = None, None
    for i in range(parts):
        rows = tokens[i * microbatch:(i + 1) * microbatch]
        out = fn(params, place(rows[:, :-1]), place(rows[:, 1:]))
        part, g = out if with_grad else (out, None)
        total = part if total is None else total + part
        if with_grad:
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    if with_grad:
        grads = jax.tree.map(lambda a: a / parts, grads)
    return total / parts, grads


@jax.jit
def compare_gradients(system, ref):
    """-> (|system|, |reference|, cosine) over the whole trees. Sums of
    elementwise products: a dot product would run at the TPU's default
    (bfloat16) matmul precision."""
    pairs = list(zip(jax.tree.leaves(system), jax.tree.leaves(ref)))
    dot = sum(jnp.sum(a * b) for a, b in pairs)
    ns = jnp.sqrt(sum(jnp.sum(a * a) for a, _ in pairs))
    nr = jnp.sqrt(sum(jnp.sum(b * b) for _, b in pairs))
    return ns, nr, dot / (ns * nr)
