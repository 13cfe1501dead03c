"""A decoder that mixes window and full attention over grouped heads, gates
its attention output and routes its feed-forward part to experts (the
``afmoe`` layer equations, at whatever sizes the config gives), for
training.

- Two kinds of attention layer in one stack, by ``layer_types``:
  ``sliding_attention`` layers see the last ``sliding_window`` keys, their
  own position among them, and carry rotary positions (halves rotated, all
  ``head_dim`` dimensions); ``full_attention`` layers see every earlier key
  and carry no positional term. ``num_attention_heads`` query heads read
  ``num_key_value_heads`` key-value heads, query head j the head ``j //
  group``: ``ops.attention.causal_self_attention`` takes both, and its kernel
  repeats no key or value in memory; at this family's published head width
  of 128 and past 2,048 tokens the kernel writes its output into, and reads
  that output's cotangent from, the [B, T, H x 128] array that the gate and
  ``o_proj`` read, and writes dK and dV as [B, T, H_kv x 128].
  Each head's queries and keys pass an
  RMSNorm over ``head_dim`` (one scale vector each, shared by the heads)
  before the rotation: the layer calls
  ``ops.attention.normed_rotary_self_attention``, which at that width and
  length on a TPU makes both in ``ops/rotary.py``'s kernels, from the
  projections' own arrays to the flash kernels' operands and from their
  float32 dQ^T back, and elsewhere in ``jnp``;
  the attention output is multiplied by ``sigmoid(x W_g)`` before ``W_o``.
- A block has four norms: ``h = h + N2(Attn(N1(h)))``, ``h = h + N4(F(N3(h)))``.
  ``F`` is a dense SwiGLU in the first ``num_dense_layers`` layers and the
  routed-expert layer of ``models/mla_moe.py`` (``RoutedExperts``: sigmoid
  scores, a selection bias, ``num_experts_per_tok`` experts a token, one
  shared expert, the slice ``expert_shard`` of the experts held here and
  no pair dropped) in every later one.
- The embedding is scaled by ``sqrt(hidden_size)``; the head is untied; the
  loss is the mean next-token cross-entropy (``ops.xent.chunked_xent``).

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic and the norms' statistics are float32. The selection bias
is a parameter that takes a zero gradient (its balance update is a training
recipe, not part of the model), and there is no auxiliary loss.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import (RMSNorm, RMSNormScale, SwiGLU,
                                  rope_frequencies)
from ray_tpu.models.mla_moe import RoutedExperts, held_expert_load
from ray_tpu.ops import xent
from ray_tpu.ops.attention import normed_rotary_self_attention
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes, replicated

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys under their published names. ``num_experts`` is
    the router's width, all experts of the model; ``expert_shard`` says
    which slice of them this program holds. ``layer_types`` names each
    layer's attention; left empty it is the published rule, a full layer
    every ``global_attn_every_n_layers``."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_dense_layers: int = 2
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    route_norm: bool = True
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Tuple[str, ...] = ()
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)   # (index, of)
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits and ``xent.fused_xent``

    def __post_init__(self):
        index, of = self.expert_shard
        assert 0 <= index < of and self.num_experts % of == 0, (
            self.expert_shard, self.num_experts)
        assert self.num_attention_heads % self.num_key_value_heads == 0
        if not self.layer_types:
            every = self.global_attn_every_n_layers
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % every == 0 else WINDOW
                for i in range(self.num_hidden_layers)))
        assert len(self.layer_types) == self.num_hidden_layers and set(
            self.layer_types) <= {WINDOW, FULL}, self.layer_types

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_shard[1]

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    intermediate_size=128, moe_intermediate_size=32,
                    num_dense_layers=1, num_experts=8, num_experts_per_tok=3,
                    sliding_window=8, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: AfmoeConfig):
    return nn.initializers.normal(c.initializer_range)


def rotate_halves(x, cos, sin):
    """x [B, T, H, D] turned by the position's angles, the first half of D
    against the second (dimension i with i + D/2); cos, sin [B, T, D/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


class GatedAttention(nn.Module):
    """One attention layer; ``window`` None is a full layer."""
    config: AfmoeConfig
    window: Any = None

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        B, T, _ = x.shape
        H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=c.dtype,
                                         kernel_init=_init(c), name=name)
        scale = lambda name: RMSNormScale(D, name=name)()
        q = on_batch_axes(dense(H * D, "q_proj")(x).reshape(B, T, H, D))
        k = on_batch_axes(dense(G * D, "k_proj")(x).reshape(B, T, G, D))
        v = on_batch_axes(dense(G * D, "v_proj")(x).reshape(B, T, G, D))
        gate = dense(H * D, "gate_proj")(x)
        # each head's q and k normed over its width; turned in the window
        # layers alone
        cos, sin = (None, None) if self.window is None else (
            rope_frequencies(D, positions, c.rope_theta))
        y = normed_rotary_self_attention(
            q, k, v, scale("q_norm"), scale("k_norm"), cos, sin,
            eps=c.rms_norm_eps, attention=c.attention, window=self.window)
        y = on_batch_axes(y.reshape(B, T, H * D)) * jax.nn.sigmoid(gate)
        return dense(c.hidden_size, "o_proj")(y)


class Block(nn.Module):
    """-> (x, tokens per held expert; of length 0 in a dense layer)."""
    config: AfmoeConfig
    dense: bool = False
    window: Any = None

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        with device_scope("mixer"):
            attended = GatedAttention(c, self.window, name="attn")(
                norm("input_norm")(x), positions)
            x = on_batch_axes(x + norm("post_attn_norm")(attended))
        with device_scope("mlp" if self.dense else "experts"):
            h = norm("pre_mlp_norm")(x)
            if self.dense:
                y, tokens = SwiGLU(c.intermediate_size, c.dtype, _init(c),
                                   name="mlp")(h), jnp.zeros((0,), jnp.int32)
            else:
                y, tokens = RoutedExperts(
                    experts=c.num_experts, expert_shard=c.expert_shard,
                    width=c.moe_intermediate_size,
                    per_token=c.num_experts_per_tok, scale=c.route_scale,
                    normalize=c.route_norm, shared=c.num_shared_experts,
                    dtype=c.dtype, kernel_init=_init(c), name="moe")(h)
            return on_batch_axes(x + norm("post_mlp_norm")(y)), tokens


class Afmoe(nn.Module):
    config: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids):
        """-> (hidden [B, T, d] after the final norm, tokens [expert
        layers, held]). The head's matrix is the parameter ``lm_head``,
        [V, d]."""
        c = self.config
        _, T = input_ids.shape
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        positions = jnp.arange(T)[None, :]   # one table for every row
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x = on_batch_axes(embed(input_ids) * math.sqrt(c.hidden_size))
        tokens = []
        for i, kind in enumerate(c.layer_types):
            dense = i < c.num_dense_layers
            window = c.sliding_window if kind == WINDOW else None
            x, n = block(c, dense, window, name=f"layers_{i}")(x, positions)
            if not dense:
                tokens.append(n)
        tokens = (jnp.stack(tokens) if tokens
                  else jnp.zeros((0, c.experts_held), jnp.int32))
        return RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x), tokens


def loss_fn(params, model, batch):
    """-> (loss, {"tokens_per_expert"}) over ``batch = {"input_ids",
    "labels"}`` (and an optional ``mask``): the mean next-token
    cross-entropy through the untied head."""
    c = model.config
    hidden, tokens = model.apply({"params": params}, batch["input_ids"])
    head, labels, mask = params["lm_head"], batch["labels"], batch.get("mask")
    with device_scope("vocab"):
        if c.loss_chunks:
            loss = xent.chunked_xent(hidden, head, labels, mask,
                                     n_chunks=c.loss_chunks)
        else:
            loss = xent.fused_xent(hidden @ head.T.astype(hidden.dtype),
                                   labels, mask)
    return loss, {"tokens_per_expert": tokens}


def init_params(config: AfmoeConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Afmoe(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Afmoe(dataclasses.replace(config, remat=False, attention="xla"))
    return model, init.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


def make_train_state(config: AfmoeConfig, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss,
    tokens_per_expert)``: ``parallel.build_train_step`` over this model's
    loss and its auxiliary output."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate,
        has_aux=True)


def param_shardings(params, mesh):
    """The rule for this model's parameters on ``mesh``: replicated, as
    ``mla_moe.param_shardings`` and for its reasons."""
    return jax.tree.map(lambda _: replicated(mesh), params)


def shard_train_state(params, opt_state, mesh):
    return train_step.place_train_state(
        params, opt_state, param_shardings(params, mesh))


def step_metrics(loss, tokens_per_expert, *, pairs=None) -> dict:
    """What a loop hands ``train.report`` after a step of
    ``build_train_step``: the loss and the held experts' load
    (``mla_moe.held_expert_load``; ``pairs`` is the step's tokens x
    ``num_experts_per_tok``), and the same as one ``counters`` record
    ``train/step_aux`` in the step observatory. Reads both results back to
    the host, in one round: call it where the loop reads its loss."""
    loss, tokens_per_expert = jax.device_get((loss, tokens_per_expert))
    metrics = {"loss": float(loss),
               **held_expert_load(tokens_per_expert, pairs)}
    steptrace.record_counters("train/step_aux", metrics)
    return metrics
