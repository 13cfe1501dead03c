"""A latent-attention, routed-expert decoder with a multi-token-prediction
module (the DeepSeek-V3 layer equations, at whatever sizes the config
gives), for training.

- Attention is multi-head latent attention in its per-head (unabsorbed)
  training form: queries through a low-rank bottleneck, keys and values
  decompressed from one latent per token, a rotary key of
  ``qk_rope_head_dim`` that all heads share. A head's keys are
  ``qk_nope_head_dim + qk_rope_head_dim`` wide and its values
  ``v_head_dim``: ``ops.attention.causal_self_attention`` chooses the
  kernel by those widths. The absorbed form and the latent cache are
  serving's and are not here.
- The first ``first_k_dense_replace`` layers have a dense SwiGLU; every
  later layer routes each token to ``num_experts_per_tok`` of
  ``n_routed_experts`` experts by sigmoid scores plus a selection bias
  (``ops.moe.topk_routing``) and adds ``n_shared_experts`` shared ones. A
  chip holds the slice ``expert_shard = (index, of)`` of the routed experts,
  stacked on an axis, routes over all of them and computes its own experts'
  part, dropping no pair (``ops.moe.held_expert_ffn``). What the absent
  experts would add is left out: on one chip the layer runs without its
  exchange.
- One prediction module (``num_nextn_predict_layers`` = 1): the trunk's
  output at position i and the embedding of token i + 1, each normed,
  concatenated and projected, go through one more expert layer, a norm and
  the shared head to predict token i + 2. The loss is ``main +
  mtp_loss_weight * mtp``; both terms walk the untied head
  (``ops.xent.chunked_xent``).

Parameters are float32, compute is ``dtype``; the router's scores and every
softmax statistic are float32. The selection bias is a parameter that takes
a zero gradient (its balance update is a training recipe, not part of the
model).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import (ReLU2, RMSNorm, SwiGLU, apply_rope,
                                  rope_frequencies)
from ray_tpu.ops import moe, xent
from ray_tpu.ops.attention import causal_self_attention
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes, replicated


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    """The published keys under their published names. ``n_routed_experts``
    is the router's width, all experts of the model; ``expert_shard`` says
    which slice of them this program holds."""
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    mtp_loss_weight: float = 0.3
    expert_shard: Tuple[int, int] = (0, 1)   # (index, of)
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits and ``xent.fused_xent``

    def __post_init__(self):
        index, of = self.expert_shard
        assert 0 <= index < of and self.n_routed_experts % of == 0, (
            self.expert_shard, self.n_routed_experts)
        assert self.num_nextn_predict_layers in (0, 1)

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.expert_shard[1]

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    intermediate_size=128, moe_intermediate_size=32,
                    n_routed_experts=8, num_experts_per_tok=3,
                    rope_theta=10000.0, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: MLAMoEConfig):
    return nn.initializers.normal(c.initializer_range)


class LatentAttention(nn.Module):
    config: MLAMoEConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        B, T, _ = x.shape
        H, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=c.dtype,
                                         kernel_init=_init(c), name=name)
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        q = dense(H * (nope + rope), "q_b_proj")(
            norm("q_a_norm")(dense(c.q_lora_rank, "q_a_proj")(x)))
        q = on_batch_axes(q.reshape(B, T, H, nope + rope))
        latent = dense(c.kv_lora_rank + rope, "kv_a_proj")(x)
        c_kv, k_rope = jnp.split(latent, [c.kv_lora_rank], axis=-1)
        kv = dense(H * (nope + c.v_head_dim), "kv_b_proj")(
            norm("kv_a_norm")(c_kv))
        kv = on_batch_axes(kv.reshape(B, T, H, nope + c.v_head_dim))
        k_nope, v = jnp.split(kv, [nope], axis=-1)
        cos, sin = rope_frequencies(rope, positions, c.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], axis=-1)
        # one rotary key a token, shared by every head
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, T, H, rope))], axis=-1)
        y = causal_self_attention(q, k, v, c.attention)
        y = on_batch_axes(y.reshape(B, T, H * c.v_head_dim))
        return dense(c.hidden_size, "o_proj")(y)


class RoutedExperts(nn.Module):
    """The expert feed-forward part: a router over all ``experts``, the share
    ``expert_shard = (index, of)`` held here, ``shared`` shared experts on
    every token (0: none, and no parameter of one), multiplied by
    ``sigmoid(x w_g)`` where ``shared_gate`` (a parameter ``shared_gate``
    [d, 1]; off, there is none); SwiGLUs of ``width``, or with
    ``activation="relu2"`` un-gated ``relu(x W_i)^2 W_o`` of ``width`` (the
    shared expert of the same form), weighted as ``ops.moe.topk_routing``
    says of ``score``. -> (y, tokens a held expert)."""
    experts: int
    expert_shard: Tuple[int, int]
    width: int
    per_token: int
    scale: float
    normalize: bool
    shared: int
    dtype: Any
    kernel_init: Any
    eps: float = 1e-20
    score: str = "sigmoid"
    shared_gate: bool = False
    activation: str = "swiglu"

    @nn.compact
    def __call__(self, x):
        (B, T, d), (index, of) = x.shape, self.expert_shard
        gated = self.activation == "swiglu"
        held, width, init = self.experts // of, self.width, self.kernel_init
        router = self.param("router", init, (d, self.experts))
        bias = self.param("router_bias", nn.initializers.zeros,
                          (self.experts,))
        wi = self.param("experts_wi", init,
                        (held, d, 2 * width if gated else width))
        wo = self.param("experts_wo", init, (held, width, d))
        shared = (SwiGLU if gated else ReLU2)(
            width * self.shared, self.dtype, init,
            name="shared_experts") if self.shared else None
        flat = x.reshape(B * T, d)
        experts, weights = moe.topk_routing(
            flat, router, bias, self.per_token, self.scale, self.normalize,
            self.eps, self.score)
        y, tokens = moe.held_expert_ffn(flat, experts, weights, wi, wo,
                                        index=index, of=of,
                                        activation=self.activation)
        if shared is None:   # the routed part alone
            return y.reshape(B, T, d), tokens
        if not self.shared_gate:
            return shared(x) + y.reshape(B, T, d), tokens
        gate = jax.nn.sigmoid(jnp.dot(
            x, self.param("shared_gate", init, (d, 1)).astype(self.dtype),
            preferred_element_type=jnp.float32))
        return ((gate * shared(x)).astype(self.dtype) + y.reshape(B, T, d),
                tokens)


class Block(nn.Module):
    """-> (x, tokens per held expert; of length 0 in a dense layer)."""
    config: MLAMoEConfig
    dense: bool = False

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        with device_scope("mixer"):
            x = on_batch_axes(x + LatentAttention(c, name="attn")(
                norm("input_norm")(x), positions))
        with device_scope("mlp" if self.dense else "experts"):
            h = norm("post_attn_norm")(x)
            if self.dense:
                y, tokens = SwiGLU(c.intermediate_size, c.dtype, _init(c),
                                   name="mlp")(h), jnp.zeros((0,), jnp.int32)
            else:
                y, tokens = RoutedExperts(
                    experts=c.n_routed_experts, expert_shard=c.expert_shard,
                    width=c.moe_intermediate_size,
                    per_token=c.num_experts_per_tok,
                    scale=c.routed_scaling_factor, normalize=c.norm_topk_prob,
                    shared=c.n_shared_experts, dtype=c.dtype,
                    kernel_init=_init(c), name="moe")(h)
            return on_batch_axes(x + y), tokens


class MLAMoE(nn.Module):
    config: MLAMoEConfig

    @nn.compact
    def __call__(self, input_ids, next_ids=None):
        """-> (hidden [B, T, d] after the final norm, the prediction
        module's hidden or None, tokens [expert layers, held]). With
        ``next_ids`` [B, T], the token after each position (the labels), the
        prediction module runs: its output at position i predicts token
        i + 2. The head's matrix is the parameter ``lm_head``, [V, d]."""
        c = self.config
        B, T = input_ids.shape
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens = on_batch_axes(embed(input_ids)), []
        for i in range(c.num_hidden_layers):
            dense = i < c.first_k_dense_replace
            x, n = block(c, dense, name=f"layers_{i}")(x, positions)
            if not dense:
                tokens.append(n)
        hidden, predicted = norm("norm")(x), None
        if next_ids is not None and c.num_nextn_predict_layers:
            with device_scope("vocab"):
                ahead = embed(next_ids)
            # the prediction module's way in: a dense projection
            with device_scope("mlp"):
                joined = jnp.concatenate(
                    [norm("mtp_enorm")(ahead), norm("mtp_hnorm")(hidden)],
                    axis=-1)
                z = nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype,
                             kernel_init=_init(c), name="mtp_eh_proj")(joined)
            z, n = block(c, False, name="mtp_block")(on_batch_axes(z),
                                                     positions)
            tokens.append(n)
            predicted = norm("mtp_norm")(z)
        tokens = (jnp.stack(tokens) if tokens
                  else jnp.zeros((0, c.experts_held), jnp.int32))
        return hidden, predicted, tokens


def second_term_targets(labels, mask=None):
    """-> (targets, mask) of the prediction module: position i holds token
    i + 2, the labels shifted once more; the last position has none and is
    masked, with whatever ``mask`` [B, T] already leaves out."""
    shift = lambda a: jnp.concatenate(
        [a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1)
    keep = jnp.ones(labels.shape, jnp.float32).at[:, -1].set(0.0)
    if mask is not None:
        keep = keep * shift(mask).astype(jnp.float32)
    return shift(labels), keep


def loss_fn(params, model, batch):
    """-> (loss, {"main", "mtp", "tokens_per_expert"}) over ``batch =
    {"input_ids", "labels"}`` (and an optional ``mask``): ``main +
    mtp_loss_weight * mtp``, both through the one untied head."""
    c = model.config
    labels, mask = batch["labels"], batch.get("mask")
    hidden, predicted, tokens = model.apply(
        {"params": params}, batch["input_ids"],
        labels if c.num_nextn_predict_layers else None)
    head = params["lm_head"]

    def term(h, targets, weights):
        if c.loss_chunks:
            return xent.chunked_xent(h, head, targets, weights,
                                     n_chunks=c.loss_chunks)
        with device_scope("vocab"):
            logits = h @ head.T.astype(h.dtype)
        return xent.fused_xent(logits, targets, weights)

    main = term(hidden, labels, mask)
    mtp = jnp.float32(0.0)
    if predicted is not None:
        mtp = term(predicted, *second_term_targets(labels, mask))
    return main + c.mtp_loss_weight * mtp, {
        "main": main, "mtp": mtp, "tokens_per_expert": tokens}


def init_params(config: MLAMoEConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = MLAMoE(config)
    dummy = jnp.zeros((1, 8), jnp.int32)
    # parameter shapes do not depend on recomputation or on the path
    init = MLAMoE(dataclasses.replace(config, remat=False, attention="xla"))
    return model, init.init(rng, dummy, dummy)["params"]


def make_train_state(config: MLAMoEConfig, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss,
    main, mtp, tokens_per_expert)``: ``parallel.build_train_step`` over
    this model's loss and its auxiliary output."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate,
        has_aux=True)


def param_shardings(params, mesh):
    """The rule for this model's parameters on ``mesh``: replicated. The
    routed experts a program holds are its share of a wider deployment
    already; an ``expert`` axis inside one program (with its exchange) and a
    split of the state over an ``fsdp`` axis are not here."""
    return jax.tree.map(lambda _: replicated(mesh), params)


def shard_train_state(params, opt_state, mesh):
    return train_step.place_train_state(
        params, opt_state, param_shardings(params, mesh))


def held_expert_load(tokens_per_expert, pairs=None) -> dict:
    """What the expert layers' row buffers held in a step, from the tokens
    each held expert received [expert layers, held]: the most and the mean
    tokens of any held expert, ``rows_present``, the pairs that fell on
    held experts, ``rows_buffered``, the lengths the layers took for them
    (``ops.moe.row_buffer_rung``, which the layer itself asks), both summed
    over the expert layers, and their quotient ``rows_fill``. ``pairs`` is
    the step's tokens x experts a token, the most a layer can hold, which
    the experts' counts do not tell: without it ``rows_present`` alone.
    Empty for a model with no expert layer."""
    import numpy as np

    tokens = np.asarray(tokens_per_expert)
    if not tokens.size:
        return {}
    present = tokens.sum(axis=-1)
    load = {"expert_tokens_max": int(tokens.max()),
            "expert_tokens_mean": float(tokens.mean()),
            "rows_present": int(present.sum())}
    if pairs is not None:
        buffered = np.asarray(moe.row_buffer_rungs(pairs))[
            moe.row_buffer_rung(present, pairs)]
        load.update(rows_buffered=int(buffered.sum()),
                    rows_fill=float(present.sum() / buffered.sum()))
    return load


def step_metrics(loss, main, mtp, tokens_per_expert, *, pairs=None) -> dict:
    """What a loop hands ``train.report`` after a step of
    ``build_train_step``: the loss, its two terms and the held experts'
    load (``held_expert_load``; ``pairs`` is the step's tokens x
    ``num_experts_per_tok``). The same numbers go to the step observatory
    as one ``counters`` record ``train/step_aux`` under the step they
    belong to. Reads the four results back to the host: call it where the
    loop reads its loss."""
    metrics = {"loss": float(loss), "loss_main": float(main),
               "loss_mtp": float(mtp),
               **held_expert_load(tokens_per_expert, pairs)}
    steptrace.record_counters("train/step_aux", metrics)
    return metrics
