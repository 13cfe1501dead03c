"""A decoder most of whose layers mix tokens by a gated delta rule (linear
attention with a matrix state a head) and a few by gated softmax attention,
every layer with softmax-routed experts beside a gated shared expert (the
``qwen3_next`` layer equations, at whatever sizes the config gives), for
training.

- Every block is ``h = h + Mixer(N1(h)); h = h + F(N2(h))``. ``N`` is the
  zero-centred RMSNorm, ``x / rms(x) * (1 + w)`` with ``w`` initialised 0
  (``ZeroCentredRMSNorm``), also the final norm and the full layer's head
  norms. No biases anywhere. ``Mixer`` is, by the layer's entry in
  ``layer_types`` (``full_attention`` where ``(i + 1) %
  full_attention_interval == 0``):

  - ``linear_attention``: ``[q | k | v | z] = u W_qkvz`` and ``[b | a] = u
    W_ba``; ``[q | k | v]`` pass a depthwise causal convolution of
    ``linear_conv_kernel_dim`` taps and a SiLU (``ops.conv.causal_conv``:
    on a TPU the Pallas kernels ``causal_conv_fwd`` / ``causal_conv_bwd``,
    one pass over ``[q | k | v]`` each way, forward again where a block is
    recomputed; XLA's padded slices elsewhere);
    q and k are L2-normalised over each head's width, q scaled by
    width^-0.5; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
    dt_bias)`` in float32; ``o = ops.delta.gated_delta_rule(q, k, v, g,
    beta)``: a [d_k, d_v] state a value head, zero at a sequence's start,
    ``linear_num_key_heads`` key heads serving ``linear_num_value_heads``
    value heads; ``y = (o / rms(o) * w) * silu(z)`` over each head's width
    (a plain norm, ``w`` initialised 1); out ``y W_out``. The layer has no
    term in the square of the length.
  - ``full_attention``: ``q_proj`` gives a head its query and its gate ([T,
    H, 2 D]); ``N`` over D on q and on k (one scale vector each); rotary
    positions on the first ``partial_rotary_factor x D`` dimensions, halves
    of those rotated; ``num_attention_heads`` query heads on
    ``num_key_value_heads`` key-value heads
    (``ops.attention.causal_self_attention``); ``y = attn * sigmoid(gate)``;
    ``W_o``.

- ``F`` is ``models/mla_moe.py``'s ``RoutedExperts``: a softmax over all
  ``num_experts``, ``num_experts_per_tok`` a token, weights normalised over
  the chosen, the slice ``expert_shard`` of the experts held here, no pair
  dropped, plus the shared expert times ``sigmoid(x w_g)``. Its selection
  bias is zero in the published router.
- ``kept_layers`` names the published indices this program runs (all of
  ``layer_types`` if empty); parameters are named by the published index.
- The head is untied; the loss is the mean next-token cross-entropy
  (``ops.xent.chunked_xent``). The published multi-token-prediction module
  is not here.

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic, the norms' statistics, the rule's state and decays and
the convolution's sum over its taps are float32. Under ``remat`` a block is
recomputed in the backward pass from its input; the flash kernel's and the
rule's outputs are kept (``ops.remat.remat_policy``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
from ray_tpu.models.afmoe import rotate_halves, step_metrics  # noqa: F401
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import rope_frequencies
from ray_tpu.models.mla_moe import RoutedExperts
from ray_tpu.ops import xent
from ray_tpu.ops.attention import causal_self_attention
from ray_tpu.ops.conv import causal_conv
from ray_tpu.ops.delta import gated_delta_rule
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes, replicated

LINEAR, FULL = "linear_attention", "full_attention"
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published keys under their published names. ``num_experts`` is
    the router's width, all experts of the model; ``expert_shard`` says
    which slice of them this program holds. ``layer_types`` left empty is
    the published rule over ``num_hidden_layers``."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    layer_types: Tuple[str, ...] = ()
    kept_layers: Tuple[int, ...] = ()
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)   # (index, of)
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits and ``xent.fused_xent``

    def __post_init__(self):
        set_ = lambda name, value: object.__setattr__(self, name, value)
        every = self.full_attention_interval
        set_("layer_types", tuple(self.layer_types) or tuple(
            FULL if (i + 1) % every == 0 else LINEAR
            for i in range(self.num_hidden_layers)))
        set_("kept_layers", tuple(self.kept_layers)
             or tuple(range(len(self.layer_types))))
        index, of = self.expert_shard
        assert 0 <= index < of and self.num_experts % of == 0, (
            self.expert_shard, self.num_experts)
        assert set(self.layer_types) <= {LINEAR, FULL}, self.layer_types
        assert list(self.kept_layers) == sorted(set(self.kept_layers)) \
            and self.kept_layers[-1] < len(self.layer_types), self.kept_layers
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.linear_num_value_heads % self.linear_num_key_heads == 0
        assert (self.shared_expert_intermediate_size
                % self.moe_intermediate_size == 0)

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_shard[1]

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def layers(self) -> Tuple[Tuple[int, str], ...]:
        """(published index, kind) of the layers run."""
        return tuple((i, self.layer_types[i]) for i in self.kept_layers)

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=8,
                    kept_layers=(0, 1, 2, 3), num_attention_heads=4,
                    num_key_value_heads=2, head_dim=32,
                    linear_num_key_heads=2, linear_num_value_heads=4,
                    linear_key_head_dim=16, linear_value_head_dim=16,
                    moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: Qwen3NextConfig):
    return nn.initializers.normal(c.initializer_range)


def _dense(c, features, name):
    return nn.Dense(features, use_bias=False, dtype=c.dtype,
                    kernel_init=_init(c), name=name)


def _rms(x, eps):
    xf = x.astype(_F32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


class ZeroCentredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + w)``, ``w`` initialised 0; statistics float32."""
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    @device_scope("norm")
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        return (_rms(x, self.eps) * (1.0 + scale)).astype(self.dtype)


class GatedRMSNorm(nn.Module):
    """``(o / rms(o) * w) * silu(z)`` over the last axis, ``w`` initialised
    1; float32 until its one rounding."""
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, o, z):
        scale = self.param("scale", nn.initializers.ones, (o.shape[-1],))
        return (_rms(o, self.eps) * scale
                * jax.nn.silu(z.astype(_F32))).astype(self.dtype)


def _decay_init(key, shape, dtype=_F32):
    """``A_log``: the log of a uniform draw from (0, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def l2_normalised(x, eps: float = 1e-6):
    xf = x.astype(_F32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def rotate_part(x, cos, sin):
    """x [B, T, H, D] with its first ``2 x cos.shape[-1]`` dimensions turned
    by the position's angles (halves of those rotated), the rest as they
    are."""
    rotary = 2 * cos.shape[-1]
    return jnp.concatenate(
        [rotate_halves(x[..., :rotary], cos, sin), x[..., rotary:]], axis=-1)


class LinearAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, u):
        c = self.config
        B, T, _ = u.shape
        Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        keys, values = Hk * dk, Hv * dv
        qkvz = on_batch_axes(_dense(c, 2 * keys + 2 * values,
                                    "in_proj_qkvz")(u))
        ba = _dense(c, 2 * Hv, "in_proj_ba")(u).astype(_F32)
        taps = self.param("conv_weight", _init(c),
                          (c.linear_conv_kernel_dim, 2 * keys + values))
        decay = self.param("A_log", _decay_init, (Hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,))
        mixed = on_batch_axes(causal_conv(qkvz[..., :2 * keys + values],
                                          taps, jax.nn.silu))
        heads = lambda t, n, d: t.reshape(B, T, n, d)
        q = (l2_normalised(heads(mixed[..., :keys], Hk, dk))
             * dk ** -0.5).astype(c.dtype)
        k = l2_normalised(heads(mixed[..., keys:2 * keys], Hk, dk)).astype(
            c.dtype)
        v = heads(mixed[..., 2 * keys:], Hv, dv)
        z = heads(qkvz[..., 2 * keys + values:], Hv, dv)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(decay) * jax.nn.softplus(ba[..., Hv:] + dt_bias)
        o = gated_delta_rule(q, k, v, g, beta)
        y = GatedRMSNorm(c.rms_norm_eps, c.dtype, name="norm")(o, z)
        return _dense(c, c.hidden_size, "out_proj")(
            on_batch_axes(y.reshape(B, T, values)))


class GatedAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        B, T, _ = x.shape
        H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        norm = lambda name: ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype,
                                               name=name)
        both = on_batch_axes(_dense(c, H * 2 * D, "q_proj")(x).reshape(
            B, T, H, 2 * D))
        q, gate = both[..., :D], both[..., D:]
        k = on_batch_axes(_dense(c, G * D, "k_proj")(x).reshape(B, T, G, D))
        v = on_batch_axes(_dense(c, G * D, "v_proj")(x).reshape(B, T, G, D))
        q, k = norm("q_norm")(q), norm("k_norm")(k)
        cos, sin = rope_frequencies(c.rotary_dim, positions, c.rope_theta)
        q, k = rotate_part(q, cos, sin), rotate_part(k, cos, sin)
        y = causal_self_attention(q, k, v, c.attention)
        y = on_batch_axes((y * jax.nn.sigmoid(gate)).reshape(B, T, H * D))
        return _dense(c, c.hidden_size, "o_proj")(y)


class Block(nn.Module):
    """-> (x, tokens per held expert)."""
    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        norm = lambda name: ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype,
                                               name=name)
        with device_scope("mixer"):
            u = norm("input_norm")(x)
            if self.kind == LINEAR:
                mixed = LinearAttention(c, name="linear_attn")(u)
            else:
                mixed = GatedAttention(c, name="attn")(u, positions)
            x = on_batch_axes(x + mixed)
        with device_scope("experts"):
            y, tokens = RoutedExperts(
                experts=c.num_experts, expert_shard=c.expert_shard,
                width=c.moe_intermediate_size,
                per_token=c.num_experts_per_tok,
                scale=1.0, normalize=c.norm_topk_prob,
                shared=(c.shared_expert_intermediate_size
                        // c.moe_intermediate_size),
                dtype=c.dtype, kernel_init=_init(c), eps=0.0, score="softmax",
                shared_gate=True, name="moe")(norm("post_attn_norm")(x))
            return on_batch_axes(x + y), tokens


class Qwen3Next(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids):
        """-> (hidden [B, T, d] after the final norm, tokens [layers,
        held]). The head's matrix is the parameter ``lm_head``, [V, d]."""
        c = self.config
        B, T = input_ids.shape
        kinds = [kind for _, kind in c.layers]
        steptrace.record_counters("model/layer_kinds", {
            LINEAR: kinds.count(LINEAR), FULL: kinds.count(FULL),
            "expert": len(kinds), "layers": len(kinds),
            "published_layers": len(c.layer_types)})
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens = on_batch_axes(embed(input_ids)), []
        for i, kind in c.layers:
            x, n = block(c, kind, name=f"layers_{i}")(x, positions)
            tokens.append(n)
        return (ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x),
                jnp.stack(tokens))


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


def init_params(config: Qwen3NextConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Qwen3Next(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Qwen3Next(dataclasses.replace(config, remat=False,
                                         attention="xla"))
    return model, init.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


def make_train_state(config: Qwen3NextConfig, rng,
                     learning_rate: float = 3e-4, weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss,
    tokens_per_expert)``: ``parallel.build_train_step`` over this model's
    loss and its auxiliary output. What a loop reports of both is
    ``step_metrics`` (``models/afmoe.py``'s: the loss and the held experts'
    load, and one ``train/step_aux`` record)."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate,
        has_aux=True)


def param_shardings(params, mesh):
    """The rule for this model's parameters on ``mesh``: replicated, as
    ``afmoe.param_shardings`` (the batch alone is split)."""
    return jax.tree.map(lambda _: replicated(mesh), params)


def shard_train_state(params, opt_state, mesh):
    return train_step.place_train_state(
        params, opt_state, param_shardings(params, mesh))
