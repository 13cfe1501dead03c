"""A decoder most of whose layers mix tokens by a gated short convolution
and a few by grouped attention, with routed experts and no shared one (the
``lfm2_moe`` layer equations, at whatever sizes the config gives), for
training.

- Every block is ``h = h + Op(N1(h)); h = h + F(N2(h))``: RMSNorms with a
  learned scale, no biases anywhere. ``Op`` is, by the layer's entry in
  ``layer_types``:

  - ``conv``: ``[B | C | x] = u W_in`` (hidden -> 3 x hidden); ``y =
    ops.conv.gated_short_conv``: ``z = B * x``, a causal depthwise
    convolution of ``conv_L_cache`` taps over ``z`` (zeros before the
    sequence's first position, no bias, no activation), times ``C``; out
    ``y W_out``. The layer has no term in the square of the length and its
    state is ``conv_L_cache - 1`` rows of ``z``.
  - ``full_attention``: ``num_attention_heads`` query heads on
    ``num_key_value_heads`` key-value heads (query head j reads head ``j //
    group``), an RMSNorm over each head's width on q and on k (one scale
    vector each), rotary positions on the whole width (halves rotated),
    causal softmax attention (``ops.attention.causal_self_attention``),
    ``W_o``.

- ``F`` is a SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` of the layers run and ``models/mla_moe.py``'s
  ``RoutedExperts`` in the others: sigmoid scores over ``num_experts``, a
  selection bias, ``num_experts_per_tok`` a token, weights normalised over
  the chosen scores plus ``route_eps``, the slice ``expert_shard`` of the
  experts held here, no pair dropped, **no shared expert**.
- ``kept_layers`` names the published indices this program runs (all of
  ``layer_types`` if empty); parameters are named by the published index
  (``layers_2``).
- The head is tied to the embedding; a final RMSNorm; the loss is the mean
  next-token cross-entropy (``ops.xent.chunked_xent`` over the tied [V, d]).

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic, the convolution's sum over its taps and the norms'
statistics are float32. The selection bias is a parameter that takes a zero
gradient (its balance update is a training recipe), and there is no
auxiliary loss. Under ``remat`` a block is recomputed in the backward pass
from its input; the flash kernel's output is kept
(``ops.remat.remat_policy``), the convolution's is made again.
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
from ray_tpu.models.llama import RMSNorm, SwiGLU, rope_frequencies
from ray_tpu.models.mla_moe import RoutedExperts
from ray_tpu.ops import xent
from ray_tpu.ops.attention import causal_self_attention
from ray_tpu.ops.conv import gated_short_conv
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes, replicated

CONV, FULL = "conv", "full_attention"
# the published stack: two convolution layers, then attention every fourth
# layer, the last period one layer short
PUBLISHED_LAYER_TYPES = tuple(
    FULL if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The published keys under their published names. ``num_experts`` is
    the router's width, all experts of the model; ``expert_shard`` says
    which slice of them this program holds. ``num_dense_layers`` counts
    among the layers run."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    kept_layers: Tuple[int, ...] = ()
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    route_eps: float = 1e-6
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)   # (index, of)
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits and ``xent.fused_xent``

    def __post_init__(self):
        set_ = lambda name, value: object.__setattr__(self, name, value)
        set_("layer_types", tuple(self.layer_types))
        set_("kept_layers", tuple(self.kept_layers)
             or tuple(range(len(self.layer_types))))
        index, of = self.expert_shard
        assert 0 <= index < of and self.num_experts % of == 0, (
            self.expert_shard, self.num_experts)
        assert set(self.layer_types) <= {CONV, FULL}, self.layer_types
        assert list(self.kept_layers) == sorted(set(self.kept_layers)) \
            and self.kept_layers[-1] < len(self.layer_types), self.kept_layers
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.hidden_size % self.num_attention_heads == 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_shard[1]

    @property
    def layers(self) -> Tuple[Tuple[int, str, bool], ...]:
        """(published index, kind, dense feed-forward) of the layers run."""
        return tuple((i, self.layer_types[i], n < self.num_dense_layers)
                     for n, i in enumerate(self.kept_layers))

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=128, intermediate_size=192,
                    moe_intermediate_size=48, num_attention_heads=4,
                    num_key_value_heads=2, layer_types=(CONV, CONV, FULL,
                                                        CONV, CONV, CONV),
                    kept_layers=(0, 2, 3, 4, 5), num_dense_layers=1,
                    num_experts=8, num_experts_per_tok=3, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: Lfm2Config):
    return nn.initializers.normal(c.initializer_range)


def _dense(c, features, name):
    return nn.Dense(features, use_bias=False, dtype=c.dtype,
                    kernel_init=_init(c), name=name)


class ShortConv(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, u):
        c = self.config
        bcx = on_batch_axes(_dense(c, 3 * c.hidden_size, "in_proj")(u))
        taps = self.param("conv_weight", _init(c),
                          (c.conv_L_cache, c.hidden_size))
        y = on_batch_axes(gated_short_conv(bcx, taps))
        return _dense(c, c.hidden_size, "out_proj")(y)


class Attention(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        B, T, _ = x.shape
        H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        norm = lambda name: RMSNorm(c.norm_eps, c.dtype, name=name)
        q = on_batch_axes(_dense(c, H * D, "q_proj")(x).reshape(B, T, H, D))
        k = on_batch_axes(_dense(c, G * D, "k_proj")(x).reshape(B, T, G, D))
        v = on_batch_axes(_dense(c, G * D, "v_proj")(x).reshape(B, T, G, D))
        q, k = norm("q_norm")(q), norm("k_norm")(k)
        cos, sin = rope_frequencies(D, positions, c.rope_theta)
        q, k = rotate_halves(q, cos, sin), rotate_halves(k, cos, sin)
        y = causal_self_attention(q, k, v, c.attention)
        return _dense(c, c.hidden_size, "o_proj")(
            on_batch_axes(y.reshape(B, T, H * D)))


class Block(nn.Module):
    """-> (x, tokens per held expert; of length 0 with a dense
    feed-forward)."""
    config: Lfm2Config
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        norm = lambda name: RMSNorm(c.norm_eps, c.dtype, name=name)
        with device_scope("mixer"):
            u = norm("operator_norm")(x)
            if self.kind == CONV:
                mixed = ShortConv(c, name="conv")(u)
            else:
                mixed = Attention(c, name="attn")(u, positions)
            x = on_batch_axes(x + mixed)
        with device_scope("mlp" if self.dense else "experts"):
            h = norm("ffn_norm")(x)
            if self.dense:
                y, tokens = SwiGLU(c.intermediate_size, c.dtype, _init(c),
                                   name="mlp")(h), jnp.zeros((0,), jnp.int32)
            else:
                y, tokens = RoutedExperts(
                    experts=c.num_experts, expert_shard=c.expert_shard,
                    width=c.moe_intermediate_size,
                    per_token=c.num_experts_per_tok,
                    scale=c.routed_scaling_factor, normalize=c.norm_topk_prob,
                    shared=0, dtype=c.dtype, kernel_init=_init(c),
                    eps=c.route_eps, name="moe")(h)
            return on_batch_axes(x + y), tokens


class Lfm2(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, input_ids):
        """-> (hidden [B, T, d] after the final norm, tokens [expert
        layers, held]). The head's matrix is the embedding's,
        ``params["embed"]["embedding"]`` [V, d]."""
        c = self.config
        B, T = input_ids.shape
        kinds = [kind for _, kind, _ in c.layers]
        steptrace.record_counters("model/layer_kinds", {
            CONV: kinds.count(CONV), FULL: kinds.count(FULL),
            "dense": sum(dense for _, _, dense in c.layers),
            "expert": sum(not dense for _, _, dense in c.layers),
            "layers": len(kinds), "published_layers": len(c.layer_types)})
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens = on_batch_axes(embed(input_ids)), []
        for i, kind, dense in c.layers:
            x, n = block(c, kind, dense, name=f"layers_{i}")(x, positions)
            if not dense:
                tokens.append(n)
        tokens = (jnp.stack(tokens) if tokens
                  else jnp.zeros((0, c.experts_held), jnp.int32))
        return RMSNorm(c.norm_eps, c.dtype, name="norm")(x), tokens


def loss_fn(params, model, batch):
    """-> (loss, {"tokens_per_expert"}) over ``batch = {"input_ids",
    "labels"}`` (and an optional ``mask``): the mean next-token
    cross-entropy through the tied head."""
    c = model.config
    hidden, tokens = model.apply({"params": params}, batch["input_ids"])
    head, labels, mask = (params["embed"]["embedding"], batch["labels"],
                          batch.get("mask"))
    with device_scope("vocab"):
        if c.loss_chunks:
            loss = xent.chunked_xent(hidden, head, labels, mask,
                                     n_chunks=c.loss_chunks)
        else:
            loss = xent.fused_xent(hidden @ head.T.astype(hidden.dtype),
                                   labels, mask)
    return loss, {"tokens_per_expert": tokens}


def init_params(config: Lfm2Config, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Lfm2(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Lfm2(dataclasses.replace(config, remat=False, attention="xla"))
    return model, init.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


def make_train_state(config: Lfm2Config, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
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
