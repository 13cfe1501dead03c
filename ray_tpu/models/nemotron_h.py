"""A decoder whose blocks are ONE mixer each, of three kinds laid out by a
pattern string: Mamba-2 state-space mixers, grouped softmax attention without
a positional term, and sigmoid-routed un-gated ``relu^2`` experts beside a
shared expert (the ``nemotron_h`` layer equations, at whatever sizes the
config gives), for training.

- Every block is ``h = h + Mixer_i(N_i(h))``, ``N`` the plain RMSNorm ``x /
  rms(x) * w`` (also the final norm). The kind of block ``i`` is character
  ``i`` of ``hybrid_override_pattern``: ``M`` mamba, ``*`` attention, ``E``
  experts (``-``, a dense feed-forward block, is not in this program). No
  bias but the convolution's.

  - ``M`` (``Mamba2Mixer``): ``[z | x | B | C | dt] = u W_in`` (``mamba_num_heads
    x mamba_head_dim`` inner channels, NOT ``expand x d``; ``n_groups x
    ssm_state_size`` for each of B and C; one ``dt`` a head): one parameter
    ``in_proj`` [d, all], multiplied a part at a time so that no [B, T, all]
    array is made and cut up. ``x``, ``B``, ``C`` each pass the depthwise
    causal convolution of ``conv_kernel`` taps WITH a bias and a SiLU
    (``ops.conv.causal_conv``: their slices of the one ``conv_weight`` /
    ``conv_bias``; on a TPU the kernels ``causal_conv_fwd`` /
    ``causal_conv_bwd``). ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` in float32. ``y = ops.ssm.ssd_scan(x, dt, A, B, C, D)``: a
    [head_dim, states] float32 state a head, zero at a sequence's start,
    ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T``, ``y_t = S_t C_t + D
    x_t``, head ``h`` reading group ``h // (heads / groups)`` (on a TPU the
    kernels ``ssd_fwd`` / ``ssd_bwd``). Then ``y = GroupRMSNorm(y *
    silu(z))``, the norm over each of ``n_groups`` groups of channels with
    one weight a channel (``ops.norm.gated_group_rms_norm``; on a TPU the
    kernels ``group_norm_fwd`` / ``group_norm_bwd``), and ``y W_out``.
  - ``*`` (``Attention``): ``num_attention_heads`` query heads on
    ``num_key_value_heads`` key-value heads of ``head_dim``, causal softmax
    at ``head_dim^-0.5``, NO positional term (the state-space blocks carry
    position): ``ops.attention.causal_self_attention``.
  - ``E``: ``models/mla_moe.py``'s ``RoutedExperts`` with
    ``activation="relu2"``: ``s = sigmoid(u W_r)`` over all
    ``n_routed_experts``, the ``num_experts_per_tok`` largest of ``s +
    router_bias``, weights ``s`` of the chosen over their sum plus 1e-20,
    times ``routed_scaling_factor``; each expert ``relu(u W_i)^2 W_o``; the
    slice ``expert_shard`` of the experts held here, no pair dropped; beside
    them on every token one shared expert of the same form.

- ``kept_layers`` names the published indices this program runs (all of the
  pattern if empty); parameters are named by the published index.
- The head is untied; the loss is the mean next-token cross-entropy
  (``ops.xent.chunked_xent``).

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic, the norms' statistics, ``dt``, the decays, the scan's state
and the convolution's sum over its taps are float32. Under ``remat`` a block
is recomputed in the backward pass from its input; the flash kernel's and
the scan's outputs are kept (``ops.remat.remat_policy``).
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
from ray_tpu.models.afmoe import step_metrics  # noqa: F401
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.mla_moe import RoutedExperts
from ray_tpu.ops import xent
from ray_tpu.ops.attention import causal_self_attention
from ray_tpu.ops.conv import causal_conv
from ray_tpu.ops.norm import gated_group_rms_norm
from ray_tpu.ops.remat import remat_policy
from ray_tpu.ops.ssm import ssd_scan
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes, replicated

MAMBA, ATTENTION, EXPERT = "mamba", "attention", "expert"
KINDS = {"M": MAMBA, "*": ATTENTION, "E": EXPERT}
_F32 = jnp.float32


def layer_kinds(pattern: str) -> Tuple[str, ...]:
    """The kind of every published block, from the pattern string."""
    unknown = set(pattern) - set(KINDS)
    assert not unknown, f"hybrid_override_pattern has {sorted(unknown)}"
    return tuple(KINDS[c] for c in pattern)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys under their published names. ``n_routed_experts``
    is the router's width, all experts of the model; ``expert_shard`` says
    which slice of them this program holds."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    kept_layers: Tuple[int, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    rescale_prenorm_residual: bool = True
    expert_shard: Tuple[int, int] = (0, 1)   # (index, of)
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits and ``xent.fused_xent``

    def __post_init__(self):
        kinds = layer_kinds(self.hybrid_override_pattern)
        object.__setattr__(self, "kept_layers", tuple(self.kept_layers)
                           or tuple(range(len(kinds))))
        index, of = self.expert_shard
        assert 0 <= index < of and self.n_routed_experts % of == 0, (
            self.expert_shard, self.n_routed_experts)
        assert list(self.kept_layers) == sorted(set(self.kept_layers)) \
            and self.kept_layers[-1] < len(kinds), self.kept_layers
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.mamba_num_heads % self.n_groups == 0
        assert (self.moe_shared_expert_intermediate_size
                % self.moe_intermediate_size == 0)

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.expert_shard[1]

    @property
    def layers(self) -> Tuple[Tuple[int, str], ...]:
        """(published index, kind) of the blocks run."""
        kinds = layer_kinds(self.hybrid_override_pattern)
        return tuple((i, kinds[i]) for i in self.kept_layers)

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64,
                    hybrid_override_pattern="MEM*EMEM", num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
                    mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                    chunk_size=16, moe_intermediate_size=32,
                    moe_shared_expert_intermediate_size=64,
                    n_routed_experts=8, num_experts_per_tok=3, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: NemotronHConfig, out_projection: bool = False):
    """Normal at ``initializer_range``; a Mamba block's out-projection
    divided by sqrt(published blocks) where ``rescale_prenorm_residual``."""
    scale = c.initializer_range
    if out_projection and c.rescale_prenorm_residual:
        scale /= math.sqrt(c.num_hidden_layers)
    return nn.initializers.normal(scale)


def _dense(c, features, name, out_projection=False):
    return nn.Dense(features, use_bias=False, dtype=c.dtype,
                    kernel_init=_init(c, out_projection), name=name)


def _taps_init(taps: int):
    """A depthwise convolution's default, for its taps and its bias alike:
    uniform within 1 / sqrt(taps)."""
    bound = taps ** -0.5
    return lambda key, shape, dtype=_F32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def _decay_init(key, shape, dtype=_F32):
    """``A_log = log(1 .. heads)``."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def dt_bias_init(low: float, high: float, floor: float):
    """The inverse softplus of a log-uniform draw from [low, high], no less
    than ``floor``: ``softplus(dt_bias)`` starts as that step."""
    def init(key, shape, dtype=_F32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(high) - math.log(low)) + math.log(low))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class GroupRMSNorm(nn.Module):
    """``x / rms(x) * w`` with the mean square over each of ``groups`` equal
    groups of the last axis, one weight a channel (initialised 1), of ``y *
    silu(z)``; float32 until its one rounding
    (``ops.norm.gated_group_rms_norm``: a kernel pair where its shapes and
    surroundings admit one)."""
    groups: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        return gated_group_rms_norm(y, z, scale, groups=self.groups,
                                    eps=self.eps).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        c = self.config
        B, T, d = u.shape
        H, P, G, N = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                      c.ssm_state_size)
        inner, states = H * P, G * N
        mixed = inner + 2 * states                  # what the taps pass over
        w_in = self.param("in_proj", _init(c), (d, inner + mixed + H))
        taps = self.param("conv_weight", _taps_init(c.conv_kernel),
                          (c.conv_kernel, mixed))
        conv_bias = self.param("conv_bias", _taps_init(c.conv_kernel),
                               (mixed,))
        decay = self.param("A_log", _decay_init, (H,))
        skip = self.param("D", nn.initializers.ones, (H,))
        dt_bias = self.param("dt_bias", dt_bias_init(
            c.time_step_min, c.time_step_max, c.time_step_floor), (H,))

        def part(start, width, out_dtype=None):
            """``u W_in[:, start:start + width]``: a part of the published
            [z | x | B | C | dt] made on its own."""
            return jnp.dot(u, w_in[:, start:start + width].astype(c.dtype),
                           preferred_element_type=out_dtype or c.dtype)

        def convolved(start, width):
            at = slice(start - inner, start - inner + width)
            return on_batch_axes(causal_conv(
                on_batch_axes(part(start, width)), taps[:, at], jax.nn.silu,
                conv_bias[at]))

        z = on_batch_axes(part(0, inner))
        x = convolved(inner, inner).reshape(B, T, H, P)
        b = convolved(2 * inner, states).reshape(B, T, G, N)
        cm = convolved(2 * inner + states, states).reshape(B, T, G, N)
        dt = jax.nn.softplus(part(inner + mixed, H, _F32) + dt_bias)
        y = ssd_scan(x, dt, -jnp.exp(decay), b, cm, skip,
                     chunk=c.chunk_size)
        y = GroupRMSNorm(G, c.layer_norm_epsilon, c.dtype, name="norm")(
            y.reshape(B, T, inner), z)
        return _dense(c, d, "out_proj", out_projection=True)(
            on_batch_axes(y))


class Attention(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        c = self.config
        B, T, _ = u.shape
        H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        heads = lambda name, n: on_batch_axes(
            _dense(c, n * D, name)(u).reshape(B, T, n, D))
        y = causal_self_attention(heads("q_proj", H), heads("k_proj", G),
                                  heads("v_proj", G), c.attention)
        return _dense(c, c.hidden_size, "o_proj")(
            on_batch_axes(y.reshape(B, T, H * D)))


class Block(nn.Module):
    """-> (x, tokens per held expert; of length 0 where the block has no
    experts)."""
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        # one mixer a block: an expert block's module is named ``mixer``
        # too, and its class is ``experts``
        with device_scope("experts" if self.kind == EXPERT else "mixer"):
            u = RMSNorm(c.layer_norm_epsilon, c.dtype, name="norm")(x)
            tokens = jnp.zeros((0,), jnp.int32)
            if self.kind == MAMBA:
                y = Mamba2Mixer(c, name="mixer")(u)
            elif self.kind == ATTENTION:
                y = Attention(c, name="mixer")(u)
            else:
                y, tokens = RoutedExperts(
                    experts=c.n_routed_experts, expert_shard=c.expert_shard,
                    width=c.moe_intermediate_size,
                    per_token=c.num_experts_per_tok,
                    scale=c.routed_scaling_factor, normalize=c.norm_topk_prob,
                    shared=(c.moe_shared_expert_intermediate_size
                            // c.moe_intermediate_size),
                    dtype=c.dtype, kernel_init=_init(c), eps=1e-20,
                    score="sigmoid", activation="relu2", name="mixer")(u)
            return on_batch_axes(x + y), tokens


class NemotronH(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        """-> (hidden [B, T, d] after the final norm, tokens [expert blocks,
        held]). The head's matrix is the parameter ``lm_head``, [V, d]."""
        c = self.config
        kinds = [kind for _, kind in c.layers]
        steptrace.record_counters("model/layer_kinds", {
            MAMBA: kinds.count(MAMBA), ATTENTION: kinds.count(ATTENTION),
            EXPERT: kinds.count(EXPERT), "layers": len(kinds),
            "published_layers": c.num_hidden_layers})
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens = on_batch_axes(embed(input_ids)), []
        for i, kind in c.layers:
            x, n = block(c, kind, name=f"layers_{i}")(x)
            if kind == EXPERT:
                tokens.append(n)
        tokens = (jnp.stack(tokens) if tokens
                  else jnp.zeros((0, c.experts_held), jnp.int32))
        return RMSNorm(c.layer_norm_epsilon, c.dtype, name="norm")(x), tokens


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


def init_params(config: NemotronHConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = NemotronH(config)
    # parameter shapes do not depend on recomputation or on the path
    init = NemotronH(dataclasses.replace(config, remat=False,
                                         attention="xla"))
    return model, init.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


def make_train_state(config: NemotronHConfig, rng,
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
