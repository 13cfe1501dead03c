"""A decoder whose blocks are of five kinds and hand state down the stack
(the ``phi4flash`` layer equations, at whatever sizes the config gives), for
training.

- Every block is ``h = h + Mix(LN1(h)); h = h + MLP(LN2(h))``: LayerNorms
  with scale and bias, ``MLP(x) = (silu(g) * u) W_down`` with ``[g | u] = x
  W_up``, no biases. ``Mix`` is, by the layer's published index ``i`` of
  ``L = num_hidden_layers`` (``layer_kinds``):

  - ``ssm`` (``i`` even, ``i < L/2 + 2``): a Mamba-1 layer. ``[x | z] = u
    W_in``; ``x = silu(conv(x))``, a causal depthwise convolution of
    ``d_conv`` taps with a bias; ``[dt | B | C] = x W_x``; ``delta =
    softplus(dt W_dt + b_dt)``; ``y = ops.ssm.selective_scan(x, delta,
    -exp(A_log), B, C, D)``; out ``(y * silu(z)) W_out``. Layer ``L/2``,
    the last of them, also hands ``y`` (before the gate) down the stack:
    the memory ``M``.
  - ``window`` (``i`` odd, ``i < L/2``) and ``full`` (``i = L/2 + 1``):
    differential attention (below), a window layer over the last
    ``sliding_window`` keys. The full layer hands its keys and values down.
  - ``gmu`` (``i >= L/2 + 2``, even): a gated memory unit, ``(M * silu(x
    W1)) W2`` on the memory of layer ``L/2``.
  - ``cross`` (``i >= L/2 + 2``, odd): differential attention of the
    layer's own queries on the full layer's keys and values; it has no key
    or value projection.

- Differential attention pairs heads: query heads ``(2j, 2j + 1)`` are
  ``(q1_j, q2_j)``, key-value heads ``(2m, 2m + 1)`` give ``(k1_m, k2_m)``
  and ``V_m = [v1_m | v2_m]``, query pair ``j`` reads key-value pair ``j //
  group``. ``o_j = RMSNorm((softmax(q1 k1^T / sqrt(d)) - lam softmax(q2
  k2^T / sqrt(d))) V_m) (1 - lam_init)``, the norm over the pair's ``2 d``
  with a learned scale; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 i)``. Each map is one call of
  ``ops.attention.causal_self_attention`` with keys ``d`` wide and values
  ``2 d``: two kernel calls a layer, no scores in HBM. No positional term
  anywhere: the state-space layers carry position.
- ``kept_layers`` names the published indices this program runs (all of
  them if empty); a memory unit needs layer ``L/2`` among them and a cross
  layer ``L/2 + 1``. Parameters are named by the published index
  (``layers_16``).
- The embedding is tied to the head; final LayerNorm; the loss is the mean
  next-token cross-entropy (``ops.xent.chunked_xent`` over the tied [V, d]).

Parameters are float32, compute is ``dtype``; ``delta``, ``A``, the scan's
state, every softmax statistic, ``lam`` and the norms' statistics are
float32. Under ``remat`` a block is recomputed in the backward pass from its
inputs, which are the hidden state and whatever was handed down to it; the
kernels' outputs are kept (``ops.remat.remat_policy``), so neither the
flash kernels nor the forward scan run again.
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
from ray_tpu.models.llama import RMSNorm
from ray_tpu.ops import xent
from ray_tpu.ops.attention import causal_self_attention
from ray_tpu.ops.remat import remat_policy
from ray_tpu.ops.ssm import selective_scan
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes, replicated

SSM, WINDOW, FULL, GMU, CROSS = "ssm", "window", "full", "gmu", "cross"
KINDS = (SSM, WINDOW, FULL, GMU, CROSS)


def layer_kinds(num_hidden_layers: int) -> Tuple[str, ...]:
    """The published rule: the kind of every layer of the whole stack."""
    half = num_hidden_layers // 2

    def kind(i):
        if i >= half + 2:
            return CROSS if i % 2 else GMU
        if i % 2 == 0:
            return SSM
        return FULL if i == half + 1 else WINDOW

    return tuple(kind(i) for i in range(num_hidden_layers))


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The published keys under their published names; the state-space
    layer's sizes (``d_state``, ``d_conv``, ``expand``, ``dt_rank``) under
    Mamba's."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32       # of the published stack: the rule's L
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                  # 0: ceil(hidden_size / 16)
    kept_layers: Tuple[int, ...] = ()
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits and ``xent.fused_xent``

    def __post_init__(self):
        set_ = lambda name, value: object.__setattr__(self, name, value)
        if not self.dt_rank:
            set_("dt_rank", -(-self.hidden_size // 16))
        set_("kept_layers", tuple(self.kept_layers)
             or tuple(range(self.num_hidden_layers)))
        kinds = layer_kinds(self.num_hidden_layers)
        kept = [kinds[i] for i in self.kept_layers]
        half = self.num_hidden_layers // 2
        assert list(self.kept_layers) == sorted(set(self.kept_layers))
        assert GMU not in kept or half in self.kept_layers, self.kept_layers
        assert CROSS not in kept or half + 1 in self.kept_layers
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.num_key_value_heads % 2 == 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def kinds(self) -> Tuple[Tuple[int, str], ...]:
        """(published index, kind) of the layers run."""
        kinds = layer_kinds(self.num_hidden_layers)
        return tuple((i, kinds[i]) for i in self.kept_layers)

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=2, sliding_window=8, d_state=8,
                    loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: Phi4FlashConfig):
    return nn.initializers.normal(c.initializer_range)


def _dense(c, features, name, bias=False):
    return nn.Dense(features, use_bias=bias, dtype=c.dtype,
                    kernel_init=_init(c), name=name)


class GatedMLP(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        gate, up = jnp.split(
            _dense(c, 2 * c.intermediate_size, "up_proj")(x), 2, axis=-1)
        return _dense(c, c.hidden_size, "down_proj")(nn.silu(gate) * up)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The usual initialisation of Mamba's step: ``softplus(bias)`` is
    log-uniform over [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape) * (math.log(0.1)
                                                   - math.log(0.001))
                 + math.log(0.001)).clip(1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _uniform(bound):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class Mamba(nn.Module):
    """-> (the layer's output, the scan's output y before the gate)."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u):
        c = self.config
        T, inner, n, rank = u.shape[1], c.d_inner, c.d_state, c.dt_rank
        f32 = jnp.float32
        x, z = jnp.split(_dense(c, 2 * inner, "in_proj")(u), 2, axis=-1)
        taps = self.param("conv_weight", _uniform(c.d_conv ** -0.5),
                          (c.d_conv, inner))
        conv_bias = self.param("conv_bias", _uniform(c.d_conv ** -0.5),
                               (inner,))
        padded = jnp.pad(x, ((0, 0), (c.d_conv - 1, 0), (0, 0)))
        x = nn.silu(sum(padded[:, k:k + T].astype(f32) * taps[k]
                        for k in range(c.d_conv)) + conv_bias).astype(c.dtype)
        x = on_batch_axes(x)
        dt, b, cc = jnp.split(_dense(c, rank + 2 * n, "x_proj")(x),
                              [rank, rank + n], axis=-1)
        w_dt = self.param("dt_proj", _uniform(rank ** -0.5), (rank, inner))
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        delta = jax.nn.softplus(jnp.dot(
            dt, w_dt.astype(c.dtype), preferred_element_type=f32) + dt_bias)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=f32), shape)), (inner, n))
        skip = self.param("D", nn.initializers.ones, (inner,))
        y = on_batch_axes(selective_scan(x, delta, -jnp.exp(a_log), b, cc,
                                         skip))
        return _dense(c, c.hidden_size, "out_proj")(y * nn.silu(z)), y


class DiffAttention(nn.Module):
    """Differential attention at published layer ``index``; ``window`` None
    sees every earlier key. Given ``kv`` (a cross layer) it projects no key
    or value. -> (output, (k, v) as projected or as given)."""
    config: Phi4FlashConfig
    index: int
    window: Any = None

    @nn.compact
    def __call__(self, x, kv=None):
        c = self.config
        B, T, _ = x.shape
        H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = _dense(c, H * D, "q_proj", bias=True)(x)
        if kv is None:
            kv = (_dense(c, G * D, "k_proj", bias=True)(x),
                  _dense(c, G * D, "v_proj", bias=True)(x))
        k, v = kv
        q = on_batch_axes(q.reshape(B, T, H // 2, 2, D))
        k_pairs = on_batch_axes(k.reshape(B, T, G // 2, 2, D))
        values = on_batch_axes(v.reshape(B, T, G // 2, 2 * D))
        signal, noise = (
            causal_self_attention(q[:, :, :, m], k_pairs[:, :, :, m], values,
                                  c.attention, self.window)
            for m in (0, 1))
        lam_init = 0.8 - 0.6 * math.exp(-0.3 * self.index)
        lq1, lk1, lq2, lk2 = (
            self.param(name, nn.initializers.normal(0.1), (D,))
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
            + lam_init
        mixed = signal.astype(jnp.float32) - lam * noise.astype(jnp.float32)
        out = RMSNorm(c.layer_norm_eps, c.dtype, name="subln")(mixed) \
            * (1.0 - lam_init)
        out = on_batch_axes(out.reshape(B, T, H * D).astype(c.dtype))
        return _dense(c, c.hidden_size, "o_proj", bias=True)(out), kv


class GatedMemory(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        c = self.config
        gate = nn.silu(_dense(c, c.d_inner, "in_proj")(x))
        return _dense(c, c.hidden_size, "out_proj")(memory * gate)


class Block(nn.Module):
    """``(h, side) -> (h, handed)``: ``side`` is what the kind reads of the
    layers above (``gmu``: the memory; ``cross``: (k, v); else None) and
    ``handed`` what it hands down (``ssm``: its scan's output; ``full``:
    its (k, v); else None)."""
    config: Phi4FlashConfig
    index: int
    kind: str

    @nn.compact
    def __call__(self, h, side=None):
        c = self.config
        norm = lambda name: nn.LayerNorm(epsilon=c.layer_norm_eps,
                                         dtype=c.dtype, name=name)
        with device_scope("mixer"):
            with device_scope("norm"):
                u, handed = norm("ln1")(h), None
            if self.kind == SSM:
                mixed, handed = Mamba(c, name="mixer")(u)
            elif self.kind == GMU:
                mixed = GatedMemory(c, name="mixer")(u, side)
            else:
                window = c.sliding_window if self.kind == WINDOW else None
                mixed, kv = DiffAttention(c, self.index, window,
                                          name="mixer")(
                    u, side if self.kind == CROSS else None)
                handed = kv if self.kind == FULL else None
            h = on_batch_axes(h + mixed)
        with device_scope("mlp"):
            with device_scope("norm"):
                u = norm("ln2")(h)
            return on_batch_axes(h + GatedMLP(c, name="mlp")(u)), handed


class Phi4Flash(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, input_ids):
        """-> hidden [B, T, d] after the final norm. The head's matrix is
        the embedding's, ``params["embed"]["embedding"]`` [V, d]."""
        c = self.config
        half = c.num_hidden_layers // 2
        steptrace.record_counters("model/layer_kinds", {
            **{kind: sum(k == kind for _, k in c.kinds) for kind in KINDS},
            "hands_memory": half, "hands_keys_values": half + 1,
            "layers": len(c.kinds), "published_layers": c.num_hidden_layers})
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            h = on_batch_axes(embed(input_ids))
        memory = keys_values = None
        for i, kind in c.kinds:
            side = {GMU: memory, CROSS: keys_values}.get(kind)
            h, handed = block(c, i, kind, name=f"layers_{i}")(h, side)
            if i == half:
                memory = handed
            elif kind == FULL:
                keys_values = handed
        with device_scope("norm"):
            return nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=c.dtype,
                                name="norm")(h)


def loss_fn(params, model, batch):
    """The mean next-token cross-entropy over ``batch = {"input_ids",
    "labels"}`` (and an optional ``mask``), through the tied head."""
    c = model.config
    hidden = model.apply({"params": params}, batch["input_ids"])
    head, labels, mask = (params["embed"]["embedding"], batch["labels"],
                          batch.get("mask"))
    with device_scope("vocab"):
        if c.loss_chunks:
            return xent.chunked_xent(hidden, head, labels, mask,
                                     n_chunks=c.loss_chunks)
        return xent.fused_xent(hidden @ head.T.astype(hidden.dtype), labels,
                               mask)


def init_params(config: Phi4FlashConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Phi4Flash(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Phi4Flash(dataclasses.replace(config, remat=False,
                                         attention="xla"))
    return model, init.init(rng, jnp.zeros((1, 16), jnp.int32))["params"]


def make_train_state(config: Phi4FlashConfig, rng,
                     learning_rate: float = 3e-4, weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss)``:
    ``parallel.build_train_step`` over this model's loss."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate)


def param_shardings(params, mesh):
    """The rule for this model's parameters on ``mesh``: replicated, as
    ``afmoe.param_shardings`` (the batch alone is split)."""
    return jax.tree.map(lambda _: replicated(mesh), params)


def shard_train_state(params, opt_state, mesh):
    return train_step.place_train_state(
        params, opt_state, param_shardings(params, mesh))
