"""A decoder whose window layers stand three to one over full layers, both
with rotary positions from a table of their own, and whose every
feed-forward part is routed to experts (the ``mellum`` layer equations, at
whatever sizes the config gives), for training.

- Two kinds of attention layer in one stack, by ``layer_types``:
  ``sliding_attention`` layers see the last ``sliding_window`` keys, their
  own position among them; ``full_attention`` layers see every earlier key.
  BOTH carry rotary positions (halves rotated, all ``head_dim`` dimensions),
  each from its own entry of ``rope_parameters`` (``llama.rope_table``): the
  window layers' plain, the full layers' YaRN-scaled, whose cos and sin are
  multiplied by the ``attention_factor``. ``num_attention_heads`` query
  heads read ``num_key_value_heads`` key-value heads, query head j the head
  ``j // group`` (``ops.attention.causal_self_attention`` takes both and
  the window). Each head's queries and keys pass an RMSNorm over
  ``head_dim`` before the rotation (one scale vector each, shared by the
  heads: the lineage's convention, which the published config has no key
  for), both inside ``ops.attention.normed_rotary_self_attention``
  (``ops/rotary.py``'s kernels at the published width and the cell's
  length on a TPU, ``jnp`` elsewhere); there is no gate on the output and
  no bias anywhere.
- A block has two norms: ``h = h + Attn(N1(h))``, ``h = h + F(N2(h))``.
  ``F`` is, in EVERY layer, the routed-expert layer of ``models/mla_moe.py``
  (``RoutedExperts``: softmax scores over all ``num_experts``,
  ``num_experts_per_tok`` experts a token, their weights normalised where
  ``norm_topk_prob``, no shared expert, the slice ``expert_shard`` of the
  experts held here and no pair dropped). There is no dense layer.
- A final norm, an untied head; the loss is the mean next-token
  cross-entropy (``afmoe.loss_fn``, which asks of a model only what this
  one hands back: ``ops.xent.chunked_xent`` over ``lm_head``). No
  prediction module: the published config has no key for one.

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic and the norms' statistics are float32. The router's
selection bias is zero as published (the published router has none) and
takes a zero gradient; there is no auxiliary loss. One ``counters`` record
``model/layer_kinds`` a traced pass says what the stack holds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
# What ``afmoe`` says of any model that hands back (hidden, tokens a held
# expert) under an untied ``lm_head`` is this family's too, and is one copy:
# the loss, the step over it, the parameters' placement, a loop's report
from ray_tpu.models.afmoe import (  # noqa: F401 (this module's names too)
    FULL, WINDOW, build_train_step, loss_fn, param_shardings,
    shard_train_state, step_metrics)
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import RMSNorm, RMSNormScale, rope_table
from ray_tpu.models.mla_moe import RoutedExperts
from ray_tpu.ops import sparse_index
from ray_tpu.ops.attention import normed_rotary_self_attention
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel.mesh_utils import on_batch_axes

_PUBLISHED_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    WINDOW: {"rope_type": "default", "rope_theta": 500000},
}


def _pairs(mapping) -> tuple:
    """A mapping as its sorted pairs, a mapping inside it likewise: what a
    frozen config can hold and hash."""
    return tuple(sorted(
        (key, _pairs(value) if hasattr(value, "items") else value)
        for key, value in dict(mapping).items()))


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The published keys under their published names. ``num_experts`` is
    the router's width, all experts of the model; ``expert_shard`` says
    which slice of them this program holds. ``layer_types`` names each
    layer's attention; left empty it is the published rule, a full layer
    every fourth. ``rope_parameters`` maps a layer's kind to its table's
    entry (held as sorted pairs; a mapping is taken)."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    layer_types: Tuple[str, ...] = ()
    rope_parameters: Any = _pairs(_PUBLISHED_ROPE)
    rms_norm_eps: float = 1e-6
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
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % 4 == 0 else WINDOW
                for i in range(self.num_hidden_layers)))
        assert len(self.layer_types) == self.num_hidden_layers and set(
            self.layer_types) <= {WINDOW, FULL}, self.layer_types
        object.__setattr__(self, "rope_parameters",
                           _pairs(self.rope_parameters))
        assert set(self.layer_types) <= set(dict(self.rope_parameters)), (
            self.rope_parameters)

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_shard[1]

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, sliding_window=8, loss_chunks=4,
                    rope_parameters={
                        FULL: {**_PUBLISHED_ROPE[FULL], "rope_theta": 10000,
                               "factor": 4,
                               "original_max_position_embeddings": 16,
                               "attention_factor": None},
                        WINDOW: {"rope_type": "default",
                                 "rope_theta": 10000}})
        base.update(kw)
        return cls(**base)


def _init(c: MellumConfig):
    return nn.initializers.normal(c.initializer_range)


class Attention(nn.Module):
    """One attention layer; ``window`` None is a full layer. ``cos``, ``sin``
    are its kind's table. ``blocks`` (``models/sdar.py``'s layers): the T
    positions are two streams under the block-diffusion mask. ``sparse``
    (``models/keye.py``'s layers; an object with ``heads``, ``width``,
    ``topk``, ``dtype``): a learned indexer beside q, k and v picks the
    ``topk`` keys each query sees (``ops/sparse_index.py``), and the call
    returns (y, the layer's sum over its queries of the indexer's KL)."""
    config: MellumConfig
    window: Any = None
    blocks: Any = None
    sparse: Any = None

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.config
        B, T, _ = x.shape
        H, G, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=c.dtype,
                                         kernel_init=_init(c), name=name)
        scale = lambda name: RMSNormScale(D, name=name)()
        q = on_batch_axes(dense(H * D, "q_proj")(x).reshape(B, T, H, D))
        k = on_batch_axes(dense(G * D, "k_proj")(x).reshape(B, T, G, D))
        v = on_batch_axes(dense(G * D, "v_proj")(x).reshape(B, T, G, D))
        o_proj = lambda y: dense(c.hidden_size, "o_proj")(
            on_batch_axes(y.reshape(B, T, H * D)))
        if self.sparse is not None:
            return self._selected(x, q, k, v, scale, cos, sin, o_proj)
        # each head's q and k normed over its width, then turned
        y = normed_rotary_self_attention(
            q, k, v, scale("q_norm"), scale("k_norm"), cos, sin,
            eps=c.rms_norm_eps, attention=c.attention, window=self.window,
            blocks=self.blocks)
        return o_proj(y)

    def _selected(self, x, q, k, v, scale, cos, sin, o_proj):
        """The layer under its indexer's selection. The indexer reads ``x``
        as a constant and nothing of it but the KL is differentiated: the
        trunk is moved by the language loss alone, the indexer by the KL
        alone. ``q_idx`` [B, T, J, W] and the one index key a token ``k_idx``
        [B, T, W] (a LayerNorm over W, float32) go to the index matmul in
        ``sparse.dtype``; ``w`` [B, T, J] is float32 at precision highest,
        scaled ``J^-0.5 W^-0.5``; no rotation inside the indexer."""
        c, sp = self.config, self.sparse
        B, T, _ = x.shape
        xd = jax.lax.stop_gradient(x)
        # the two projections in the index matmul's own type: a float32
        # index is float32 from ``x`` on
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=sp.dtype, kernel_init=_init(c),
            precision=sparse_index.precision_of(sp.dtype),
            name=name)
        q_idx = dense(sp.heads * sp.width, "index_q")(xd).reshape(
            B, T, sp.heads, sp.width)
        k_idx = nn.LayerNorm(epsilon=c.rms_norm_eps, dtype=jnp.float32,
                             name="index_k_norm")(
            dense(sp.width, "index_k")(xd)).astype(sp.dtype)
        w = nn.Dense(sp.heads, use_bias=False, dtype=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST,
                     kernel_init=_init(c), name="index_w")(
            xd.astype(jnp.float32)) * (sp.heads * sp.width) ** -0.5
        chosen = sparse_index.select(q_idx, k_idx, w, sp.topk)
        # for a side run that asks for it (``mutable=["intermediates"]``):
        # the set as a dense int8 [B, T keys, T queries], which no step makes
        if self.is_mutable_collection("intermediates"):
            self.sow("intermediates", "selected",
                     sparse_index.unpack(chosen.mask))
        y, (qf, kf, lse) = normed_rotary_self_attention(
            q, k, v, scale("q_norm"), scale("k_norm"), cos, sin,
            eps=c.rms_norm_eps, attention=c.attention, selected=chosen.mask,
            topk=sp.topk)
        kl = sparse_index.index_kl(
            q_idx, k_idx, w, chosen, qf, kf, lse, topk=sp.topk,
            sm_scale=c.head_dim ** -0.5)
        return o_proj(y), kl


class Block(nn.Module):
    """-> (x, tokens per held expert), and under ``sparse`` the layer's
    indexer's KL third."""
    config: MellumConfig
    window: Any = None
    blocks: Any = None
    sparse: Any = None

    @nn.compact
    def __call__(self, x, cos, sin):
        c = self.config
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        with device_scope("mixer"):
            y = Attention(c, self.window, self.blocks, self.sparse,
                          name="attn")(norm("input_norm")(x), cos, sin)
            y, *kl = y if self.sparse is not None else (y,)
            x = on_batch_axes(x + y)
        with device_scope("experts"):
            y, tokens = RoutedExperts(
                experts=c.num_experts, expert_shard=c.expert_shard,
                width=c.moe_intermediate_size,
                per_token=c.num_experts_per_tok,
                scale=1.0, normalize=c.norm_topk_prob, shared=0,
                dtype=c.dtype, kernel_init=_init(c), eps=0.0,
                score="softmax", name="moe")(norm("post_attn_norm")(x))
            return (on_batch_axes(x + y), tokens, *kl)


class Mellum(nn.Module):
    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids):
        """-> (hidden [B, T, d] after the final norm, tokens [layers,
        held]). The head's matrix is the parameter ``lm_head``, [V, d]."""
        c = self.config
        _, T = input_ids.shape
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        steptrace.record_counters("model/layer_kinds", {
            WINDOW: c.layer_types.count(WINDOW),
            FULL: c.layer_types.count(FULL), "expert": c.num_hidden_layers,
            "layers": c.num_hidden_layers,
            "published_layers": MellumConfig.num_hidden_layers})
        # one table a kind of layer, [1, T, head_dim / 2], for every row
        positions, ropes = jnp.arange(T)[None, :], dict(c.rope_parameters)
        with device_scope("mixer"):
            tables = {kind: rope_table(c.head_dim, positions, ropes[kind])
                      for kind in sorted(set(c.layer_types))}
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens = on_batch_axes(embed(input_ids)), []
        for i, kind in enumerate(c.layer_types):
            window = c.sliding_window if kind == WINDOW else None
            x, n = block(c, window, name=f"layers_{i}")(x, *tables[kind])
            tokens.append(n)
        return (RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x),
                jnp.stack(tokens))


def init_params(config: MellumConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Mellum(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Mellum(dataclasses.replace(config, remat=False, attention="xla"))
    return model, init.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]


def make_train_state(config: MellumConfig, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)
