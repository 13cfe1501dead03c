"""A decoder whose every attention layer sees, for each query, the keys a
learned indexer picks (the ``KeyeVL2`` language model's layer equations, at
whatever sizes the config gives: ``qwen3_moe``'s block with a sparse
attention of DeepSeek-V3.2-Exp's kind in front of every layer and positions
of three components), for training. The vision tower is not here: the
trunk reads ids, and what it keeps of images is their positions.

- Positions. ``position_ids`` [3, B, T]: a token's temporal, height and
  width components. The rotary table's frequency pairs read them by
  ``mrope_section`` (``llama.rope_table``: the first pairs the temporal
  row, the next the height's, the rest the width's). ``image_layout`` makes
  the ids of a sequence with image spans by the Qwen2-VL family's rule: a
  text token takes ``(p, p, p)`` and ``p += 1``; a span of grid ``gh x gw``
  that starts at ``p0`` gives token ``(r, c)`` the triple ``(p0, p0 + r, p0
  + c)``, then ``p = p0 + max(gh, gw)``.
- A block (``mellum.Block`` with ``sparse``) has two norms: ``h = h +
  Attn(N1(h))``, ``h = h + F(N2(h))``. ``Attn``: ``num_attention_heads``
  query heads on ``num_key_value_heads`` key-value heads, each head's q and
  k through an RMSNorm over ``head_dim`` and the rotation of its halves;
  beside them the indexer (``ops/sparse_index.py``): ``indexer_num_heads``
  index queries of ``indexer_head_dim`` and ONE index key a token (through
  a LayerNorm), a weight an index head, ``I[t, s] = sum_j w[t, j] relu(qI[t,
  j] . kI[s])``, no rotation; each query sees its ``topk`` highest-scored
  earlier keys (all of them while it has no more), in sequence order
  whatever the position ids say, all heads the same set: exactly, the mask
  made once a layer as bits, a bit a pair (``sparse_index.select``), read
  by the flash kernels' tiles
  (``ops.attention.normed_rotary_self_attention(..., selected=)``) and kept
  for a recomputed block's backward (``remat_policy``). ``F`` is, in every
  layer, ``models/mla_moe.py``'s routed-expert layer (softmax scores,
  ``num_experts_per_tok`` a token,
  normalised, no shared expert, the slice ``expert_shard`` held here).
- The loss is ``L_lm + L_I``. ``L_lm``: next-token cross-entropy through
  the final norm and the untied head over the targets ``loss_weights``
  marks (the text targets; the denominator their count). ``L_I = (1 /
  (layers B T)) sum_layers sum_t KL(p[t, S_t] || softmax(I[t, S_t]))``, ``p``
  the mean over the query heads of the layer's own attention probabilities,
  a constant. The indexer reads the block's normed input as a constant too:
  the trunk is moved by ``L_lm`` alone and the indexer by ``L_I`` alone
  (DeepSeek-V3.2-Exp's sparse training stage; its dense warm-up stage is not
  run).

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic, the norms' statistics, the rotary table, the indexer's
weights ``w``, its LayerNorm, its thresholds and the KL are float32; the
index matmul's operands are ``index_dtype``. One ``counters`` record
``model/layer_kinds`` a traced pass says what the stack holds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
from ray_tpu.models.afmoe import (  # noqa: F401 (this module's names too)
    param_shardings, shard_train_state)
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import RMSNorm, rope_table
from ray_tpu.models.mellum import Block
from ray_tpu.models.mla_moe import held_expert_load
from ray_tpu.ops import xent
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes


@dataclasses.dataclass(frozen=True)
class Indexer:
    """``sa_config``'s keys as ``mellum.Attention`` reads them."""
    heads: int = 16
    width: int = 64
    topk: int = 2048
    dtype: Any = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """The published keys under their published names (``sa_config``'s
    and ``rope_scaling.mrope_section`` flat). ``num_experts`` is the
    router's width, all experts of the model; ``expert_shard`` says which
    slice of them this program holds."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 10000000.0
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)   # (index, of)
    dtype: Any = jnp.bfloat16
    index_dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "auto"   # as GPT2Config.attention: auto, xla, flash
    loss_chunks: int = 8      # 0: whole logits

    def __post_init__(self):
        index, of = self.expert_shard
        assert 0 <= index < of and self.num_experts % of == 0, (
            self.expert_shard, self.num_experts)
        assert self.num_attention_heads % self.num_key_value_heads == 0
        object.__setattr__(self, "mrope_section", tuple(self.mrope_section))
        assert sum(self.mrope_section) == self.head_dim // 2, (
            self.mrope_section, self.head_dim)

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_shard[1]

    @property
    def indexer(self) -> Indexer:
        return Indexer(self.indexer_num_heads, self.indexer_head_dim,
                       self.topk, self.index_dtype)

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, rope_theta=10000.0,
                    mrope_section=(2, 3, 3), indexer_num_heads=4,
                    indexer_head_dim=8, topk=8, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def image_layout(seq: int, offsets=(), grid=(1, 1)):
    """(position_ids int32 [3, seq], is_image bool [seq]) of one sequence
    whose image spans, each ``grid[0] x grid[1]`` tokens row by row, start
    at ``offsets``, text everywhere else: this file's position rule. numpy,
    on the host."""
    gh, gw = grid
    starts = {int(o): None for o in offsets}
    ids, image = np.zeros((3, seq), np.int32), np.zeros(seq, bool)
    at = p = 0
    while at < seq:
        if at in starts:
            assert at + gh * gw <= seq, (at, grid, seq)
            rows, cols = np.divmod(np.arange(gh * gw), gw)
            ids[:, at:at + gh * gw] = p + np.stack(
                [np.zeros_like(rows), rows, cols])
            image[at:at + gh * gw] = True
            at, p = at + gh * gw, p + max(gh, gw)
        else:
            ids[:, at] = p
            at, p = at + 1, p + 1
    return ids, image


def _init(c: KeyeConfig):
    return nn.initializers.normal(c.initializer_range)


class Keye(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, input_ids, position_ids):
        """``input_ids`` [B, T], ``position_ids`` [3, B, T] -> (hidden [B,
        T, d] after the final norm, tokens [layers, held], the indexers' KL
        [layers], each a layer's sum over its queries). The head's matrix is
        the parameter ``lm_head``, [V, d]."""
        c = self.config
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        steptrace.record_counters("model/layer_kinds", {
            "sparse": c.num_hidden_layers, "expert": c.num_hidden_layers,
            "layers": c.num_hidden_layers,
            "published_layers": KeyeConfig.num_hidden_layers,
            "topk": c.topk})
        # one table, [B, T, head_dim / 2], its pairs from three rows
        with device_scope("mixer"):
            cos, sin = rope_table(c.head_dim, position_ids, {
                "rope_type": "default", "rope_theta": c.rope_theta,
                "mrope_section": c.mrope_section})
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens, kl = on_batch_axes(embed(input_ids)), [], []
        for i in range(c.num_hidden_layers):
            x, n, layer_kl = block(c, sparse=c.indexer, name=f"layers_{i}")(
                x, cos, sin)
            tokens.append(n)
            kl.append(layer_kl)
        return (RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x),
                jnp.stack(tokens), jnp.stack(kl))


def loss_fn(params, model, batch):
    """-> (loss, {"lm_loss", "index_loss", "tokens_per_expert"}) over
    ``batch = {"input_ids", "labels", "position_ids" [3, B, T],
    "loss_weights" [B, T] (1 where the target is text; left out: every
    target)}``: ``L_lm + L_I`` of this file's docstring."""
    c = model.config
    ids = batch["input_ids"]
    hidden, tokens, kl = model.apply({"params": params}, ids,
                                     batch["position_ids"])
    head, labels = params["lm_head"], batch["labels"]
    weights = batch.get("loss_weights")
    with device_scope("vocab"):
        if weights is None:
            weights = jnp.ones(labels.shape, jnp.float32)
        if c.loss_chunks:
            lm = xent.chunked_xent(hidden, head, labels, weights,
                                   n_chunks=c.loss_chunks)
        else:
            ll = xent.token_log_likelihood(
                hidden @ head.T.astype(hidden.dtype), labels)
            lm = -(ll * weights).sum() / weights.sum()
    with device_scope("mixer"):   # the indexers' loss
        index = kl.sum() / (c.num_hidden_layers * ids.size)
    return lm + index, {"lm_loss": lm, "index_loss": index,
                        "tokens_per_expert": tokens}


def init_params(config: KeyeConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Keye(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Keye(dataclasses.replace(config, remat=False, attention="xla"))
    seq = 8
    return model, init.init(
        rng, jnp.zeros((1, seq), jnp.int32),
        jnp.zeros((3, 1, seq), jnp.int32))["params"]


def make_train_state(config: KeyeConfig, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss,
    index_loss, lm_loss, tokens_per_expert)`` (the aux's leaves in their
    keys' order): ``parallel.build_train_step`` over this model's loss."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate,
        has_aux=True)


def step_metrics(loss, index_loss, lm_loss, tokens_per_expert, *,
                 pairs=None) -> dict:
    """What a loop hands ``train.report`` after a step of
    ``build_train_step``: the loss, its two terms and the held experts' load
    (``mla_moe.held_expert_load``; ``pairs`` is the step's tokens x
    ``num_experts_per_tok``), and the same as one ``counters`` record
    ``train/step_aux``. Reads the results back to the host, in one round:
    call it where the loop reads its loss."""
    loss, index_loss, lm_loss, tokens_per_expert = jax.device_get(
        (loss, index_loss, lm_loss, tokens_per_expert))
    metrics = {"loss": float(loss), "lm_loss": float(lm_loss),
               "index_loss": float(index_loss),
               **held_expert_load(tokens_per_expert, pairs)}
    steptrace.record_counters("train/step_aux", metrics)
    return metrics
