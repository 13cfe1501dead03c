"""A decoder trained by block diffusion (the ``sdar_moe`` objective over
``qwen3_moe``'s layer equations, at whatever sizes the config gives): a
step reads every sequence TWICE, a noisy copy and the clean one, under one
mask by block, and learns to fill the noisy copy's masked positions.

- The objective. A data sequence ``x_0`` of ``L`` tokens is cut into blocks
  of ``block_length`` tokens. ``noise`` draws one rate a block, ``t`` in
  (0, 1] stratified over the step's blocks, ``p = (1 - noise_eps) t +
  noise_eps``, and masks each position of the block with probability ``p``:
  ``x_t[i] = mask_token_id`` there, ``x_0[i]`` elsewhere. The draw is part
  of the jitted step, from ``fold_in(PRNGKey(noise_seed), count)`` with
  ``count`` the optimizer's own (``parallel.build_train_step(...,
  with_count=True)``): every step redraws, and step 0's draw is a function
  of the config and the batch's shape alone.
- The trunk reads the ``2 L`` ids ``[x_t ; x_0]`` at positions ``[0..L-1 ;
  0..L-1]`` through every layer. In attention a noisy query sees the noisy
  keys of its own block and the clean keys of the blocks before it; a clean
  query the clean keys of its own block and of those before it, and no
  noisy key (``ops.attention.seen_by_block``; the flash kernels under that
  mask at the published width and the cell's length on a TPU, the dense
  mask in ``jnp`` elsewhere). ``num_attention_heads`` query heads read
  ``num_key_value_heads`` key-value heads; each head's queries and keys
  pass an RMSNorm over ``head_dim`` and the rotation of its halves at
  ``rope_theta`` (``ops.attention.normed_rotary_self_attention``, whose
  table holds a stream's positions twice); no bias anywhere.
- A block (``mellum.Block``, with ``blocks`` for the mask) has two norms:
  ``h = h + Attn(N1(h))``, ``h = h + F(N2(h))``. ``F`` is, in EVERY layer,
  the routed-expert layer of ``models/mla_moe.py`` (softmax scores over all
  ``num_experts``, ``num_experts_per_tok`` a token, weights normalised where
  ``norm_topk_prob``, no shared expert, the slice ``expert_shard`` held here
  and no pair dropped), over both streams.
- The final norm and the untied head run over the noisy stream's ``L``
  positions alone. The loss is ``(1 / (B L)) sum_i m_i (1 / p_i) (-log
  softmax(W h_i)[x_0[i]])``: the position's own token, no shift (a batch's
  ``labels`` go unused), weights whose denominator is not their sum
  (``ops.xent.chunked_xent(..., denom=B L)``).

Parameters are float32, compute is ``dtype``; the router's scores, every
softmax statistic and the norms' statistics are float32. The router's
selection bias is zero as published and takes a zero gradient; there is no
auxiliary loss. One ``counters`` record ``model/layer_kinds`` a traced pass
says what the stack holds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
from ray_tpu.models.afmoe import (  # noqa: F401 (this module's names too)
    param_shardings, shard_train_state)
from ray_tpu.models.gpt2 import make_optimizer  # the one AdamW recipe
from ray_tpu.models.llama import RMSNorm, rope_table
# the block is ``mellum``'s (head norms, the rotation, softmax-routed experts
# as every feed-forward, no shared expert), which asks of a config the
# published keys both families share, with ``blocks`` for the mask
from ray_tpu.models.mellum import Block
from ray_tpu.models.mla_moe import held_expert_load
from ray_tpu.ops import xent
from ray_tpu.ops.remat import remat_policy
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import on_batch_axes


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published keys under their published names, then the objective's
    (which the published config does not give). ``num_experts`` is the
    router's width, all experts of the model; ``expert_shard`` says which
    slice of them this program holds. ``mask_token_id`` None is the last
    row of the vocabulary held."""
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
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    block_length: int = 4
    noise_eps: float = 1e-3
    noise_seed: int = 0
    mask_token_id: Any = None
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
        if self.mask_token_id is None:
            object.__setattr__(self, "mask_token_id", self.vocab_size - 1)
        assert 0 <= self.mask_token_id < self.vocab_size

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_shard[1]

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=3, rope_theta=10000.0, loss_chunks=4)
        base.update(kw)
        return cls(**base)


def _init(c: SdarConfig):
    return nn.initializers.normal(c.initializer_range)


class Sdar(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, both_ids):
        """``both_ids`` [B, 2 L]: the noisy copy, then the clean one. ->
        (hidden [B, L, d] of the NOISY stream after the final norm, tokens
        [layers, held] over both streams). The head's matrix is the
        parameter ``lm_head``, [V, d]."""
        c = self.config
        _, T = both_ids.shape
        assert T % (2 * c.block_length) == 0, (T, c.block_length)
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                         embedding_init=_init(c), name="embed")
        self.param("lm_head", _init(c), (c.vocab_size, c.hidden_size))
        steptrace.record_counters("model/layer_kinds", {
            "block_diffusion": c.num_hidden_layers,
            "expert": c.num_hidden_layers, "layers": c.num_hidden_layers,
            "published_layers": SdarConfig.num_hidden_layers,
            "block_length": c.block_length, "streams": 2})
        # one table, [1, 2 L, head_dim / 2]: a stream's positions, twice
        positions = jnp.tile(jnp.arange(T // 2), 2)[None, :]
        with device_scope("mixer"):
            cos, sin = rope_table(c.head_dim, positions, {
                "rope_type": "default", "rope_theta": c.rope_theta})
        block = nn.remat(Block, policy=remat_policy()) if c.remat else Block
        with device_scope("vocab"):
            x, tokens = on_batch_axes(embed(both_ids)), []
        for i in range(c.num_hidden_layers):
            x, n = block(c, blocks=c.block_length, name=f"layers_{i}")(
                x, cos, sin)
            tokens.append(n)
        with device_scope("norm"):
            noisy = on_batch_axes(x[:, :T // 2])
            return (RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(noisy),
                    jnp.stack(tokens))


def noise(config: SdarConfig, input_ids, count):
    """The step's draw for ``input_ids`` [B, L] at the optimizer's
    ``count``: -> (x_t [B, L], masked [B, L] bool, p [B, L] float32, a
    block's rate at each of its positions). One offset ``u`` and one
    permutation of the step's ``n`` blocks give block ``k`` the rate ``t =
    1 - (u + perm[k] / n) mod 1``, in (0, 1] and stratified over the step."""
    c = config
    B, L = input_ids.shape
    n = B * (L // c.block_length)
    key = jax.random.fold_in(jax.random.PRNGKey(c.noise_seed), count)
    k_offset, k_order, k_mask = jax.random.split(key, 3)
    strata = (jax.random.uniform(k_offset, ())
              + jax.random.permutation(k_order, n) / n) % 1.0
    p = (1.0 - c.noise_eps) * (1.0 - strata) + c.noise_eps
    p = jnp.repeat(p.reshape(B, L // c.block_length), c.block_length, axis=1)
    masked = jax.random.uniform(k_mask, (B, L)) < p
    return jnp.where(masked, c.mask_token_id, input_ids), masked, p


def loss_fn(params, model, batch, count):
    """-> (loss, {"masked_share", "tokens_per_expert"}) over ``batch =
    {"input_ids"}`` at the optimizer's ``count``: block diffusion's
    weighted cross-entropy of the masked positions' own tokens, over every
    position there is."""
    c = model.config
    clean = batch["input_ids"]
    # the draw makes the ids the embedding looks up: the vocabulary's
    with device_scope("vocab"):
        noisy, masked, p = noise(c, clean, count)
        both = jnp.concatenate([noisy, clean], axis=1)
    hidden, tokens = model.apply({"params": params}, both)
    with device_scope("vocab"):
        head, weights = params["lm_head"], masked / p
        if c.loss_chunks:
            loss = xent.chunked_xent(hidden, head, clean, weights,
                                     n_chunks=c.loss_chunks, denom=clean.size)
        else:
            ll = xent.token_log_likelihood(
                hidden @ head.T.astype(hidden.dtype), clean)
            loss = -(ll * weights).sum() / clean.size
        return loss, {"masked_share": masked.mean(dtype=jnp.float32),
                      "tokens_per_expert": tokens}


def init_params(config: SdarConfig, rng):
    """Model + freshly initialised parameters (no optimizer state)."""
    model = Sdar(config)
    # parameter shapes do not depend on recomputation or on the path
    init = Sdar(dataclasses.replace(config, remat=False, attention="xla"))
    return model, init.init(
        rng, jnp.zeros((1, 2 * config.block_length), jnp.int32))["params"]


def make_train_state(config: SdarConfig, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted ``(params, opt_state, batch) -> (params, opt_state, loss,
    masked_share, tokens_per_expert)``: ``parallel.build_train_step`` over
    this model's loss, which is handed the optimizer's count."""
    return train_step.build_train_step(
        lambda params, batch, count: loss_fn(params, model, batch, count),
        tx, donate, has_aux=True, with_count=True)


def step_metrics(loss, masked_share, tokens_per_expert, *, pairs=None) -> dict:
    """What a loop hands ``train.report`` after a step of
    ``build_train_step``: the loss, the step's share of masked positions
    and the held experts' load (``mla_moe.held_expert_load``; ``pairs`` is
    the step's POSITIONS, both streams, x ``num_experts_per_tok``), and the
    same as one ``counters`` record ``train/step_aux``. Reads the results
    back to the host, in one round: call it where the loop reads its loss."""
    loss, masked_share, tokens_per_expert = jax.device_get(
        (loss, masked_share, tokens_per_expert))
    metrics = {"loss": float(loss), "masked_share": float(masked_share),
               **held_expert_load(tokens_per_expert, pairs)}
    steptrace.record_counters("train/step_aux", metrics)
    return metrics
