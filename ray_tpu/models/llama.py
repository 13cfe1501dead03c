"""Llama-family decoder in Flax — the flagship *serving* model.

The driver's BASELINE config benches "Serve Llama-2-7B with TPU replica
autoscaling" (BASELINE.md notes; reference serves LLMs through
ray: python/ray/serve + vLLM in release tests). TPU-native design:

- params f32 (or bf16 for serving), compute bf16 so matmuls hit the MXU;
- RoPE / RMSNorm / SwiGLU / grouped-query attention (GQA) — the Llama-2/3
  architecture family, selected by config;
- prefill + decode split for serving: prefill is one big causal-attention
  matmul pass (MXU-bound), decode is a KV-cache step with static shapes so
  the compiled step is reused every token (no retrace, no dynamic shapes);
- tensor-parallel sharding as GSPMD annotations (column/row like GPT-2's
  ``shard_params_tp``) with the KV cache sharded over heads, so a 7B fits
  across a v5e slice and decode allreduces ride ICI.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu._private.steptrace import device_scope
from ray_tpu.ops.xent import fused_xent
from ray_tpu.parallel import train_step


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_embd: int = 4096
    n_head: int = 32
    n_kv_head: int = 32          # < n_head => GQA (Llama-2-70B / Llama-3 style)
    intermediate: int = 11008    # SwiGLU hidden dim
    n_positions: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama3_8b(cls, **kw):
        base = dict(vocab_size=128256, n_embd=4096, n_layer=32, n_head=32,
                    n_kv_head=8, intermediate=14336, n_positions=8192,
                    rope_theta=500000.0)
        base.update(kw)
        return cls(**base)

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=256, n_layer=2, n_embd=64, n_head=4,
                    n_kv_head=2, intermediate=128, n_positions=128)
        base.update(kw)
        return cls(**base)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        emb = self.vocab_size * self.n_embd
        attn = (self.n_embd * self.n_embd
                + 2 * self.n_embd * self.n_kv_head * self.head_dim
                + self.n_embd * self.n_embd)
        mlp = 3 * self.n_embd * self.intermediate
        block = attn + mlp + 2 * self.n_embd
        # untied LM head
        return 2 * emb + self.n_layer * block + self.n_embd


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    @device_scope("norm")
    def __call__(self, x):
        # normalize in f32 (rsqrt of a bf16 mean-square loses mantissa),
        # scale in compute dtype
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (n * scale).astype(self.dtype)


class RMSNormScale(nn.Module):
    """The scale of an ``RMSNorm`` whose arithmetic its caller's function
    does (a head's norm inside
    ``ops.attention.normed_rotary_self_attention``): the same parameter
    under the same name, [width], ones."""
    width: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.width,))


def rope_frequencies(head_dim: int, positions, theta: float):
    """(..., T) int positions -> cos/sin of shape (..., T, head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def yarn_correction_range(head_dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the rotary dimensions between which YaRN blends. A
    dimension that turns ``r`` times over the ``original`` positions is
    ``c(r) = head_dim ln(original / (2 pi r)) / (2 ln theta)``; below
    ``low = floor(c(beta_fast))`` the published frequency stands, from
    ``high = ceil(c(beta_slow))`` on it is divided by the factor (both held
    to the head's dimensions), as ``transformers``'
    ``_compute_yarn_parameters`` states it."""
    turns = lambda r: (head_dim * math.log(original / (2 * math.pi * r))
                       / (2 * math.log(theta)))
    return (max(math.floor(turns(beta_fast)), 0),
            min(math.ceil(turns(beta_slow)), head_dim - 1))


def rope_table(head_dim: int, positions, parameters):
    """(..., T) int positions -> cos/sin (..., T, head_dim//2) by one entry
    of a config's ``rope_parameters`` (a mapping, or its pairs): ``rope_type``
    ``default`` is ``rope_frequencies`` at ``rope_theta``, to the bit;
    ``yarn`` blends each frequency between itself and itself over ``factor``
    along the ramp ``clip((i - low) / (high - low), 0, 1)`` of
    ``yarn_correction_range`` and multiplies cos and sin by
    ``attention_factor`` (left out: ``0.1 ln(factor) + 1``), so that a
    layer's scores carry its square. Static: the same table at every
    sequence length. One ``counters`` record ``rope/table`` a traced call
    says what was built.

    Given ``mrope_section`` (three counts that add up to ``head_dim // 2``;
    ``rope_type`` ``default``), ``positions`` is (3, ..., T), a token's
    temporal, height and width components, and frequency pair ``i`` takes
    its angle from the row its section names: the first ``section[0]``
    pairs from row 0, the next ``section[1]`` from row 1, the rest from row
    2 (in order, not interleaved). Three equal rows give the plain table to
    the bit; the record says the sections."""
    p = dict(parameters)
    kind, theta = p.get("rope_type", "default"), float(p["rope_theta"])
    said = {"kind": {"default": "plain"}.get(kind, kind), "theta": theta,
            "factor": 1.0, "original": 0, "low": 0, "high": 0,
            "attention_factor": 1.0, "dims": head_dim}
    section = tuple(p.get("mrope_section") or ())
    if section:
        if kind != "default" or len(section) != 3 or sum(
                section) != head_dim // 2 or positions.shape[0] != 3:
            raise ValueError(
                f"mrope_section {section}: three counts that add up to "
                f"{head_dim // 2}, rope_type default, positions (3, ..., T)")
        steptrace.record_counters("rope/table", {
            **said, "kind": "sections", "section_t": section[0],
            "section_h": section[1], "section_w": section[2]})
        cos, sin = rope_frequencies(head_dim, positions, theta)
        row = np.repeat(np.arange(3), section)      # a pair's row
        pick = lambda t: jnp.moveaxis(
            t[row, ..., np.arange(head_dim // 2)], 0, -1)
        return pick(cos), pick(sin)
    if kind == "default":
        steptrace.record_counters("rope/table", said)
        return rope_frequencies(head_dim, positions, theta)
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: expected default or yarn")
    factor = float(p["factor"])
    original = int(p["original_max_position_embeddings"])
    low, high = yarn_correction_range(
        head_dim, theta, original, float(p.get("beta_fast", 32)),
        float(p.get("beta_slow", 1)))
    scale = float(p.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    steptrace.record_counters("rope/table", {
        **said, "factor": factor, "original": original, "low": low,
        "high": high, "attention_factor": scale})
    plain = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = (1.0 - ramp) * plain + ramp * plain / factor
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin):
    """x: (B, T, H, D); rotate pairs (even, odd) by the position angle."""
    x1, x2 = x[..., ::2], x[..., 1::2]
    # cos/sin: (B, T, D/2) -> broadcast over heads
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = xf1 * c - xf2 * s
    r2 = xf2 * c + xf1 * s
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_index=None):
        """Full-sequence causal pass when ``kv_cache`` is None; otherwise a
        decode step: x is (B, 1, C), cache holds (k, v) of shape
        (B, n_positions, n_kv_head, D), cache_index is the write offset."""
        c = self.config
        B, T, C = x.shape
        D = c.head_dim
        q = nn.Dense(c.n_head * D, use_bias=False, dtype=c.dtype,
                     name="q_proj")(x).reshape(B, T, c.n_head, D)
        k = nn.Dense(c.n_kv_head * D, use_bias=False, dtype=c.dtype,
                     name="k_proj")(x).reshape(B, T, c.n_kv_head, D)
        v = nn.Dense(c.n_kv_head * D, use_bias=False, dtype=c.dtype,
                     name="v_proj")(x).reshape(B, T, c.n_kv_head, D)
        cos, sin = rope_frequencies(D, positions, c.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

        new_cache = None
        if kv_cache is None:
            # prefill / training: XLA's own attention, on this runtime
            # plain fusions that write the [B, H, T, T] scores to HBM (8.491
            # ms a layer against the Pallas kernel's 3.166 at GPT-2's
            # geometry: PERF.md section 6, PR 25). ops.attention's kernel
            # takes as many key-value heads as query heads, so grouped
            # heads stay here (ROADMAP Reach 6-9)
            y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        else:
            ck, cv = kv_cache
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, cache_index, 0, 0))
            new_cache = (ck, cv)
            # causal relative to the cache: query i (global position
            # cache_index + i) sees key j iff j <= cache_index + i. Covers
            # both T=1 decode and T-wide prefill through the cache path.
            q_pos = cache_index + jnp.arange(T)
            k_pos = jnp.arange(ck.shape[1])
            bias = jnp.where(k_pos[None, :] <= q_pos[:, None], 0.0, -1e9)
            y = jax.nn.dot_product_attention(
                q, ck, cv,
                bias=bias[None, None, :, :].astype(jnp.float32),
            )
        y = y.reshape(B, T, c.n_head * D)
        out = nn.Dense(C, use_bias=False, dtype=c.dtype, name="o_proj")(y)
        return out, new_cache


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))`` of inner width ``intermediate``, no
    biases: this family's feed-forward part, and the dense and shared parts
    of ``models/mla_moe.py``."""
    intermediate: int
    dtype: Any = jnp.bfloat16
    kernel_init: Any = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  kernel_init=self.kernel_init)
        g = dense(self.intermediate, name="gate_proj")(x)
        u = dense(self.intermediate, name="up_proj")(x)
        return dense(x.shape[-1], name="down_proj")(nn.silu(g) * u)


class ReLU2(nn.Module):
    """``down(relu(up(x))^2)`` of inner width ``intermediate``, no gate and
    no biases: the shared expert beside ``models/mla_moe.RoutedExperts``'s
    un-gated experts (``activation="relu2"``)."""
    intermediate: int
    dtype: Any = jnp.bfloat16
    kernel_init: Any = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  kernel_init=self.kernel_init)
        u = dense(self.intermediate, name="up_proj")(x)
        return dense(x.shape[-1], name="down_proj")(
            jnp.square(nn.relu(u)))


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_index=None):
        c = self.config
        with device_scope("mixer"):
            h, new_cache = LlamaAttention(c, name="attn")(
                RMSNorm(c.rms_eps, c.dtype, name="input_norm")(x),
                positions, kv_cache, cache_index,
            )
            x = x + h
        with device_scope("mlp"):
            x = x + SwiGLU(c.intermediate, c.dtype, name="mlp")(
                RMSNorm(c.rms_eps, c.dtype, name="post_attn_norm")(x)
            )
        return x, new_cache


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, kv_caches=None,
                 cache_index=None):
        """Returns (logits, new_kv_caches). ``kv_caches`` is a list of
        per-layer (k, v) for decode, or None for prefill/training."""
        c = self.config
        B, T = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        with device_scope("vocab"):
            x = nn.Embed(c.vocab_size, c.n_embd, dtype=c.dtype,
                         name="embed")(input_ids)
        block = LlamaBlock
        if c.remat and kv_caches is None:
            block = nn.remat(LlamaBlock, static_argnums=())
        new_caches = []
        for i in range(c.n_layer):
            cache = kv_caches[i] if kv_caches is not None else None
            x, nc = block(c, name=f"h_{i}")(x, positions, cache, cache_index)
            new_caches.append(nc)
        x = RMSNorm(c.rms_eps, c.dtype, name="norm")(x)
        with device_scope("vocab"):
            logits = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                              name="lm_head")(x)
        if kv_caches is None:
            return logits, None
        return logits, new_caches


def init_params(config: LlamaConfig, rng):
    model = Llama(config)
    dummy = jnp.zeros((1, min(8, config.n_positions)), dtype=jnp.int32)
    return model, model.init(rng, dummy)["params"]


def loss_fn(params, model, batch):
    logits, _ = model.apply({"params": params}, batch["input_ids"])
    return fused_xent(logits, batch["labels"], batch.get("mask"))


def build_train_step(model, tx, donate: bool = True):
    """Jitted (params, opt_state, batch) -> (params, opt_state, loss);
    sharding inferred from placed args, same contract as gpt2's."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate)


def init_kv_caches(config: LlamaConfig, batch_size: int,
                   max_len: Optional[int] = None, dtype=None):
    """Static-shape per-layer (k, v) caches for decode."""
    L = max_len or config.n_positions
    dtype = dtype or config.dtype
    shape = (batch_size, L, config.n_kv_head, config.head_dim)
    return [
        (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        for _ in range(config.n_layer)
    ]


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
def _decode_step(model, params, token, index, caches):
    B = token.shape[0]
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    logits, caches = model.apply(
        {"params": params}, token, positions=positions,
        kv_caches=caches, cache_index=index,
    )
    return logits[:, -1, :], caches


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def _prefill(model, params, ids, caches):
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    logits, caches = model.apply(
        {"params": params}, ids, positions=positions,
        kv_caches=caches, cache_index=0,
    )
    return logits[:, -1, :], caches


def build_decode_step(model: Llama):
    """Jitted single-token decode: (params, token, index, caches) ->
    (next_token_logits, new_caches). Static shapes end to end — one compile
    per (model, shapes), cached module-level (flax modules hash by
    structure, so repeated generate() calls reuse the executable); ``index``
    is a traced scalar so position advance doesn't retrace."""
    return functools.partial(_decode_step, model)


def generate(model: Llama, params, prompt_ids, max_new_tokens: int,
             temperature: float = 0.0, rng=None):
    """Greedy/sampled generation: one cache-filling prefill pass, then
    jitted decode steps. Prompt shapes are static per (B, T) pair; both
    compiled steps are cached across calls (see build_decode_step)."""
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 requires an explicit rng key")
    config = model.config
    B, T = prompt_ids.shape
    caches = init_kv_caches(config, B, max_len=T + max_new_tokens)

    logits, caches = _prefill(model, params, prompt_ids, caches)
    decode = build_decode_step(model)

    out = [prompt_ids]
    tok = None
    for i in range(max_new_tokens):
        if temperature > 0.0:
            rng, sub = jax.random.split(rng)
            tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = tok[:, None].astype(jnp.int32)
        out.append(tok)
        if i + 1 < max_new_tokens:
            logits, caches = decode(params, tok, jnp.int32(T + i), caches)
    return jnp.concatenate(out, axis=1)


def shard_params_tp(params, mesh: Mesh, model_axis: str = "model"):
    """Megatron-style TP for the Llama family: q/k/v and gate/up are
    column-sharded (output features over ``model_axis``), o_proj/down_proj
    row-sharded; XLA inserts one allreduce per block after each row-sharded
    matmul. Embedding + lm_head column-sharded over vocab is skipped at this
    scale — both stay replicated, norms replicated."""
    col = PartitionSpec(None, model_axis)
    row = PartitionSpec(model_axis, None)
    rep = PartitionSpec()

    def spec_for(path) -> PartitionSpec:
        keys = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        if any(k in keys for k in ("q_proj", "k_proj", "v_proj",
                                   "gate_proj", "up_proj")):
            return col
        if any(k in keys for k in ("o_proj", "down_proj")):
            return row
        return rep

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path)), params
    )


def shard_kv_caches_tp(caches, mesh: Mesh, model_axis: str = "model"):
    """Shard decode KV caches over heads (axis 2) so cached attention stays
    local to each TP shard — decode's only cross-chip traffic is the o_proj
    allreduce."""
    sh = NamedSharding(mesh, PartitionSpec(None, None, model_axis, None))
    return jax.tree.map(lambda x: jax.device_put(x, sh), caches)


def synthetic_batch(rng, batch_size: int, seq_len: int, vocab: int):
    from ray_tpu.models.gpt2 import synthetic_batch as _sb

    return _sb(rng, batch_size, seq_len, vocab)
