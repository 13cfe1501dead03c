"""GPT-2 in Flax — the flagship benchmark model (124M config).

The reference benches Ray Train with torch GPT-2 DDP (ray: release/air_tests/
air_benchmarks/ + driver BASELINE config "GPT-2-124M data-parallel").
TPU-native: params in f32, compute in bf16 so matmuls hit the MXU; batch
sharded over the data/fsdp mesh axes; gradients reduced by the XLA partitioner
from the sharding annotations; optional remat recomputes a block in backward,
all of it but the flash kernel where it ran (``ops.remat.remat_policy``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu._private.steptrace import device_scope
from ray_tpu.ops.attention import causal_self_attention
from ray_tpu.ops.remat import remat_policy
from ray_tpu.ops.xent import (chunked_xent, fused_xent,
                              token_log_likelihood)
from ray_tpu.parallel import train_step
from ray_tpu.parallel.mesh_utils import (on_batch_axes, replicated,
                                         shard_params_fsdp)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # "auto": by backend and shape (``ops.attention.auto_attention``): the
    # "flash" path where it was measured faster than XLA's, else "xla";
    # "xla": jax.nn.dot_product_attention — on this runtime plain XLA
    # fusions that write the [B, H, T, T] scores to HBM, forward and saved
    # for backward, and no kernel;
    # "flash": ray_tpu.ops Pallas/scan flash kernel;
    # "ring": sequence-parallel ring attention — the model must run inside
    # shard_map with mesh axis ``sp_axis`` sharding the sequence dim
    # (use build_train_step_sp).
    attention: str = "auto"
    sp_axis: str = "sp"
    # >0: compute the LM loss in ``loss_chunks`` sequence chunks, the head's
    # gradient formed beside each chunk's loss (``ops.xent.chunked_xent``): the
    # [B, T, vocab] logits tensor (12.3GB f32 at batch 64 / seq 1024) never
    # materializes, peak loss memory is one chunk's logits, and nothing is
    # recomputed: the three vocabulary matmuls the fused loss runs too.
    loss_chunks: int = 0

    @classmethod
    def gpt2_124m(cls, **kw):
        return cls(**kw)

    @classmethod
    def small_test(cls, **kw):
        base = dict(vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4)
        base.update(kw)
        return cls(**base)

    def num_params(self) -> int:
        wpe = self.n_positions * self.n_embd
        wte = self.vocab_size * self.n_embd
        block = 12 * self.n_embd * self.n_embd + 13 * self.n_embd
        return wte + wpe + self.n_layer * block + 2 * self.n_embd


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        c = self.config
        B, T, C = x.shape
        qkv = nn.Dense(3 * C, dtype=c.dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        heads = c.n_head
        q = on_batch_axes(q.reshape(B, T, heads, C // heads))
        k = on_batch_axes(k.reshape(B, T, heads, C // heads))
        v = on_batch_axes(v.reshape(B, T, heads, C // heads))
        if c.attention == "ring":
            from ray_tpu.ops import ring_attention

            bhsd = lambda t: t.transpose(0, 2, 1, 3)
            y = ring_attention(
                bhsd(q), bhsd(k), bhsd(v), axis_name=c.sp_axis, causal=True
            ).transpose(0, 2, 1, 3)
        else:
            y = causal_self_attention(q, k, v, c.attention)
        y = on_batch_axes(y.reshape(B, T, C))
        return nn.Dense(C, dtype=c.dtype, name="c_proj")(y)


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        c = self.config
        h = nn.Dense(4 * c.n_embd, dtype=c.dtype, name="c_fc")(x)
        h = on_batch_axes(h)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(c.n_embd, dtype=c.dtype, name="c_proj")(h)


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        c = self.config
        # a class holds its residual sum; the innermost segment decides
        with device_scope("mixer"):
            with device_scope("norm"):
                h = nn.LayerNorm(dtype=c.dtype, name="ln_1")(x)
            x = on_batch_axes(x + CausalSelfAttention(c, name="attn")(
                h, deterministic))
        with device_scope("mlp"):
            with device_scope("norm"):
                h = nn.LayerNorm(dtype=c.dtype, name="ln_2")(x)
            x = on_batch_axes(x + MLP(c, name="mlp")(h, deterministic))
        return x


class GPT2(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic=True, return_hidden=False):
        c = self.config
        B, T = input_ids.shape
        wte = nn.Embed(c.vocab_size, c.n_embd, dtype=c.dtype, name="wte")
        wpe = nn.Embed(c.n_positions, c.n_embd, dtype=c.dtype, name="wpe")
        pos = jnp.arange(T)[None, :]
        if c.attention == "ring":
            # under shard_map T is the LOCAL sequence chunk; offset to
            # global positions for this sequence shard
            pos = pos + jax.lax.axis_index(c.sp_axis) * T
        # nn.Embed's own lookup, on a table gathered whole first: looked up
        # in its shards at rest (split by features) the rows would come
        # back split by features and cross to the batch axes by all-to-all
        with device_scope("vocab"):
            table = on_batch_axes(wte.embedding.astype(c.dtype),
                                  batch_dim=None)
            x = on_batch_axes(jnp.take(table, input_ids, axis=0) + wpe(pos))
        block = Block
        if c.remat:
            block = nn.remat(Block, static_argnums=(2,), policy=remat_policy())
        for i in range(c.n_layer):
            x = block(c, name=f"h_{i}")(x, deterministic)
        with device_scope("norm"):
            x = on_batch_axes(nn.LayerNorm(dtype=c.dtype, name="ln_f")(x))
        if return_hidden:
            # chunked-loss path: hand back the final hidden states so the
            # loss can run the tied vocab matmul chunk by chunk
            return x
        # weight-tied LM head; bf16 matmul (MXU) — loss upcasts per-element
        with device_scope("vocab"):
            logits = on_batch_axes(wte.attend(x))
        return logits


def loss_fn(params, model, batch):
    c = model.config
    if c.loss_chunks:
        hidden = model.apply(
            {"params": params}, batch["input_ids"], return_hidden=True
        )
        return chunked_xent(
            hidden, params["wte"]["embedding"], batch["labels"],
            batch.get("mask"), n_chunks=c.loss_chunks,
        )
    logits = model.apply({"params": params}, batch["input_ids"])
    return fused_xent(logits, batch["labels"], batch.get("mask"))


def init_params(config: GPT2Config, rng):
    """Model + freshly initialized params (no optimizer state)."""
    model = GPT2(config)
    dummy = jnp.zeros((1, min(8, config.n_positions)), dtype=jnp.int32)
    init_model = model
    if config.attention == "ring":
        # ring attention needs a bound mesh axis; param shapes don't depend
        # on the attention impl, so initialize outside shard_map without it
        init_model = GPT2(dataclasses.replace(config, attention="auto"))
    return model, init_model.init(rng, dummy)["params"]


def make_optimizer(learning_rate: float = 3e-4, weight_decay: float = 0.1):
    """The one adamw recipe every train-state builder shares — PP runs are
    loss-matched against DP runs, so the hyperparams must not fork."""
    return optax.adamw(learning_rate, b1=0.9, b2=0.95,
                       weight_decay=weight_decay)


def make_train_state(config: GPT2Config, rng, learning_rate: float = 3e-4,
                     weight_decay: float = 0.1):
    model, params = init_params(config, rng)
    tx = make_optimizer(learning_rate, weight_decay)
    return model, params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted (params, opt_state, batch) -> (params, opt_state, loss):
    ``parallel.build_train_step`` over this model's loss. The layout is
    the placed arguments' (``shard_train_state`` / ``shard_batch`` first),
    and the model keeps its activations on the batch axes
    (``mesh_utils.on_batch_axes``); on one device both add nothing."""
    return train_step.build_train_step(
        lambda params, batch: loss_fn(params, model, batch), tx, donate)


def build_train_step_sp(model, tx, mesh: Mesh, *, sp_axis: str = "sp",
                        batch_axis: str = "data", donate: bool = True):
    """Sequence-parallel train step: batch dim sharded over ``batch_axis``,
    sequence dim over ``sp_axis`` (ring attention on the ICI ring inside
    shard_map); params replicated, gradients pmean'd over both axes.

    The model must have been built with ``attention="ring"``.
    """
    from jax import shard_map

    axes = (batch_axis, sp_axis)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, model, batch)
        grads = jax.lax.pmean(grads, axes)
        loss = jax.lax.pmean(loss, axes)
        with device_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    bspec = PartitionSpec(batch_axis, sp_axis)
    fn = shard_map(
        local_step, mesh=mesh,
        in_specs=(PartitionSpec(), PartitionSpec(),
                  {"input_ids": bspec, "labels": bspec}),
        out_specs=(PartitionSpec(), PartitionSpec(), PartitionSpec()),
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


def shard_train_state(params, opt_state, mesh: Mesh, fsdp: bool = False):
    """Place params + optimizer state on the mesh (DP replicate or FSDP
    shard); optimizer moments inherit their parameter's sharding
    (``parallel.place_train_state``)."""
    if fsdp:
        p_sh = shard_params_fsdp(params, mesh)
    else:
        p_sh = jax.tree.map(lambda _: replicated(mesh), params)
    return train_step.place_train_state(params, opt_state, p_sh)


def shard_params_tp(params, mesh: Mesh, model_axis: str = "model"):
    """Megatron-style tensor parallelism as GSPMD sharding annotations.

    No model-code changes: column-shard the first matmul of each pair
    (attention qkv, MLP up-projection) and row-shard the second (attention
    output, MLP down-projection) over ``model_axis``; XLA's partitioner
    propagates the sharding through the reshape into attention heads and
    inserts the one allreduce per block after each row-sharded matmul —
    the same comm pattern Megatron hand-codes with NCCL (reference
    exercises TP via Alpa release tests,
    ray: release/alpa_tests/train_opt_2_7b_minimum.py; SURVEY §2.9).

    Embeddings, layernorms, and the (tied) LM head stay replicated: at
    GPT-2 scale the vocab matmul is cheap relative to the blocks, and a
    replicated wte keeps the fused cross-entropy local.
    """
    col = PartitionSpec(None, model_axis)  # shard output features
    row = PartitionSpec(model_axis, None)  # shard input features
    colb = PartitionSpec(model_axis)       # bias of a column-sharded matmul
    rep = PartitionSpec()

    def spec_for(path) -> PartitionSpec:
        keys = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        if "c_attn" in keys or "c_fc" in keys:
            return col if keys[-1] == "kernel" else colb
        if "c_proj" in keys:
            return row if keys[-1] == "kernel" else rep
        return rep

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path)), params
    )


def shard_train_state_tp(params, opt_state, mesh: Mesh,
                         model_axis: str = "model"):
    """Place params + optimizer state with TP sharding (moments inherit
    their parameter's layout)."""
    return train_step.place_train_state(
        params, opt_state, shard_params_tp(params, mesh, model_axis))


def make_pipeline_train_state(config: GPT2Config, rng, n_stages: int,
                              learning_rate: float = 3e-4,
                              weight_decay: float = 0.1):
    """Pipeline-parallel train state: the transformer blocks are regrouped
    into ``n_stages`` stages with a leading (stage, layers_per_stage) axis
    pair (shard the stage axis over the ``pipeline`` mesh axis); embeddings
    and the final layernorm stay replicated (they run on every pipeline
    rank; their grads are completed by a psum — see build_train_step_pp).

    Initialized from the SAME init as make_train_state, so a PP run is
    numerically comparable to the DP run of the same seed."""
    from ray_tpu.parallel.pipeline import stack_stage_params

    if config.n_layer % n_stages != 0:
        raise ValueError(f"n_layer={config.n_layer} not divisible by "
                         f"n_stages={n_stages}")
    per_stage = config.n_layer // n_stages
    _, params = init_params(config, rng)
    blocks = [params[f"h_{i}"] for i in range(config.n_layer)]
    stages = stack_stage_params([
        stack_stage_params(blocks[s * per_stage:(s + 1) * per_stage])
        for s in range(n_stages)
    ])
    pp_params = {
        "stages": stages,
        "embed": {
            "wte": params["wte"], "wpe": params["wpe"],
            "ln_f": params["ln_f"],
        },
    }
    tx = make_optimizer(learning_rate, weight_decay)
    return pp_params, tx, tx.init(pp_params)


def shard_pipeline_state(pp_params, opt_state, mesh: Mesh,
                         axis: str = "pipeline"):
    """Place PP params + optimizer moments: stage leaves sharded over the
    pipeline axis (leading dim), everything else replicated."""
    stage_sh = NamedSharding(mesh, PartitionSpec(axis))
    p_sh = {
        "stages": jax.tree.map(lambda _: stage_sh, pp_params["stages"]),
        "embed": jax.tree.map(lambda _: replicated(mesh), pp_params["embed"]),
    }
    return train_step.place_train_state(pp_params, opt_state, p_sh)


def build_train_step_pp(config: GPT2Config, tx, mesh: Mesh, *,
                        n_microbatches: int, axis: str = "pipeline",
                        batch_axis: str = "data", donate: bool = True):
    """Pipelined train step over a (data, pipeline) mesh.

    Inside shard_map, each pipeline rank embeds the (replicated-within-
    pipeline, sharded-over-data) batch, runs its OWN stage of blocks in the
    ppermute pipeline (ray_tpu.parallel.pipeline), and the LAST rank's
    head + loss is broadcast back with a psum. Grad bookkeeping:
    - stage grads arrive complete on their owning rank (cotangents routed
      by the reverse ppermute chain) — no pipeline reduction;
    - replicated embed/head grads are partial per rank (loss path lands on
      the last rank, the injection path on rank 0) — a psum over the
      pipeline axis completes them;
    - everything is then pmean'd over the data axis (plain DP).
    """
    from ray_tpu.parallel.pipeline import pipeline_apply

    from jax import shard_map

    block = Block(config)
    ln_f = nn.LayerNorm(dtype=config.dtype)

    def stage_fn(stage_params, x):
        def body(h, p):
            return block.apply({"params": p}, h), None

        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    def local_grads(params, batch):
        ids, labels = batch["input_ids"], batch["labels"]
        B, T = ids.shape
        M = n_microbatches
        assert B % M == 0, (B, M)

        def loss_of(params):
            emb = params["embed"]
            x = (emb["wte"]["embedding"][ids]
                 + emb["wpe"]["embedding"][jnp.arange(T)][None])
            x = x.astype(config.dtype)
            mb = x.reshape(M, B // M, T, x.shape[-1])
            own = jax.tree.map(lambda p: p[0], params["stages"])
            y = pipeline_apply(stage_fn, own, mb, axis_name=axis)
            y = y.reshape(B, T, -1).astype(config.dtype)
            y = ln_f.apply({"params": emb["ln_f"]}, y)
            logits = y @ emb["wte"]["embedding"].astype(config.dtype).T
            ll = token_log_likelihood(logits, labels)
            mask = batch.get("mask")
            mask = jnp.ones_like(ll) if mask is None else mask
            # Global token-weighted normalization, like the DP loss_fn over
            # the full batch: sum the masked ll and the mask count across
            # the data axis so shards with fewer valid tokens don't get
            # up-weighted (a pmean of per-shard masked means would).
            # Masking to the LAST pipeline rank pins the head/loss grad
            # path to one rank, so the psum over the pipeline axis below
            # completes replicated-param grads exactly once.
            is_last = jax.lax.axis_index(axis) == jax.lax.axis_size(axis) - 1
            numer = jax.lax.psum(
                jnp.where(is_last, -(ll * mask).sum(), 0.0),
                (axis, batch_axis),
            )
            denom = jax.lax.psum(
                jnp.where(is_last, mask.sum(), 0.0), (axis, batch_axis)
            )
            return numer / jnp.maximum(denom, 1.0)

        # loss_of is the GLOBAL loss (psum-normalized inside), identical on
        # every mesh cell; each cell's grads are partials of that one
        # scalar, so replicated params complete with a SUM over the axes
        # they are replicated on (stages: data only; embed: both).
        loss, grads = jax.value_and_grad(loss_of)(params)
        grads = {
            "stages": jax.lax.psum(grads["stages"], batch_axis),
            "embed": jax.lax.psum(grads["embed"], (axis, batch_axis)),
        }
        return loss, grads

    param_specs = {
        "stages": PartitionSpec(axis),
        "embed": PartitionSpec(),
    }
    # single spec = pytree prefix: every batch leaf (input_ids, labels,
    # optional mask) shards its leading batch dim over the data axis
    bspec = PartitionSpec(batch_axis)
    grad_fn = shard_map(
        local_grads, mesh=mesh,
        in_specs=(param_specs, bspec),
        out_specs=(PartitionSpec(), param_specs),
    )

    def step(params, opt_state, batch):
        loss, grads = grad_fn(params, batch)
        with device_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def shard_batch(batch, mesh: Mesh):
    from ray_tpu.parallel.mesh_utils import data_sharding

    sh = data_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)


def synthetic_batch(rng, batch_size: int, seq_len: int, vocab: int):
    ids = jax.random.randint(rng, (batch_size, seq_len + 1), 0, vocab, dtype=jnp.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
