"""Vision models in Flax: ViT-B/16 and ResNet-50 — the Train/Tune bench models.

The driver's BASELINE configs bench "TorchTrainer ResNet-50/CIFAR-10" and
"Tune ASHA over ViT-B/16" (BASELINE.md notes; reference workloads under
ray: release/air_tests/air_benchmarks/workloads/). TPU-native: NHWC layout
(XLA's native conv layout on TPU — NCHW would transpose on every conv),
bf16 compute / f32 params, and batch-stat-free normalization options so the
train step stays a pure function under jit.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ray_tpu.parallel import train_step


# ---------------------------------------------------------------- ViT


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    mlp_dim: int = 3072
    num_classes: int = 1000
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @classmethod
    def vit_b16(cls, **kw):
        return cls(**kw)

    @classmethod
    def small_test(cls, **kw):
        base = dict(image_size=32, patch_size=8, n_embd=64, n_layer=2,
                    n_head=4, mlp_dim=128, num_classes=10)
        base.update(kw)
        return cls(**base)


class ViTBlock(nn.Module):
    config: ViTConfig

    @nn.compact
    def __call__(self, x, deterministic=True):
        c = self.config
        h = nn.LayerNorm(dtype=c.dtype)(x)
        B, T, C = h.shape
        D = C // c.n_head
        qkv = nn.Dense(3 * C, dtype=c.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        reshape = lambda t: t.reshape(B, T, c.n_head, D)
        y = jax.nn.dot_product_attention(reshape(q), reshape(k), reshape(v))
        y = nn.Dense(C, dtype=c.dtype, name="proj")(y.reshape(B, T, C))
        x = x + y
        h = nn.LayerNorm(dtype=c.dtype)(x)
        h = nn.Dense(c.mlp_dim, dtype=c.dtype)(h)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(C, dtype=c.dtype)(h)
        return x + h


class ViT(nn.Module):
    """ViT with learned position embeddings and a class token."""

    config: ViTConfig

    @nn.compact
    def __call__(self, images, deterministic=True):
        c = self.config
        B = images.shape[0]
        # patchify = one conv with stride=patch (a single big MXU matmul)
        x = nn.Conv(c.n_embd, (c.patch_size, c.patch_size),
                    strides=(c.patch_size, c.patch_size), dtype=c.dtype,
                    name="patch_embed")(images.astype(c.dtype))
        x = x.reshape(B, -1, c.n_embd)
        cls_tok = self.param("cls", nn.initializers.zeros, (1, 1, c.n_embd))
        x = jnp.concatenate(
            [jnp.broadcast_to(cls_tok, (B, 1, c.n_embd)).astype(c.dtype), x],
            axis=1,
        )
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, x.shape[1], c.n_embd))
        x = x + pos.astype(c.dtype)
        block = nn.remat(ViTBlock, static_argnums=(2,)) if c.remat else ViTBlock
        for i in range(c.n_layer):
            x = block(c, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_f")(x)
        return nn.Dense(c.num_classes, dtype=jnp.float32, name="head")(x[:, 0])


# ---------------------------------------------------------------- ResNet


class ResNetBlock(nn.Module):
    """Bottleneck block (1x1 -> 3x3 -> 1x1) with GroupNorm.

    GroupNorm instead of BatchNorm keeps the train step a pure function of
    (params, batch) — no mutable batch_stats collection to thread through
    jit/psum (the reference's torch ResNet syncs running stats through DDP;
    GN sidesteps that and matches accuracy at bench scale)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def norm(name=None):
            groups = min(32, self.filters)
            return nn.GroupNorm(num_groups=groups, dtype=self.dtype,
                                name=name)
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = nn.relu(norm()(y))
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.relu(norm()(y))
        y = nn.Conv(4 * self.filters, (1, 1), use_bias=False,
                    dtype=self.dtype)(y)
        y = norm()(y)
        if x.shape != y.shape:
            x = nn.Conv(4 * self.filters, (1, 1), self.strides,
                        use_bias=False, dtype=self.dtype, name="shortcut")(x)
            x = norm(name="shortcut_norm")(x)
        return nn.relu(x + y)


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: Sequence[int] = (3, 4, 6, 3)  # ResNet-50
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    # CIFAR stem: 3x3 stride-1 conv, no maxpool (32x32 inputs)
    cifar_stem: bool = False

    @classmethod
    def resnet50(cls, **kw):
        return cls(**kw)

    @classmethod
    def resnet50_cifar(cls, **kw):
        base = dict(num_classes=10, cifar_stem=True)
        base.update(kw)
        return cls(**base)

    @classmethod
    def small_test(cls, **kw):
        base = dict(stage_sizes=(1, 1), num_classes=10, width=16,
                    cifar_stem=True)
        base.update(kw)
        return cls(**base)


class ResNet(nn.Module):
    config: ResNetConfig

    @nn.compact
    def __call__(self, images):
        c = self.config
        x = images.astype(c.dtype)
        if c.cifar_stem:
            x = nn.Conv(c.width, (3, 3), use_bias=False, dtype=c.dtype,
                        name="stem")(x)
        else:
            x = nn.Conv(c.width, (7, 7), (2, 2), use_bias=False,
                        dtype=c.dtype, name="stem")(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        x = nn.relu(nn.GroupNorm(num_groups=min(32, c.width),
                                 dtype=c.dtype)(x))
        for stage, n_blocks in enumerate(c.stage_sizes):
            for block in range(n_blocks):
                strides = (2, 2) if stage > 0 and block == 0 else (1, 1)
                x = ResNetBlock(c.width * 2 ** stage, strides,
                                dtype=c.dtype)(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(c.num_classes, dtype=jnp.float32, name="head")(x)


# ---------------------------------------------------------------- shared


def classification_loss(logits, labels):
    """Mean softmax cross-entropy over int labels, f32 accumulation."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0] - lse
    return -ll.mean()


def make_train_state(model, config, rng, learning_rate: float = 1e-3,
                     input_shape=None):
    import optax

    if input_shape is None:
        if isinstance(config, ViTConfig):
            s = config.image_size
        else:
            s = 32 if config.cifar_stem else 224
        input_shape = (1, s, s, 3)
    params = model.init(rng, jnp.zeros(input_shape, jnp.float32))["params"]
    tx = optax.adamw(learning_rate)
    return params, tx, tx.init(params)


def build_train_step(model, tx, donate: bool = True):
    """Jitted (params, opt_state, batch{'image','label'}) ->
    (params, opt_state, loss); DP/FSDP come from arg placement like gpt2."""
    def loss_of(params, batch):
        logits = model.apply({"params": params}, batch["image"])
        return classification_loss(logits, batch["label"])

    return train_step.build_train_step(loss_of, tx, donate)


def synthetic_image_batch(rng, batch_size: int, image_size: int,
                          num_classes: int):
    k1, k2 = jax.random.split(rng)
    return {
        "image": jax.random.normal(k1, (batch_size, image_size, image_size, 3)),
        "label": jax.random.randint(k2, (batch_size,), 0, num_classes,
                                    dtype=jnp.int32),
    }
