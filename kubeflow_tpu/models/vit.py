"""Vision Transformer: image classification on the shared encoder blocks.

Widens the model-family coverage beyond the reference's CNN/BERT workload
archetypes (its vision examples are tf_cnn_benchmarks CNNs run as TFJobs,
``/root/reference/tf-controller-examples/tf-cnn/``) with the
transformer-native image workload, built from the same Block stack as the
LM/BERT models so every mesh axis rule (dp/tp/sp, remat, scanned layers)
applies unchanged.

TPU-first choices: the patch stem is a non-overlapping conv (a reshaped
GEMM — tiles the MXU perfectly, unlike small-channel 7×7 stems), 1D RoPE
over raster-ordered patches instead of a learned position table (nothing
extra to shard or resize), mean pooling instead of a [CLS] token (keeps
the sequence length a power of two and the pooling a bandwidth-trivial
reduce).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from kubeflow_tpu.models.transformer import (
    Block,
    RMSNorm,
    TransformerConfig,
    _constrain,
    remat_block,
    rope_tables,
)


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def encoder_config(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=1,  # unused: the stem is a patch conv, not a table
            d_model=self.d_model,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_heads,
            d_ff=self.d_ff,
            max_seq_len=self.n_patches,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            remat=self.remat,
            scan_layers=self.scan_layers,
            causal=False,  # every patch attends to every patch
        )


def vit_base(num_classes: int = 1000) -> ViTConfig:
    return ViTConfig(num_classes=num_classes)


def vit_large(num_classes: int = 1000) -> ViTConfig:
    return ViTConfig(num_classes=num_classes, d_model=1024, n_layers=24,
                     n_heads=16, d_ff=4096)


def vit_tiny(num_classes: int = 10) -> ViTConfig:
    """Test-sized config."""
    return ViTConfig(image_size=32, patch_size=8, num_classes=num_classes,
                     d_model=64, n_layers=2, n_heads=4, d_ff=128,
                     remat=False, scan_layers=False)


class ViT(nn.Module):
    config: ViTConfig

    @nn.compact
    def __call__(self, images: jnp.ndarray,
                 train: bool = True) -> jnp.ndarray:
        """images: (B, H, W, C) -> logits (B, num_classes) float32.

        ``train`` is accepted for API parity with the ResNet family (the
        image train step passes it); the ViT has no train-only state."""
        c = self.config
        ec = c.encoder_config()
        B, H, W, _ = images.shape
        if H != c.image_size or W != c.image_size:
            raise ValueError(
                f"expected {c.image_size}² input, got {H}x{W}")

        # patch stem: non-overlapping conv == one big GEMM over
        # (patch_size² · C)-dim pixels — MXU-shaped by construction
        x = nn.Conv(
            c.d_model, (c.patch_size, c.patch_size),
            strides=(c.patch_size, c.patch_size), padding="VALID",
            use_bias=True, dtype=c.dtype, param_dtype=c.param_dtype,
            name="patch_embed",
        )(images.astype(c.dtype))
        x = x.reshape(B, -1, c.d_model)  # (B, N, D) raster order
        x = _constrain(x, ec.rules, "batch", "seq", None)
        sin, cos = rope_tables(x.shape[1], ec.head_dim, ec.rope_theta)

        block_cls = Block
        if c.remat:
            block_cls = remat_block()
        if c.scan_layers:
            x, _ = nn.scan(
                block_cls,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=c.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(ec, name="blocks")(x, (sin, cos))
        else:
            for i in range(c.n_layers):
                x, _ = block_cls(ec, name=f"block_{i}")(x, (sin, cos))

        x = RMSNorm(param_dtype=c.param_dtype, name="final_norm")(x)
        x = jnp.mean(x, axis=1)  # mean pool over patches
        return nn.Dense(
            c.num_classes, dtype=jnp.float32, param_dtype=c.param_dtype,
            name="head",
        )(x.astype(jnp.float32))
