"""BERT-family encoder: bidirectional transformer + masked-LM head.

The DDP-BERT archetype of BASELINE.md config 3 (the reference ran BERT as
an opaque PyTorchJob DDP workload, ``/root/reference/kubeflow/pytorch-job/
prototypes/pytorch-job.jsonnet:69-80``); here it is in-framework so the
same mesh/sharding axes apply. TPU-first choices over classic BERT:
RoPE positions instead of learned embeddings (no position table to shard),
RMSNorm, bf16 activations, scanned/remat blocks — weight compatibility
with original BERT checkpoints is a non-goal; the *workload shape*
(bidirectional encoder, MLM objective, base/large sizes) is the parity
target. Reuses the flagship blocks with ``causal=False``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

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

MASK_TOKEN_ID = 103  # conventionally [MASK] in the BERT vocab


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2      # sentence A/B segments
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True
    # "auto" = the Pallas flash kernels on the TPU backend (the
    # longcontext blocking treatment applied to seq-512 bidirectional,
    # ROADMAP item 3's BERT-MFU lever), the dense XLA path elsewhere —
    # dense is the parity oracle the flash route is gated against
    # (tests/test_bert.py). Force "flash" to run the kernels in the
    # interpreter off-TPU.
    attention_impl: str = "auto"
    # flash tile overrides; None = the shape-keyed tile table
    # (kubeflow_tpu/ops/autotune.py)
    attention_block_q: Any = None
    attention_block_k: Any = None

    def encoder_config(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=self.vocab_size,
            d_model=self.d_model,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_heads,
            d_ff=self.d_ff,
            max_seq_len=self.max_seq_len,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            remat=self.remat,
            scan_layers=self.scan_layers,
            causal=False,  # the defining difference from the LM flagship
            attention_impl=self.attention_impl,
            attention_block_q=self.attention_block_q,
            attention_block_k=self.attention_block_k,
        )


def bert_base() -> BertConfig:
    return BertConfig()


def bert_large() -> BertConfig:
    return BertConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096)


def bert_tiny() -> BertConfig:
    """Test-sized config."""
    return BertConfig(vocab_size=1024, d_model=64, n_layers=2, n_heads=4,
                      d_ff=128, max_seq_len=128, remat=False,
                      scan_layers=False)


class Bert(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray,
                 token_types: jnp.ndarray = None,
                 seq_lengths: jnp.ndarray = None) -> jnp.ndarray:
        """tokens: (B, S) int32 -> MLM logits (B, S, V) float32.

        ``seq_lengths`` is an optional per-row valid-length ``(B,)``
        int32 padding mask: positions at/past a row's length are
        excluded from every attention (dense and flash paths alike);
        logits AT padded positions are unspecified — mask them with the
        MLM loss weights, which real padding already zeroes.
        """
        c = self.config
        ec = c.encoder_config()
        B, S = tokens.shape

        embed = self.param(
            "token_embed",
            nn.initializers.normal(stddev=c.d_model ** -0.5),
            (c.vocab_size, c.d_model),
            c.param_dtype,
        )
        x = jnp.take(embed.astype(c.dtype), tokens, axis=0)
        if c.type_vocab_size:
            type_embed = self.param(
                "type_embed",
                nn.initializers.normal(stddev=c.d_model ** -0.5),
                (c.type_vocab_size, c.d_model),
                c.param_dtype,
            )
            if token_types is None:
                token_types = jnp.zeros_like(tokens)
            x = x + jnp.take(type_embed.astype(c.dtype), token_types, axis=0)
        x = _constrain(x, ec.rules, "batch", "seq", None)
        sin, cos = rope_tables(S, ec.head_dim, ec.rope_theta)

        aux = (sin, cos, seq_lengths)
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
            )(ec, name="blocks")(x, aux)
        else:
            for i in range(c.n_layers):
                x, _ = block_cls(ec, name=f"block_{i}")(x, aux)

        x = RMSNorm(param_dtype=c.param_dtype, name="final_norm")(x)
        # MLM head: dense transform + tied-embedding decode (BERT's
        # cls/predictions/transform shape)
        w = self.param("mlm_transform",
                       nn.initializers.normal(stddev=c.d_model ** -0.5),
                       (c.d_model, c.d_model), c.param_dtype)
        x = nn.gelu(jnp.einsum("bsd,de->bse", x, w.astype(c.dtype)))
        logits = jnp.einsum(
            "bsd,vd->bsv", x, embed.astype(c.dtype)
        ).astype(jnp.float32)
        return _constrain(logits, ec.rules, "batch", None, "vocab")


def mask_tokens(rng, tokens: jnp.ndarray, *, mask_prob: float = 0.15,
                mask_id: int = MASK_TOKEN_ID) -> Tuple[jnp.ndarray,
                                                       jnp.ndarray]:
    """The MLM corruption: returns (masked_tokens, weights) where weights
    mark positions whose original token must be predicted."""
    import jax

    mask = jax.random.bernoulli(rng, mask_prob, tokens.shape)
    masked = jnp.where(mask, jnp.full_like(tokens, mask_id), tokens)
    return masked, mask.astype(jnp.float32)
