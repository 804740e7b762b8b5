"""Seeded weights in the published (Hugging Face Llama-style) layout.

One function makes every tensor of a decoder from a PRNG key, so the
drivers call it under one ``jit`` on the device and the plain reference
calls the same function with the same seed: neither takes a tensor the
other made. Layers are stacked on a leading axis.

    embed      (V, D)          tied input embedding / output head
    final_norm (D,)
    attn_norm  (L, D)          mlp_norm (L, D)
    wq (L, D, H*Dh)  wk, wv (L, D, KH*Dh)  wo (L, H*Dh, D)
    w_gate, w_up (L, D, F)     w_down (L, F, D)

Matrices are N(0, initializer_range); norm weights are 1 + jitter*N(0,1)
so that a norm whose weight is dropped shows in the comparison.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("embed", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("final_norm", "attn_norm", "mlp_norm")


def weight_shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, h, kh = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    dh = cfg["head_dim"]
    return {
        "embed": (v, d), "final_norm": (d,),
        "attn_norm": (n, d), "mlp_norm": (n, d),
        "wq": (n, d, h * dh), "wk": (n, d, kh * dh), "wv": (n, d, kh * dh),
        "wo": (n, h * dh, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2 ** 32))
    return jax.random.fold_in(key, np.uint32((seed // 2 ** 32) % 2 ** 32))


def init_weights(cfg: dict, key: jax.Array, dtype) -> dict:
    """Every tensor from ``key``; traceable, so callers jit it."""
    std = cfg["assumed"]["initializer_range"]
    jitter = cfg["assumed"]["norm_weight_jitter"]
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name in NORMS:
            w = 1.0 + jitter * jax.random.normal(k, shape, jnp.float32)
        else:
            w = std * jax.random.normal(k, shape, jnp.float32)
        out[name] = w.astype(dtype)
    return out
