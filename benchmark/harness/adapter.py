"""Between the published layout and the program's: the one place that
knows how ``kubeflow_tpu.models.Transformer`` names and shapes its
parameters. Both directions are reshapes, so they run inside the jitted
weight initialisation and cost nothing.
"""

from __future__ import annotations

import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(cfg: dict, **overrides):
    """The program's ``TransformerConfig`` at the configuration's sizes."""
    from kubeflow_tpu.models.transformer import TransformerConfig

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("the program derives head_dim as hidden/heads")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the program has no untied output head")
    base = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]))
    base.update(overrides)
    return TransformerConfig(**base)


def to_program_params(w: dict, cfg: dict) -> dict:
    """Published layout -> the scanned flax tree of ``Transformer``."""
    n = cfg["num_hidden_layers"]
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "token_embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {
            "attn_norm": {"scale": w["attn_norm"]},
            "mlp_norm": {"scale": w["mlp_norm"]},
            "attn": {
                "q_proj": w["wq"].reshape(n, d, h, dh),
                "k_proj": w["wk"].reshape(n, d, kh, dh),
                "v_proj": w["wv"].reshape(n, d, kh, dh),
                "o_proj": w["wo"].reshape(n, h, dh, d),
            },
            "mlp": {"gate_proj": w["w_gate"], "up_proj": w["w_up"],
                    "down_proj": w["w_down"]},
        },
    }


def from_program_params(p: dict, cfg: dict) -> dict:
    """The inverse: a program tree (params, gradients, Adam moments)
    back in the published layout."""
    n = cfg["num_hidden_layers"]
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b = p["blocks"]
    return {
        "embed": p["token_embed"],
        "final_norm": p["final_norm"]["scale"],
        "attn_norm": b["attn_norm"]["scale"],
        "mlp_norm": b["mlp_norm"]["scale"],
        "wq": b["attn"]["q_proj"].reshape(n, d, h * dh),
        "wk": b["attn"]["k_proj"].reshape(n, d, kh * dh),
        "wv": b["attn"]["v_proj"].reshape(n, d, kh * dh),
        "wo": b["attn"]["o_proj"].reshape(n, h * dh, d),
        "w_gate": b["mlp"]["gate_proj"], "w_up": b["mlp"]["up_proj"],
        "w_down": b["mlp"]["down_proj"],
    }
