"""Between the published layout of ``weights_hybrid.py`` and the
program's: the one place that knows how
``kubeflow_tpu.models.hybrid.HybridDecoder`` names and shapes its
parameters. Reshapes, slices of a stacked tensor and one concatenation
(q | k | v into the program's fused projection), all inside the jitted
weight initialisation.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness.adapter import dtype_of  # noqa: F401  (re-export)


def program_config(cfg: dict, **overrides):
    """The program's ``HybridConfig`` at the configuration's sizes."""
    from kubeflow_tpu.models.hybrid import HybridConfig

    base = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]),
        first_k_dense=cfg["first_k_dense_replace"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        conv_kernel=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        n_experts=cfg["num_experts_total"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]))
    if cfg["experts_held"][1] != cfg["num_experts"]:
        raise ValueError("experts_held must hold num_experts experts")
    base.update(overrides)
    return HybridConfig(**base)


def to_program_params(w: dict, cfg: dict) -> dict:
    """Published layout -> the flax tree of ``HybridDecoder``."""
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    d, r = cfg["hidden_size"], cfg["kv_lora_rank"]
    seen = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
    out = {"token_embed": w["embed"], "lm_head": w["lm_head"],
           "final_norm": {"scale": w["final_norm"]}}
    for i, kind in enumerate(cfg["layer_types"]):
        j = seen[kind]
        seen[kind] += 1
        if kind == "kda":
            mixer = {
                "qkv_proj": jnp.concatenate(
                    [w["kda_wq"][j], w["kda_wk"][j], w["kda_wv"][j]], -1),
                "decay_proj": w["kda_wg"][j], "gate_proj": w["kda_wog"][j],
                "beta_proj": w["kda_wbeta"][j], "o_proj": w["kda_wo"][j],
                "conv": w["kda_conv"][j], "a_log": w["kda_a_log"][j],
                "dt_bias": w["kda_dt_bias"][j],
                "o_norm": {"scale": w["kda_o_norm"][j]}}
        else:
            mixer = {
                "q_proj": w["mla_wq"][j].reshape(d, h, nope + rope),
                "kv_a_proj": w["mla_wkva"][j],
                "kv_norm": {"scale": w["mla_kv_norm"][j]},
                "kv_b_proj": w["mla_wkvb"][j].reshape(r, h, nope + vd),
                "q_norm": {"scale": w["mla_q_norm"][j]},
                "k_norm": w["mla_k_norm"][j],
                "gate_proj": w["mla_wgate"][j],
                "o_proj": w["mla_wo"][j].reshape(h, vd, d)}
        mlp_kind = "dense" if i < cfg["first_k_dense_replace"] else "moe"
        j = seen[mlp_kind]
        seen[mlp_kind] += 1
        if mlp_kind == "dense":
            mlp = {"gate_proj": w["dense_gate"][j],
                   "up_proj": w["dense_up"][j],
                   "down_proj": w["dense_down"][j]}
        else:
            mlp = {"router": w["router"][j],
                   "router_bias": w["router_bias"][j],
                   "gate_proj": w["exp_gate"][j], "up_proj": w["exp_up"][j],
                   "down_proj": w["exp_down"][j],
                   "shared_gate": w["sh_gate"][j],
                   "shared_up": w["sh_up"][j],
                   "shared_down": w["sh_down"][j]}
        out[f"layer_{i}"] = {
            "attn_norm": {"scale": w["attn_norm"][i]},
            "mlp_norm": {"scale": w["mlp_norm"][i]},
            "mixer": mixer, "mlp": mlp}
    return out
