"""Between the published layout of ``weights_dsa.py`` and the program's:
the one place that knows how ``kubeflow_tpu.models.hybrid.HybridDecoder``
names and shapes the parameters of its sparse-latent-attention ("dsa")
layers. Reshapes and slices of a stacked tensor, all inside the jitted
weight initialisation.
"""

from __future__ import annotations

from benchmark.harness.adapter import dtype_of  # noqa: F401  (re-export)


def program_config(cfg: dict, **overrides):
    """The program's ``HybridConfig`` at the configuration's sizes. A
    program without the sparse mixer (any commit before PR 33) has no
    such fields: the TypeError ends the run at once."""
    from kubeflow_tpu.models.hybrid import HybridConfig

    rs = cfg["rope_scaling"]
    base = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        layer_types=tuple(cfg["layer_types"]),
        first_k_dense=cfg["first_k_dense_replace"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        use_qk_norm=False, head_gate=False,
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_len=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        prefill_chunk=cfg["assumed"]["engine"]["prefill_chunk"],
        n_experts=cfg["n_routed_experts_total"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]))
    if cfg["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("experts_held must hold n_routed_experts experts")
    base.update(overrides)
    return HybridConfig(**base)


def to_program_params(w: dict, cfg: dict) -> dict:
    """Published layout -> the flax tree of ``HybridDecoder``."""
    h, d = cfg["num_attention_heads"], cfg["hidden_size"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    seen = {"dense": 0, "moe": 0}
    out = {"token_embed": w["embed"], "lm_head": w["lm_head"],
           "final_norm": {"scale": w["final_norm"]}}
    for i in range(cfg["num_hidden_layers"]):
        mixer = {
            "q_a_proj": w["mla_wqa"][i],
            "q_a_norm": {"scale": w["mla_q_a_norm"][i]},
            "q_b_proj": w["mla_wqb"][i].reshape(qr, h, nope + rope),
            "kv_a_proj": w["mla_wkva"][i],
            "kv_norm": {"scale": w["mla_kv_norm"][i]},
            "kv_b_proj": w["mla_wkvb"][i].reshape(r, h, nope + vd),
            "o_proj": w["mla_wo"][i].reshape(h, vd, d),
            "index_q_proj": w["idx_wq"][i].reshape(qr, j, di),
            "index_k_proj": w["idx_wk"][i],
            "index_w_proj": w["idx_ww"][i],
            "index_k_norm_scale": w["idx_k_norm_w"][i],
            "index_k_norm_bias": w["idx_k_norm_b"][i]}
        kind = "dense" if i < cfg["first_k_dense_replace"] else "moe"
        n = seen[kind]
        seen[kind] += 1
        if kind == "dense":
            mlp = {"gate_proj": w["dense_gate"][n],
                   "up_proj": w["dense_up"][n],
                   "down_proj": w["dense_down"][n]}
        else:
            mlp = {"router": w["router"][n],
                   "router_bias": w["router_bias"][n],
                   "gate_proj": w["exp_gate"][n], "up_proj": w["exp_up"][n],
                   "down_proj": w["exp_down"][n],
                   "shared_gate": w["sh_gate"][n],
                   "shared_up": w["sh_up"][n],
                   "shared_down": w["sh_down"][n]}
        out[f"layer_{i}"] = {
            "attn_norm": {"scale": w["attn_norm"][i]},
            "mlp_norm": {"scale": w["mlp_norm"][i]},
            "mixer": mixer, "mlp": mlp}
    return out
