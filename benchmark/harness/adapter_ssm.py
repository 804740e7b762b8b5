"""Between the published layout of ``weights_ssm.py`` and the program's:
the one place that knows how ``kubeflow_tpu.models.hybrid.HybridDecoder``
names and shapes the parameters of its one-sublayer blocks ("ssm", "gqa",
"moe"). Slices of a stacked tensor, all inside the jitted weight
initialisation; no tensor is reshaped or re-ordered. The program is built
at the published widths (``d_expert`` 1856); how it STORES a routed
expert is its own business (``HybridConfig.expert_width``), and the
published tensors reach that store through the program's own
``stored_expert``, as a loader of real weights would hand them over.
"""

from __future__ import annotations

from benchmark.harness.adapter import dtype_of  # noqa: F401  (re-export)

BLOCKS = {"M": "ssm", "*": "gqa", "E": "moe"}   # the program's names


def program_config(cfg: dict, **overrides):
    """The program's ``HybridConfig`` at the configuration's sizes. A
    program without the one-sublayer blocks (any commit before PR 35) has
    no such fields: the TypeError ends the run at once."""
    from kubeflow_tpu.models.hybrid import HybridConfig

    base = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(BLOCKS[x] for x in cfg["hybrid_override_pattern"]),
        first_k_dense=0, norm_eps=float(cfg["layer_norm_epsilon"]),
        max_seq_len=cfg["max_position_embeddings"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_state=cfg["ssm_state_size"], ssm_groups=cfg["n_groups"],
        ssm_chunk=cfg["chunk_size"], conv_kernel=cfg["conv_kernel"],
        n_experts=cfg["n_routed_experts_total"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        expert_act=cfg["mlp_hidden_act"],
        experts_held=tuple(cfg["experts_held"]))
    if cfg["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("experts_held must hold n_routed_experts experts")
    base.update(overrides)
    return HybridConfig(**base)


def to_program_params(w: dict, cfg: dict) -> dict:
    """Published layout -> the flax tree of ``HybridDecoder``."""
    from kubeflow_tpu.models.hybrid import stored_expert

    pc = program_config(cfg)
    seen = dict.fromkeys(BLOCKS.values(), 0)
    out = {"token_embed": w["embed"], "lm_head": w["lm_head"],
           "final_norm": {"scale": w["final_norm"]}}
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        kind = BLOCKS[letter]
        j = seen[kind]
        seen[kind] += 1
        block = {"norm": {"scale": w["block_norm"][i]}}
        if kind == "ssm":
            block["mixer"] = {
                "in_proj": w["ssm_in"][j], "out_proj": w["ssm_out"][j],
                "conv": w["ssm_conv_w"][j], "conv_bias": w["ssm_conv_b"][j],
                "a_log": w["ssm_a_log"][j], "dt_bias": w["ssm_dt_bias"][j],
                "d": w["ssm_d"][j], "norm": w["ssm_norm"][j]}
        elif kind == "gqa":
            block["mixer"] = {
                "q_proj": w["attn_wq"][j], "k_proj": w["attn_wk"][j],
                "v_proj": w["attn_wv"][j], "o_proj": w["attn_wo"][j]}
        else:
            block["mlp"] = {
                "router": w["router"][j], "router_bias": w["router_bias"][j],
                "up_proj": stored_expert(w["exp_up"][j], pc, 2),
                "down_proj": stored_expert(w["exp_down"][j], pc, 1),
                "shared_up": w["sh_up"][j], "shared_down": w["sh_down"][j]}
        out[f"layer_{i}"] = block
    return out
