"""Between the published layout of ``weights_swa.py`` and the program's:
the one place that knows how ``kubeflow_tpu.models.hybrid.HybridDecoder``
names and shapes the parameters of its global (``"attn"``) and window
(``"swa"``) attention layers. Slices of a stacked tensor, all inside the
jitted weight initialisation.
"""

from __future__ import annotations

from benchmark.harness.adapter import dtype_of  # noqa: F401  (re-export)


def layer_types(cfg: dict) -> tuple:
    """The program's kinds from the published pattern: 1 a window layer,
    0 a global one."""
    return tuple("swa" if w else "attn" for w in cfg["hybrid_layer_pattern"])


def first_dense(cfg: dict) -> int:
    """The leading dense-MLP layers; the routed ones follow them all."""
    freq = list(cfg["moe_layer_freq"])
    n = freq.index(1) if 1 in freq else len(freq)
    if any(f == 0 for f in freq[n:]):
        raise ValueError("moe_layer_freq must be dense layers, then routed")
    return n


def program_config(cfg: dict, **overrides):
    """The program's ``HybridConfig`` at the configuration's sizes. A
    program without the window layers (any commit before PR 40) has no
    such fields: the TypeError ends the run at once."""
    from kubeflow_tpu.models.hybrid import HybridConfig

    base = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        v_head_dim=cfg["v_head_dim"], layer_types=layer_types(cfg),
        first_k_dense=first_dense(cfg), norm_eps=cfg["layernorm_epsilon"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        n_kv_heads=cfg["num_key_value_heads"],
        swa_kv_heads=cfg["swa_num_key_value_heads"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        window=cfg["sliding_window"],
        attn_sinks=bool(cfg["add_swa_attention_sink_bias"]),
        value_scale=float(cfg["attention_value_scale"]),
        prefill_chunk=cfg["assumed"]["engine"]["prefill_chunk"],
        n_experts=cfg["n_routed_experts_total"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling=float(cfg["routed_scaling_factor"] or 1.0),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        d_expert=cfg["moe_intermediate_size"],
        d_shared=(cfg["n_shared_experts"] or 0)
        * cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]))
    if cfg["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("experts_held must hold n_routed_experts experts")
    base.update(overrides)
    return HybridConfig(**base)


def to_program_params(w: dict, cfg: dict) -> dict:
    """Published layout -> the flax tree of ``HybridDecoder``."""
    seen = {"g": 0, "s": 0, "dense": 0, "moe": 0}
    out = {"token_embed": w["embed"], "lm_head": w["lm_head"],
           "final_norm": {"scale": w["final_norm"]}}
    for i, window in enumerate(cfg["hybrid_layer_pattern"]):
        kind = "s" if window else "g"
        a = seen[kind]
        seen[kind] += 1
        mixer = {"q_proj": w[f"{kind}_wq"][a], "k_proj": w[f"{kind}_wk"][a],
                 "v_proj": w[f"{kind}_wv"][a], "o_proj": w[f"{kind}_wo"][a]}
        if window and cfg["add_swa_attention_sink_bias"]:
            mixer["sinks"] = w["s_sinks"][a]
        mlp_kind = "moe" if cfg["moe_layer_freq"][i] else "dense"
        n = seen[mlp_kind]
        seen[mlp_kind] += 1
        if mlp_kind == "dense":
            mlp = {"gate_proj": w["dense_gate"][n],
                   "up_proj": w["dense_up"][n],
                   "down_proj": w["dense_down"][n]}
        else:
            mlp = {"router": w["router"][n],
                   "router_bias": w["router_bias"][n],
                   "gate_proj": w["exp_gate"][n], "up_proj": w["exp_up"][n],
                   "down_proj": w["exp_down"][n]}
        out[f"layer_{i}"] = {
            "attn_norm": {"scale": w["attn_norm"][i]},
            "mlp_norm": {"scale": w["mlp_norm"][i]},
            "mixer": mixer, "mlp": mlp}
    return out
