"""Operations and bytes that the hybrid decoder's ALGORITHM needs, from
the configuration's shapes and the routed layers' counters. Kept with the
benchmark so that no later PR can move a share of a peak by recounting:
padding, recomputation and whatever else an implementation adds do not
count. ``cfg`` is ``benchmark/configs/ling-3.0-flash-vl.json`` (or a
file of its keys); weights are 2 bytes, the router and the KDA state 4.
"""

from __future__ import annotations


def _dims(cfg: dict):
    d, h, dh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    kinds = list(cfg["layer_types"])
    n_dense = cfg["first_k_dense_replace"]
    return d, h, dh, kinds.count("kda"), kinds.count("mla"), n_dense, \
        len(kinds) - n_dense


def param_counts(cfg: dict) -> dict:
    """Parameters held on this chip, by part (per layer of a kind times
    the layers of that kind)."""
    d, h, dh, lk, lm, ld, le = _dims(cfg)
    c = h * dh
    r, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    taps = cfg["short_conv_kernel_size"]
    fe, fs = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    kda = 6 * d * c + d * h + taps * 3 * c + h + c + dh
    mla = (d * h * (nope + rope) + d * (r + rope) + r + r * h * (nope + vd)
           + 2 * (nope + rope) + d * h + h * vd * d)
    return {
        "kda": lk * kda, "mla": lm * mla,
        "dense_mlp": ld * 3 * d * cfg["intermediate_size"],
        "router": le * (d + 1) * cfg["num_experts_total"],
        "shared": le * 3 * d * fs,
        "experts": le * cfg["num_experts"] * 3 * d * fe,
        "embed_head": 2 * cfg["vocab_size"] * d,
        "norms": (2 * (lk + lm) + 1) * d,
    }


def params_total(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def held_pairs_per_token(cfg: dict) -> float:
    """Routed (token, expert) pairs a layer that fall on experts held
    here, if routing is even: experts_per_tok x held / all."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["num_experts_total"])


def hybrid_forward_flops_per_token(cfg: dict, context, with_head: bool = True,
                                   pairs_per_token=None):
    """Forward pass of one token whose MLA layers attend to ``context``
    positions (a number or an array: one result each). The routed experts
    count ``pairs_per_token`` products a layer (measured, or the even
    share). A causal prefill of p tokens is p such tokens at the mean
    context (p + 1) / 2, with the head at the last position only."""
    d, h, dh, lk, lm, ld, le = _dims(cfg)
    c = h * dh
    r, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    fe, fs = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    if pairs_per_token is None:
        pairs_per_token = held_pairs_per_token(cfg)
    # KDA: six projections and beta, the conv, and per head the decayed
    # state's product with k, the rank-one update and the product with q
    kda = 2.0 * (6 * d * c + d * h) + 2.0 * cfg["short_conv_kernel_size"] \
        * 3 * c + h * 6.0 * dh * dh
    mla = 2.0 * (d * h * (nope + rope) + d * (r + rope)
                 + r * h * (nope + vd) + d * h + h * vd * d)
    attend = 2.0 * h * (nope + rope + vd) * context
    routed = 2.0 * (d * cfg["num_experts_total"] + 3 * d * fs
                    + pairs_per_token * 3 * d * fe)
    flops = (lk * kda + lm * (mla + attend)
             + ld * 6.0 * d * cfg["intermediate_size"] + le * routed)
    if with_head:
        flops = flops + 2.0 * d * cfg["vocab_size"]
    return flops


def expert_bytes(cfg: dict) -> float:
    """One expert's three matrices."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def non_expert_weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads whatever the routing: all but
    the routed experts, and of the embedding only the head (a step
    gathers ``slots`` rows of the input embedding: counted with them)."""
    p = param_counts(cfg)
    two = p["kda"] + p["mla"] + p["dense_mlp"] + p["shared"] + p["norms"] \
        + p["embed_head"] // 2
    return 2.0 * two + 4.0 * p["router"]


def state_bytes_per_slot(cfg: dict) -> float:
    """What a slot's KDA layers hold: the float32 state and the conv
    tail (bf16)."""
    _d, h, dh, lk, _lm, _ld, _le = _dims(cfg)
    tail = (cfg["short_conv_kernel_size"] - 1) * 3 * h * dh * 2
    return lk * (h * dh * dh * 4.0 + tail)


def latent_bytes_per_token(cfg: dict) -> float:
    """A cached token of the MLA layers: the latent, the shared rope key
    and the per-head key scales, bf16."""
    _d, h, _dh, _lk, lm, _ld, _le = _dims(cfg)
    return lm * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + h) * 2.0


def hybrid_decode_step_bytes(cfg: dict, live_context_tokens: float,
                             experts_hit: float, slots: int) -> float:
    """One decode step: the non-expert weights once, the held experts
    that the step's rows hit (``experts_hit``: summed over the routed
    layers), every slot's KDA state read and written, the latent of the
    positions that live rows hold, and the rows' embeddings."""
    return (non_expert_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + 2.0 * slots * state_bytes_per_slot(cfg)
            + latent_bytes_per_token(cfg) * live_context_tokens
            + slots * cfg["hidden_size"] * 2.0)


def grouped_product_cost(cfg: dict, pairs: float, hit: float):
    """(flops, bytes) of ONE of a routed layer's three grouped products
    (gate, up or down) over ``pairs`` routed rows that hit ``hit``
    experts: the hit experts' matrix once, the rows in and out."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2.0 * pairs * d * fe, hit * d * fe * 2.0 + pairs * (d + fe) * 2.0


def kda_step_cost(cfg: dict, slots: int):
    """(flops, bytes) of one layer's one-token state update over
    ``slots`` rows: the float32 state read once and written once; the
    vectors are small beside it."""
    _d, h, dh, _lk, _lm, _ld, _le = _dims(cfg)
    return slots * h * 6.0 * dh * dh, 2.0 * slots * h * dh * dh * 4.0
