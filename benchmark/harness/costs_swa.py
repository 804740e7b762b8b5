"""Operations and bytes that MiMo-V2-Flash's decoder ALGORITHM needs, from
the configuration's shapes and the program's counters. Kept with the
benchmark so that no later PR can move a share of a peak by recounting:
the merged-lane products' wider queries, recomputation and whatever else
an implementation adds do not count. ``cfg`` is
``benchmark/configs/mimo-v2-flash.json`` (or a file of its keys);
weights are 2 bytes, the router and the sinks 4.
"""

from __future__ import annotations


def layer_counts(cfg: dict):
    """(global layers, window layers, dense-MLP layers, routed layers)."""
    pattern, moe = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    n = len(pattern)
    return n - sum(pattern), sum(pattern), n - sum(moe), sum(moe)


def attn_params(cfg: dict, kv_heads: int) -> int:
    """One attention layer's projections at ``kv_heads`` KV heads."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    return d * h * dk + d * kv_heads * (dk + dv) + h * dv * d


def param_counts(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    lg, ls, ld, le = layer_counts(cfg)
    d = cfg["hidden_size"]
    return {
        "attn_global": lg * attn_params(cfg, cfg["num_key_value_heads"]),
        "attn_window": ls * attn_params(cfg, cfg["swa_num_key_value_heads"]),
        "sinks": ls * cfg["num_attention_heads"],
        "dense_mlp": ld * 3 * d * cfg["intermediate_size"],
        "router": le * (d + 1) * cfg["n_routed_experts_total"],
        "experts": le * cfg["n_routed_experts"] * 3 * d
        * cfg["moe_intermediate_size"],
        "embed_head": 2 * cfg["vocab_size"] * d,
        "norms": (2 * (lg + ls) + 1) * d,
    }


def params_total(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def held_pairs_per_token(cfg: dict) -> float:
    """Routed (token, expert) pairs a layer that fall on experts held
    here, if routing is even."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_total"])


def window_of(cfg: dict, context):
    """Positions a window layer's query at ``context`` attends to."""
    w = cfg["sliding_window"]
    return context - (context - w) * (context > w)    # min, arrays too


def swa_forward_flops_per_token(cfg: dict, context, with_head: bool = True,
                                pairs_per_token=None):
    """Forward pass of one token at ``context`` positions (its own
    included; a number or an array: one result each): a global layer
    attends to all of them, a window layer to the last ``sliding_window``.
    The routed experts count ``pairs_per_token`` products a layer
    (measured, or the even share). A causal prefill of p tokens is the sum
    over its tokens' contexts 1..p, with the head at the last position
    only."""
    lg, ls, ld, le = layer_counts(cfg)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qkv = cfg["head_dim"] + cfg["v_head_dim"]
    fe = cfg["moe_intermediate_size"]
    if pairs_per_token is None:
        pairs_per_token = held_pairs_per_token(cfg)
    p = param_counts(cfg)
    flops = (2.0 * (p["attn_global"] + p["attn_window"])
             + lg * 2.0 * h * qkv * context
             + ls * 2.0 * h * qkv * window_of(cfg, context)
             + ld * 6.0 * d * cfg["intermediate_size"]
             + le * 2.0 * (d * cfg["n_routed_experts_total"]
                           + pairs_per_token * 3 * d * fe))
    if with_head:
        flops = flops + 2.0 * d * cfg["vocab_size"]
    return flops


def swa_prefill_flops(cfg: dict, prompt_tokens: int, pairs_per_token=None):
    """A causal prefill of ``prompt_tokens``: every token at its own
    context, the head once."""
    import numpy as np

    ctx = np.arange(1, int(prompt_tokens) + 1, dtype=np.float64)
    body = swa_forward_flops_per_token(cfg, ctx, False, pairs_per_token)
    return float(np.sum(body)) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def expert_bytes(cfg: dict) -> float:
    """One expert's three matrices."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def non_expert_weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads whatever the routing: all but
    the routed experts, and of the embedding only the head."""
    p = param_counts(cfg)
    two = (p["attn_global"] + p["attn_window"] + p["dense_mlp"]
           + p["norms"] + p["embed_head"] // 2)
    return 2.0 * two + 4.0 * (p["router"] + p["sinks"])


def kv_bytes_per_position(cfg: dict, window: bool) -> float:
    """One layer's cached K and V of one position, bf16."""
    kh = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    return kh * (cfg["head_dim"] + cfg["v_head_dim"]) * 2.0


def swa_decode_step_bytes(cfg: dict, global_positions: float,
                          window_positions: float, experts_hit: float,
                          slots: int) -> float:
    """One decode step: the non-expert weights once, the held experts
    that the step's rows hit (``experts_hit``: summed over the routed
    layers), the cached K and V that the rows' attention reads
    (``global_positions`` / ``window_positions``: summed over the rows,
    a layer of the kind) and the rows' embeddings."""
    lg, ls, _, _ = layer_counts(cfg)
    return (non_expert_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + lg * global_positions * kv_bytes_per_position(cfg, False)
            + ls * window_positions * kv_bytes_per_position(cfg, True)
            + slots * cfg["hidden_size"] * 2.0)


def step_attend_cost(cfg: dict, global_positions: float,
                     window_positions: float, slots: int):
    """(flops, bytes) of one decode step's one-token attention calls,
    every layer of both kinds: the cached K and V the rows read (as
    :func:`swa_decode_step_bytes` counts them), each row's queries in
    and outputs out, bf16; QK and PV over the heads."""
    lg, ls, _, _ = layer_counts(cfg)
    h, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    read = (lg * global_positions * kv_bytes_per_position(cfg, False)
            + ls * window_positions * kv_bytes_per_position(cfg, True))
    io = (lg + ls) * slots * h * (dk + dv) * 2.0
    flops = 2.0 * h * (dk + dv) * (lg * global_positions
                                   + ls * window_positions)
    return flops, read + io
