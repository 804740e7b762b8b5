"""Operations and bytes that DeepSeek-V3.2's decoder ALGORITHM needs,
from the configuration's shapes and the program's counters. Kept with the
benchmark so that no later PR can move a share of a peak by recounting:
padding (the latent row's fifth lane tile), the absorbed form's wider
products, recomputation and whatever else an implementation adds do not
count. ``cfg`` is ``benchmark/configs/deepseek-v3.2.json`` (or a file of
its keys); weights are 2 bytes, the router 4.
"""

from __future__ import annotations


def _layers(cfg: dict):
    n, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return n, n_dense, n - n_dense


def param_counts(cfg: dict) -> dict:
    """Parameters held on this chip, by part."""
    n, ld, le = _layers(cfg)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    fe = cfg["moe_intermediate_size"]
    mla = (d * qr + qr * h * (nope + rope) + d * (r + rope)
           + r * h * (nope + vd) + h * vd * d)
    indexer = qr * j * di + d * di + d * j
    return {
        "mla": n * mla, "indexer": n * indexer,
        "dense_mlp": ld * 3 * d * cfg["intermediate_size"],
        "router": le * (d + 1) * cfg["n_routed_experts_total"],
        "shared": le * 3 * d * cfg["n_shared_experts"] * fe,
        "experts": le * cfg["n_routed_experts"] * 3 * d * fe,
        "embed_head": 2 * cfg["vocab_size"] * d,
        # layer norms, the query and kv latents' norms, the index key's
        # LayerNorm (weight and bias)
        "norms": (2 * n + 1) * d + n * (qr + r + 2 * di),
    }


def params_total(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def held_pairs_per_token(cfg: dict) -> float:
    """Routed (token, expert) pairs a layer that fall on experts held
    here, if routing is even."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_total"])


def selected_of(cfg: dict, context):
    """Positions a query at ``context`` cached positions attends to."""
    k = cfg["index_topk"]
    return context - (context - k) * (context > k)   # min, arrays too


def dsa_forward_flops_per_token(cfg: dict, context, with_head: bool = True,
                                pairs_per_token=None):
    """Forward pass of one token whose layers score ``context`` cached
    positions and attend to the ``index_topk`` (or all, where fewer) they
    keep (a number or an array: one result each). The routed experts count
    ``pairs_per_token`` products a layer (measured, or the even share). A
    causal prefill of p tokens is the sum over its tokens' contexts 1..p,
    with the head at the last position only."""
    n, ld, le = _layers(cfg)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    fe = cfg["moe_intermediate_size"]
    if pairs_per_token is None:
        pairs_per_token = held_pairs_per_token(cfg)
    p = param_counts(cfg)
    proj = 2.0 * (p["mla"] + p["indexer"]) / n
    score = 2.0 * j * di * context
    attend = 2.0 * h * (nope + rope + vd) * selected_of(cfg, context)
    routed = 2.0 * (d * cfg["n_routed_experts_total"]
                    + 3 * d * cfg["n_shared_experts"] * fe
                    + pairs_per_token * 3 * d * fe)
    flops = (n * (proj + score + attend)
             + ld * 6.0 * d * cfg["intermediate_size"] + le * routed)
    if with_head:
        flops = flops + 2.0 * d * cfg["vocab_size"]
    return flops


def dsa_prefill_flops(cfg: dict, prompt_tokens: int, pairs_per_token=None):
    """A causal prefill of ``prompt_tokens``: every token at its own
    context, the head once."""
    import numpy as np

    ctx = np.arange(1, int(prompt_tokens) + 1, dtype=np.float64)
    body = dsa_forward_flops_per_token(cfg, ctx, False, pairs_per_token)
    return float(np.sum(body)) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def expert_bytes(cfg: dict) -> float:
    """One expert's three matrices."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def non_expert_weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads whatever the routing: all but
    the routed experts, and of the embedding only the head."""
    p = param_counts(cfg)
    two = (p["mla"] + p["indexer"] + p["dense_mlp"] + p["shared"]
           + p["norms"] + p["embed_head"] // 2)
    return 2.0 * two + 4.0 * p["router"]


def index_key_bytes_per_token(cfg: dict) -> float:
    """A cached position's index keys over the layers, bf16."""
    return cfg["num_hidden_layers"] * cfg["index_head_dim"] * 2.0


def latent_bytes_per_token(cfg: dict) -> float:
    """A cached position's latent and shared rope key over the layers,
    bf16, without the lane padding."""
    return cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2.0


def cache_bytes_per_token(cfg: dict) -> float:
    return index_key_bytes_per_token(cfg) + latent_bytes_per_token(cfg)


def dsa_decode_step_bytes(cfg: dict, live_context_tokens: float,
                          selected_tokens: float, experts_hit: float,
                          slots: int) -> float:
    """One decode step: the non-expert weights once, the held experts
    that the step's rows hit (``experts_hit``: summed over the routed
    layers), the index keys of every position that live rows hold, the
    latent of the positions the rows SELECTED (``selected_tokens``: summed
    over the rows, a layer), and the rows' embeddings. A step that reads
    the whole latent moves more than this and scores lower."""
    return (non_expert_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + index_key_bytes_per_token(cfg) * live_context_tokens
            + latent_bytes_per_token(cfg) * selected_tokens
            + slots * cfg["hidden_size"] * 2.0)


def index_scores_cost(cfg: dict, queries: float, scored: float,
                      keys: float):
    """(flops, bytes) of one layer's index scores: ``queries`` tokens
    score ``scored`` (query, position) pairs against ``keys`` cached
    positions, each read once (a decode step's rows read their own: keys
    = scored; a chunk's queries share one row's). The products over the
    heads; the keys and queries in, the float32 scores out."""
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (2.0 * j * di * scored,
            keys * di * 2.0 + scored * 4.0 + queries * j * (di * 2 + 4))
