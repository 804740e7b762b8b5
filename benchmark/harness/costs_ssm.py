"""Operations and bytes that the ALGORITHM of NVIDIA-Nemotron-3-Nano's
decoder needs (Mamba-2, rope-free GQA and relu² MoE blocks, one sublayer
a block), from the configuration's shapes and the routed blocks'
counters. Kept with the benchmark so that no later PR can move a share
of a peak by recounting: padding (the program stores an expert's 1856
columns at ``HybridConfig.expert_width`` 2048), recomputation and
whatever else an implementation adds do not count. ``cfg`` is ``benchmark/configs/nemotron-3-nano-30b-a3b.json``
(or a file of its keys, the published ones among them: ``n_routed_experts``
counts the experts held); weights are 2 bytes, the router and the state 4.
"""

from __future__ import annotations


def _dims(cfg: dict):
    """(D, H, P, N, G, I, W, blocks of M, *, E)."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    pattern = cfg["hybrid_override_pattern"]
    return (cfg["hidden_size"], h, p, n, g, h * p, h * p + 2 * g * n,
            pattern.count("M"), pattern.count("*"), pattern.count("E"))


def routed_blocks(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("E")


def experts_total(cfg: dict) -> int:
    """The router's outputs (the published file has no ``_total`` key:
    there every expert is held)."""
    return cfg.get("n_routed_experts_total", cfg["n_routed_experts"])


def param_counts(cfg: dict) -> dict:
    """Parameters held on this chip, by part (per block of a kind times
    the blocks of that kind; each block's norm with its block)."""
    d, h, _p, _n, _g, i, w, lm, la, le = _dims(cfg)
    hq, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    fe, fs = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    ssm = (d * (i + w + h) + (cfg["conv_kernel"] + 1) * w + i * d + 3 * h
           + i + d)
    attn = 2 * d * hq * dh + 2 * d * kh * dh + d
    return {
        "ssm": lm * ssm, "attn": la * attn,
        "router": le * (d + 1) * experts_total(cfg),
        "shared": le * 2 * d * fs,
        "experts": le * cfg["n_routed_experts"] * 2 * d * fe,
        "moe_norms": le * d,
        "embed_head": 2 * cfg["vocab_size"] * d,
        "final_norm": d,
    }


def params_total(cfg: dict) -> int:
    return sum(param_counts(cfg).values())


def held_pairs_per_token(cfg: dict) -> float:
    """Routed (token, expert) pairs a block that fall on experts held
    here, if routing is even: experts_per_tok x held / all."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / experts_total(cfg))


def forward_flops_per_token(cfg: dict, context, with_head: bool = True,
                            pairs_per_token=None):
    """Forward pass of one token whose attention blocks attend to
    ``context`` positions (a number or an array: one result each). The
    routed experts count ``pairs_per_token`` products a block (measured,
    or the even share). A causal prefill of p tokens is p such tokens at
    the mean context (p + 1) / 2, with the head at the last position
    only."""
    d, h, p, n, _g, i, w, lm, la, le = _dims(cfg)
    hq, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    fe, fs = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    if pairs_per_token is None:
        pairs_per_token = held_pairs_per_token(cfg)
    # both projections, the conv, and per head the decayed state plus the
    # outer product (3 P N) and the product with C (2 P N)
    ssm = (2.0 * (d * (i + w + h) + i * d) + 2.0 * cfg["conv_kernel"] * w
           + h * 5.0 * p * n)
    attn = 2.0 * (2 * d * hq * dh + 2 * d * kh * dh)
    attend = 4.0 * hq * dh * context
    routed = 2.0 * (d * experts_total(cfg) + 2 * d * fs
                    + pairs_per_token * 2 * d * fe)
    flops = lm * ssm + la * (attn + attend) + le * routed
    if with_head:
        flops = flops + 2.0 * d * cfg["vocab_size"]
    return flops


def expert_bytes(cfg: dict) -> float:
    """One expert's two matrices."""
    return 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def non_expert_weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads whatever the routing: all but
    the routed experts, and of the embedding only the head (a step
    gathers ``slots`` rows of the input embedding: counted with them)."""
    p = param_counts(cfg)
    two = (p["ssm"] + p["attn"] + p["shared"] + p["moe_norms"]
           + p["final_norm"] + p["embed_head"] // 2)
    return 2.0 * two + 4.0 * p["router"]


def state_bytes_per_slot(cfg: dict) -> float:
    """What a slot's Mamba-2 blocks hold: the float32 state and the conv
    tail (bf16)."""
    _d, h, p, n, _g, _i, w, lm, _la, _le = _dims(cfg)
    return lm * (h * p * n * 4.0 + (cfg["conv_kernel"] - 1) * w * 2.0)


def kv_bytes_per_token(cfg: dict) -> float:
    """A cached position of the attention blocks: K and V, bf16."""
    la = cfg["hybrid_override_pattern"].count("*")
    return la * 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2.0


def decode_step_bytes(cfg: dict, live_context_tokens: float,
                      experts_hit: float, slots: int) -> float:
    """One decode step: the non-expert weights once, the held experts
    that the step's rows hit (``experts_hit``: summed over the routed
    blocks), every slot's state read and written, K and V of the
    positions that live rows hold, and the rows' embeddings."""
    return (non_expert_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + 2.0 * slots * state_bytes_per_slot(cfg)
            + kv_bytes_per_token(cfg) * live_context_tokens
            + slots * cfg["hidden_size"] * 2.0)


def grouped_product_cost(cfg: dict, pairs: float, hit: float):
    """(flops, bytes) of ONE of a routed block's two grouped products (up
    or down) over ``pairs`` routed rows that hit ``hit`` experts: the hit
    experts' matrix once, the rows in and out."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2.0 * pairs * d * fe, hit * d * fe * 2.0 + pairs * (d + fe) * 2.0


def ssm_step_cost(cfg: dict, slots: int):
    """(flops, bytes) of one block's one-token state update over
    ``slots`` rows: the float32 state read once and written once; the
    vectors are small beside it."""
    _d, h, p, n, _g, _i, _w, _lm, _la, _le = _dims(cfg)
    return slots * h * 5.0 * p * n, 2.0 * slots * h * p * n * 4.0
