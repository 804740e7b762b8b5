"""Operations and bytes that the ALGORITHM needs, from shapes alone.

Kept with the benchmark so that no later PR can move a roofline by
recounting: these count what the mathematics requires (recomputation,
padding and whatever else an implementation adds do not count). A
configuration names the functions that apply to it.
"""

from __future__ import annotations


def _body_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = d * h * dh + 2 * d * kh * dh + h * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer


def _head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def _params(cfg: dict) -> int:
    return (_body_matmul_params(cfg) + _head_params(cfg)
            + (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"])


def decoder_forward_flops_per_token(cfg: dict, context,
                                    with_head: bool = True):
    """Forward pass of one token that attends to ``context`` positions (a
    number, or an array of them: one result each). A causal prefill of p
    tokens is p such tokens at the mean context (p + 1) / 2, with the head
    at the last position only."""
    flops = 2.0 * _body_matmul_params(cfg)
    if with_head:
        flops += 2.0 * _head_params(cfg)
    attn = (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)
    return flops + attn


def decoder_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 N + 6 L S d: forward and backward of every matrix (the tied
    embedding counted once, as the head), causal attention at half the
    square. Recomputation is not counted."""
    return (6.0 * _params(cfg) + 6.0 * cfg["num_hidden_layers"] * seq_len
            * cfg["num_attention_heads"] * cfg["head_dim"])


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> float:
    return float(_params(cfg) * bytes_per_param)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> float:
    return float(2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                 * cfg["head_dim"] * bytes_per_value)


def dense_decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """One decode step: every weight once, and the keys and values of the
    positions that live rows hold (whatever the cache reserves)."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * live_context_tokens


def flash_kernel_cost(kind: str, batch: int, heads: int, seq: int,
                      head_dim: int, bytes_per_value: int = 2):
    """(flops, bytes) of one causal flash-attention kernel call.

    fwd:  S = QK^T, O = PV                      2 products
    dq:   S, dP = dO V^T, dQ = dS K             3 products
    dkv:  S, dV = P^T dO, dP, dK = dS^T Q       4 products
    each 2 B H S^2 Dh flops, halved by causality. Bytes: the operands and
    results once (q, k, v, o, do and the per-row statistics are small).
    """
    products = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    tensors = {"fwd": 4, "dq": 5, "dkv": 6}[kind]
    unit = 2.0 * batch * heads * seq * seq * head_dim
    flops = products * unit / 2.0
    nbytes = tensors * batch * heads * seq * head_dim * bytes_per_value
    return flops, float(nbytes)


def none(*_args, **_kwargs):
    return None
