"""Seeded weights of DeepSeek-V3.2's decoder (MLA with a low-rank query,
the lightning indexer, a routed MLP with a shared expert), in the
published layout, stacked by kind of layer.

One function makes every tensor from a PRNG key; the driver and the
plain reference both call it with the same seed. ``cfg`` is the
configuration file: the first ``first_k_dense_replace`` layers have the
dense MLP and the rest the routed one, ``n_routed_experts`` is the number
of experts HELD here (``experts_held`` = [lo, lo + n) of the router's
``n_routed_experts_total``), ``vocab_size`` the rows of the vocabulary
held.

    embed, lm_head (V, D)      final_norm (D,)     (untied head)
    attn_norm, mlp_norm (L, D)
  attention (L layers), qr = q_lora_rank, r = kv_lora_rank, n / p / v =
  nope / rope / value dims, H heads:
    mla_wqa (L, D, qr)         mla_q_a_norm (L, qr)
    mla_wqb (L, qr, H*(n+p))   mla_wkva (L, D, r+p)   mla_kv_norm (L, r)
    mla_wkvb (L, r, H*(n+v))   per head [k_nope | v]  mla_wo (L, H*v, D)
  its indexer, J heads of d:
    idx_wq (L, qr, J*d)        idx_wk (L, D, d)       idx_ww (L, D, J)
    idx_k_norm_w, idx_k_norm_b (L, d)   the key's LayerNorm
  dense MLP layers (Ld): dense_gate, dense_up (Ld, D, F)  dense_down
  routed MLP layers (Le), E held experts of width Fe, Et routed over:
    router (Le, D, Et) f32     router_bias (Le, Et) f32
    exp_gate, exp_up (Le, E, D, Fe)    exp_down (Le, E, Fe, D)
    sh_gate, sh_up (Le, D, Fs)         sh_down (Le, Fs, D)

Matrices are N(0, initializer_range). What a forward pass could lose
without a crash is drawn away from its neutral value, so that dropping it
shows in the comparison: norm weights 1 + jitter * N(0, 1), the
LayerNorm's bias jitter * N(0, 1). The router's selection bias is drawn
N(0, router_bias_std) and then FITTED as ``weights_hybrid`` fits Ling's
(the aux-loss-free balancing update, which this model's family
introduced: DeepSeek-V3, arXiv:2412.19437, section 2.1.2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights_hybrid import seed_key  # noqa: F401

NORMS = ("final_norm", "attn_norm", "mlp_norm", "mla_q_a_norm",
         "mla_kv_norm", "idx_k_norm_w")
FLOAT32 = ("router", "router_bias")


def layer_counts(cfg: dict):
    """(layers, dense-MLP layers, routed layers)."""
    n, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    if list(cfg["layer_types"]) != ["dsa"] * n:
        raise ValueError("layer_types must list num_hidden_layers 'dsa'")
    return n, n_dense, n - n_dense


def weight_shapes(cfg: dict) -> dict:
    d, v, h = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    e, et = cfg["n_routed_experts"], cfg["n_routed_experts_total"]
    n, ld, le = layer_counts(cfg)
    return {
        "embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
        "attn_norm": (n, d), "mlp_norm": (n, d),
        "mla_wqa": (n, d, qr), "mla_q_a_norm": (n, qr),
        "mla_wqb": (n, qr, h * (nope + rope)), "mla_wkva": (n, d, r + rope),
        "mla_kv_norm": (n, r), "mla_wkvb": (n, r, h * (nope + vd)),
        "mla_wo": (n, h * vd, d),
        "idx_wq": (n, qr, j * di), "idx_wk": (n, d, di), "idx_ww": (n, d, j),
        "idx_k_norm_w": (n, di), "idx_k_norm_b": (n, di),
        "dense_gate": (ld, d, f), "dense_up": (ld, d, f),
        "dense_down": (ld, f, d),
        "router": (le, d, et), "router_bias": (le, et),
        "exp_gate": (le, e, d, fe), "exp_up": (le, e, d, fe),
        "exp_down": (le, e, fe, d),
        "sh_gate": (le, d, fs), "sh_up": (le, d, fs), "sh_down": (le, fs, d),
    }


def init_weights(cfg: dict, key: jax.Array, dtype,
                 router_bias=None) -> dict:
    """Every tensor from ``key``; traceable. The router and its bias stay
    float32 whatever ``dtype`` is. ``router_bias`` (Le, Et), where given,
    takes the drawn bias's place."""
    a = cfg["assumed"]
    std, jitter = a["initializer_range"], a["norm_weight_jitter"]

    def draw(name, k, shape):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + jitter * z
        elif name == "idx_k_norm_b":
            w = jitter * z
        elif name == "router_bias":
            w = a["router_bias_std"] * z
        else:
            w = std * z
        return w.astype(jnp.float32 if name in FLOAT32 else dtype)

    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if len(shape) >= 3 and shape[0]:
            # layer by layer: a caller that takes one layer's slice of
            # the stack then never holds the stack beside its slices
            out[name] = jnp.stack([draw(name, jax.random.fold_in(k, j),
                                        shape[1:])
                                   for j in range(shape[0])])
        else:
            out[name] = draw(name, k, shape)
    if router_bias is not None:
        out["router_bias"] = router_bias.astype(jnp.float32)
    return out


def balanced_router_bias(cfg: dict, key: jax.Array, dtype, ref) -> jax.Array:
    """The selection bias (Le, Et) under which every expert of a routed
    layer is chosen about equally often; traceable. As
    ``weights_hybrid.balanced_router_bias``: the reference's float32
    forward runs ``rows`` sequences of ``tokens`` ids drawn from ``key``
    over the held vocabulary, and layer by layer, on that layer's own
    inputs, the drawn bias takes ``steps`` updates b_e += rate *
    sign(mean load - load_e), the rate falling geometrically
    (``assumed.router_balance``)."""
    a = cfg["assumed"]["router_balance"]
    w = init_weights(cfg, key, dtype)
    toks = jax.random.randint(jax.random.fold_in(key, 2 ** 31 - 1),
                              (a["rows"], a["tokens"]), 0, cfg["vocab_size"])
    rates = jnp.geomspace(a["rate_first"], a["rate_last"], a["steps"])
    fitted = []

    def fit(x, lw):
        s = jax.nn.sigmoid(ref.matmul(x, lw["router"], None))

        def update(b, rate):
            load = jnp.sum(jax.nn.one_hot(ref.select_experts(s + b, cfg),
                                          s.shape[-1]), axis=(0, 1))
            return b + rate * jnp.sign(jnp.mean(load) - load), None

        b, _ = jax.lax.scan(update, lw["router_bias"], rates)
        fitted.append(b)
        return b

    ref.forward(w, toks, cfg, fit_bias=fit)
    return jnp.stack(fitted)
