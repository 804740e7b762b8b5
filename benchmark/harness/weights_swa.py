"""Seeded weights of MiMo-V2-Flash's decoder (global and sliding-window
rotary GQA layers, the window layers' sinks, a dense MLP layer, then
routed MLP layers with no shared expert), in the published layout,
stacked by kind of layer.

One function makes every tensor from a PRNG key; the driver and the
plain reference both call it with the same seed. ``cfg`` is the
configuration file: ``hybrid_layer_pattern`` says which layers are
window (1) and global (0), ``moe_layer_freq`` which have the routed MLP,
``n_routed_experts`` is the number of experts HELD here (``experts_held``
= [lo, lo + n) of the router's ``n_routed_experts_total``),
``vocab_size`` the rows of the vocabulary held.

    embed, lm_head (V, D)      final_norm (D,)     (untied head)
    attn_norm, mlp_norm (L, D)
  global attention layers (Lg), H heads, KH = num_key_value_heads:
    g_wq (Lg, D, H*dk)   g_wk (Lg, D, KH*dk)   g_wv (Lg, D, KH*dv)
    g_wo (Lg, H*dv, D)
  window attention layers (Ls), KH = swa_num_key_value_heads: s_wq,
    s_wk, s_wv, s_wo as above, and s_sinks (Ls, H) f32
  dense MLP layers (Ld): dense_gate, dense_up (Ld, D, F)  dense_down
  routed MLP layers (Le), E held experts of width Fe, Et routed over:
    router (Le, D, Et) f32     router_bias (Le, Et) f32
    exp_gate, exp_up (Le, E, D, Fe)    exp_down (Le, E, Fe, D)

Matrices are N(0, initializer_range). Norm weights are 1 + jitter * N(0,
1), so that dropping one shows in the comparison; the sinks N(sink_mean,
sink_std) (``assumed.sink_init``). The router's selection bias is drawn
N(0, router_bias_std) and then FITTED as ``weights_dsa`` fits
DeepSeek-V3.2's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights_hybrid import seed_key  # noqa: F401

NORMS = ("final_norm", "attn_norm", "mlp_norm")
FLOAT32 = ("router", "router_bias", "s_sinks")


def layer_counts(cfg: dict):
    """(global layers, window layers, dense-MLP layers, routed layers)."""
    pattern, moe = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    n = cfg["num_hidden_layers"]
    if len(pattern) != n or len(moe) != n:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq must "
                         "list num_hidden_layers entries")
    return n - sum(pattern), sum(pattern), n - sum(moe), sum(moe)


def weight_shapes(cfg: dict) -> dict:
    d, v, h = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    kg, ks = cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, et = cfg["n_routed_experts"], cfg["n_routed_experts_total"]
    n = cfg["num_hidden_layers"]
    lg, ls, ld, le = layer_counts(cfg)
    return {
        "embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
        "attn_norm": (n, d), "mlp_norm": (n, d),
        "g_wq": (lg, d, h * dk), "g_wk": (lg, d, kg * dk),
        "g_wv": (lg, d, kg * dv), "g_wo": (lg, h * dv, d),
        "s_wq": (ls, d, h * dk), "s_wk": (ls, d, ks * dk),
        "s_wv": (ls, d, ks * dv), "s_wo": (ls, h * dv, d),
        "s_sinks": (ls, h),
        "dense_gate": (ld, d, f), "dense_up": (ld, d, f),
        "dense_down": (ld, f, d),
        "router": (le, d, et), "router_bias": (le, et),
        "exp_gate": (le, e, d, fe), "exp_up": (le, e, d, fe),
        "exp_down": (le, e, fe, d),
    }


def init_weights(cfg: dict, key: jax.Array, dtype,
                 router_bias=None) -> dict:
    """Every tensor from ``key``; traceable. The router, its bias and the
    sinks stay float32 whatever ``dtype`` is. ``router_bias`` (Le, Et),
    where given, takes the drawn bias's place."""
    a = cfg["assumed"]
    std, jitter = a["initializer_range"], a["norm_weight_jitter"]

    def draw(name, k, shape):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + jitter * z
        elif name == "s_sinks":
            w = a["sink_mean"] + a["sink_std"] * z
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
    ``weights_dsa.balanced_router_bias``: the reference's float32 forward
    runs ``rows`` sequences of ``tokens`` ids drawn from ``key`` over the
    held vocabulary, and layer by layer, on that layer's own inputs, the
    drawn bias takes ``steps`` updates b_e += rate * sign(mean load -
    load_e), the rate falling geometrically (``assumed.router_balance``)."""
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
