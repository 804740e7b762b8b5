"""Seeded weights of the hybrid (KDA + MLA + routed-MLP) decoder, in the
published layout, stacked by kind of layer.

One function makes every tensor from a PRNG key; the driver and the
plain reference both call it with the same seed. ``cfg`` is the
configuration file: ``layer_types`` lists the kept layers' mixers
(``"kda"`` | ``"mla"``), the first ``first_k_dense_replace`` layers have
the dense MLP and the rest the routed one, ``num_experts`` is the number
of experts HELD here (``experts_held`` = [lo, lo + n) of the router's
``num_experts_total``), ``vocab_size`` the rows of the vocabulary held.

    embed, lm_head (V, D)      final_norm (D,)     (untied head)
    attn_norm, mlp_norm (L, D)
  KDA layers (Lk), H heads of dk = dv = head_dim, C = H * head_dim:
    kda_wq, kda_wk, kda_wv, kda_wg, kda_wog (Lk, D, C)   kda_wo (Lk, C, D)
    kda_wbeta (Lk, D, H)       kda_conv (Lk, 4, 3C)  taps of q | k | v,
                               oldest first
    kda_a_log (Lk, H)          kda_dt_bias (Lk, C)   kda_o_norm (Lk, dv)
  MLA layers (Lm), r = kv_lora_rank, n / p / v = nope / rope / value dims:
    mla_wq (Lm, D, H*(n+p))    mla_wkva (Lm, D, r+p)  mla_kv_norm (Lm, r)
    mla_wkvb (Lm, r, H*(n+v))  per head [k_nope | v]
    mla_q_norm, mla_k_norm (Lm, n+p)   mla_wgate (Lm, D, H)
    mla_wo (Lm, H*v, D)
  dense MLP layers (Ld): dense_gate, dense_up (Ld, D, F)  dense_down
  routed MLP layers (Le), E held experts of width Fe, Et routed over:
    router (Le, D, Et) f32     router_bias (Le, Et) f32
    exp_gate, exp_up (Le, E, D, Fe)    exp_down (Le, E, Fe, D)
    sh_gate, sh_up (Le, D, Fs)         sh_down (Le, Fs, D)

Matrices are N(0, initializer_range). What a forward pass could lose
without a crash is drawn away from its neutral value, so that dropping it
shows in the comparison: norm weights 1 + jitter * N(0, 1), the conv
taps N(0, conv_std), ``a_log`` N(0, a_log_std) and ``dt_bias``
N(dt_bias_mean, dt_bias_std) (decays alpha between about 0.2 and 0.95 a
token, so the state carries tens of tokens).

The router's selection bias is drawn N(0, router_bias_std) and then
FITTED (``balanced_router_bias``), as a deployed router's is: random
router weights alone make some experts popular with every token (the
hidden states share a common direction), and how popular the experts
held here are would then be the seed's, and the work of a decode step
with it. The fit is the aux-loss-free balancing update of the source's
family (DeepSeek-V3, arXiv:2412.19437, section 2.1.2: b_e grows by a
step where expert e is under the mean load and shrinks where over),
run over calibration tokens drawn from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("final_norm", "attn_norm", "mlp_norm", "kda_o_norm", "mla_kv_norm",
         "mla_q_norm", "mla_k_norm")
FLOAT32 = ("router", "router_bias", "kda_a_log", "kda_dt_bias")


def layer_kinds(cfg: dict):
    """(mixer kinds, n_kda, n_mla, n_dense, n_moe) of the kept layers."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must list num_hidden_layers mixers")
    n_dense = cfg["first_k_dense_replace"]
    return (kinds, kinds.count("kda"), kinds.count("mla"), n_dense,
            len(kinds) - n_dense)


def weight_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    c = h * dh
    r, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    e, et = cfg["num_experts"], cfg["num_experts_total"]
    taps = cfg["short_conv_kernel_size"]
    kinds, lk, lm, ld, le = layer_kinds(cfg)
    n = len(kinds)
    return {
        "embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
        "attn_norm": (n, d), "mlp_norm": (n, d),
        "kda_wq": (lk, d, c), "kda_wk": (lk, d, c), "kda_wv": (lk, d, c),
        "kda_wg": (lk, d, c), "kda_wog": (lk, d, c), "kda_wo": (lk, c, d),
        "kda_wbeta": (lk, d, h), "kda_conv": (lk, taps, 3 * c),
        "kda_a_log": (lk, h), "kda_dt_bias": (lk, c), "kda_o_norm": (lk, dh),
        "mla_wq": (lm, d, h * (nope + rope)), "mla_wkva": (lm, d, r + rope),
        "mla_kv_norm": (lm, r), "mla_wkvb": (lm, r, h * (nope + vd)),
        "mla_q_norm": (lm, nope + rope), "mla_k_norm": (lm, nope + rope),
        "mla_wgate": (lm, d, h), "mla_wo": (lm, h * vd, d),
        "dense_gate": (ld, d, f), "dense_up": (ld, d, f),
        "dense_down": (ld, f, d),
        "router": (le, d, et), "router_bias": (le, et),
        "exp_gate": (le, e, d, fe), "exp_up": (le, e, d, fe),
        "exp_down": (le, e, fe, d),
        "sh_gate": (le, d, fs), "sh_up": (le, d, fs), "sh_down": (le, fs, d),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2 ** 32))
    return jax.random.fold_in(key, np.uint32((seed // 2 ** 32) % 2 ** 32))


def init_weights(cfg: dict, key: jax.Array, dtype,
                 router_bias=None) -> dict:
    """Every tensor from ``key``; traceable. Tensors named in ``FLOAT32``
    stay float32 whatever ``dtype`` is (the router and the decay's
    parameters are small and feed an exponential). ``router_bias``
    (Le, Et), where given, takes the drawn bias's place."""
    a = cfg["assumed"]
    std, jitter = a["initializer_range"], a["norm_weight_jitter"]

    def draw(name, k, shape):
        z = jax.random.normal(k, shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + jitter * z
        elif name == "router_bias":
            w = a["router_bias_std"] * z
        elif name == "kda_conv":
            w = a["conv_std"] * z
        elif name == "kda_a_log":
            w = a["a_log_std"] * z
        elif name == "kda_dt_bias":
            w = a["dt_bias_mean"] + a["dt_bias_std"] * z
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
    layer is chosen about equally often; traceable. ``ref`` is the plain
    reference: its float32 forward runs ``rows`` sequences of ``tokens``
    ids drawn from ``key`` over the held vocabulary, and layer by layer,
    on that layer's own inputs, the drawn bias takes ``steps`` updates
    b_e += rate * sign(mean load - load_e), the rate falling
    geometrically from ``rate_first`` to ``rate_last``
    (``assumed.router_balance``), before the layer's output is computed
    with what was fitted."""
    a = cfg["assumed"]["router_balance"]
    w = init_weights(cfg, key, dtype)
    toks = jax.random.randint(jax.random.fold_in(key, 2 ** 31 - 1),
                              (a["rows"], a["tokens"]), 0, cfg["vocab_size"])
    rates = jnp.geomspace(a["rate_first"], a["rate_last"], a["steps"])
    fitted = []

    def fit(x, lw):
        s = jax.nn.sigmoid(ref.matmul(x, lw["router"], None))

        def update(b, rate):
            load = jnp.sum(jax.nn.one_hot(ref.select(s + b, cfg),
                                          s.shape[-1]), axis=(0, 1))
            return b + rate * jnp.sign(jnp.mean(load) - load), None

        b, _ = jax.lax.scan(update, lw["router_bias"], rates)
        fitted.append(b)
        return b

    ref.forward(w, toks, cfg, fit_bias=fit)
    return jnp.stack(fitted)
