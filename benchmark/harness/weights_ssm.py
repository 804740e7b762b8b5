"""Seeded weights of NVIDIA-Nemotron-3-Nano-30B-A3B's decoder (Mamba-2,
rope-free GQA and relu² MoE blocks, one sublayer a block), in the
published layout, stacked by kind of block.

One function makes every tensor from a PRNG key; the driver and the plain
reference both call it with the same seed. ``cfg`` is the configuration
file: ``hybrid_override_pattern`` spells the kept blocks (``M`` Mamba-2,
``*`` attention, ``E`` MoE), ``n_routed_experts`` is the number of
experts HELD here (``experts_held`` = [lo, lo + n) of the router's
``n_routed_experts_total``), ``vocab_size`` the rows of the vocabulary
held.

    embed, lm_head (V, D)      final_norm (D,)     (untied head)
    block_norm (L, D)
  M blocks (Lm), H heads of P, state N, G groups, I = H P, W = I + 2 G N:
    ssm_in (Lm, D, I + W + H)   columns [z | x | B | C | dt]
    ssm_conv_w (Lm, 4, W)       taps over x | B | C, oldest first
    ssm_conv_b (Lm, W)          ssm_norm (Lm, I)     ssm_out (Lm, I, D)
    ssm_a_log, ssm_dt_bias, ssm_d (Lm, H) f32
  * blocks (La): attn_wq (La, D, Hq Dh)  attn_wk, attn_wv (La, D, KH Dh)
    attn_wo (La, Hq Dh, D)
  E blocks (Le), E held experts of width Fe, Et routed over:
    router (Le, D, Et) f32      router_bias (Le, Et) f32
    exp_up (Le, E, D, Fe)       exp_down (Le, E, Fe, D)
    sh_up (Le, D, Fs)           sh_down (Le, Fs, D)

Matrices are N(0, initializer_range). What a forward pass could lose
without a crash is drawn away from its neutral value, so that dropping it
shows in the comparison: norm weights and ``D`` 1 + jitter * N(0, 1), the
conv taps N(0, conv_std) and its bias N(0, conv_bias_std). ``A_log`` and
``dt_bias`` are drawn as the published initialiser draws them: A uniform
in ``A_init_range``, dt log-uniform in [time_step_min, time_step_max],
floored at time_step_floor, ``dt_bias`` its inverse softplus.

The router's selection bias is drawn N(0, router_bias_std) and then
FITTED (``balanced_router_bias``), as ``weights_hybrid.py`` says and does
for Ling's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights_hybrid import seed_key  # noqa: F401

NORMS = ("final_norm", "block_norm", "ssm_norm", "ssm_d")
FLOAT32 = ("router", "router_bias", "ssm_a_log", "ssm_dt_bias", "ssm_d")
KINDS = {"M": "ssm", "*": "attn", "E": "moe"}


def block_kinds(cfg: dict) -> list:
    """The kept blocks' kinds (``ssm`` | ``attn`` | ``moe``), in order."""
    kinds = [KINDS[letter] for letter in cfg["hybrid_override_pattern"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern must spell "
                         "num_hidden_layers blocks")
    return kinds


def weight_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    i = h * p
    w = i + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    hq, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    fe = cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    e, et = cfg["n_routed_experts"], cfg["n_routed_experts_total"]
    kinds = block_kinds(cfg)
    lm, la, le = (kinds.count(k) for k in ("ssm", "attn", "moe"))
    return {
        "embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
        "block_norm": (len(kinds), d),
        "ssm_in": (lm, d, i + w + h), "ssm_conv_w": (lm, cfg["conv_kernel"], w),
        "ssm_conv_b": (lm, w), "ssm_norm": (lm, i), "ssm_out": (lm, i, d),
        "ssm_a_log": (lm, h), "ssm_dt_bias": (lm, h), "ssm_d": (lm, h),
        "attn_wq": (la, d, hq * dh), "attn_wk": (la, d, kh * dh),
        "attn_wv": (la, d, kh * dh), "attn_wo": (la, hq * dh, d),
        "router": (le, d, et), "router_bias": (le, et),
        "exp_up": (le, e, d, fe), "exp_down": (le, e, fe, d),
        "sh_up": (le, d, fs), "sh_down": (le, fs, d),
    }


def init_weights(cfg: dict, key: jax.Array, dtype,
                 router_bias=None) -> dict:
    """Every tensor from ``key``; traceable. Tensors named in ``FLOAT32``
    stay float32 whatever ``dtype`` is. ``router_bias`` (Le, Et), where
    given, takes the drawn bias's place."""
    a = cfg["assumed"]
    std, jitter = a["initializer_range"], a["norm_weight_jitter"]

    def draw(name, k, shape):
        if name == "ssm_a_log":
            lo, hi = a["A_init_range"]
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        elif name == "ssm_dt_bias":
            lo, hi = cfg["time_step_min"], cfg["time_step_max"]
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(lo), jnp.log(hi))),
                cfg["time_step_floor"])
            w = dt + jnp.log(-jnp.expm1(-dt))      # softplus(w) = dt
        else:
            z = jax.random.normal(k, shape, jnp.float32)
            if name in NORMS:
                w = 1.0 + jitter * z
            elif name == "router_bias":
                w = a["router_bias_std"] * z
            elif name == "ssm_conv_w":
                w = a["conv_std"] * z
            elif name == "ssm_conv_b":
                w = a["conv_bias_std"] * z
            else:
                w = std * z
        return w.astype(jnp.float32 if name in FLOAT32 else dtype)

    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if len(shape) >= 3 and shape[0]:
            # block by block: a caller that takes one block's slice of
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
    block is chosen about equally often; traceable. As
    ``weights_hybrid.balanced_router_bias``: the reference's float32
    forward runs ``rows`` sequences of ``tokens`` ids drawn from ``key``
    over the held vocabulary, and block by block, on that block's own
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
            load = jnp.sum(jax.nn.one_hot(ref.select(s + b, cfg),
                                          s.shape[-1]), axis=(0, 1))
            return b + rate * jnp.sign(jnp.mean(load) - load), None

        b, _ = jax.lax.scan(update, lw["router_bias"], rates)
        fitted.append(b)
        return b

    ref.forward(w, toks, cfg, fit_bias=fit)
    return jnp.stack(fitted)
