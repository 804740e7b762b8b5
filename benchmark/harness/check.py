"""The comparison that decides ``correct``.

Every number compared has a limit of its own in
``benchmark/limits/<workload>.json`` (set from readings on the chip,
PERF.md section 2). ``judge`` holds each number to its limit and returns
the rows that the run prints beside its result.

Serving: a sample of the greedy requests that the window finished is run
once through the plain reference (prompt and served tokens, teacher
forced); the number is the gap by which a served token's reference logit
lies below the reference's best at that position. Training: the first
three steps of the very object the window then drives are followed by the
reference; losses, raw gradient norms, per-leaf norms of the first
clipped gradient (from Adam's first moment) and per-leaf norms of the
parameters' change are compared.
"""

from __future__ import annotations

import json
import math
import os
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.harness import adapter, common


def load_reference(name: str = "decoder_f32"):
    return common.load_module(
        os.path.join(common.BENCH, "references", f"{name}.py"))


def load_limits(workload: str, data_root: str = common.BENCH) -> dict:
    with open(os.path.join(data_root, "limits", f"{workload}.json")) as f:
        return json.load(f)


def judge(numbers: Dict[str, float], limits: dict):
    """(correct, rows): every limit's number must be there, finite and
    within it. Rows are (name, value, limit)."""
    rows, ok = [], True
    for name, spec in limits["numbers"].items():
        value = numbers.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= spec["limit"])
        ok = ok and good
        rows.append((name, value, spec["limit"]))
    return ok, rows


# -- serving -------------------------------------------------------------------

def pick_sample(requests: list, k: int, seed: int) -> list:
    """``k`` finished greedy requests with at least two served tokens:
    the longest, and the rest drawn from the seed."""
    pool = [r for r in requests
            if r.greedy and r.done and not r.error and len(r.tokens) >= 2]
    if not pool:
        return []
    pool.sort(key=lambda r: r.index)
    longest = max(pool, key=lambda r: r.prompt.size + len(r.tokens))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng([int(seed) % 2 ** 32, int(seed) // 2 ** 32, 7])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in picks]


def serve_gaps(cfg: dict, seed: int, sample: list, width: int,
               max_out: int, control: Optional[Callable] = None,
               ref=None) -> dict:
    """Reference logits over each sampled request's prompt and served
    tokens, in one call at a fixed shape. Returns the per-token gaps of
    the served tokens and, with ``control``, of the tokens that the
    reference computed through ``control`` puts first."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights as W

    ref = ref or load_reference()
    n = len(sample)
    toks = np.zeros((n, width), np.int32)
    pos = np.zeros((n, max_out), np.int32)
    served = np.zeros((n, max_out), np.int32)
    live = np.zeros((n, max_out), bool)
    for i, r in enumerate(sample):
        p, m = r.prompt.size, len(r.tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        toks[i, :seq.size] = seq
        pos[i, :m] = p - 1 + np.arange(m)
        served[i, :m] = r.tokens
        live[i, :m] = True

    def run(key, toks, pos, served):
        w = W.init_weights(cfg, key, adapter.dtype_of(cfg["torch_dtype"]))
        h = ref.hidden(w, toks, cfg)
        h = jnp.take_along_axis(h, pos[:, :, None], axis=1)
        head = w["embed"].astype(jnp.float32).T
        logits = jnp.matmul(h, head, precision=ref.HIGHEST)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        out = {"served": best - got}
        if control is not None:
            hc = ref.hidden(w, toks, cfg, control)
            hc = jnp.take_along_axis(hc, pos[:, :, None], axis=1)
            first = jnp.argmax(ref.matmul(hc, head, control), axis=-1)
            cgot = jnp.take_along_axis(logits, first[..., None], -1)[..., 0]
            out["control"] = best - cgot
        return out

    out = jax.jit(run)(W.seed_key(seed), toks, pos, served)
    return {k: np.asarray(v)[live] for k, v in out.items()}


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}


# -- training ------------------------------------------------------------------

def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf of a tree in the published layout; a stacked
    leaf gives one norm per layer. Traceable."""
    import jax.numpy as jnp

    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        stacked = name not in ("embed", "final_norm")
        axes = tuple(range(1, x.ndim)) if stacked else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def _flat(norms: dict) -> Dict[str, float]:
    flat = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            flat[name] = float(v)
        else:
            for i, x in enumerate(v):
                flat[f"{name}.{i}"] = float(x)
    return flat


def worst_leaf_gap(prog: dict, ref: dict, keep=None):
    """The widest gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns (gap, leaf)."""
    p, r = _flat(prog), _flat(ref)
    med = float(np.median(list(r.values())))
    worst, at = 0.0, ""
    for name, rv in r.items():
        if keep is not None and name not in keep:
            continue
        gap = abs(p[name] - rv) / max(rv, med)
        if gap > worst or not math.isfinite(gap):
            worst, at = gap, name
    return worst, at


def moving_leaves(ref_grad_norms: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    r = _flat(ref_grad_norms)
    med = float(np.median(list(r.values())))
    return {n for n, v in r.items() if v >= 1e-3 * med}


def train_reference(cfg: dict, opt: dict, seed: int,
                    batches: List[np.ndarray],
                    control: Optional[Callable] = None, ref=None,
                    rows: Optional[slice] = None) -> dict:
    """Follow the first ``len(batches)`` steps in the plain reference.
    ``rows`` plants the half-batch fault (the mean over those rows)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights as W

    ref = ref or load_reference()
    w0 = jax.jit(lambda k: W.init_weights(cfg, k, jnp.float32))(
        W.seed_key(seed))

    def one(w, st, toks):
        w, st, loss, raw, g = ref.train_step(w, st, toks, cfg, opt, control)
        return w, st, loss, raw, leaf_norms(g)

    step = jax.jit(one, donate_argnums=(0, 1))
    w, st = jax.tree_util.tree_map(jnp.copy, w0), ref.adamw_init(w0)
    losses, raws, g1 = [], [], None
    for i, toks in enumerate(batches):
        toks = jnp.asarray(toks if rows is None else toks[rows])
        w, st, loss, raw, gn = step(w, st, toks)
        losses.append(loss)
        raws.append(raw)
        if i == 0:
            g1 = gn
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))(w, w0)
    out = {"loss": [float(x) for x in losses],
           "grad_norm": [float(x) for x in raws],
           "first_grad": jax.device_get(g1),
           "change": jax.device_get(change)}
    del w, st, w0
    return out


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared for a training cell (program against
    reference readings of the same shape)."""
    keep = moving_leaves(ref["first_grad"])
    rel = lambda a, b: abs(a - b) / abs(b)  # noqa: E731
    g_gap, g_at = worst_leaf_gap(prog["first_grad"], ref["first_grad"])
    c_gap, c_at = worst_leaf_gap(prog["change"], ref["change"], keep)
    return {
        "loss_gap_max": max(rel(a, b)
                            for a, b in zip(prog["loss"], ref["loss"])),
        "grad_norm_gap_max": max(
            rel(a, b) for a, b in zip(prog["grad_norm"], ref["grad_norm"])),
        "first_grad_leaf_gap": g_gap,
        "change_leaf_gap": c_gap,
        "_first_grad_leaf": g_at, "_change_leaf": c_at,
        "_leaves_left_out": len(_flat(ref["change"])) - len(keep),
    }
