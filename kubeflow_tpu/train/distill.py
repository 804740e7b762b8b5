"""Draft-model acquisition for speculative decoding: layer-truncate the
target, then distill it toward the target's next-token distribution.

Speculative decoding (``kubeflow_tpu/models/decode.py:
speculative_generate``) only pays off when the draft's greedy proposals
match the target's often enough; this module is the recipe that
*produces* such a draft from the target itself — no separate pretraining
run, no external checkpoint:

1. :func:`truncate_draft` — keep an evenly-strided subset of the
   target's stacked transformer blocks (``nn.scan`` stacks layer params
   on axis 0, so truncation is one gather per leaf) and share the
   embeddings and final norm. A strided skeleton retains far more of
   the target's function than random init.
2. :func:`distill_draft` — KL-distill the truncated draft on token
   sequences (ideally sequences the target itself generates, so the
   draft concentrates capacity exactly where verification will happen).
3. Export the result with ``export_model(..., draft_of="<model>@<ver>")``
   — the serving repository pairs it with its target automatically and
   routes ``speculative: true`` requests through the pair
   (``kubeflow_tpu/serving/server.py:run_generate``).

Reference parity bar: the reference wires model + server + service in
one usable step (``/root/reference/kubeflow/tf-serving/
tf-serving-template.libsonnet:33-48``); a capability that cannot serve a
request end-to-end is not shipped. This module closes that loop for
speculative decoding.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.transformer import Transformer, TransformerConfig


def truncate_draft(config: TransformerConfig, params: Any,
                   n_layers: int) -> Tuple[TransformerConfig, Any]:
    """Layer-truncated draft: ``n_layers`` evenly-strided blocks (always
    including the first and last) of the target, sharing its embeddings
    and final norm. Requires ``scan_layers=True`` params (the default) —
    layer truncation is then a single axis-0 gather per block leaf.

    Returns ``(draft_config, draft_params)``; the params are NEW arrays
    (gathers), so the draft can be trained without touching the target.
    """
    if not config.scan_layers:
        raise ValueError("truncate_draft needs scan_layers=True params "
                         "(stacked block leaves)")
    L = config.n_layers
    if not 1 <= n_layers <= L:
        raise ValueError(f"n_layers must be in [1, {L}], got {n_layers}")
    if "blocks" not in params:
        raise ValueError("params has no 'blocks' collection — not a "
                         "scan-stacked transformer param tree")
    # evenly spaced, first and last always kept: the bottom layers feed
    # every representation and the top layers shape the logits
    idx = np.unique(np.linspace(0, L - 1, n_layers).round().astype(int))
    draft_config = dataclasses.replace(config, n_layers=int(idx.size),
                                       remat=False)
    draft_params = dict(params)
    draft_params["blocks"] = jax.tree_util.tree_map(
        lambda leaf: jnp.asarray(leaf)[jnp.asarray(idx)],
        params["blocks"])
    return draft_config, draft_params


def sample_corpus(config: TransformerConfig, params: Any, *,
                  n_seqs: int, seq_len: int, seed: int = 0,
                  temperature: float = 1.0) -> np.ndarray:
    """Self-distillation corpus: ``(n_seqs, seq_len)`` token sequences
    sampled FROM THE TARGET (one random BOS-ish token, then the target's
    own continuation). Distilling on the target's generations focuses
    the draft on the distribution speculative verification will actually
    traverse."""
    from kubeflow_tpu.models.decode import generate

    rng = jax.random.key(seed)
    k_prompt, k_gen = jax.random.split(rng)
    first = jax.random.randint(k_prompt, (n_seqs, 1), 0,
                               config.vocab_size)
    rest = generate(config, params, first,
                    max_new_tokens=seq_len - 1,
                    temperature=temperature, rng=k_gen)
    return np.concatenate([np.asarray(first), np.asarray(rest)], axis=1)


def distill_draft(target_config: TransformerConfig, target_params: Any,
                  draft_config: TransformerConfig, draft_params: Any,
                  corpus: np.ndarray, *, steps: int = 100,
                  batch: int = 8, lr: float = 1e-3,
                  seed: int = 0) -> Tuple[Any, Dict[str, Any]]:
    """KL-distill the draft toward the target on ``corpus`` (N, S)
    int32 tokens. Loss is ``KL(target || draft)`` over every next-token
    position, target frozen. Returns ``(trained_draft_params, stats)``
    with ``stats = {"first_loss", "last_loss"}``.

    All-device-resident and jit-compiled: the target's logits for a
    batch are computed under the same step (no materialized logit
    corpus — at 32k vocab a stored logit set would dwarf the corpus).
    """
    import optax

    corpus = np.asarray(corpus, np.int32)
    if corpus.ndim != 2:
        raise ValueError(f"corpus must be (N, S) tokens, got "
                         f"{corpus.shape}")
    n = corpus.shape[0]
    if n < batch:
        batch = n
    target = Transformer(target_config)
    draft = Transformer(draft_config)
    tx = optax.adamw(lr)
    opt_state = tx.init(draft_params)

    # target params enter as a jit ARGUMENT: closing over them would
    # embed the full frozen target as HLO constants — catastrophic at
    # real model sizes (a 167M-param target is a ~334 MB program body
    # to compile, cache and ship)
    # one ad-hoc distillation program per make_draft call, closed over
    # this tx/draft pair — billed by the CompileLedger listener; there
    # is no long-lived runner to hang an AOT handle on
    @jax.jit
    def step(dparams, opt_state, tokens, tparams):  # tpulint: disable=TPU018
        t_logits = target.apply({"params": tparams}, tokens)
        t_probs = jax.nn.softmax(t_logits.astype(jnp.float32), axis=-1)
        t_logp = jax.nn.log_softmax(t_logits.astype(jnp.float32), -1)

        def loss_fn(p):
            d_logits = draft.apply({"params": p}, tokens)
            d_logp = jax.nn.log_softmax(
                d_logits.astype(jnp.float32), axis=-1)
            # KL(t||d) = sum t*(log t - log d); constant t-entropy kept
            # (it doesn't affect gradients, and the reported loss → 0
            # exactly when the draft matches)
            kl = jnp.sum(t_probs * (t_logp - d_logp), axis=-1)
            return jnp.mean(kl)

        loss, grads = jax.value_and_grad(loss_fn)(dparams)
        updates, opt_state = tx.update(grads, opt_state, dparams)
        return optax.apply_updates(dparams, updates), opt_state, loss

    rng = np.random.default_rng(seed)
    # first-step loss stays a device value until after the loop: a
    # float() inside would stall the host on step 1's dispatch queue
    first_loss: Optional[jnp.ndarray] = None
    loss = jnp.float32(0.0)
    for _ in range(steps):
        rows = rng.integers(0, n, size=(batch,))
        draft_params, opt_state, loss = step(
            draft_params, opt_state, jnp.asarray(corpus[rows]),
            target_params)
        if first_loss is None:
            first_loss = loss
    return draft_params, {
        "first_loss": round(float(first_loss) if first_loss is not None
                            else 0.0, 4),
        "last_loss": round(float(loss), 4)}


def make_draft(config: TransformerConfig, params: Any, *,
               n_layers: int, distill_steps: int = 100,
               corpus: Optional[np.ndarray] = None,
               corpus_seqs: int = 64, corpus_len: int = 64,
               batch: int = 8, lr: float = 1e-3,
               seed: int = 0) -> Tuple[TransformerConfig, Any,
                                       Dict[str, Any]]:
    """The one-call recipe: truncate, (optionally self-)sample a corpus,
    distill. Returns ``(draft_config, draft_params, stats)`` ready for
    ``export_model(..., draft_of=...)``."""
    draft_config, draft_params = truncate_draft(config, params, n_layers)
    if distill_steps > 0:
        if corpus is None:
            # the self-sampled corpus must fit the target's context
            corpus_len = min(corpus_len, config.max_seq_len)
            corpus = sample_corpus(config, params, n_seqs=corpus_seqs,
                                   seq_len=corpus_len, seed=seed)
        draft_params, stats = distill_draft(
            config, params, draft_config, draft_params, corpus,
            steps=distill_steps, batch=batch, lr=lr, seed=seed)
    else:
        stats = {"first_loss": 0.0, "last_loss": 0.0}
    stats["n_layers"] = draft_config.n_layers
    return draft_config, draft_params, stats
