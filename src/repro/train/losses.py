"""Loss functions (f32 softmax-CE regardless of model dtype)."""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.model_api import ModelFns


def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-element cross entropy. logits (..., V) f-any, targets (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - gold


def lm_loss(logits: jax.Array, tokens: jax.Array, text_offset: int = 0) -> jax.Array:
    """Mean next-token CE. logits (B, P+T, V); tokens (B, T) text region
    starting at position ``text_offset`` within the logits."""
    pred = logits[:, text_offset : text_offset + tokens.shape[1] - 1]
    return jnp.mean(_xent(pred, tokens[:, 1:]))


def cls_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(_xent(logits, labels))


def label_token_loss(logits: jax.Array, label_tokens: jax.Array) -> jax.Array:
    """CE of the *next token after the sequence* against a class-label token —
    the prompt-style classification objective used in the paper's LLM runs."""
    return jnp.mean(_xent(logits[:, -1], label_tokens))


# One loss_fn per model: FibecFed memoizes compiled programs by loss_fn
# identity, so handing every runner the same function object (rather than a
# fresh closure per call) is what lets baselines/engines share compiles.
_LOSS_FN_CACHE: Dict[int, Callable] = {}


def make_loss_fn(model: ModelFns) -> Callable:
    """(params, lora, batch) -> scalar. Dispatches on family/batch contents.

    Calls with the same ``model`` return the same function object (memoized).
    The returned function carries a ``.masked`` attribute:
    ``masked(params, lora, batch, sample_mask)`` computes the same loss
    restricted to the mask's valid samples with a *single* batched forward
    (per-sample CE weighted by the mask). For every loss here the masked
    value equals the plain loss of the corresponding ragged sub-batch, which
    is what lets the vectorized FL engine train on padded fixed-shape
    batches at full batched-matmul efficiency. The MoE load-balance aux term
    is mask-aware too: the mask is threaded to the router as a per-sample
    weight, so padded and ragged batches produce identical aux losses.
    A model with ``forward_stats`` (the held-expert MoE) also gets
    ``.masked_stats(params, lora, batch, sample_mask) -> (loss, stats)`` for
    the label-token objective, the counts weighted by the mask.
    """
    cached = _LOSS_FN_CACHE.get(id(model))
    if cached is not None:
        return cached
    cfg = model.cfg

    def loss_fn(params, lora, batch: Dict[str, Any]):
        logits, aux = model.forward(params, lora, batch)
        with jax.named_scope("loss"):
            if cfg.family == "encoder":
                return cls_loss(logits, batch["labels"]) + aux
            if "label_token" in batch:
                return label_token_loss(logits, batch["label_token"]) + aux
            offset = cfg.num_prefix_embeddings if cfg.family == "vlm" else 0
            return lm_loss(logits, batch["tokens"], offset) + aux

    def masked_mean(logits, aux, batch, sample_mask):
        with jax.named_scope("loss"):
            m = sample_mask.astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(m), 1.0)
            if cfg.family == "encoder":
                per = _xent(logits, batch["labels"])
            elif "label_token" in batch:
                per = _xent(logits[:, -1], batch["label_token"])
            else:
                offset = cfg.num_prefix_embeddings if cfg.family == "vlm" else 0
                tokens = batch["tokens"]
                pred = logits[:, offset : offset + tokens.shape[1] - 1]
                per = jnp.mean(_xent(pred, tokens[:, 1:]), axis=-1)
            return jnp.sum(per * m) / denom + aux

    def masked(params, lora, batch: Dict[str, Any], sample_mask):
        if cfg.family == "moe":
            batch = dict(batch, sample_mask=sample_mask)
        logits, aux = model.forward(params, lora, batch)
        return masked_mean(logits, aux, batch, sample_mask)

    loss_fn.masked = masked
    if model.forward_stats is not None:

        def masked_stats(params, lora, batch: Dict[str, Any], sample_mask):
            """``masked``'s loss and the forward's counts over the valid
            samples: ``(loss, stats)``."""
            batch = dict(batch, sample_mask=sample_mask)
            logits, aux, stats = model.forward_stats(params, lora, batch)
            return masked_mean(logits, aux, batch, sample_mask), stats

        loss_fn.masked_stats = masked_stats
    # hold the model ref so id() stays unique for the cache's lifetime
    loss_fn._model = model
    _LOSS_FN_CACHE[id(model)] = loss_fn
    return loss_fn


def per_sample_losses(loss_fn: Callable, params, lora, batch) -> jax.Array:
    """(B,) per-sample losses from a mean-over-samples batch ``loss_fn``.

    Evaluates the loss on singleton-batch slices under vmap. For every loss in
    this module the batch loss equals the mean of these values (all samples in
    a batch share one sequence length), so a mask-weighted mean reproduces the
    loss of a ragged sub-batch exactly — the contract the vectorized FL engine
    relies on for padded fixed-shape batches.
    """
    expanded = jax.tree.map(lambda x: x[:, None], batch)
    return jax.vmap(lambda s: loss_fn(params, lora, s))(expanded)


def masked_mean_loss(loss_fn: Callable, params, lora, batch, sample_mask) -> jax.Array:
    """Batch loss restricted to ``sample_mask`` (B,) valid samples."""
    per = per_sample_losses(loss_fn, params, lora, batch)
    m = sample_mask.astype(jnp.float32)
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)


def make_label_token_loss(model: ModelFns) -> Callable:
    def loss_fn(params, lora, batch):
        logits, aux = model.forward(params, lora, batch)
        return label_token_loss(logits, batch["label_token"]) + aux

    return loss_fn


def make_logits_loss(cfg: ModelConfig) -> Callable:
    """loss(logits, batch) used by the GAL probe (gradient w.r.t. noise)."""

    def fn(logits, batch):
        if cfg.family == "encoder":
            return cls_loss(logits, batch["labels"])
        if "label_token" in batch:
            return label_token_loss(logits, batch["label_token"])
        offset = cfg.num_prefix_embeddings if cfg.family == "vlm" else 0
        return lm_loss(logits, batch["tokens"], offset)

    return fn
