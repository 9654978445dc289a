"""LoRA parameter trees + FibecFed masking helpers.

The LoRA tree mirrors the model's stacked-layer layout:

- dense / moe / vlm / encoder: ``{"layers": {target: {"a": (L, d_in, r),
  "b": (L, r, d_out)}}}`` (targets = wq/wk/wv/wo; with latent attention
  wq/wkv_a/wkv_b/wo, one stack over every layer, leading dense ones too)
- encdec: ``{"encoder": {...(Le)}, "decoder": {... incl. cwq..cwo (Ld)}}``
- ssm: ``{"layers": {"in_proj"|"out_proj": {a, b}}}``
- hybrid: ``{"mamba": stacked(L), "shared": unstacked}``

FibecFed operates at two granularities on this tree:

* **GAL (layer) masks** — a boolean per *logical layer* (see
  :func:`lora_num_logical_layers`); GAL layers' LoRA is globally aggregated,
  the rest stays client-local (paper §4.3.1).
* **Neuron masks** — booleans over the *output dimension* of each target
  (rows of the full weight matrix, Eq. 12); frozen neurons mask the columns
  of LoRA ``b`` so their delta never changes (paper §4.3.2). ``a`` is shared
  by all neurons and stays trainable.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig


def _attn_dims(cfg: ModelConfig) -> Dict[str, tuple]:
    if cfg.mla is not None:
        from repro.models.mla import mla_dims  # lazy: breaks lora<->models cycle

        return mla_dims(cfg)
    hd = cfg.resolved_head_dim
    return {
        "wq": (cfg.d_model, cfg.num_heads * hd),
        "wk": (cfg.d_model, cfg.num_kv_heads * hd),
        "wv": (cfg.d_model, cfg.num_kv_heads * hd),
        "wo": (cfg.num_heads * hd, cfg.d_model),
    }


def _ssm_lora_dims(cfg: ModelConfig) -> Dict[str, tuple]:
    from repro.models.ssm import ssm_dims  # lazy: breaks lora<->models cycle

    dims = ssm_dims(cfg)
    return {
        "in_proj": (cfg.d_model, dims["in_dim"]),
        "out_proj": (dims["d_inner"], cfg.d_model),
    }


def _init_target_stack(rng, n_layers, dims: Dict[str, tuple], rank: int):
    out = {}
    for i, (t, (d_in, d_out)) in enumerate(sorted(dims.items())):
        key = jax.random.fold_in(rng, i)
        shape_a = (n_layers, d_in, rank) if n_layers else (d_in, rank)
        shape_b = (n_layers, rank, d_out) if n_layers else (rank, d_out)
        out[t] = {
            "a": jax.random.normal(key, shape_a, jnp.float32) / rank,
            "b": jnp.zeros(shape_b, jnp.float32),
        }
    return out


def init_lora(rng, cfg: ModelConfig) -> Dict[str, Any]:
    rank = cfg.lora_rank
    if cfg.family in ("encdec", "audio"):
        attn_d = _attn_dims(cfg)
        cross_d = {f"c{k}": v for k, v in attn_d.items()}
        return {
            "encoder": _init_target_stack(jax.random.fold_in(rng, 0), cfg.encoder_layers, attn_d, rank),
            "decoder": _init_target_stack(
                jax.random.fold_in(rng, 1), cfg.num_layers, {**attn_d, **cross_d}, rank
            ),
        }
    if cfg.family == "ssm":
        return {"layers": _init_target_stack(rng, cfg.num_layers, _ssm_lora_dims(cfg), rank)}
    if cfg.family == "hybrid":
        return {
            "mamba": _init_target_stack(
                jax.random.fold_in(rng, 0), cfg.num_layers, _ssm_lora_dims(cfg), rank
            ),
            "shared": _init_target_stack(jax.random.fold_in(rng, 1), 0, _attn_dims(cfg), rank),
        }
    # dense / moe / vlm / audio-decoder / encoder
    return {"layers": _init_target_stack(rng, cfg.num_layers, _attn_dims(cfg), rank)}


def zeros_like_lora(lora) -> Any:
    return jax.tree.map(jnp.zeros_like, lora)


def lora_param_count(lora) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(lora))


# ---------------------------------------------------------------------------
# logical layer bookkeeping
# ---------------------------------------------------------------------------


def lora_num_logical_layers(cfg: ModelConfig) -> int:
    if cfg.family in ("encdec", "audio"):
        return cfg.encoder_layers + cfg.num_layers
    if cfg.family == "hybrid":
        return cfg.num_layers + 1  # + the shared attention block
    return cfg.num_layers


def _group_offsets(cfg: ModelConfig) -> Dict[str, tuple]:
    """Map top-level lora group -> (layer_offset, n_layers|0 for unstacked)."""
    if cfg.family in ("encdec", "audio"):
        return {"encoder": (0, cfg.encoder_layers), "decoder": (cfg.encoder_layers, cfg.num_layers)}
    if cfg.family == "hybrid":
        return {"mamba": (0, cfg.num_layers), "shared": (cfg.num_layers, 0)}
    return {"layers": (0, cfg.num_layers)}


def lora_layer_index_tree(cfg: ModelConfig, lora) -> Any:
    """Pytree matching `lora` whose leaves are int arrays of per-slice layer ids."""
    out = {}
    for group, (offset, n) in _group_offsets(cfg).items():
        idx = np.arange(offset, offset + n) if n else np.array(offset)

        def mk(leaf, idx=idx, stacked=bool(n)):
            if stacked:
                shape = (len(idx),) + (1,) * (leaf.ndim - 1)
                return jnp.asarray(idx).reshape(shape)
            return jnp.asarray(idx)

        out[group] = jax.tree.map(mk, lora[group])
    return out


def stack_adapter_trees(adapters) -> Any:
    """Stack a list of same-shaped LoRA trees along a new leading adapter
    axis: each leaf (L, d_in, r) → (A, L, d_in, r), unstacked (d_in, r) →
    (A, d_in, r). The registry format for multi-tenant serving."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *adapters)


def gather_adapter_slots(cfg: ModelConfig, stacked, idx: jax.Array) -> Any:
    """Gather per-slot adapters out of a :func:`stack_adapter_trees` stack.

    ``idx``: (B,) int32 adapter index per batch slot. Stacked-group leaves
    (A, L, ...) gather to (B, L, ...) then move the layer axis back in front
    → (L, B, ...), so a layer scan slices per-slot (B, ...) leaves that
    :func:`repro.models.layers.linear` applies row-wise. Unstacked groups
    ((A, d_in, r), e.g. hybrid "shared") gather straight to (B, d_in, r).
    """
    out = {}
    for group, (_, n) in _group_offsets(cfg).items():
        if n:
            out[group] = jax.tree.map(
                lambda leaf: jnp.moveaxis(leaf[idx], 0, 1), stacked[group]
            )
        else:
            out[group] = jax.tree.map(lambda leaf: leaf[idx], stacked[group])
    return out


def gal_mask_tree(cfg: ModelConfig, lora, gal_layers: jax.Array) -> Any:
    """gal_layers: bool (num_logical_layers,). Returns {0.,1.} masks matching lora."""
    gal = jnp.asarray(gal_layers, jnp.float32)
    out = {}
    for group, (offset, n) in _group_offsets(cfg).items():
        if n:
            seg = gal[offset : offset + n]

            def mk(leaf, seg=seg):
                return seg.reshape((n,) + (1,) * (leaf.ndim - 1)) * jnp.ones((), jnp.float32)

            out[group] = jax.tree.map(mk, lora[group])
        else:
            val = gal[offset]
            out[group] = jax.tree.map(lambda leaf: val * jnp.ones((), jnp.float32), lora[group])
    return out


def rank_mask_tree(lora, rank: int) -> Any:
    """Per-leaf {0.,1.} masks keeping only the first ``rank`` LoRA rank
    components trainable (resource-adaptive per-client rank).

    A rank-``r_i`` client updates the leading ``r_i`` columns of ``a`` and
    rows of ``b``; the remaining components stay frozen at the pulled global
    values, so its delta is exactly zero beyond ``r_i`` — heterogeneous-rank
    aggregation into the full server rank is then plain (weighted) delta
    summation, with the pull side projecting down to ``r_i`` components.
    ``rank >=`` the LoRA rank returns all-ones (the exact no-op).
    """

    def mk(ab):
        r = ab["a"].shape[-1]
        keep = (jnp.arange(r) < rank).astype(jnp.float32)
        return {
            "a": keep * jnp.ones_like(ab["a"], jnp.float32),
            "b": keep[:, None] * jnp.ones_like(ab["b"], jnp.float32),
        }

    return {
        group: {t: mk(ab) for t, ab in targets.items()}
        for group, targets in lora.items()
    }


def neuron_mask_tree(cfg: ModelConfig, lora, neuron_masks: Dict[str, Any]) -> Any:
    """Build per-leaf update masks from per-target neuron keep-masks.

    neuron_masks mirrors the lora tree at target granularity:
    ``{group: {target: keep (L, d_out) or (d_out,)}}``. The mask multiplies
    LoRA ``b`` columns; ``a`` is always trainable (1.0).
    """
    out = {}
    for group, targets in lora.items():
        g = {}
        for t, ab in targets.items():
            keep = neuron_masks[group][t].astype(jnp.float32)
            if ab["b"].ndim == 3:  # stacked (L, r, d_out); keep (L, d_out)
                bmask = keep[:, None, :]
            else:
                bmask = keep[None, :]
            g[t] = {"a": jnp.ones_like(ab["a"], jnp.float32), "b": bmask * jnp.ones_like(ab["b"], jnp.float32)}
        out[group] = g
    return out
