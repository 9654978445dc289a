"""Unified model interface over all architecture families.

``build_model(cfg)`` returns a :class:`ModelFns` bundle:

- ``init_params(rng)`` — frozen base model
- ``init_lora(rng)`` — trainable LoRA tree (see repro.lora)
- ``forward(params, lora, batch)`` → (logits, aux_loss); LM families return
  (B, S, V) token logits, encoder-only returns (B, num_classes)
- ``init_cache(batch, cache_len)`` / ``prefill`` / ``decode_step`` for serving
- ``input_specs(shape)`` — ShapeDtypeStruct stand-ins for every data input of
  the given InputShape (the dry-run contract; no allocation)
- ``supports(shape)`` — whether the (arch, shape) pair is runnable
  (e.g. long_500k needs sub-quadratic attention; encoder-only has no decode)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import InputShape, ModelConfig
from repro.lora import init_lora as _init_lora_tree
from repro.models import encdec as _encdec
from repro.models import hybrid as _hybrid
from repro.models import ssm_model as _ssm
from repro.models import transformer as _tf
from repro.models.layers import rms_norm


@dataclasses.dataclass(frozen=True)
class ModelFns:
    cfg: ModelConfig
    init_params: Callable[[jax.Array], Any]
    init_lora: Callable[[jax.Array], Any]
    forward: Callable[..., Any]  # (params, lora, batch) -> (logits, aux)
    # (params, lora, batch, embed_noise=None) -> (logits, aux, layer_norms)
    # — the FibecFed GAL sensitivity probe (per-logical-layer Frobenius norms)
    forward_probe: Callable[..., Any]
    init_cache: Callable[..., Any]  # (batch, cache_len) -> cache
    prefill: Callable[..., Any]  # (params, lora, batch, cache_len) -> (logits, cache, pos)
    # (params, lora, token, cache, position) -> (logits, cache).
    # ``position`` is a scalar (uniform batch, the training-eval path) or a
    # (B,) int32 vector of per-slot positions (continuous-batching serving,
    # where each cache row is at its own depth). ``lora`` leaves may carry a
    # per-slot batch axis — a: (L, B, d_in, r), b: (L, B, r, d_out) (see
    # repro.lora.gather_adapter_slots) — giving every batch row its own
    # adapter; unbatched leaves mean one shared adapter, exactly as before.
    decode_step: Callable[..., Any]
    input_specs: Callable[[InputShape], Dict[str, Any]]
    supports: Callable[[InputShape], bool]
    # (params, lora, batch) -> (logits, aux, stats): ``forward`` plus the
    # held-expert layers' counts (``{"held_assignments", "expert_rows",
    # "dense_fallbacks"}`` scalars, assignments weighted by
    # ``batch["sample_mask"]``); None where the model keeps none
    forward_stats: Optional[Callable[..., Any]] = None


def _token_dtype():
    return jnp.int32


def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    """Text tokens after reserving room for prefix (patch/frame) embeddings."""
    if cfg.family == "vlm" and cfg.num_prefix_embeddings:
        return seq_len - cfg.num_prefix_embeddings
    return seq_len


def _make_input_specs(cfg: ModelConfig):
    def input_specs(shape: InputShape) -> Dict[str, Any]:
        B, S = shape.global_batch, shape.seq_len
        emb_dtype = jnp.dtype(cfg.dtype)
        if shape.kind in ("train", "prefill"):
            T = _text_len(cfg, S)
            specs: Dict[str, Any] = {
                "tokens": jax.ShapeDtypeStruct((B, T), _token_dtype())
            }
            if cfg.family == "vlm":
                specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_prefix_embeddings, cfg.d_model), emb_dtype
                )
            if cfg.family in ("encdec", "audio"):
                specs["encoder_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq_len, cfg.d_model), emb_dtype
                )
            if cfg.family == "encoder" and shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((B,), _token_dtype())
            return specs
        # decode: one new token against a cache of length S
        return {"token": jax.ShapeDtypeStruct((B, 1), _token_dtype())}

    return input_specs


def _make_supports(cfg: ModelConfig):
    def supports(shape: InputShape) -> bool:
        if shape.kind == "decode":
            if cfg.family == "encoder":
                return False  # encoder-only: no autoregressive decode
            if shape.seq_len > 65536 and not cfg.supports_long_context:
                return False  # long_500k needs sub-quadratic attention
        return True

    return supports


# ---------------------------------------------------------------------------
# family adapters
# ---------------------------------------------------------------------------


def _decoder_fns(cfg: ModelConfig) -> ModelFns:
    def forward(params, lora, batch):
        return _tf.decoder_forward(
            params, lora["layers"], batch["tokens"], cfg,
            prefix_embeds=batch.get("prefix_embeds"),
            # optional (B,) validity weights for the MoE aux loss; the masked
            # loss passes them so padded batches score like their ragged
            # originals (ignored by non-MoE families)
            sample_weight=batch.get("sample_mask"),
        )

    def forward_probe(params, lora, batch, embed_noise=None):
        return _tf.decoder_forward(
            params, lora["layers"], batch["tokens"], cfg,
            prefix_embeds=batch.get("prefix_embeds"),
            embed_noise=embed_noise, collect_layer_norms=True,
        )

    def forward_stats(params, lora, batch):
        return _tf.decoder_forward(
            params, lora["layers"], batch["tokens"], cfg,
            sample_weight=batch.get("sample_mask"), with_stats=True,
        )

    def init_cache(batch, cache_len):
        return _tf.init_kv_cache(cfg, batch, cache_len)

    def prefill(params, lora, batch, cache_len):
        return _tf.decoder_prefill(
            params, lora["layers"], batch["tokens"], cfg, cache_len,
            prefix_embeds=batch.get("prefix_embeds"),
        )

    def decode_step(params, lora, token, cache, position):
        ring = cfg.attention_window is not None and (
            cache["k"].shape[2] <= cfg.attention_window
        )
        return _tf.decoder_decode_step(
            params, lora["layers"], token, cfg, cache, position, ring=ring
        )

    return ModelFns(
        cfg=cfg,
        init_params=lambda rng: _tf.init_decoder(rng, cfg),
        init_lora=lambda rng: _init_lora_tree(rng, cfg),
        forward=forward,
        forward_probe=forward_probe,
        init_cache=init_cache,
        prefill=prefill,
        decode_step=decode_step,
        input_specs=_make_input_specs(cfg),
        supports=_make_supports(cfg),
        forward_stats=forward_stats if _tf.keeps_expert_stats(cfg) else None,
    )


def _encoder_fns(cfg: ModelConfig) -> ModelFns:
    """Encoder-only classifier (RoBERTa-style, the paper's own model)."""

    def init_params(rng):
        params = _tf.init_decoder(rng, cfg)
        params.pop("lm_head", None)
        k = jax.random.fold_in(rng, 99)
        params["cls_head"] = (
            jax.random.normal(k, (cfg.d_model, cfg.num_classes), jnp.float32) * 0.02
        ).astype(jnp.dtype(cfg.dtype))
        return params

    def _forward_impl(params, lora, batch, embed_noise=None, collect=False):
        tokens = batch["tokens"]
        lora_scale = cfg.lora_alpha / cfg.lora_rank
        h = jnp.take(params["embed"], tokens, axis=0)
        if embed_noise is not None:
            h = h + embed_noise.astype(h.dtype)
        S = h.shape[1]
        positions = jnp.arange(S)[None, :]

        def body(carry, xs):
            h = carry
            p_slice, lora_slice = xs
            h, _, _ = _tf.decoder_layer(
                h, p_slice, lora_slice, cfg, positions,
                lora_scale=lora_scale, causal=False,  # bidirectional encoder
            )
            norm = jnp.sqrt(jnp.sum(jnp.square(h.astype(jnp.float32)), axis=(1, 2)))
            return h, (norm if collect else None)

        h, norms = jax.lax.scan(body, h, (params["layers"], lora["layers"]))
        if cfg.norm == "layernorm":
            from repro.models.layers import layer_norm

            h = layer_norm(h, params["final_norm_w"], params["final_norm_b"])
        else:
            h = rms_norm(h, params["final_norm_w"])
        pooled = jnp.mean(h, axis=1)
        logits = jnp.einsum("bd,dc->bc", pooled, params["cls_head"].astype(h.dtype))
        if collect:
            return logits, jnp.zeros((), jnp.float32), norms
        return logits, jnp.zeros((), jnp.float32)

    def forward(params, lora, batch):
        return _forward_impl(params, lora, batch)

    def forward_probe(params, lora, batch, embed_noise=None):
        return _forward_impl(params, lora, batch, embed_noise, collect=True)

    def _no_decode(*a, **k):
        raise NotImplementedError("encoder-only model has no decode path")

    return ModelFns(
        cfg=cfg,
        init_params=init_params,
        init_lora=lambda rng: _init_lora_tree(rng, cfg),
        forward=forward,
        forward_probe=forward_probe,
        init_cache=_no_decode,
        prefill=_no_decode,
        decode_step=_no_decode,
        input_specs=_make_input_specs(cfg),
        supports=_make_supports(cfg),
    )


def _encdec_fns(cfg: ModelConfig) -> ModelFns:
    def forward(params, lora, batch):
        return _encdec.encdec_forward(params, lora, batch, cfg)

    def forward_probe(params, lora, batch, embed_noise=None):
        return _encdec.encdec_forward(
            params, lora, batch, cfg, embed_noise=embed_noise,
            collect_layer_norms=True,
        )

    def init_cache(batch, cache_len):
        return _encdec.init_encdec_cache(cfg, batch, cache_len)

    def prefill(params, lora, batch, cache_len):
        return _encdec.encdec_prefill(params, lora, batch, cfg, cache_len)

    def decode_step(params, lora, token, cache, position):
        ring = cfg.attention_window is not None and (
            cache["k"].shape[2] <= cfg.attention_window
        )
        return _encdec.encdec_decode_step(params, lora, token, cfg, cache, position, ring=ring)

    return ModelFns(
        cfg=cfg,
        init_params=lambda rng: _encdec.init_encdec(rng, cfg),
        init_lora=lambda rng: _init_lora_tree(rng, cfg),
        forward=forward,
        forward_probe=forward_probe,
        init_cache=init_cache,
        prefill=prefill,
        decode_step=decode_step,
        input_specs=_make_input_specs(cfg),
        supports=_make_supports(cfg),
    )


def _ssm_fns(cfg: ModelConfig) -> ModelFns:
    def forward(params, lora, batch):
        return _ssm.ssm_forward(params, lora["layers"], batch["tokens"], cfg)

    def forward_probe(params, lora, batch, embed_noise=None):
        return _ssm.ssm_forward(
            params, lora["layers"], batch["tokens"], cfg,
            embed_noise=embed_noise, collect_layer_norms=True,
        )

    return ModelFns(
        cfg=cfg,
        init_params=lambda rng: _ssm.init_ssm_model(rng, cfg),
        init_lora=lambda rng: _init_lora_tree(rng, cfg),
        forward=forward,
        forward_probe=forward_probe,
        init_cache=lambda batch, cache_len: _ssm.init_ssm_cache(cfg, batch, cache_len),
        prefill=lambda params, lora, batch, cache_len: _ssm.ssm_prefill(
            params, lora["layers"], batch["tokens"], cfg, cache_len
        ),
        decode_step=lambda params, lora, token, cache, position: _ssm.ssm_decode_step(
            params, lora["layers"], token, cfg, cache, position
        ),
        input_specs=_make_input_specs(cfg),
        supports=_make_supports(cfg),
    )


def _hybrid_fns(cfg: ModelConfig) -> ModelFns:
    def forward(params, lora, batch):
        return _hybrid.hybrid_forward(params, lora, batch["tokens"], cfg)

    def forward_probe(params, lora, batch, embed_noise=None):
        return _hybrid.hybrid_forward(
            params, lora, batch["tokens"], cfg,
            embed_noise=embed_noise, collect_layer_norms=True,
        )

    return ModelFns(
        cfg=cfg,
        init_params=lambda rng: _hybrid.init_hybrid(rng, cfg),
        init_lora=lambda rng: _init_lora_tree(rng, cfg),
        forward=forward,
        forward_probe=forward_probe,
        init_cache=lambda batch, cache_len: _hybrid.init_hybrid_cache(cfg, batch, cache_len),
        prefill=lambda params, lora, batch, cache_len: _hybrid.hybrid_prefill(
            params, lora, batch["tokens"], cfg, cache_len
        ),
        decode_step=lambda params, lora, token, cache, position: _hybrid.hybrid_decode_step(
            params, lora, token, cfg, cache, position
        ),
        input_specs=_make_input_specs(cfg),
        supports=_make_supports(cfg),
    )


def build_model(cfg: ModelConfig) -> ModelFns:
    if cfg.family in ("dense", "moe", "vlm"):
        return _decoder_fns(cfg)
    if cfg.family in ("encdec", "audio"):
        return _encdec_fns(cfg)
    if cfg.family == "ssm":
        return _ssm_fns(cfg)
    if cfg.family == "hybrid":
        return _hybrid_fns(cfg)
    if cfg.family == "encoder":
        return _encoder_fns(cfg)
    raise ValueError(f"unknown family {cfg.family}")
