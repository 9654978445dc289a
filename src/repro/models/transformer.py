"""Decoder-only transformer (dense, MoE, VLM/audio-prefix) with scan-over-layers.

All per-layer weights are stacked on a leading layer axis and the layer loop
is a ``lax.scan`` — keeps HLO size O(1) in depth (essential for compiling 48+
layer configs against a 512-device mesh). LoRA trees mirror the stacked
layout; the scan consumes (param_slice, lora_slice[, cache_slice]) per step.

Supported knobs (ModelConfig): GQA ratios, qkv bias (qwen2), qk-norm (qwen3),
RoPE full/half ("2d", chatglm), parallel residual, rms/layer norm, SwiGLU/GELU
MLP, MoE FFN (+shared expert), sliding-window attention, prefix embeddings
(paligemma patches / audio frames), logit soft-cap, tied embeddings; and the
DeepSeek-V3 block (Moonlight): latent attention (``models.mla``), leading
dense layers, then sigmoid-routed held experts (``moe.apply_held_moe``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (
    apply_rope,
    init_embed,
    init_stacked_dense,
    linear,
    rms_norm,
    layer_norm,
    soft_cap,
)
from repro.models.mla import init_mla_stack, mla_attention
from repro.models.mlp import apply_mlp, init_mlp
from repro.models.moe import apply_held_moe, apply_moe, init_moe

LORA_ATTN_TARGETS = ("wq", "wk", "wv", "wo")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_attn_layer_stack(rng, n_layers: int, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    r = jax.random.split(rng, 4)
    p = {
        "wq": init_stacked_dense(r[0], n_layers, D, H * hd, dtype),
        "wk": init_stacked_dense(r[1], n_layers, D, KVH * hd, dtype),
        "wv": init_stacked_dense(r[2], n_layers, D, KVH * hd, dtype),
        "wo": init_stacked_dense(r[3], n_layers, H * hd, D, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_layers, H * hd), dtype)
        p["bk"] = jnp.zeros((n_layers, KVH * hd), dtype)
        p["bv"] = jnp.zeros((n_layers, KVH * hd), dtype)
    if cfg.qk_norm:
        p["q_norm_w"] = jnp.ones((n_layers, hd), dtype)
        p["k_norm_w"] = jnp.ones((n_layers, hd), dtype)
    return p


def _init_norms(n_layers: int, d: int, kind: str, dtype, names) -> Dict[str, Any]:
    out = {}
    for name in names:
        out[f"{name}_w"] = jnp.ones((n_layers, d), dtype)
        if kind == "layernorm":
            out[f"{name}_b"] = jnp.zeros((n_layers, d), dtype)
    return out


def init_decoder(rng, cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.mla is not None:
        return init_mla_decoder(rng, cfg)
    dtype = jnp.dtype(cfg.dtype)
    r = jax.random.split(rng, 6)
    L = cfg.num_layers
    layers: Dict[str, Any] = {}
    layers.update(init_attn_layer_stack(r[0], L, cfg, dtype))
    layers.update(_init_norms(L, cfg.d_model, cfg.norm, dtype, ["attn_norm", "mlp_norm"]))
    if cfg.family == "moe":
        layers.update(init_moe(r[1], L, cfg.d_model, cfg.moe, dtype))
    else:
        layers.update(init_mlp(r[1], L, cfg.d_model, cfg.d_ff, cfg.mlp, dtype))
    params = {
        "embed": init_embed(r[2], cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm_w": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_stacked_dense(r[3], 1, cfg.d_model, cfg.vocab_size, dtype)[0]
    return params


def init_lora_attn(rng, n_layers: int, cfg: ModelConfig, targets=LORA_ATTN_TARGETS):
    """LoRA A ~ N(0, 1/r), B = 0 (standard init). Stacked over layers, f32."""
    hd = cfg.resolved_head_dim
    dims = {
        "wq": (cfg.d_model, cfg.num_heads * hd),
        "wk": (cfg.d_model, cfg.num_kv_heads * hd),
        "wv": (cfg.d_model, cfg.num_kv_heads * hd),
        "wo": (cfg.num_heads * hd, cfg.d_model),
    }
    rank = cfg.lora_rank
    out = {}
    for i, t in enumerate(targets):
        d_in, d_out = dims[t]
        key = jax.random.fold_in(rng, i)
        out[t] = {
            "a": jax.random.normal(key, (n_layers, d_in, rank), jnp.float32) / rank,
            "b": jnp.zeros((n_layers, rank, d_out), jnp.float32),
        }
    return out


def _mla_dense_layers(cfg: ModelConfig) -> int:
    """Leading layers of an MLA decoder that run a dense SwiGLU (all of them
    without experts)."""
    return cfg.num_layers if cfg.moe is None else cfg.moe.first_dense_layers


def init_mla_decoder(rng, cfg: ModelConfig) -> Dict[str, Any]:
    """An MLA decoder (DeepSeek-V3 layout): ``dense_layers`` stacks the
    leading layers (MLA + a SwiGLU of width ``d_ff``), ``layers`` the expert
    layers (MLA + the sigmoid-routed experts and the shared expert); an
    untied head unless ``tie_embeddings``."""
    dtype = jnp.dtype(cfg.dtype)
    n0 = _mla_dense_layers(cfg)
    r = jax.random.split(rng, 6)
    params: Dict[str, Any] = {"embed": init_embed(r[0], cfg.vocab_size, cfg.d_model, dtype)}
    stacks = (("dense_layers", n0, r[1], r[2]), ("layers", cfg.num_layers - n0, r[3], r[4]))
    for name, n, r_attn, r_ffn in stacks:
        if not n:
            continue
        stack = init_mla_stack(r_attn, n, cfg, dtype)
        stack.update(_init_norms(n, cfg.d_model, cfg.norm, dtype, ["attn_norm", "mlp_norm"]))
        if name == "dense_layers":
            stack.update(init_mlp(r_ffn, n, cfg.d_model, cfg.d_ff, "swiglu", dtype))
        else:
            stack.update(init_moe(r_ffn, n, cfg.d_model, cfg.moe, dtype))
        params[name] = stack
    params["final_norm_w"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_stacked_dense(r[5], 1, cfg.d_model, cfg.vocab_size, dtype)[0]
    return params


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------


def _norm(h, p, name, kind, eps: float = 1e-6):
    if kind == "layernorm":
        return layer_norm(h, p[f"{name}_w"], p[f"{name}_b"])
    return rms_norm(h, p[f"{name}_w"], eps)


def _project_qkv(x, p, lora, cfg: ModelConfig, lora_scale):
    hd = cfg.resolved_head_dim
    lget = (lambda k: lora.get(k) if lora else None)
    q = linear(x, {"w": p["wq"], **({"b": p["bq"]} if "bq" in p else {})}, lget("wq"), lora_scale)
    k = linear(x, {"w": p["wk"], **({"b": p["bk"]} if "bk" in p else {})}, lget("wk"), lora_scale)
    v = linear(x, {"w": p["wv"], **({"b": p["bv"]} if "bv" in p else {})}, lget("wv"), lora_scale)
    B = x.shape[0]
    S = x.shape[1]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm_w"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm_w"], cfg.norm_eps)
    return q, k, v


def attention_sublayer(
    x: jax.Array,
    p,
    lora,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    lora_scale: float,
    causal: bool = True,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_position=None,
    ring: bool = False,
):
    """Self-attention over x. If cache is given (k,v) do one-token decode.

    Returns (out, new_cache_or_None).
    """
    q, k, v = _project_qkv(x, p, lora, cfg, lora_scale)
    q = apply_rope(q, positions, theta=cfg.rope_theta, mode=cfg.rope)
    k = apply_rope(k, positions, theta=cfg.rope_theta, mode=cfg.rope)
    new_cache = None
    if cache is not None:
        k_cache, v_cache = cache
        T = k_cache.shape[1]
        slot = (cache_position % T) if ring else cache_position
        k_cache = attn.scatter_decode_kv(k_cache, k, slot)
        v_cache = attn.scatter_decode_kv(v_cache, v, slot)
        o = attn.decode_attention(q, k_cache, v_cache, cache_position, ring=ring)
        new_cache = (k_cache, v_cache)
    else:
        o = attn.blockwise_attention(
            q, k, v, causal=causal, window=cfg.attention_window,
            score_dtype=jnp.dtype(cfg.attn_score_dtype),
        )
    B, S = x.shape[0], x.shape[1]
    o = o.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim)
    lget = (lambda kk: lora.get(kk) if lora else None)
    out = linear(o, {"w": p["wo"]}, lget("wo"), lora_scale)
    return out, new_cache


def _ffn(x, p, cfg: ModelConfig, lora, lora_scale, sample_weight=None):
    if cfg.family == "moe":
        y, aux = apply_moe(
            x, p, cfg.moe, token_parallel=cfg.moe_token_parallel,
            sample_weight=sample_weight,
        )
        return y, aux
    return apply_mlp(x, p, cfg.mlp, lora, lora_scale), jnp.zeros((), jnp.float32)


def decoder_layer(
    h, p, lora, cfg: ModelConfig, positions, *, lora_scale,
    cache=None, cache_position=None, ring=False, causal=True,
    sample_weight=None,
):
    """One transformer block. Returns (h, aux_loss, new_cache).

    Its ops carry the named scopes ``attention`` and ``mlp`` (norm and
    residual add included), which label them in a profile."""
    with jax.named_scope("attention"):
        x = _norm(h, p, "attn_norm", cfg.norm, cfg.norm_eps)
        attn_out, new_cache = attention_sublayer(
            x, p, lora, cfg, positions, lora_scale=lora_scale, causal=causal,
            cache=cache, cache_position=cache_position, ring=ring,
        )
    if cfg.parallel_residual:
        with jax.named_scope("mlp"):
            mlp_out, aux = _ffn(x, p, cfg, lora, lora_scale, sample_weight)
            h = h + attn_out + mlp_out
    else:
        with jax.named_scope("attention"):
            h = h + attn_out
        with jax.named_scope("mlp"):
            x2 = _norm(h, p, "mlp_norm", cfg.norm, cfg.norm_eps)
            mlp_out, aux = _ffn(x2, p, cfg, lora, lora_scale, sample_weight)
            h = h + mlp_out
    return h, aux, new_cache


# ---------------------------------------------------------------------------
# full forward passes
# ---------------------------------------------------------------------------


def _embed_inputs(params, tokens, cfg: ModelConfig, prefix_embeds=None):
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family in ("vlm",):
        h = h * jnp.sqrt(jnp.array(cfg.d_model, jnp.float32)).astype(h.dtype)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    return h


@jax.named_scope("lm_head")
def _lm_logits(h, params, cfg: ModelConfig):
    if cfg.norm == "layernorm":
        h = layer_norm(h, params["final_norm_w"], params["final_norm_b"])
    else:
        h = rms_norm(h, params["final_norm_w"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))
    return soft_cap(logits, cfg.logit_soft_cap)


def decoder_forward(
    params,
    lora,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    prefix_embeds: Optional[jax.Array] = None,
    lora_scale: Optional[float] = None,
    embed_noise: Optional[jax.Array] = None,
    collect_layer_norms: bool = False,
    sample_weight: Optional[jax.Array] = None,
    with_stats: bool = False,
):
    """Training/eval forward. Returns (logits (B, S_total, V), aux_loss).

    ``embed_noise`` (B, S_total, D) is added to the embedding output — the
    FibecFed GAL-sensitivity probe (paper Eq. 6-9). With
    ``collect_layer_norms`` the per-layer per-sample Frobenius norms of the
    hidden states are returned as a third output (num_layers, B).
    ``sample_weight`` (B,) restricts the MoE load-balance aux loss to valid
    samples (padded-batch training); logits are unaffected.
    An MLA decoder (``cfg.mla``) takes :func:`_mla_decoder_forward`; with
    ``with_stats`` it also returns the counts of its held-expert layers
    (:func:`keeps_expert_stats`): ``held_assignments``, the (token, held
    expert) assignments, each sample weighted by ``sample_weight``,
    ``expert_rows``, the expert rows those layers computed, and
    ``dense_fallbacks``, the held experts those layers ran over every token.
    """
    lora_scale = lora_scale if lora_scale is not None else cfg.lora_alpha / cfg.lora_rank
    if cfg.mla is not None:
        return _mla_decoder_forward(
            params, lora, tokens, cfg, lora_scale=lora_scale, embed_noise=embed_noise,
            collect_layer_norms=collect_layer_norms, sample_weight=sample_weight,
            with_stats=with_stats,
        )
    h = _embed_inputs(params, tokens, cfg, prefix_embeds)
    if embed_noise is not None:
        h = h + embed_noise.astype(h.dtype)
    S = h.shape[1]
    positions = jnp.arange(S)[None, :]

    layer_params = params["layers"]

    def layer_fn(h, p_slice, lora_slice):
        h, aux_l, _ = decoder_layer(
            h, p_slice, lora_slice, cfg, positions, lora_scale=lora_scale,
            sample_weight=sample_weight,
        )
        if cfg.seq_parallel:
            from repro.models.sharding_ctx import constrain

            h = constrain(h, ("dp", "model", None))
        return h, aux_l

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn)  # recompute activations in bwd

    def body(carry, xs):
        h, aux = carry
        p_slice, lora_slice = xs
        h, aux_l = layer_fn(h, p_slice, lora_slice)
        norm = jnp.sqrt(jnp.sum(jnp.square(h.astype(jnp.float32)), axis=(1, 2)))
        return (h, aux + aux_l), (norm if collect_layer_norms else None)

    (h, aux), norms = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), (layer_params, lora)
    )
    logits = _lm_logits(h, params, cfg)
    if collect_layer_norms:
        return logits, aux, norms
    return logits, aux


def keeps_expert_stats(cfg: ModelConfig) -> bool:
    """Whether the model has held-expert layers, whose counts
    ``decoder_forward(..., with_stats=True)`` returns: the sigmoid router's
    layers (:func:`apply_held_moe`)."""
    return cfg.moe is not None and cfg.moe.router == "sigmoid_bias"


def _no_counts():
    return {k: jnp.zeros((), jnp.float32)
            for k in ("held_assignments", "expert_rows", "dense_fallbacks")}


def _mla_layer(h, p, lora, cfg: ModelConfig, positions, *, lora_scale, experts: bool,
               sample_weight=None):
    """One MLA block: attention (scopes ``attention`` > ``mla``), then the
    sigmoid-routed experts (``mlp`` > ``router``, ``routed_experts``,
    ``shared_experts``) or a dense SwiGLU (``dense_ffn``). Returns ``(h,
    counts)``, :func:`apply_held_moe`'s counts, 0 in a dense layer."""
    with jax.named_scope("attention"):
        x = rms_norm(h, p["attn_norm_w"], cfg.norm_eps)
        h = h + mla_attention(x, p, lora, cfg, positions, lora_scale=lora_scale)
    if experts:
        with jax.named_scope("mlp"):
            x = rms_norm(h, p["mlp_norm_w"], cfg.norm_eps)
            y, counts = apply_held_moe(x, p, cfg.moe, sample_weight=sample_weight)
            return h + y, counts
    with jax.named_scope("dense_ffn"):
        x = rms_norm(h, p["mlp_norm_w"], cfg.norm_eps)
        return h + apply_mlp(x, p, "swiglu"), _no_counts()


def _mla_decoder_forward(params, lora, tokens, cfg: ModelConfig, *, lora_scale,
                         embed_noise=None, collect_layer_norms=False, sample_weight=None,
                         with_stats=False):
    """:func:`decoder_forward` of an MLA decoder: a scan over the leading
    dense layers with ``lora[:n0]``, then a scan over the expert layers with
    ``lora[n0:]`` (the LoRA tree is one stack over every layer)."""
    h = _embed_inputs(params, tokens, cfg)
    if embed_noise is not None:
        h = h + embed_noise.astype(h.dtype)
    positions = jnp.arange(h.shape[1])[None, :]
    n0 = _mla_dense_layers(cfg)
    counts = _no_counts()
    norms = []
    for name, lo, experts in (
        ("dense_layers", jax.tree.map(lambda x: x[:n0], lora), False),
        ("layers", jax.tree.map(lambda x: x[n0:], lora), cfg.moe is not None),
    ):
        if name not in params:
            continue

        def layer_fn(h, p_slice, lora_slice, experts=experts):
            return _mla_layer(h, p_slice, lora_slice, cfg, positions, lora_scale=lora_scale,
                              experts=experts, sample_weight=sample_weight)

        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn)

        def body(carry, xs, layer_fn=layer_fn):
            h, c = carry
            h, c_l = layer_fn(h, *xs)
            norm = jnp.sqrt(jnp.sum(jnp.square(h.astype(jnp.float32)), axis=(1, 2)))
            return (h, jax.tree.map(jnp.add, c, c_l)), (norm if collect_layer_norms else None)

        (h, counts), n = jax.lax.scan(body, (h, counts), (params[name], lo))
        norms.append(n)
    logits = _lm_logits(h, params, cfg)
    aux = jnp.zeros((), jnp.float32)  # sigmoid routing balances by its bias: no aux loss
    if collect_layer_norms:
        return logits, aux, jnp.concatenate(norms)
    if with_stats:
        return logits, aux, counts
    return logits, aux


def _refuse_mla(cfg: ModelConfig) -> None:
    if cfg.mla is not None:
        raise NotImplementedError(
            f"{cfg.name}: latent attention has no KV cache here; serving an MLA "
            "model (prefill, decode) is not supported"
        )


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    _refuse_mla(cfg)
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decoder_prefill(
    params, lora, tokens, cfg: ModelConfig, cache_len: int,
    *, prefix_embeds=None, lora_scale=None,
):
    """Run the prompt, fill the KV cache. Returns (last_logits, cache, pos)."""
    _refuse_mla(cfg)
    lora_scale = lora_scale if lora_scale is not None else cfg.lora_alpha / cfg.lora_rank
    h = _embed_inputs(params, tokens, cfg, prefix_embeds)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.arange(S)[None, :]
    ring = cfg.attention_window is not None and cache_len <= cfg.attention_window

    def body(h, xs):
        p_slice, lora_slice = xs
        x = _norm(h, p_slice, "attn_norm", cfg.norm, cfg.norm_eps)
        q, k, v = _project_qkv(x, p_slice, lora_slice, cfg, lora_scale)
        q = apply_rope(q, positions, theta=cfg.rope_theta, mode=cfg.rope)
        k = apply_rope(k, positions, theta=cfg.rope_theta, mode=cfg.rope)
        o = attn.blockwise_attention(q, k, v, causal=True, window=cfg.attention_window)
        o = o.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim)
        lget = (lambda kk: lora_slice.get(kk) if lora_slice else None)
        h = h + linear(o, {"w": p_slice["wo"]}, lget("wo"), lora_scale)
        x2 = _norm(h, p_slice, "mlp_norm", cfg.norm, cfg.norm_eps)
        mlp_out, _ = _ffn(x2, p_slice, cfg, lora_slice, lora_scale)
        h = h + mlp_out
        # keep the cache tail (last cache_len positions fit by construction)
        keep = min(cache_len, S)
        k_keep = k[:, S - keep :]
        v_keep = v[:, S - keep :]
        if keep < cache_len:
            pad = cache_len - keep
            k_keep = jnp.pad(k_keep, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_keep = jnp.pad(v_keep, ((0, 0), (0, pad), (0, 0), (0, 0)))
        elif ring and S % cache_len:
            # ring layout: position p lives at slot p % cache_len
            k_keep = jnp.roll(k_keep, S % cache_len, axis=1)
            v_keep = jnp.roll(v_keep, S % cache_len, axis=1)
        return h, (k_keep, v_keep)

    h, (k_cache, v_cache) = jax.lax.scan(body, h, (params["layers"], lora))
    logits = _lm_logits(h[:, -1:], params, cfg)
    cache = {"k": k_cache.astype(jnp.dtype(cfg.dtype)), "v": v_cache.astype(jnp.dtype(cfg.dtype))}
    return logits, cache, jnp.array(S, jnp.int32)


def decoder_decode_step(
    params, lora, token, cfg: ModelConfig, cache, position,
    *, lora_scale=None, ring: bool = False,
):
    """One-token step. token: (B, 1) int32; ``position`` scalar (uniform
    batch) or (B,) per-slot positions. Returns (logits, new_cache)."""
    _refuse_mla(cfg)
    lora_scale = lora_scale if lora_scale is not None else cfg.lora_alpha / cfg.lora_rank
    h = jnp.take(params["embed"], token, axis=0)
    if cfg.family == "vlm":
        h = h * jnp.sqrt(jnp.array(cfg.d_model, jnp.float32)).astype(h.dtype)
    positions = jnp.reshape(position, (-1, 1))  # (1,1) scalar / (B,1) per-slot

    def body(h, xs):
        p_slice, lora_slice, k_c, v_c = xs
        h, _, new_cache = decoder_layer(
            h, p_slice, lora_slice, cfg, positions,
            lora_scale=lora_scale, cache=(k_c, v_c), cache_position=position,
            ring=ring,
        )
        return h, new_cache

    h, (k_new, v_new) = jax.lax.scan(
        body, h, (params["layers"], lora, cache["k"], cache["v"])
    )
    logits = _lm_logits(h, params, cfg)
    return logits, {"k": k_new, "v": v_new}
