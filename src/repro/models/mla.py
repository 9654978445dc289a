"""Multi-head latent attention (MLA, DeepSeek-V2/V3), without q compression.

Per layer, from the normed hidden state ``x``:

- ``q = x W_q``; each of the H heads holds ``qk_nope_head_dim`` dims without
  positions and ``qk_rope_head_dim`` rotary dims;
- ``[c_kv, k_pe] = x W_kv_a``: a latent of ``kv_lora_rank`` and one rotary
  key of ``qk_rope_head_dim`` that every head shares;
- ``c_kv`` goes through an RMSNorm, then ``W_kv_b`` expands it to each
  head's non-rotary key and its value (``v_head_dim``);
- causal softmax attention over the concatenated keys, scale
  ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``; ``W_o`` maps the heads'
  values back to the model width.

RoPE rotates the two halves of the rotary dims as pairs (the layout of
:func:`repro.models.layers.apply_rope`). DeepSeek's published code first
reorders interleaved pairs into that layout; with weights drawn at random
that reordering is a fixed permutation of the rotary columns of ``W_q`` and
``W_kv_a``, so it is left out. LoRA may adapt ``wq``, ``wkv_a``, ``wkv_b``
and ``wo``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models.layers import apply_rope, init_stacked_dense, linear, rms_norm

LORA_TARGETS = ("wq", "wkv_a", "wkv_b", "wo")


def mla_dims(cfg: ModelConfig) -> Dict[str, tuple]:
    """(d_in, d_out) of each projection."""
    m, H, D = cfg.mla, cfg.num_heads, cfg.d_model
    return {
        "wq": (D, H * m.qk_head_dim),
        "wkv_a": (D, m.kv_lora_rank + m.qk_rope_head_dim),
        "wkv_b": (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)),
        "wo": (H * m.v_head_dim, D),
    }


def init_mla_stack(rng, n_layers: int, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    r = jax.random.split(rng, len(LORA_TARGETS))
    p = {t: init_stacked_dense(k, n_layers, *mla_dims(cfg)[t], dtype)
         for k, t in zip(r, LORA_TARGETS)}
    p["kv_norm_w"] = jnp.ones((n_layers, cfg.mla.kv_lora_rank), dtype)
    return p


def mla_attention(x: jax.Array, p, lora, cfg: ModelConfig, positions: jax.Array, *,
                  lora_scale: float) -> jax.Array:
    """Causal MLA over x (B, S, D) with the layer's slice ``p`` (and LoRA
    slice ``lora`` or None). Returns (B, S, D). Named scope ``mla``."""
    m, H = cfg.mla, cfg.num_heads
    dn, dr, dv, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    B, S = x.shape[0], x.shape[1]
    lget = (lambda k: lora.get(k) if lora else None)
    with jax.named_scope("mla"):
        q = linear(x, {"w": p["wq"]}, lget("wq"), lora_scale).reshape(B, S, H, dn + dr)
        kv_a = linear(x, {"w": p["wkv_a"]}, lget("wkv_a"), lora_scale)
        c_kv = rms_norm(kv_a[..., :r], p["kv_norm_w"], cfg.norm_eps)
        k_pe = kv_a[..., None, r:]  # (B, S, 1, dr): one rotary key for all heads
        kv = linear(c_kv, {"w": p["wkv_b"]}, lget("wkv_b"), lora_scale)
        kv = kv.reshape(B, S, H, dn + dv)
        q_pe = apply_rope(q[..., dn:], positions, theta=cfg.rope_theta)
        k_pe = apply_rope(k_pe, positions, theta=cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
        o = attn.blockwise_attention(
            q, k, kv[..., dn:], causal=True, score_dtype=jnp.dtype(cfg.attn_score_dtype)
        )
        return linear(o.reshape(B, S, H * dv), {"w": p["wo"]}, lget("wo"), lora_scale)
