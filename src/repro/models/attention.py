"""Attention: GQA with blockwise (flash-style) softmax, sliding windows, caches.

Memory-bounded attention is essential for the 32k prefill shapes: the naive
(S, S) score matrix would not fit HBM. We scan over KV blocks with an online
softmax (running max / denominator in f32), so peak memory is
O(q_block * kv_block) per head instead of O(S^2).

Sliding-window attention (``window``) gathers only the needed KV blocks per
query block via ``lax.dynamic_slice`` — truly sub-quadratic FLOPs, which is what
makes ``long_500k`` feasible for non-SSM architectures (DESIGN.md §4).

A Pallas TPU kernel with the same contract lives in
``repro.kernels.flash_attention``; this module is the jnp reference /
CPU-executable path and is what the distributed step functions call (the
kernel is validated against :func:`repro.kernels.ref.flash_attention_ref`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array, scale: float, dtype=jnp.float32) -> jax.Array:
    """q: (B, Sq, KVH, G, D), k: (B, Sk, KVH, D) -> (B, KVH, G, Sq, Sk).

    ``dtype`` sets the materialized score-buffer dtype (bf16 halves the
    dominant attention HBM traffic; the softmax max/denominator stay f32).
    """
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=dtype) * scale
    return s


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (B, KVH, G, Sq, Sk) f32, v: (B, Sk, KVH, D) -> (B, Sq, KVH, G, D)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Unblocked GQA attention (used for short sequences and decode).

    q: (B, Sq, H, D), k: (B, Sk, KVH, D), v: (B, Sk, KVH, Dv). ``q_offset`` is
    the absolute position of q[0] (for decode, Sq=1, q_offset=t). ``kv_len``
    masks the valid prefix of the KV cache. Returns (B, Sq, H, Dv).
    """
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, D)
    scale = 1.0 / jnp.sqrt(jnp.array(D, jnp.float32))
    scores = _gqa_scores(qg, k, scale)  # (B, KVH, G, Sq, Sk) f32

    q_pos = q_offset + jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask, scores, NEG_INF)
    if kv_len is not None:
        valid = k_pos < jnp.asarray(kv_len)
        scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_block: int = 512,
    kv_block: int = 512,
    valid_len: Optional[int] = None,
    score_dtype=jnp.float32,
) -> jax.Array:
    """Flash-style attention: scan over KV blocks with an online softmax.

    Shapes as :func:`full_attention` with Sq == Sk == S (self-attention /
    prefill); v's head size may differ from q's and k's (latent attention).
    With ``window`` set, each query block only visits the KV blocks inside
    ``[q_start - window, q_end]`` via a dynamic slice (sub-quadratic).
    """
    B, S, H, D = q.shape
    KVH, Dv = k.shape[2], v.shape[-1]
    G = H // KVH
    if window is not None and causal and window >= S:
        # a window covering the whole sequence IS causal attention; the
        # windowed path would pad KV spans to the window (8704-wide spans for
        # chatglm train_4k — §Perf iteration A4) for zero benefit.
        window = None
    if S <= q_block:  # short path
        return full_attention(q, k, v, causal=causal, window=window)
    if S % q_block or S % kv_block:
        # pad to a block multiple; padded KV is masked out via valid_len
        blk = max(q_block, kv_block)
        pad = (-S) % blk
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = blockwise_attention(
            qp, kp, vp, causal=causal, window=window,
            q_block=q_block, kv_block=kv_block, valid_len=S,
            score_dtype=score_dtype,
        )
        return out[:, :S]

    scale = 1.0 / jnp.sqrt(jnp.array(D, jnp.float32))
    nq = S // q_block
    qg = q.reshape(B, nq, q_block, KVH, G, D)

    if window is not None:
        # pad window up to kv_block multiple, then slice [q_start-wpad, q_end)
        wpad = ((window + kv_block - 1) // kv_block) * kv_block
        span = wpad + q_block
        kp = jnp.pad(k, ((0, 0), (wpad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (wpad, 0), (0, 0), (0, 0)))

        def one_q_block(qi):
            qb = qg[:, qi]  # (B, qb, KVH, G, D)
            start = qi * q_block  # in padded coords this is q_start - wpad + wpad
            kb = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
            scores = _gqa_scores(qb, kb, scale, score_dtype)  # (B,KVH,G,qb,span)
            q_pos = start + wpad + jnp.arange(q_block)[:, None]  # absolute+wpad
            k_pos = start + jnp.arange(span)[None, :]
            mask = k_pos <= q_pos
            mask &= k_pos > q_pos - window
            mask &= k_pos >= wpad  # mask left zero-padding
            if valid_len is not None:
                mask &= k_pos < wpad + valid_len  # mask right padding
            scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(score_dtype)
            return _gqa_out(probs, vb)  # (B,qb,KVH,G,D)

        out = jax.lax.map(one_q_block, jnp.arange(nq))  # (nq,B,qb,KVH,G,Dv)
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, Dv)
        return out

    # full/causal: online softmax over all KV blocks
    assert S % kv_block == 0
    nk = S // kv_block
    kb_all = k.reshape(B, nk, kv_block, KVH, D)
    vb_all = v.reshape(B, nk, kv_block, KVH, Dv)

    def one_q_block(qi):
        qb = qg[:, qi]  # (B,qb,KVH,G,D)
        q_pos = qi * q_block + jnp.arange(q_block)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb = kb_all[:, ki]
            vb = vb_all[:, ki]
            scores = _gqa_scores(qb, kb, scale, score_dtype)  # (B,KVH,G,qb,kvb)
            k_pos = ki * kv_block + jnp.arange(kv_block)
            if causal or valid_len is not None:
                mask = jnp.ones((q_block, kv_block), bool)
                if causal:
                    mask &= k_pos[None, :] <= q_pos[:, None]
                if valid_len is not None:
                    mask &= (k_pos < valid_len)[None, :]
                scores = jnp.where(mask, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1).astype(jnp.float32))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores.astype(jnp.float32) - m_new[..., None]).astype(score_dtype)
            l_new = l * alpha + jnp.sum(p, axis=-1).astype(jnp.float32)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(score_dtype)
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, q_block), jnp.float32)
        acc0 = jnp.zeros((B, KVH, G, q_block, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, acc0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)  # (B,qb,KVH,G,D)

    out = jax.lax.map(one_q_block, jnp.arange(nq))  # (nq,B,qb,KVH,G,Dv)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, Dv)
    return out


def scatter_decode_kv(cache: jax.Array, update: jax.Array, slot) -> jax.Array:
    """Write a decode-step KV update into its cache slot(s).

    cache: (B, T, KVH, D); update: (B, 1, KVH, D); ``slot`` a scalar write
    index (uniform batch) or a (B,) vector of per-row indices (continuous
    batching). Shared by every family's decode cache update.
    """
    upd = update.astype(cache.dtype)
    if jnp.ndim(slot) == 1:
        return jax.vmap(
            lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, axis=0)
        )(cache, upd, slot)
    return jax.lax.dynamic_update_slice_in_dim(cache, upd, slot, axis=1)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    position: jax.Array,
    *,
    ring: bool = False,
) -> jax.Array:
    """One-token attention against a cache.

    q: (B, 1, H, D); caches: (B, T, KVH, D). ``position`` = number of tokens
    already generated — a scalar (uniform batch) or a (B,) vector of per-row
    positions (continuous batching, where each slot is at its own depth).
    For a ring-buffer cache (sliding window), ``ring=True`` attends to all T
    slots that are valid once position >= T and the rotation is irrelevant to
    softmax (set union of positions).
    """
    B, _, H, D = q.shape
    T, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    qg = q.reshape(B, 1, KVH, G, D)
    scale = 1.0 / jnp.sqrt(jnp.array(D, jnp.float32))
    scores = _gqa_scores(qg, k_cache, scale)  # (B,KVH,G,1,T)
    slot = jnp.arange(T)
    if jnp.ndim(position) == 1:  # per-slot positions -> (B, T) validity
        if ring:
            valid = slot[None, :] < jnp.minimum(position + 1, T)[:, None]
        else:
            valid = slot[None, :] <= position[:, None]
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v_cache)
        return out.reshape(B, 1, H, D)
    if ring:
        valid = slot < jnp.minimum(position + 1, T)
    else:
        valid = slot <= position
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v_cache)
    return out.reshape(B, 1, H, D)
