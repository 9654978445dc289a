"""Flash attention (causal / sliding-window) Pallas kernel.

Online-softmax attention with q/kv blocks held in VMEM; running max,
denominator and output accumulator live in f32 scratch that persists across
the innermost (kv) grid dimension. Output is written on the last kv step.

This is the TPU-target twin of ``repro.models.attention.blockwise_attention``
(the jnp path used on CPU); tests assert allclose between the two and against
``repro.kernels.ref.flash_attention_ref``.

Layout: q, k, v are (BH, S, D) with heads folded into the leading grid dim
(GQA is handled by the caller folding/broadcasting kv heads). Block sizes
align to the MXU: q_block=128, kv_block=128, D padded to 128 multiples.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_lora import resolve_interpret

NEG_INF = -1e30
QB, KB = 128, 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, causal: bool, window, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * QB
    k_start = ki * KB
    # skip fully-masked blocks (causal: kv block strictly after q block)
    run = True
    if causal:
        run = k_start <= q_start + QB - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + KB - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (QB, D)
        k = k_ref[0].astype(jnp.float32)  # (KB, D)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (QB, KB)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (QB, KB), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (QB, KB), 1)
        mask = jnp.ones((QB, KB), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (BH, S, D)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    BH, S, D = q.shape
    assert S % QB == 0 and S % KB == 0, S
    nq, nk = S // QB, S // KB
    scale = 1.0 / (D**0.5)
    grid = (BH, nq, nk)
    kernel = functools.partial(
        _kernel, nk=nk, causal=causal, window=window, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, QB, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, KB, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, KB, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, QB, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((QB, 1), jnp.float32),  # running max
            pltpu.VMEM((QB, 1), jnp.float32),  # denominator
            pltpu.VMEM((QB, D), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
