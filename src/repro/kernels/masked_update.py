"""Fused masked SGD-momentum / AdamW update kernels.

FibecFed's sparse local update (§4.3.2) freezes masked-out LoRA entries:
they must receive no parameter delta AND their optimizer moments must hold
— not decay. The unfused path is a chain of elementwise ``tree.map`` passes
(grad masking, moment update, bias correction, weight decay, and a separate
``tree_where`` commit pass for padded no-op curriculum steps), each reading
and writing whole moment/param buffers. The whole update is memory-bound,
so these kernels read each ``(param, grad, mask, moments)`` tile exactly
once and write ``(new_param, new_moments)`` exactly once, folding the mask
and the per-step ``active`` predicate into the same pass — no intermediate
buffers ever reach HBM.

Frozen semantics (the oracle contract, shared with
:mod:`repro.optim.optimizers`): with ``eff = mask ⊙ active``,

  sgd       p' = eff ? p - lr·g            : p
  sgd+mom   μ' = eff ? momentum·μ + g      : μ        p' = eff ? p - lr·μ' : p
  adamw     m' = eff ? b1·m + (1-b1)·g     : m
            v' = eff ? b2·v + (1-b2)·g²    : v
            p' = eff ? p - lr·(m̂/(√v̂+ε) + wd·p) : p

Traced scalars (lr, active, Adam's bias-correction scales — functions of the
step counter ``t``, which lives outside the kernel) ride in one small SMEM
row; hyperparameters (momentum, b1, b2, eps, wd) are compile-time constants
closed over by the kernel. Layout matches :mod:`repro.kernels.fisher_diag`:
inputs reshaped to (rows, 128-multiple cols) 2-D tiles, (256, 128) blocks
aligned to the VREG lane structure, f32 compute, outputs cast back to the
parameter dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_lora import resolve_interpret

BLOCK_ROWS = 256
BLOCK_COLS = 128

# scal row layout (f32): [lr, active, mhat_scale, vhat_scale]; the SGD
# kernels only read the first two
SCAL_WIDTH = 4


def _eff(active, mask):
    pred = active != 0.0
    if mask is not None:
        pred = pred & (mask != 0.0)
    return pred


def _sgd_kernel(scal_ref, p_ref, g_ref, *rest, momentum: float, has_mask: bool):
    if momentum:
        mu_ref = rest[0]
        rest = rest[1:]
    mask_ref = rest[0] if has_mask else None
    out_refs = rest[1:] if has_mask else rest
    lr = scal_ref[0, 0]
    active = scal_ref[0, 1]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    eff = _eff(active, mask_ref[...].astype(jnp.float32) if has_mask else None)
    if momentum:
        mu = mu_ref[...].astype(jnp.float32)
        mu_new = jnp.where(eff, momentum * mu + g, mu)
        out_refs[0][...] = jnp.where(eff, p - lr * mu_new, p).astype(out_refs[0].dtype)
        out_refs[1][...] = mu_new.astype(out_refs[1].dtype)
    else:
        out_refs[0][...] = jnp.where(eff, p - lr * g, p).astype(out_refs[0].dtype)


def _adamw_kernel(
    scal_ref, p_ref, g_ref, m_ref, v_ref, *rest,
    b1: float, b2: float, eps: float, wd: float, has_mask: bool,
):
    mask_ref = rest[0] if has_mask else None
    out_refs = rest[1:] if has_mask else rest
    lr = scal_ref[0, 0]
    active = scal_ref[0, 1]
    mhat_scale = scal_ref[0, 2]
    vhat_scale = scal_ref[0, 3]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    eff = _eff(active, mask_ref[...].astype(jnp.float32) if has_mask else None)
    m_new = jnp.where(eff, b1 * m + (1.0 - b1) * g, m)
    v_new = jnp.where(eff, b2 * v + (1.0 - b2) * g * g, v)
    step = lr * (m_new * mhat_scale) / (jnp.sqrt(v_new * vhat_scale) + eps)
    if wd:
        step = step + lr * wd * p
    out_refs[0][...] = jnp.where(eff, p - step, p).astype(out_refs[0].dtype)
    out_refs[1][...] = m_new.astype(out_refs[1].dtype)
    out_refs[2][...] = v_new.astype(out_refs[2].dtype)


def _call(kernel, scal, tensors, out_dtypes, aliases, *, interpret: bool):
    """Shared pallas_call plumbing: every tensor is (R, C) tile-multiple,
    ``scal`` is the (1, SCAL_WIDTH) traced-scalar row in SMEM. Each output
    keeps its own source dtype (moments may be wider than the params — a
    param-dtype round trip would break the bit-for-bit frozen contract).

    ``aliases`` maps *tensor* index -> output index for state tensors whose
    output overwrites them (p -> p', μ -> μ', m/v -> m'/v'). Donating these
    buffers lets XLA update params and moments in place instead of
    materializing fresh output allocations: the kernel reads each state tile
    before its only write, so in-place is safe, and the wrapped callers
    (:mod:`repro.kernels.ops`) always pass freshly tiled intermediates inside
    a jit, so nothing live is clobbered. Input index 0 is the SMEM scal row,
    hence the +1 shift."""
    R, C = tensors[0].shape
    grid = (R // BLOCK_ROWS, C // BLOCK_COLS)
    tile = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, SCAL_WIDTH), lambda i, j: (0, 0), memory_space=pltpu.SMEM
            )
        ]
        + [tile] * len(tensors),
        out_specs=[tile] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((R, C), dt) for dt in out_dtypes],
        input_output_aliases={1 + t: o for t, o in aliases.items()},
        interpret=interpret,
    )(scal, *tensors)


def masked_sgd_update_2d(
    p: jax.Array,
    g: jax.Array,
    mu,
    mask,
    scal: jax.Array,
    *,
    momentum: float = 0.0,
    interpret: Optional[bool] = None,
):
    """One fused SGD(+momentum) tile pass. All tensors (R, C) tile-multiple;
    ``mu``/``mask`` may be None; ``scal`` is (1, SCAL_WIDTH) [lr, active, -, -].
    Returns ``(new_p, new_mu)`` (``new_mu`` is None without momentum)."""
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(
        _sgd_kernel, momentum=momentum, has_mask=mask is not None
    )
    tensors = (p, g) + ((mu,) if momentum else ()) + ((mask,) if mask is not None else ())
    out_dtypes = (p.dtype, mu.dtype) if momentum else (p.dtype,)
    aliases = {0: 0, 2: 1} if momentum else {0: 0}  # p -> p', μ -> μ'
    out = _call(kernel, scal, tensors, out_dtypes, aliases, interpret=interpret)
    return (out[0], out[1]) if momentum else (out[0], None)


def masked_adamw_update_2d(
    p: jax.Array,
    g: jax.Array,
    m: jax.Array,
    v: jax.Array,
    mask,
    scal: jax.Array,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    wd: float = 0.0,
    interpret: Optional[bool] = None,
):
    """One fused AdamW tile pass. ``scal`` is (1, SCAL_WIDTH)
    [lr, active, mhat_scale, vhat_scale] (bias-correction scales are computed
    from the step counter outside the kernel). Returns (new_p, new_m, new_v).
    """
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(
        _adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd, has_mask=mask is not None
    )
    tensors = (p, g, m, v) + ((mask,) if mask is not None else ())
    aliases = {0: 0, 2: 1, 3: 2}  # p -> p', m -> m', v -> v'
    return tuple(
        _call(
            kernel, scal, tensors, (p.dtype, m.dtype, v.dtype), aliases,
            interpret=interpret,
        )
    )
