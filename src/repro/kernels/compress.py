"""Fused fake-quantize / top-k compression kernel with error feedback.

The compressed-upload path (CELLM-style, see PAPERS.md) simulates the
client→server channel on-device: the GAL delta (plus the carried
error-feedback residual) is quantized and/or thresholded, the server-visible
reconstruction ``y = dequant(quant(x))`` is what enters the merge, and the
un-sent remainder ``x - y`` becomes the next round's residual. Doing the
round-trip as one tile pass keeps compression off the merge's critical path:
each ``x`` tile is read exactly once and ``(y, residual')`` written exactly
once — the same memory-bound reasoning as :mod:`repro.kernels.masked_update`,
whose tile/layout conventions (flattened leaves padded to (256·k, 128),
f32 compute, SMEM scalar row) this kernel shares.

Quantization grain is layout-significant: ``int8``/``int4`` use one scale per
128-lane row of the tiled layout (= each consecutive 128 values of the
flattened leaf, the wire format's QUANT_GROUP), computed in-kernel as
``absmax/qmax`` with a safe inverse for all-zero rows. ``topk`` modes use one
per-leaf scale and a per-leaf magnitude threshold (the k-th largest ``|x|``),
both computed outside (they need a global sort/reduce) and passed via the
SMEM row ``[thresh, scale, 0, 0]``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.masked_update import SCAL_WIDTH, _call  # noqa: F401
from repro.kernels.masked_update import BLOCK_COLS, BLOCK_ROWS  # noqa: F401
from repro.kernels.sparse_lora import resolve_interpret


def _compress_kernel(
    scal_ref, x_ref, y_ref, r_ref, *, qmax: int, use_thresh: bool,
    per_leaf_scale: bool,
):
    thresh = scal_ref[0, 0]
    leaf_scale = scal_ref[0, 1]
    x = x_ref[...].astype(jnp.float32)
    if qmax:
        if per_leaf_scale:
            scale = leaf_scale
        else:
            scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax
        safe = jnp.where(scale > 0.0, scale, 1.0)
        inv = jnp.where(scale > 0.0, 1.0 / safe, 0.0)
        y = jnp.clip(jnp.round(x * inv), -qmax, qmax) * scale
    else:
        y = x
    if use_thresh:
        y = jnp.where(jnp.abs(x) >= thresh, y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)
    r_ref[...] = (x - y).astype(r_ref.dtype)


def fake_compress_2d(
    x: jax.Array,
    scal: jax.Array,
    *,
    qmax: int = 0,
    use_thresh: bool = False,
    per_leaf_scale: bool = False,
    interpret: Optional[bool] = None,
):
    """One fused compress round-trip tile pass. ``x`` is (R, C)
    tile-multiple; ``scal`` is (1, SCAL_WIDTH) ``[thresh, scale, -, -]``
    (only read by the top-k / per-leaf-scale variants). Returns
    ``(y, residual)``, both ``x``-shaped and ``x``-dtyped, with
    ``y = dequant(quant(x))`` and ``residual = x - y``."""
    interpret = resolve_interpret(interpret)
    kernel = functools.partial(
        _compress_kernel,
        qmax=qmax,
        use_thresh=use_thresh,
        per_leaf_scale=per_leaf_scale,
    )
    # no donation: x is live in both outputs (y reads it, residual = x - y)
    return tuple(
        _call(kernel, scal, (x,), (x.dtype, x.dtype), {}, interpret=interpret)
    )
