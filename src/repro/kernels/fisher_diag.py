"""Fused momentum diag-FIM update kernel.

Computes ``fim_new = γ·fim + (1-γ)·g⊙g`` in one pass — on TPU this keeps g²
out of HBM entirely (the jnp version materializes the square), halving the
HBM traffic of the FibecFed FIM-warmup loop which is purely memory-bound.

Layout: inputs are reshaped to (rows, 128-multiple cols) 2-D tiles; block
(8, 128) aligned to the VREG lane structure, f32 accumulation.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sparse_lora import resolve_interpret

BLOCK_ROWS = 256
BLOCK_COLS = 128


def _kernel(g_ref, fim_ref, out_ref, *, momentum: float):
    g = g_ref[...].astype(jnp.float32)
    fim = fim_ref[...].astype(jnp.float32)
    out_ref[...] = momentum * fim + (1.0 - momentum) * g * g


def fisher_diag_update_2d(
    g: jax.Array, fim: jax.Array, momentum: float, *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """g, fim: (R, C) with R % BLOCK_ROWS == 0 and C % BLOCK_COLS == 0."""
    interpret = resolve_interpret(interpret)
    R, C = g.shape
    grid = (R // BLOCK_ROWS, C // BLOCK_COLS)
    return pl.pallas_call(
        lambda g_ref, f_ref, o_ref: _kernel(g_ref, f_ref, o_ref, momentum=momentum),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(g, fim)
