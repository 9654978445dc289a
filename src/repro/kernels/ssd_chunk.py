"""Mamba2 SSD intra-chunk kernel.

Computes the quadratic *within-chunk* part of SSD for one (batch, chunk,
head-block) per grid step:

    y[i] = Σ_{j≤i} exp(cs_i − cs_j) · (c_i·b_j) · x[j]

with the (Q, Q) decay·score matrix built in VMEM. The inter-chunk recurrence
stays in jnp (it is O(S/Q) and latency-bound, not compute-bound). Chunk
Q=128 and head_dim=64 tiles align with the MXU; f32 throughout (the decay
exponentials underflow bf16).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sparse_lora import resolve_interpret

NEG_INF = -1e30


def _kernel(x_ref, a_ref, b_ref, c_ref, o_ref, *, chunk: int):
    x = x_ref[0].astype(jnp.float32)  # (Q, hd)
    a = a_ref[0].astype(jnp.float32)  # (1, Q) log decays (row layout)
    b = b_ref[0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0].astype(jnp.float32)  # (Q, N)
    cs = jnp.cumsum(a[0])  # (Q,)
    diff = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.exp(jnp.where(jj <= ii, diff, NEG_INF))
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # (Q, Q)
    o_ref[0] = (jnp.dot(L * scores, x, preferred_element_type=jnp.float32)).astype(
        o_ref.dtype
    )


def ssd_chunk_intra_kernel(
    x: jax.Array,  # (G, Q, hd)   G = B*nc*nh flattened groups
    a: jax.Array,  # (G, 1, Q)    per-step log decay
    b: jax.Array,  # (G, Q, N)
    c: jax.Array,  # (G, Q, N)
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    G, Q, hd = x.shape
    N = b.shape[-1]
    kernel = functools.partial(_kernel, chunk=Q)
    return pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, Q, hd), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, hd), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Q, hd), jnp.float32),
        interpret=interpret,
    )(x, a, b, c)
