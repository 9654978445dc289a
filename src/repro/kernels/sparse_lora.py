"""Row-sparse (neuron-masked) LoRA apply kernels — single- and multi-adapter.

FibecFed freezes all but the top-ρ output neurons of each LoRA target
(§4.3.2). Structurally that means only ρ·d_out columns of ``b`` contribute
to the delta. Three kernels share that structure:

- :func:`sparse_lora_matmul` — ``y = (x @ a) @ (b ⊙ mask) · scale`` with the
  rank-r intermediate held in VMEM scratch and the column mask applied as
  the b-tile is loaded (masked columns are zero-multiplied inside the tile).
- :func:`sparse_lora_matmul_packed` — the gather-packed variant: the caller
  removes frozen columns of ``b`` on the host (they are static per cohort),
  the kernel runs the dense rank-r matmul on the packed ``(r, N_keep)``
  matrix, and the wrapper scatters back. For ρ ≤ 0.5 the frozen columns
  never reach the MXU at all.
- :func:`batched_sparse_lora_matmul` — multi-tenant serving apply: a leading
  adapter axis on ``a``/``b``/``mask`` and a per-row adapter index, so one
  matmul serves many users' adapters (Punica-style batched LoRA). The grid
  iterates adapters and accumulates row-masked contributions; cost is
  O(A) dense passes, the right trade for the small per-cohort adapter
  counts served here (a scalar-prefetch gather kernel is the next step at
  hundreds of adapters).

Grid (masked/packed): (M/bm, N/bn, K/bk); the k-axis accumulates x@a into
scratch, the last k step multiplies by the (masked/packed) b tile and
writes out. The batched kernel adds an adapter axis: (M/bm, N/bn, A, K/bk).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BN, BK = 128, 128, 512


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Platform-aware interpret default, shared by every kernel entry point.

    Explicit ``True``/``False`` wins; ``None`` follows the platform alone:
    compiled Mosaic on a TPU backend, the interpreter everywhere else.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _kernel(x_ref, a_ref, b_ref, mask_ref, o_ref, xa_ref, *, nk: int, scale: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        xa_ref[...] = jnp.zeros_like(xa_ref)

    xa_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finish():
        b = b_ref[...].astype(jnp.float32)
        if mask_ref is not None:
            b = b * mask_ref[...].astype(jnp.float32)
        o_ref[...] = (scale * jnp.dot(xa_ref[...], b, preferred_element_type=jnp.float32)).astype(
            o_ref.dtype
        )


def sparse_lora_matmul(
    x: jax.Array,  # (M, K)
    a: jax.Array,  # (K, r)
    b: jax.Array,  # (r, N)
    mask: jax.Array,  # (N,) column keep-mask
    scale: float = 1.0,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Masked apply. ``interpret=None`` resolves via :func:`resolve_interpret`
    (interpret only off-TPU)."""
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    r = a.shape[1]
    N = b.shape[1]
    assert M % BM == 0 and N % BN == 0 and K % BK == 0, (M, N, K)
    nk = K // BK
    grid = (M // BM, N // BN, nk)
    kernel = functools.partial(_kernel, nk=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda m, n, k: (m, k)),  # x
            pl.BlockSpec((BK, r), lambda m, n, k: (k, 0)),  # a
            pl.BlockSpec((r, BN), lambda m, n, k: (0, n)),  # b
            pl.BlockSpec((1, BN), lambda m, n, k: (0, n)),  # mask (row-vector)
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((BM, r), jnp.float32)],
        interpret=interpret,
    )(x, a, b, mask.reshape(1, N))


def _packed_kernel(x_ref, a_ref, b_ref, o_ref, xa_ref, *, nk: int, scale: float):
    _kernel(x_ref, a_ref, b_ref, None, o_ref, xa_ref, nk=nk, scale=scale)


def sparse_lora_matmul_packed(
    x: jax.Array,  # (M, K)
    a: jax.Array,  # (K, r)
    b_packed: jax.Array,  # (r, N_keep) — frozen columns already removed
    scale: float = 1.0,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Dense rank-r matmul on gather-packed ``b`` (no mask multiply at all).

    The caller gathers the kept columns (host-side; the neuron mask is fixed
    per cohort) and scatters the (M, N_keep) result back — see
    ``kernels.ops.sparse_lora_apply_packed``. MXU work scales with N_keep,
    not N: at ρ = 0.25 this is a 4x column reduction over the masked kernel.
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    r = a.shape[1]
    Nk = b_packed.shape[1]
    assert M % BM == 0 and Nk % BN == 0 and K % BK == 0, (M, Nk, K)
    nk = K // BK
    grid = (M // BM, Nk // BN, nk)
    kernel = functools.partial(_packed_kernel, nk=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda m, n, k: (m, k)),  # x
            pl.BlockSpec((BK, r), lambda m, n, k: (k, 0)),  # a
            pl.BlockSpec((r, BN), lambda m, n, k: (0, n)),  # b_packed
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, Nk), x.dtype),
        scratch_shapes=[pltpu.VMEM((BM, r), jnp.float32)],
        interpret=interpret,
    )(x, a, b_packed)


def _batched_kernel(
    idx_ref, x_ref, a_ref, b_ref, mask_ref, o_ref, xa_ref, acc_ref,
    *, na: int, nk: int, scale: float,
):
    ad = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((ad == 0) & (k == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k == 0)
    def _init_xa():
        xa_ref[...] = jnp.zeros_like(xa_ref)

    # rows owned by other adapters contribute exactly zero for this ad step
    rowsel = idx_ref[...] == ad  # (BM, 1)
    xz = jnp.where(rowsel, x_ref[...], jnp.zeros_like(x_ref))
    xa_ref[...] += jnp.dot(
        xz.astype(jnp.float32),
        a_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _accumulate():
        bm = b_ref[0].astype(jnp.float32) * mask_ref[...].astype(jnp.float32)
        acc_ref[...] += jnp.dot(xa_ref[...], bm, preferred_element_type=jnp.float32)

    @pl.when((ad == na - 1) & (k == nk - 1))
    def _finish():
        o_ref[...] = (scale * acc_ref[...]).astype(o_ref.dtype)


def batched_sparse_lora_matmul(
    x: jax.Array,  # (M, K)
    idx: jax.Array,  # (M,) int32 — per-row adapter index into the stacks
    a: jax.Array,  # (A, K, r)
    b: jax.Array,  # (A, r, N)
    mask: jax.Array,  # (A, N) per-adapter column keep-masks
    scale: float = 1.0,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``y[m] = (x[m] @ a[idx[m]]) @ (b[idx[m]] ⊙ mask[idx[m]]) · scale``.

    One pass serves every tenant's adapter: the grid iterates the adapter
    axis, row-masking x so each row only accumulates its own adapter's
    contribution, with per-(m, n) accumulation in f32 VMEM scratch.
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    A, _, r = a.shape
    N = b.shape[2]
    assert M % BM == 0 and N % BN == 0 and K % BK == 0, (M, N, K)
    assert idx.shape == (M,), idx.shape
    nk = K // BK
    grid = (M // BM, N // BN, A, nk)
    kernel = functools.partial(_batched_kernel, na=A, nk=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, 1), lambda m, n, ad, k: (m, 0)),  # idx column
            pl.BlockSpec((BM, BK), lambda m, n, ad, k: (m, k)),  # x
            pl.BlockSpec((1, BK, r), lambda m, n, ad, k: (ad, k, 0)),  # a
            pl.BlockSpec((1, r, BN), lambda m, n, ad, k: (ad, 0, n)),  # b
            pl.BlockSpec((1, BN), lambda m, n, ad, k: (ad, n)),  # mask
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda m, n, ad, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((BM, r), jnp.float32),
            pltpu.VMEM((BM, BN), jnp.float32),
        ],
        interpret=interpret,
    )(idx.astype(jnp.int32).reshape(M, 1), x, a, b, mask)
