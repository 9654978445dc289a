"""Kernel micro-benchmarks: jnp reference path wall-time (the CPU-executable
proxy; the Pallas kernels are TPU-target and validated in interpret mode,
where timing is meaningless). `derived` reports achieved GFLOP/s of the ref.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row
from repro.kernels import ref


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention ref: B*H=8, S=1024, D=64
    q = jax.random.normal(key, (8, 1024, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (8, 1024, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (8, 1024, 64))
    f = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c, causal=True))
    dt = _time(f, q, k, v)
    flops = 4 * 8 * 1024 * 1024 * 64 / 2  # causal half
    rows.append(csv_row("kernels/flash_ref_1k", dt * 1e6, f"gflops={flops/dt/1e9:.1f}"))

    # sparse lora ref: M=4096, K=1024, r=8, N=1024
    x = jax.random.normal(key, (4096, 1024))
    a = jax.random.normal(key, (1024, 8))
    b = jax.random.normal(key, (8, 1024))
    mask = jnp.ones((1024,))
    f = jax.jit(ref.sparse_lora_matmul_ref)
    dt = _time(f, x, a, b, mask)
    flops = 2 * 4096 * 1024 * 8 * 2
    rows.append(csv_row("kernels/sparse_lora_ref", dt * 1e6, f"gflops={flops/dt/1e9:.1f}"))

    # fisher diag ref
    g = jax.random.normal(key, (4096, 1024))
    fim = jnp.zeros((4096, 1024))
    f = jax.jit(lambda gg, ff: ref.fisher_diag_update_ref(gg, ff, 0.9))
    dt = _time(f, g, fim)
    gb = 3 * 4096 * 1024 * 4 / 1e9
    rows.append(csv_row("kernels/fisher_diag_ref", dt * 1e6, f"gbps={gb/dt:.1f}"))

    # ssd chunk ref: G=64, Q=128, hd=64, N=64
    x = jax.random.normal(key, (64, 128, 64))
    aa = -jnp.abs(jax.random.normal(key, (64, 1, 128))) * 0.1
    bb = jax.random.normal(key, (64, 128, 64))
    cc = jax.random.normal(key, (64, 128, 64))
    f = jax.jit(ref.ssd_chunk_intra_ref)
    dt = _time(f, x, aa, bb, cc)
    flops = 64 * (2 * 128 * 128 * 64 * 2)
    rows.append(csv_row("kernels/ssd_chunk_ref", dt * 1e6, f"gflops={flops/dt/1e9:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r)
