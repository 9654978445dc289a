"""Masked-optimizer update microbench: fused single-pass vs tree.map chain.

One federated local step ends in the masked optimizer update — an
elementwise, purely memory-bound pass over every LoRA/moment buffer. The
unfused path is a chain of ``tree.map`` passes (grad masking, moment
update, bias correction, weight decay, and the per-step ``active`` commit);
the fused path (``repro.kernels.ops.masked_{sgd,adamw}_update``) computes
the same frozen-moment semantics in one pass per leaf.

On this CPU container the Pallas kernel runs in interpret mode, where
timing is meaningless (see ``kernels_bench.py``), so the timed fused path
is the kernels' single-expression oracle (``use_kernel=False``) — the
CPU-executable proxy for what the TPU kernel does in one read/write pass.
Two metrics go to the JSON gate:

- ``fused_over_unfused/{sgd,adamw}`` — measured wall-time speedup of the
  vmapped update step (machine-dependent; the CI compare is warn-only);
- ``buffer_reduction/{sgd,adamw}`` — lowered (pre-fusion) HLO op-result
  count of unfused over fused, i.e. how many fewer intermediate buffers the
  fused formulation binds. Deterministic and machine-independent, so it
  rides in the payload's ``speedups_device_independent`` block, which
  ``bench_compare.py`` gates even when the run's XLA device count differs
  from the committed baseline's.

Usage:  PYTHONPATH=src python benchmarks/masked_update_bench.py
        [--iters N] [--json PATH]
Env: REPRO_BENCH_HOST_DEVICES forces the XLA host device count (set before
     jax initializes; the CI recipe is REPRO_BENCH_HOST_DEVICES=8 to match
     the tier1-multidevice regime the committed baseline records).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# must run before jax locks the device count (same idiom as fl_round_bench)
_HOST_DEVICES = os.environ.get("REPRO_BENCH_HOST_DEVICES")
if _HOST_DEVICES and "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_HOST_DEVICES}"
    ).strip()

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.optim import adamw_init, adamw_update, sgd_init, sgd_update

# a stacked cohort of LoRA trees, roughly the reduced-model regime the round
# engines train: K clients x L layers x (down, up) adapters
K_CLIENTS = 8
LAYERS = 8
D_MODEL = 2048
RANK = 8


def build_tree(key):
    params = {}
    for layer in range(LAYERS):
        k1, k2, key = jax.random.split(key, 3)
        params[f"layer{layer}"] = {
            "a": jax.random.normal(k1, (K_CLIENTS, D_MODEL, RANK), jnp.float32),
            "b": jax.random.normal(k2, (K_CLIENTS, RANK, D_MODEL), jnp.float32),
        }
    return params


def _time(fn, *args, iters: int, repeats: int = 3) -> float:
    jax.block_until_ready(fn(*args))  # compile + first dispatch
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / iters


def _lowered_ops(fn, *args) -> int:
    """Op-result count of the lowered (pre-fusion) HLO — each result is an
    intermediate buffer a naive lowering materializes."""
    return jax.jit(fn).lower(*args).as_text().count(" = ")


def bench_optimizer(name: str, *, iters: int) -> dict:
    key = jax.random.PRNGKey(0)
    params = build_tree(key)
    grads = build_tree(jax.random.fold_in(key, 1))
    mask = jax.tree.map(
        lambda x: (jax.random.uniform(jax.random.fold_in(key, 2), x.shape) > 0.5)
        .astype(jnp.float32),
        params,
    )
    active = (jnp.arange(K_CLIENTS) % 2).astype(jnp.float32)  # half padded
    lr = jnp.float32(1e-2)
    if name == "sgd":
        state = sgd_init(params, momentum=0.9)
        state["mu"] = build_tree(jax.random.fold_in(key, 3))

        def unfused(g, s, p, mk, a):
            return jax.vmap(
                lambda gg, ss, pp, mm, aa: sgd_update(gg, ss, pp, lr, mm, aa, momentum=0.9)
            )(g, s, p, mk, a)

        def fused(g, s, p, mk, a):
            return jax.vmap(
                lambda gg, ss, pp, mm, aa: ops.masked_sgd_update(
                    gg, ss, pp, lr, mm, aa, momentum=0.9, use_kernel=False
                )
            )(g, s, p, mk, a)

    elif name == "adamw":
        state = adamw_init(params)
        state["m"] = build_tree(jax.random.fold_in(key, 3))
        state["v"] = jax.tree.map(jnp.abs, build_tree(jax.random.fold_in(key, 4)))
        state["t"] = jnp.zeros((K_CLIENTS,), jnp.int32)

        def unfused(g, s, p, mk, a):
            return jax.vmap(
                lambda gg, ss, pp, mm, aa: adamw_update(gg, ss, pp, lr, mm, aa, wd=0.01)
            )(g, s, p, mk, a)

        def fused(g, s, p, mk, a):
            return jax.vmap(
                lambda gg, ss, pp, mm, aa: ops.masked_adamw_update(
                    gg, ss, pp, lr, mm, aa, wd=0.01, use_kernel=False
                )
            )(g, s, p, mk, a)

    else:
        raise ValueError(name)

    args = (grads, state, params, mask, active)
    t_unfused = _time(jax.jit(unfused), *args, iters=iters)
    t_fused = _time(jax.jit(fused), *args, iters=iters)
    ops_unfused = _lowered_ops(unfused, *args)
    ops_fused = _lowered_ops(fused, *args)
    return {
        "optimizer": name,
        "unfused_us": 1e6 * t_unfused,
        "fused_us": 1e6 * t_fused,
        "speedup": t_unfused / t_fused,
        "lowered_ops_unfused": ops_unfused,
        "lowered_ops_fused": ops_fused,
        "buffer_reduction": ops_unfused / ops_fused,
    }


def bench_all(iters: int = 20) -> tuple:
    results = {name: bench_optimizer(name, iters=iters) for name in ("sgd", "adamw")}
    speedups, indep = {}, {}
    for name, r in results.items():
        speedups[f"fused_over_unfused/{name}"] = r["speedup"]
        indep[f"buffer_reduction/{name}"] = r["buffer_reduction"]
    rows = [
        f"masked_update/{r['optimizer']},{r['fused_us']:.0f},"
        f"fused_over_unfused={r['speedup']:.2f}x;"
        f"buffers={r['lowered_ops_fused']}vs{r['lowered_ops_unfused']}"
        for r in results.values()
    ]
    return rows, speedups, indep, results


def write_json(path: str, speedups: dict, indep: dict, results: dict) -> None:
    from repro.obs import runtime_metrics

    payload = {
        "bench": "masked_update",
        "num_xla_devices": len(jax.devices()),
        "clients": K_CLIENTS,
        "layers": LAYERS,
        "d_model": D_MODEL,
        "rank": RANK,
        "optimizers": results,
        "speedups": speedups,
        "speedups_device_independent": indep,
        # informational; bench_compare passes the block through without gating
        "metrics_snapshot": {"runtime": runtime_metrics.snapshot()},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def run() -> list:
    """benchmarks.run harness entry point."""
    return bench_all()[0]


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20, help="timed update steps")
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable results (e.g. BENCH_masked_update.json)",
    )
    args = ap.parse_args()
    rows, speedups, indep, results = bench_all(iters=args.iters)
    for row in rows:
        print(row)
    if args.json:
        write_json(args.json, speedups, indep, results)
        print(f"# wrote {args.json}", file=sys.stderr)
