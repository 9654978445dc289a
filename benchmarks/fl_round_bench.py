"""Round-engine throughput: loop vs. vectorized vs. mesh-sharded rounds/sec.

The vectorized engine runs one jitted device program per federated round
(scan over curriculum steps inside a vmap over clients, fused GAL FedAvg);
the loop engine dispatches one jitted call per (client, batch) step and
aggregates on the host; the sharded engine (``--mesh``) is the vectorized
program with the stacked client axis sharded over a data-only device mesh,
each device training its shard of the cohort and the weighted GAL FedAvg
lowering to an all-reduce. All are measured at the reduced qwen2-0.5b config
in their compiled steady state (fixed late-curriculum round, so the padded
step count — and therefore the compiled program — is stable).

The default world is the cross-device FL regime the engine targets (and the
paper simulates: ~100 devices, ~10 sampled per round): many clients with
small local shards/batches, sampled in large cohorts. There the loop
engine's per-(client, batch) dispatch+sync dominates and the vectorized
engine's client-axis batching wins; with few fat clients the round is pure
GEMM time on CPU and the engines converge. Shards are size-balanced — the
padded scan runs every client to the *largest* chosen shard's step count, so
size skew costs masked padding steps (label skew is irrelevant to
throughput; see ROADMAP "Open items" for skew-aware bucketing).

Usage:  PYTHONPATH=src python benchmarks/fl_round_bench.py [--rounds N]
        [--mesh]            (also bench engine="sharded" on all XLA devices)
        [--json PATH]       (machine-readable results, e.g. BENCH_fl_round.json;
                             compare against a baseline with scripts/bench_compare.py)
        [--min-speedup X]   (non-zero exit if vectorized/loop < X)

Env: REPRO_BENCH_DEVICES (default 32) clients, half sampled per round.
     REPRO_BENCH_HOST_DEVICES forces that many XLA host devices (must be set
     before jax initializes; equivalent to
     XLA_FLAGS=--xla_force_host_platform_device_count=N) — the multi-device
     CI recipe is REPRO_BENCH_HOST_DEVICES=8 + --mesh.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# must run before jax (imported transitively below) locks the device count;
# appended so a pre-existing XLA_FLAGS keeps its other settings
_HOST_DEVICES = os.environ.get("REPRO_BENCH_HOST_DEVICES")
if _HOST_DEVICES and "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_HOST_DEVICES}"
    ).strip()

import numpy as np

from repro.config import FibecFedConfig
from repro.configs import ARCHS
from repro.data import make_keyword_task
from repro.federated import make_runner
from repro.models import build_model
from repro.train import make_loss_fn

DEVICES = int(os.environ.get("REPRO_BENCH_DEVICES", "32"))
BATCH_SIZE = 1
SAMPLES_PER_CLIENT = 4
SEQ_LEN = 12


def build_world(seed: int = 0):
    cfg = ARCHS["qwen2-0.5b"].reduced()
    model = build_model(cfg)
    n = DEVICES * SAMPLES_PER_CLIENT
    task = make_keyword_task(
        n_samples=n, seq_len=SEQ_LEN, vocab_size=cfg.vocab_size, seed=seed
    )
    parts = np.array_split(np.random.default_rng(seed).permutation(n), DEVICES)
    client_data = [
        {k: v[idx] for k, v in task.data.items() if k != "label"} for idx in parts
    ]
    return model, client_data


def fl_config(rounds: int = 100) -> FibecFedConfig:
    return FibecFedConfig(
        num_devices=DEVICES, devices_per_round=max(2, DEVICES // 2), rounds=rounds,
        batch_size=BATCH_SIZE, learning_rate=3e-3, fim_warmup_epochs=1,
        gal_fraction=0.75, sparse_ratio=0.5,
    )


def bench_engine(engine: str, *, rounds: int, repeats: int = 3, seed: int = 0) -> dict:
    model, client_data = build_world(seed=seed)
    fl = fl_config()
    runner = make_runner(
        "fibecfed", model, make_loss_fn(model), fl, client_data,
        seed=seed, optimizer="sgd", engine=engine,
    )
    t0 = time.perf_counter()
    runner.init_phase()
    init_s = time.perf_counter() - t0

    # steady state: a fixed late round (full curriculum) so batch counts —
    # and the vectorized engine's compiled step shape — no longer change
    t_star = fl.rounds - 1
    for _ in range(2):  # warmup: compile + first dispatch
        runner.run_round(t_star)
    # best-of-N blocks: scheduler noise on small shared machines only ever
    # slows a block down, so the fastest block is the cleanest estimate
    best_dt, loss = float("inf"), float("nan")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(rounds):
            loss = runner.run_round(t_star)["loss"]
        best_dt = min(best_dt, time.perf_counter() - t0)
    return {
        "engine": engine,
        "init_s": init_s,
        "rounds_per_s": rounds / best_dt,
        "ms_per_round": 1e3 * best_dt / rounds,
        "final_loss": loss,
    }


def bench_all(rounds: int = 20, engines=("loop", "vectorized")) -> tuple:
    """Returns (csv_rows, speedups dict, per-engine results dict)."""
    results = {e: bench_engine(e, rounds=rounds) for e in engines}
    speedups = {
        f"{e}_over_loop": results[e]["rounds_per_s"] / results["loop"]["rounds_per_s"]
        for e in engines
        if e != "loop"
    }
    rows = [
        f"fl_round/{r['engine']},{r['ms_per_round']:.1f},"
        f"rounds_per_s={r['rounds_per_s']:.2f};init_s={r['init_s']:.1f};"
        f"loss={r['final_loss']:.4f}"
        for r in results.values()
    ]
    for name, s in speedups.items():
        rows.append(f"fl_round/speedup,0.0,{name}={s:.2f}x")
    return rows, speedups, results


def write_json(path: str, speedups: dict, results: dict) -> None:
    """BENCH_fl_round.json — the machine-readable record scripts/
    bench_compare.py checks against a committed baseline."""
    import jax

    from repro.obs import runtime_metrics

    payload = {
        "bench": "fl_round",
        "num_xla_devices": len(jax.devices()),
        "fl_devices": DEVICES,
        "batch_size": BATCH_SIZE,
        "engines": results,
        "speedups": speedups,
        # jit program-build counters across the whole bench (informational;
        # bench_compare passes the block through without gating)
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
    ap.add_argument("--rounds", type=int, default=20, help="timed steady-state rounds")
    ap.add_argument(
        "--mesh", action="store_true",
        help="also bench engine='sharded' on a data mesh over all XLA devices",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable results (e.g. BENCH_fl_round.json)",
    )
    ap.add_argument(
        "--min-speedup", type=float, default=0.0,
        help="exit non-zero unless vectorized/loop >= this",
    )
    args = ap.parse_args()
    engines = ("loop", "vectorized") + (("sharded",) if args.mesh else ())
    rows, speedups, results = bench_all(rounds=args.rounds, engines=engines)
    for row in rows:
        print(row)
    if args.json:
        write_json(args.json, speedups, results)
        print(f"# wrote {args.json}", file=sys.stderr)
    if speedups["vectorized_over_loop"] < args.min_speedup:
        print(
            f"FAIL: speedup {speedups['vectorized_over_loop']:.2f}x"
            f" < {args.min_speedup:.2f}x"
        )
        sys.exit(1)
