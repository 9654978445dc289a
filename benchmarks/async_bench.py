"""Virtual wall-clock to target loss: sync vs async vs adaptive async.

The synchronous engines barrier every round on the slowest chosen client, so
under device heterogeneity their wall-clock is straggler-bound. This
benchmark replays three aggregation modes on the *virtual clock* of a
``repro.federated.hetero`` scenario preset and measures how long each takes
to reach the same training-loss target:

* **sync** — the sharded engine when >1 XLA device is available (else
  vectorized); each round's virtual duration is the barrier
  (``hetero.sync_round_time``: the max over the cohort of per-client
  round-trip time under the scenario's speed/latency model);
* **async** — ``FibecFed(engine="async", scenario=...)`` with a half-cohort
  buffer: the event-driven scheduler merges any K completions, stragglers
  land late and staleness-discounted, and the virtual clock advances per
  completion event instead of per barrier (the PR 3 baseline policy);
* **adaptive** — the same async engine with the adaptive policy suite on:
  step-count adaptation (slow devices train the easiest ``ceil(n/r)`` of
  their selected batches), wall-clock-aware cohort sampling (fast clients
  early in the curriculum ramp), a staleness cutoff, and completion-rate
  buffer adaptation (``AsyncAggConfig`` knobs).

The target loss is defined by the sync trajectory itself (the smoothed loss
it reaches at 75% of its round budget), so "async wins" means: the async
engine reaches the *same* loss level in less virtual time, not that it
optimizes a different objective. All runners share the same
``rounds``/curriculum schedule; only the aggregation mode (and therefore
the clock model) differs. Under ``straggler`` (4x speed skew on a quarter
of the fleet) the async engine's merge cadence follows the fast clients and
the virtual-time ratio is the headline; ``adaptive_over_async`` isolates
what the adaptive policies add on top.

A second, orthogonal axis measures **uploaded bytes to the same target
loss**: the delta-merge async engine uncompressed vs with
``CompressionConfig(mode="topk", topk_ratio=0.1, topk_values="int8")`` and
error feedback. Bytes are priced by the configured wire format (values +
group scales + top-k indices, at each leaf's actual dtype), so
``compressed_bytes_ratio`` is device-independent and gates in CI via the
``speedups_device_independent`` block.

All runs share one model/seed/data world; per-client speed assignments are
identical (``hetero.SCENARIO_SEED_OFFSET``), so the comparison is paired.

Usage:  PYTHONPATH=src python benchmarks/async_bench.py
        [--scenarios straggler,mobile]  (presets from hetero.SCENARIOS)
        [--max-rounds N]    (sync round budget; async gets 6x in merges)
        [--json PATH]       (machine-readable BENCH_async.json; gate with
                             scripts/bench_compare.py --baseline
                             benchmarks/baselines/async.json)
        [--min-speedup X]   (non-zero exit if any scenario's async-over-sync
                             virtual-time speedup < X)
        [--trace-dir DIR]   (extra telemetry-enabled adaptive pass on the
                             first scenario; writes DIR/trace.jsonl and a
                             Perfetto-loadable DIR/trace.json — inspect with
                             scripts/trace_summary.py)

Env: REPRO_BENCH_DEVICES (default 16) clients, half sampled per round.
     REPRO_BENCH_HOST_DEVICES forces that many XLA host devices (set before
     jax initializes; the multi-device CI recipe is
     REPRO_BENCH_HOST_DEVICES=8).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# must run before jax (imported transitively below) locks the device count
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
from repro.federated import AsyncAggConfig, make_runner
from repro.federated.hetero import (
    SCENARIO_SEED_OFFSET,
    SCENARIOS,
    get_scenario,
    sync_round_time,
)
from repro.models import build_model
from repro.train import make_loss_fn

DEVICES = int(os.environ.get("REPRO_BENCH_DEVICES", "16"))
BATCH_SIZE = 1
SAMPLES_PER_CLIENT = 4
SEQ_LEN = 12
SMOOTH = 3  # round-loss smoothing window (both engines, identically)


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


def fl_config(rounds: int) -> FibecFedConfig:
    return FibecFedConfig(
        num_devices=DEVICES, devices_per_round=max(2, DEVICES // 2), rounds=rounds,
        batch_size=BATCH_SIZE, learning_rate=3e-3, fim_warmup_epochs=1,
        gal_fraction=0.75, sparse_ratio=0.5,
    )


def _smoothed_best(losses):
    """Running min of the SMOOTH-round mean: first index where the smoothed
    trajectory reaches each level. Identical treatment for both engines."""
    out, best = [], float("inf")
    for i in range(len(losses)):
        lo = max(0, i - SMOOTH + 1)
        best = min(best, float(np.mean(losses[lo : i + 1])))
        out.append(best)
    return out


def run_sync(preset, *, max_rounds: int, seed: int) -> dict:
    """Sync trajectory [(virtual_time, smoothed_best_loss)] under ``preset``."""
    import jax

    engine = "sharded" if len(jax.devices()) > 1 else "vectorized"
    model, client_data = build_world(seed=seed)
    fl = fl_config(max_rounds)
    runner = make_runner(
        "fibecfed", model, make_loss_fn(model), fl, client_data,
        seed=seed, optimizer="sgd", engine=engine,
    )
    runner.init_phase()
    bound = preset.bind(DEVICES, seed=seed + SCENARIO_SEED_OFFSET)
    clock, times, losses = 0.0, [], []
    for t in range(max_rounds):
        stats = runner.run_round(t)
        info = runner.last_round_info
        clock += sync_round_time(bound, info["chosen"], info["client_steps"])
        times.append(clock)
        losses.append(stats["loss"])
    return {"engine": engine, "times": times, "best": _smoothed_best(losses)}


def adaptive_cfg(k: int) -> AsyncAggConfig:
    """The benchmark's adaptive policy bundle (the PR 3 baseline is the same
    buffer with every policy at its default): step-count adaptation paces
    stragglers to the fast cohort's cadence, sampling bias keeps early
    merges straggler-free, the cutoff discards hopeless updates, and buffer
    adaptation absorbs dropout (mobile preset)."""
    return AsyncAggConfig(
        buffer_size=max(1, k // 2),
        adapt_steps=True,
        sampling_bias=2.0,
        staleness_cutoff=4,
        adapt_buffer=True,
    )


def run_async(
    preset, *, target: float, max_rounds: int, max_merges: int, seed: int,
    async_cfg: AsyncAggConfig, telemetry=None,
) -> dict:
    """Async merges until the smoothed loss reaches ``target`` (or cap).

    The runner gets the SAME ``rounds=max_rounds`` config as the sync run —
    the curriculum ramp must be identical for the comparison to isolate the
    aggregation mode. Merges past ``max_rounds`` run at the capped (full-
    data) end of the schedule.
    """
    model, client_data = build_world(seed=seed)
    fl = fl_config(max_rounds)
    runner = make_runner(
        "fibecfed", model, make_loss_fn(model), fl, client_data,
        seed=seed, optimizer="sgd", engine="async", scenario=preset,
        async_cfg=async_cfg, telemetry=telemetry,
    )
    runner.init_phase()
    times, losses = [], []
    for t in range(max_merges):
        stats = runner.run_round(t)
        times.append(stats["virtual_time"])
        losses.append(stats["loss"])
        if _smoothed_best(losses)[-1] <= target:
            return {
                "reached": True, "time": times[-1], "merges": t + 1,
                "upload_bytes": int(np.sum(runner.comm_upload_bytes_per_round)),
            }
    return {
        "reached": False, "time": times[-1], "merges": max_merges,
        "upload_bytes": int(np.sum(runner.comm_upload_bytes_per_round)),
    }


def bench_scenario(name: str, *, max_rounds: int, seed: int = 0) -> dict:
    preset = get_scenario(name)
    sync = run_sync(preset, max_rounds=max_rounds, seed=seed)
    # the target the sync engine provably reaches inside its budget: its own
    # smoothed loss at 75% of the round budget
    t_star = max(1, int(round(0.75 * max_rounds))) - 1
    target = sync["best"][t_star]
    sync_time = next(
        tm for tm, b in zip(sync["times"], sync["best"]) if b <= target
    )
    k = fl_config(max_rounds).devices_per_round
    asy = run_async(
        preset, target=target, max_rounds=max_rounds,
        max_merges=6 * max_rounds, seed=seed,
        async_cfg=AsyncAggConfig(buffer_size=max(1, k // 2)),
    )
    ada = run_async(
        preset, target=target, max_rounds=max_rounds,
        max_merges=6 * max_rounds, seed=seed, async_cfg=adaptive_cfg(k),
    )
    # --- bytes-to-target-loss axis: the same delta-merge async engine,
    # uncompressed vs int8 top-k + error feedback. Wire bytes are priced by
    # the configured format (values + scales + indices), so the ratio is
    # device-independent by construction — it gates in CI like the virtual
    # speedups do.
    from repro.federated import CompressionConfig

    delta_cfg = AsyncAggConfig(
        buffer_size=max(1, k // 2), merge_mode="delta", server_lr=1.0
    )
    comp = CompressionConfig(
        mode="topk", topk_ratio=0.1, topk_values="int8", error_feedback=True
    )
    raw = run_async(
        preset, target=target, max_rounds=max_rounds,
        max_merges=6 * max_rounds, seed=seed, async_cfg=delta_cfg,
    )
    cmp_ = run_async(
        preset, target=target, max_rounds=max_rounds,
        max_merges=6 * max_rounds, seed=seed,
        async_cfg=AsyncAggConfig(
            buffer_size=max(1, k // 2), merge_mode="delta", server_lr=1.0,
            compression=comp,
        ),
    )
    bytes_ratio = (
        raw["upload_bytes"] / cmp_["upload_bytes"]
        if (raw["reached"] and cmp_["reached"] and cmp_["upload_bytes"])
        else 0.0
    )
    speedup = sync_time / asy["time"] if asy["reached"] else 0.0
    ada_speedup = sync_time / ada["time"] if ada["reached"] else 0.0
    return {
        "scenario": name,
        "sync_engine": sync["engine"],
        "target_loss": target,
        "sync_virtual_time": sync_time,
        "async_virtual_time": asy["time"],
        "async_reached_target": asy["reached"],
        "async_merges": asy["merges"],
        "virtual_speedup": speedup,
        "adaptive_virtual_time": ada["time"],
        "adaptive_reached_target": ada["reached"],
        "adaptive_merges": ada["merges"],
        "adaptive_speedup": ada_speedup,
        # only meaningful when BOTH runs reached the target — a capped
        # baseline time would fabricate a finite but incomparable ratio
        "adaptive_over_async": (
            asy["time"] / ada["time"]
            if (ada["reached"] and asy["reached"])
            else 0.0
        ),
        "uncompressed_upload_bytes": raw["upload_bytes"],
        "uncompressed_reached_target": raw["reached"],
        "compressed_upload_bytes": cmp_["upload_bytes"],
        "compressed_reached_target": cmp_["reached"],
        "compressed_merges": cmp_["merges"],
        "compressed_bytes_ratio": bytes_ratio,
    }


def bench_all(scenarios, *, max_rounds: int) -> tuple:
    """Returns (csv_rows, speedups dict, per-scenario results dict)."""
    results = {s: bench_scenario(s, max_rounds=max_rounds) for s in scenarios}
    speedups, di_speedups = {}, {}
    for s, r in results.items():
        speedups[f"async_over_sync/{s}"] = r["virtual_speedup"]
        speedups[f"adaptive_over_sync/{s}"] = r["adaptive_speedup"]
        speedups[f"adaptive_over_async/{s}"] = r["adaptive_over_async"]
        # uploaded-bytes-to-target ratio: wire-format arithmetic on a paired
        # virtual-clock replay, identical on any host
        di_speedups[f"compressed_bytes_ratio/{s}"] = r["compressed_bytes_ratio"]
    rows = [
        f"async/{r['scenario']},0.0,"
        f"virtual_speedup={r['virtual_speedup']:.2f}x;"
        f"adaptive_speedup={r['adaptive_speedup']:.2f}x;"
        f"adaptive_over_async={r['adaptive_over_async']:.2f}x;"
        f"compressed_bytes_ratio={r['compressed_bytes_ratio']:.2f}x;"
        f"sync_vt={r['sync_virtual_time']:.1f};"
        f"async_vt={r['async_virtual_time']:.1f};"
        f"adaptive_vt={r['adaptive_virtual_time']:.1f};"
        f"target={r['target_loss']:.4f};merges={r['async_merges']}"
        for r in results.values()
    ]
    return rows, speedups, di_speedups, results


def export_trace(trace_dir: str, *, scenario: str, target: float,
                 max_rounds: int, seed: int = 0) -> dict:
    """One extra telemetry-enabled adaptive run under ``scenario``; writes
    ``trace.jsonl`` (schema-validated event log + metrics snapshot) and a
    Perfetto-loadable ``trace.json`` into ``trace_dir``. The gated timing
    runs above stay un-instrumented; this run exists to produce the
    artifact. Returns the telemetry metrics snapshot."""
    from repro.obs import Telemetry, validate_jsonl

    os.makedirs(trace_dir, exist_ok=True)
    k = fl_config(max_rounds).devices_per_round
    tel = Telemetry(
        run_id=f"async_bench/{scenario}",
        meta={"scenario": scenario, "fl_devices": DEVICES,
              "max_rounds": max_rounds, "target_loss": target},
    )
    run_async(
        get_scenario(scenario), target=target, max_rounds=max_rounds,
        max_merges=6 * max_rounds, seed=seed, async_cfg=adaptive_cfg(k),
        telemetry=tel,
    )
    jsonl = os.path.join(trace_dir, "trace.jsonl")
    tel.export_jsonl(jsonl)
    validate_jsonl(jsonl)
    tel.export_perfetto(os.path.join(trace_dir, "trace.json"))
    print(f"# wrote {trace_dir}/trace.jsonl + trace.json", file=sys.stderr)
    return tel.snapshot()


def write_json(path: str, speedups: dict, di_speedups: dict, results: dict,
               metrics_snapshot: dict = None) -> None:
    """BENCH_async.json — compared against benchmarks/baselines/async.json
    by scripts/bench_compare.py (speedup ratios transfer across machines;
    virtual times are machine-independent by construction; the
    ``speedups_device_independent`` block — bytes-to-target ratios — always
    gates, even across machines with different device counts). The
    ``metrics_snapshot`` block is informational — bench_compare passes it
    through without gating."""
    import jax

    from repro.obs import runtime_metrics

    payload = {
        "bench": "async",
        "num_xla_devices": len(jax.devices()),
        "fl_devices": DEVICES,
        "batch_size": BATCH_SIZE,
        "scenarios": results,
        "speedups": speedups,
        "speedups_device_independent": di_speedups,
        "metrics_snapshot": (
            metrics_snapshot
            if metrics_snapshot is not None
            else {"runtime": runtime_metrics.snapshot()}
        ),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def run() -> list:
    """benchmarks.run harness entry point."""
    return bench_all(("straggler",), max_rounds=20)[0]


def _main(args) -> int:
    scenarios = [s for s in args.scenarios.split(",") if s]
    rows, speedups, di_speedups, results = bench_all(
        scenarios, max_rounds=args.max_rounds
    )
    for row in rows:
        print(row)
    snap = None
    if args.trace_dir:
        first = scenarios[0]
        snap = export_trace(
            args.trace_dir, scenario=first,
            target=results[first]["target_loss"], max_rounds=args.max_rounds,
        )
    if args.json:
        write_json(args.json, speedups, di_speedups, results, snap)
        print(f"# wrote {args.json}", file=sys.stderr)
    worst = min(speedups.values())
    if worst < args.min_speedup:
        print(f"FAIL: virtual speedup {worst:.2f}x < {args.min_speedup:.2f}x")
        return 1
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--scenarios", default="straggler",
        help=f"comma-separated preset names from {sorted(SCENARIOS)}",
    )
    ap.add_argument(
        "--max-rounds", type=int, default=25,
        help="sync round budget (async gets 6x that in merges)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable results (e.g. BENCH_async.json)",
    )
    ap.add_argument(
        "--min-speedup", type=float, default=0.0,
        help="exit non-zero unless every scenario's virtual speedup >= this",
    )
    ap.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="run one extra telemetry-enabled adaptive pass on the first"
             " scenario and write trace.jsonl + Perfetto trace.json there",
    )
    args = ap.parse_args()
    sys.exit(_main(args))
