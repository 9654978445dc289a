"""Population-scale client state: rounds/sec and peak RSS at 10k+ clients.

The in-memory engines materialize every client's LoRA/optimizer state up
front, so resident memory grows linearly with the population — fine at the
paper's ~100 simulated devices, prohibitive at cross-device scale. The
``OutOfCoreStore`` (``repro.federated.store``) keeps an LRU hot set of
resident clients and spills the rest to flat-npz cold files
(``repro.checkpoint``), so peak RSS is bounded by the hot-set size while the
population grows arbitrarily. This bench demonstrates that bound: each row
runs a short fibecfed-cohort experiment (curriculum + GAL FedAvg on the
vectorized cohort engine) at a given ``(num_clients, hot_slots)`` and
reports steady-state rounds/sec, init time, peak RSS, and the store's
fetch/evict counters.

Client shards are generated lazily (a ``Sequence`` that synthesizes shard
``ci`` on demand and exposes ``sample_counts``), so neither the data nor the
client states are ever resident all at once. Each row runs in a fresh
subprocess because ``ru_maxrss`` is process-monotonic — a second row in the
same process would inherit the first row's high-water mark.

The headline check is the ``rss_hot_bound`` ratio (small-population peak RSS
over large-population peak RSS, both at the same hot-set size): bounded
client state keeps it near 1.0 regardless of machine, so it gates as a
``speedups_device_independent`` metric in ``scripts/bench_compare.py`` even
across device-count mismatches. Absolute rounds/sec rows gate warn-only on
shared CI runners.

Usage:  PYTHONPATH=src python benchmarks/population_bench.py [--rounds N]
        [--json PATH]   (machine-readable results, e.g. BENCH_population.json;
                         compare with scripts/bench_compare.py --baseline
                         benchmarks/baselines/population.json)
        [--row C,H]     (internal: run one (clients, hot_slots) row in this
                         process and print its JSON record to stdout)

Env: REPRO_BENCH_HOST_DEVICES forces that many XLA host devices (set before
     jax initializes; the CI recipe is REPRO_BENCH_HOST_DEVICES=8).
     REPRO_BENCH_POPULATIONS overrides the row list (e.g. "1000,10000").
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

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

POPULATIONS = tuple(
    int(c) for c in os.environ.get("REPRO_BENCH_POPULATIONS", "1000,10000").split(",")
)
HOT_SLOTS = 64
COHORT = 8
SAMPLES_PER_CLIENT = 8
BATCH_SIZE = 4
SEQ_LEN = 8
VOCAB = 256


class LazyShards:
    """Per-client data shards synthesized on demand from one shared pool.

    Indexing materializes only the requested client's shard (a tiny slice of
    a fixed sample pool, chosen deterministically from the client id), and
    ``sample_counts`` answers the population-wide size query without
    touching any shard — the two properties the ``ClientStore`` contract
    needs for the runner to stay O(hot_slots) resident.
    """

    def __init__(self, num_clients: int, seed: int = 0):
        from repro.data import make_keyword_task

        # pool >> shard so clients differ; shards index it copy-on-slice
        task = make_keyword_task(
            n_samples=512, seq_len=SEQ_LEN, vocab_size=VOCAB, seed=seed
        )
        self._pool = {k: v for k, v in task.data.items() if k != "label"}
        self._pool_n = 512
        self._num = num_clients
        self._seed = seed
        self.sample_counts = np.full(num_clients, SAMPLES_PER_CLIENT, np.int64)

    def __len__(self) -> int:
        return self._num

    def __getitem__(self, ci: int):
        if not 0 <= ci < self._num:
            raise IndexError(ci)
        idx = np.random.default_rng(self._seed * 100003 + ci).choice(
            self._pool_n, SAMPLES_PER_CLIENT, replace=False
        )
        return {k: v[idx] for k, v in self._pool.items()}


def run_row(num_clients: int, hot_slots: int, rounds: int, seed: int = 0) -> dict:
    from repro.config import FibecFedConfig, ModelConfig
    from repro.federated import OutOfCoreStore, make_runner
    from repro.models import build_model
    from repro.obs import Telemetry
    from repro.train import make_loss_fn

    cfg = ModelConfig(
        name="tiny-lm", family="dense", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, d_ff=32, vocab_size=VOCAB, head_dim=8, rope="full",
        norm="rmsnorm", mlp="swiglu", dtype="float32", lora_rank=2,
        max_seq_len=SEQ_LEN,
    )
    # score-blind config (random curriculum, all-layer GAL, dense updates):
    # init skips the per-client sensitivity probe, so setup cost is the
    # store's create/spill sweep — the thing this bench is about
    fl = FibecFedConfig(
        num_devices=num_clients, devices_per_round=COHORT, rounds=rounds,
        batch_size=BATCH_SIZE, learning_rate=5e-3, fim_warmup_epochs=1,
        gal_fraction=1.0, sparse_ratio=0.5,
    )
    model = build_model(cfg)
    shards = LazyShards(num_clients, seed=seed)
    tel = Telemetry(run_id=f"population_{num_clients}")
    with tempfile.TemporaryDirectory(prefix="pop_bench_") as spill_dir:
        store = OutOfCoreStore(spill_dir, hot_slots=hot_slots)
        runner = make_runner(
            "random_select", model, make_loss_fn(model), fl, shards,
            seed=seed, optimizer="sgd", engine="vectorized", store=store,
            telemetry=tel,
        )
        t0 = time.perf_counter()
        runner.init_phase()
        init_s = time.perf_counter() - t0

        t_star = fl.rounds - 1  # fixed late round: stable compiled step shape
        runner.run_round(t_star)  # warmup: compile + first cohort fetch
        t0 = time.perf_counter()
        loss = float("nan")
        for _ in range(rounds):
            loss = runner.run_round(t_star)["loss"]
        dt = time.perf_counter() - t0

        snap = tel.metrics.snapshot()
    # linux ru_maxrss is KiB; this is the whole row process's high-water mark
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {
        "clients": num_clients,
        "hot_slots": hot_slots,
        "init_s": init_s,
        "rounds_per_s": rounds / dt,
        "ms_per_round": 1e3 * dt / rounds,
        "final_loss": loss,
        "peak_rss_mb": peak_kb / 1024.0,
        "store_counters": {
            k: v for k, v in snap.get("counters", {}).items() if k.startswith("store.")
        },
    }


def ckpt_overhead(rounds: int, num_clients: int = 256, seed: int = 0) -> dict:
    """Steady-state cost of per-round run checkpoints (the federation
    service's crash-consistency layer) on an out-of-core population.

    Times the same runner twice — ``rounds`` plain rounds, then ``rounds``
    rounds each followed by :func:`repro.checkpoint.save_run_checkpoint` —
    and reports ``ckpt_overhead_ratio`` = plain time over checkpointed time
    (throughput retained with checkpointing on; 1.0 = free, lower = the
    snapshot dominates the round). A within-process ratio of two wall
    times on identical work, so it transfers across machines and gates in
    the ``speedups_device_independent`` block (warn-only on shared CI)."""
    from repro.checkpoint import save_run_checkpoint
    from repro.config import FibecFedConfig, ModelConfig
    from repro.federated import OutOfCoreStore, make_runner
    from repro.models import build_model
    from repro.train import make_loss_fn

    cfg = ModelConfig(
        name="tiny-lm", family="dense", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, d_ff=32, vocab_size=VOCAB, head_dim=8, rope="full",
        norm="rmsnorm", mlp="swiglu", dtype="float32", lora_rank=2,
        max_seq_len=SEQ_LEN,
    )
    fl = FibecFedConfig(
        num_devices=num_clients, devices_per_round=COHORT, rounds=rounds,
        batch_size=BATCH_SIZE, learning_rate=5e-3, fim_warmup_epochs=1,
        gal_fraction=1.0, sparse_ratio=0.5,
    )
    model = build_model(cfg)
    shards = LazyShards(num_clients, seed=seed)
    with tempfile.TemporaryDirectory(prefix="pop_ckpt_") as workdir:
        store = OutOfCoreStore(
            os.path.join(workdir, "store"), hot_slots=HOT_SLOTS
        )
        runner = make_runner(
            "random_select", model, make_loss_fn(model), fl, shards,
            seed=seed, optimizer="sgd", engine="vectorized", store=store,
        )
        runner.init_phase()
        t_star = fl.rounds - 1
        runner.run_round(t_star)  # warmup: compile + first cohort fetch

        t0 = time.perf_counter()
        for _ in range(rounds):
            runner.run_round(t_star)
        plain_s = time.perf_counter() - t0

        ckpt_dir = os.path.join(workdir, "ckpt")
        t0 = time.perf_counter()
        for i in range(rounds):
            runner.run_round(t_star)
            save_run_checkpoint(ckpt_dir, runner, i + 1, keep=2)
        ckpt_s = time.perf_counter() - t0
    return {
        "clients": num_clients,
        "rounds": rounds,
        "plain_ms_per_round": 1e3 * plain_s / rounds,
        "ckpt_ms_per_round": 1e3 * ckpt_s / rounds,
        "ckpt_overhead_ratio": plain_s / ckpt_s,
    }


def _spawn_row(num_clients: int, hot_slots: int, rounds: int) -> dict:
    """Run one row in a fresh interpreter (ru_maxrss never resets)."""
    out = subprocess.run(
        [
            sys.executable, os.path.abspath(__file__),
            "--row", f"{num_clients},{hot_slots}", "--rounds", str(rounds),
        ],
        check=True, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": _pythonpath()},
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _pythonpath() -> str:
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    existing = os.environ.get("PYTHONPATH", "")
    return f"{src}:{existing}" if existing else src


def bench_all(rounds: int = 5) -> tuple:
    """Returns (csv_rows, results dict, device_independent dict)."""
    results = {
        f"pop{c}_hot{HOT_SLOTS}": _spawn_row(c, HOT_SLOTS, rounds)
        for c in POPULATIONS
    }
    keys = sorted(results, key=lambda k: results[k]["clients"])
    small, large = results[keys[0]], results[keys[-1]]
    # bounded client state: growing the population 10x at a fixed hot set
    # must not grow peak RSS with it (ratio ~1; a per-client leak drags it
    # toward hot/population). Machine-independent, so it gates even when
    # the device-dependent rows are skipped.
    # not merged into `results`: that dict becomes the JSON "engines" block,
    # which bench_compare reads rounds_per_s from — the ckpt record instead
    # contributes its ratio to the device-independent gate below
    ck = ckpt_overhead(rounds)
    device_independent = {
        "rss_hot_bound": small["peak_rss_mb"] / large["peak_rss_mb"],
        # throughput retained with per-round run checkpoints on (1.0 =
        # free); a within-run wall-time ratio, so it gates device-
        # independently like the RSS bound
        "ckpt_overhead_ratio": ck["ckpt_overhead_ratio"],
    }
    rows = [
        f"population/{name},{r['ms_per_round']:.1f},"
        f"rounds_per_s={r['rounds_per_s']:.2f};init_s={r['init_s']:.1f};"
        f"peak_rss_mb={r['peak_rss_mb']:.0f};"
        f"evictions={r['store_counters'].get('store.evictions', 0)}"
        for name, r in results.items()
    ]
    rows.append(
        f"population/rss_hot_bound,0.0,"
        f"small_over_large={device_independent['rss_hot_bound']:.2f}x"
    )
    rows.append(
        f"population/ckpt_overhead,{ck['ckpt_ms_per_round']:.1f},"
        f"plain_ms={ck['plain_ms_per_round']:.1f};"
        f"throughput_retained={ck['ckpt_overhead_ratio']:.2f}x"
    )
    return rows, results, device_independent


def write_json(path: str, results: dict, device_independent: dict) -> None:
    """BENCH_population.json — scripts/bench_compare.py gates the
    ``engines`` rounds/sec rows (device-dependent, warn-only on CI) and the
    RSS-bound ratio (device-independent, always gated)."""
    import jax

    payload = {
        "bench": "population",
        "num_xla_devices": len(jax.devices()),
        "hot_slots": HOT_SLOTS,
        "cohort": COHORT,
        "engines": results,
        "speedups_device_independent": device_independent,
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
    ap.add_argument("--rounds", type=int, default=5, help="timed steady-state rounds")
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable results (e.g. BENCH_population.json)",
    )
    ap.add_argument(
        "--row", default=None, metavar="C,H",
        help="internal: run one (clients, hot_slots) row and print JSON",
    )
    args = ap.parse_args()
    if args.row:
        c, h = (int(x) for x in args.row.split(","))
        print(json.dumps(run_row(c, h, args.rounds)))
        sys.exit(0)
    rows, results, device_independent = bench_all(rounds=args.rounds)
    for row in rows:
        print(row)
    if args.json:
        write_json(args.json, results, device_independent)
        print(f"# wrote {args.json}", file=sys.stderr)
