"""Benchmark harness — one module per paper table (deliverable d).

Prints ``name,us_per_call,derived`` CSV. Modules:
  table1_accuracy  — Table 1 (convergence accuracy vs baselines)
  table2_time      — Table 2/7 (time-to-target-accuracy)
  table13_comm     — Table 13 (communication overhead)
  table5_selection — Table 5/6, App. G.2 (data-selection strategies)
  fig7_ablations   — §5.7, Fig. 7, Table 12 (curriculum/GAL/sparse/β)
  kernels_bench    — kernel reference-path micro-benchmarks
  masked_update_bench — fused vs unfused masked optimizer update step
  async_bench      — sync vs async virtual wall-clock under device skew
  population_bench — out-of-core client store at 1k/10k clients (RSS bound)
  roofline         — §Roofline table from the dry-run artifacts

Each module runs in a child process of its own and this parent never
imports JAX: an accelerator belongs to one process at a time, so a parent
that touched it would leave every child (and ``population_bench``'s own
per-row children) without a device.

Env: REPRO_BENCH_ROUNDS / REPRO_BENCH_DEVICES scale the FL runs;
``--only <module>`` runs a single table.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

MODULES = [
    "kernels_bench",
    "masked_update_bench",
    "fl_round_bench",
    "async_bench",
    "population_bench",
    "table1_accuracy",
    "table2_time",
    "table13_comm",
    "table5_selection",
    "fig7_ablations",
    "roofline",
]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child: enable the shared compile cache, then stream the module's rows
_CHILD = (
    "import sys\n"
    "from repro.utils.compile_cache import enable_compile_cache\n"
    "enable_compile_cache()\n"
    "mod = __import__('benchmarks.' + sys.argv[1], fromlist=['run'])\n"
    "for row in mod.run():\n"
    "    print(row, flush=True)\n"
)


def _child_env() -> dict:
    paths = [os.path.join(ROOT, "src"), ROOT]
    if os.environ.get("PYTHONPATH"):
        paths.append(os.environ["PYTHONPATH"])
    return {**os.environ, "PYTHONPATH": os.pathsep.join(paths)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    mods = [args.only] if args.only else MODULES
    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name in mods:
        t0 = time.perf_counter()
        rc = subprocess.run(
            [sys.executable, "-c", _CHILD, name], cwd=ROOT, env=_child_env()
        ).returncode
        if rc != 0:
            print(f"{name},0.0,ERROR", flush=True)
            failures += 1
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
