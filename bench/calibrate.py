#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from (on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For each seed it builds and sets up the cell's job exactly as ``run.py``
does (no measured window) and prints one JSON line with the numbers the
check compares:

- ``program``: the program against the float32 reference (sound runs;
  the limit's lower reading is their largest);
- ``control`` (control seeds): the reference computed with float8 operands,
  the precision below the configuration's bfloat16, put in the program's
  place;
- the job's planted faults (control seeds; ``Job.faults``), such as
  ``half_batch``: the reference with half of every batch left out and the
  mean taken over the rest, in the program's place.

Every seed runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.lib import spec  # noqa: E402


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, args.workload)
    config = spec.load_config(root, bench, cell["config"])
    traffic = spec.load_traffic(root, cell["traffic"])
    job_mod = spec.load_job(root, traffic["kind"])
    reference = spec.load_reference(root, config["reference"])
    sys.path.insert(0, str(root / "src"))
    jax = run.start_jax(root)
    if require_tpu and jax.devices()[0].platform != "tpu":
        return run.fail("needs a TPU")
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        job = job_mod.Job(config=config, traffic=traffic, seed=seed, reference=reference,
                          chips=cell["chips"])
        job.setup()
        job.release()
        gc.collect()
        got = job.program_outputs()
        t1 = time.perf_counter()
        want = job.reference()
        line = {"seed": seed, "setup_s": t1 - t0, "reference_s": time.perf_counter() - t1,
                "program": job.numbers(got, want)}
        if hasattr(job, "detail"):
            line["detail"] = job.detail(got, want)
        if seed in control:
            line["control"] = job.numbers(job.reference(mode="fp8"), want)
            for name, outputs in job.faults().items():
                line[name] = job.numbers(outputs, want)
        print(json.dumps(line), flush=True)
        del job
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
