#!/usr/bin/env python3
"""The chip benchmark: one cell, one seed, one measured window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. Everything is found by name from ``BENCHMARK.json`` (see
``bench/lib/spec.py``): the configuration, the traffic mix (whose ``kind``
names the job in ``bench/jobs/``), the limits of the check, and each
per-layer metric's reader.

A run builds the job from the seed and sets it up (weights, data, the
program's set-up, a warm-up of every shape the window uses: ``setup_s``),
measures for ``--seconds`` (nothing may compile in that window), reads peak
device memory, frees the program's state, and recomputes what the program
produced with the plain reference. It prints the compared numbers beside
their limits as its last lines on stderr, and as the last line of stdout one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end ones with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device`` and, when traced, ``breakdown``; ``checks`` comes last.

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or no program next to the benchmark.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up counts from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib import compare, peaks, spec, xplane  # noqa: E402

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def start_jax(root: Path):
    """Imports JAX with the compile cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(root / ".jax_cache"))
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


class CompileCounter:
    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.n += 1


def profile_options(jax):
    """Device ops, program runs and the host's annotations; no Python
    tracer (it slows the host enough to move the idle share) and no HLO
    protos (they make the trace large)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def measure(jax, job, kind: str, seconds: float, trace_dir):
    """The window: ``job.step()`` in a closed loop until ``seconds`` have
    passed; it ends with the last completed step. Returns the steps, the
    window's length and its start on the host clock."""
    steps, ends = [], []
    if trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options(jax))
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation(f"bench.{kind}"):
                steps.append(job.step())
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        window_s = ends[-1] - t0
    if trace_dir:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace: stopping took {time.perf_counter() - t!r} s", file=sys.stderr)
    each = sorted(b - a for a, b in zip([t0] + ends, ends))
    print(f"window: {len(steps)} steps in {window_s!r} s; step median {each[len(each) // 2]!r} s, "
          f"max {each[-1]!r} s", file=sys.stderr)
    return steps, window_s, t0


def read_trace(trace_dir: str, spans, t0: float):
    """The reduced trace of the window. ``spans`` are the program's own
    spans (name, start, end on the host clock); placed on the trace's clock
    by the window's start, they label the idle gaps."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    tr = xplane.Trace.load(paths[0])
    window = tr.annotation(xplane.WINDOW)
    if window is not None:
        tr.host += [(window[0] + (s - t0) * 1e9, window[0] + (e - t0) * 1e9, f"{xplane.ANNOTATION_PREFIX}{n}")
                    for n, s, e in spans]
    return xplane.reduce(tr, window)


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    try:
        bench = spec.load_benchmark(root)
        cell = spec.find_cell(bench, args.workload)
        config = spec.load_config(root, bench, cell["config"])
        traffic = spec.load_traffic(root, cell["traffic"])
        limits = spec.load_limits(root, cell["name"])
        job_mod = spec.load_job(root, traffic["kind"])
        reference = spec.load_reference(root, config["reference"])
    except spec.SpecError as e:
        return fail(str(e))
    if not (root / "src" / "repro").is_dir():
        return fail(f"no program under {root / 'src'}")
    sys.path.insert(0, str(root / "src"))

    jax = start_jax(root)
    devices = jax.devices()
    chips = cell["chips"]
    if require_tpu and devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        return fail(f"cell {cell['name']} needs {chips} chips; JAX found {len(devices)}")
    kind = devices[0].device_kind
    peak = peaks.peak(kind) if require_tpu else None
    compiles = CompileCounter(jax)

    job = job_mod.Job(config=config, traffic=traffic, seed=args.seed, reference=reference,
                      chips=chips)
    job.setup()
    setup_s = time.perf_counter() - T0

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    before = compiles.n
    steps, window_s, t0 = measure(jax, job, traffic["kind"], args.seconds, trace_dir)
    in_window = compiles.n - before

    used = devices[:chips]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": int(mem)}

    out = {"correct": False, "attempted": len(steps),
           "failed": sum(not math.isfinite(s.get("loss", 0.0)) for s in steps)}
    breakdown = None
    if args.trace:
        t = time.perf_counter()
        reduced = read_trace(trace_dir, job.host_spans(), t0)
        print(f"trace: reading took {time.perf_counter() - t!r} s", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"steps": steps, "window_s": window_s, "trace": reduced, "chips": chips,
               "peak_flops": peak["bf16_flops"] if peak else None,
               "required_flops": job.required_flops(steps)}
        metrics = {}
        for m in spec.per_layer_metrics(bench, cell["name"]):
            v = spec.load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduced and reduced.get("chips"):
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = job.end_to_end(window_s, steps)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in spec.end_to_end_metrics(bench, cell["name"]) if m["name"] in e2e}

    job.release()
    gc.collect()
    numbers = job.check()
    numbers["compiles_in_window"] = float(in_window)
    correct, checks = compare.verdict(numbers, dict(limits, compiles_in_window=0.0))

    out.update(correct=correct, metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} <= {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
