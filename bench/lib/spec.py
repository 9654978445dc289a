"""Finds a cell's pieces by name.

Everything a cell needs sits in files of its own, found through the names in
``BENCHMARK.json``:

- the configuration: the ``file`` of its ``configs`` entry (sizes as
  published, plus ``run_as``, the settings the program runs it with);
- the traffic mix: ``bench/traffic/<traffic>.json``;
- the limits of the comparison that decides ``correct``:
  ``bench/limits/<cell>.json``;
- each per-layer metric's reader: ``bench/metrics/<metric>.py``, a module
  with ``read(ctx) -> float | None``;
- the plain reference of a configuration: ``bench/reference/<name>.py``,
  named by the configuration's ``reference`` key. It is also where the
  harness learns the architecture: the module gives the hooks in
  ``ARCH_HOOKS`` (``bench/README.md`` states their contract).

A later change adds a cell, a configuration or a metric by adding such files
and ``BENCHMARK.json`` entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH_DIR = "bench"
# what a reference module gives the harness: the shapes, the weights, and the
# operations the algorithm requires
ARCH_HOOKS = ("sizes", "make_weights", "lora_train_flops", "input_grad_flops", "forward_flops")


class SpecError(Exception):
    """The benchmark's files do not describe the requested cell."""


def _json(path: Path) -> Any:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path) -> Dict[str, Any]:
    return _json(Path(root) / "BENCHMARK.json")


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(root: Path, bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(Path(root) / c["file"])
    raise SpecError(f"no configuration named {name!r} in BENCHMARK.json")


def load_traffic(root: Path, traffic: str) -> Dict[str, Any]:
    return _json(Path(root) / BENCH_DIR / "traffic" / f"{traffic}.json")


def load_limits(root: Path, cell: str) -> Dict[str, float]:
    return _json(Path(root) / BENCH_DIR / "limits" / f"{cell}.json")["limits"]


def _metric_applies(m: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in m:
        return cell in m["workloads"]
    return m.get("moves", m["name"]) in e2e_names


def end_to_end_metrics(bench: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    """The end-to-end metrics this cell reports (``--trace 0``)."""
    return [m for m in bench["end_to_end"] if "workloads" not in m or cell in m["workloads"]]


def per_layer_metrics(bench: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    """The per-layer metrics this cell reports (``--trace 1``): those that list
    it, and those without a list that move an end-to-end metric it reports."""
    e2e = [m["name"] for m in end_to_end_metrics(bench, cell)]
    return [m for m in bench["per_layer"] if _metric_applies(m, cell, e2e)]


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str) -> Callable[[Dict[str, Any]], Any]:
    mod = _load_module(Path(root) / BENCH_DIR / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_").replace("-", "_"))
    return mod.read


def load_reference(root: Path, name: str):
    """The reference module ``name``; refuses one that lacks an architecture
    hook, before anything is built from it."""
    path = Path(root) / BENCH_DIR / "reference" / f"{name}.py"
    mod = _load_module(path, "bench_reference_" + name.replace(".", "_").replace("-", "_"))
    missing = [h for h in ARCH_HOOKS if not callable(getattr(mod, h, None))]
    if missing:
        raise SpecError(f"{path} lacks the architecture hook(s) {', '.join(missing)}")
    return mod


def load_job(root: Path, kind: str):
    """The generator and window step of a traffic ``kind``:
    ``bench/jobs/<kind>.py``."""
    return _load_module(Path(root) / BENCH_DIR / "jobs" / f"{kind}.py",
                        "bench_job_" + kind.replace(".", "_").replace("-", "_"))
