"""The benchmark harness on the CPU: the yardstick's arithmetic, the trace
reduction, discovery by name, and the refusals.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.lib import peaks, spec, xplane  # noqa: E402

TESTDATA = REPO / "bench" / "testdata"


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


def _dense():
    return spec.load_reference(REPO, "dense_decoder")


# -- required operations ----------------------------------------------------------


def test_required_flops_qwen2_by_hand():
    # per layer: q 896x896, k and v 896x128, o 896x896, gate/up/down 3x896x4864
    base = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert base == 14_909_440
    lora = 8 * (896 + 896) + 2 * 8 * (896 + 128) + 8 * (896 + 896)
    assert lora == 45_056
    attn = 2 * 2 * 14 * 64 * (128 * 129 // 2)  # causal: query i sees i + 1 keys
    layer = 2 * (2 * 128 * base) + 3 * (2 * 128 * lora) + 3 * attn
    head = 2 * (2 * 896 * 151936)  # one loss position, forward + input gradient
    want = 24 * layer + head
    assert want == 186_712_653_824
    dense = _dense()
    got = dense.lora_train_flops(dense.sizes(_config("qwen2-0.5b")), 128, loss_positions=1)
    assert got == pytest.approx(want, rel=1e-12)
    # per token, about 1.46 GFLOP
    assert got / 128 == pytest.approx(1.4587e9, rel=1e-4)


def test_forward_flops_counts_the_head_where_asked():
    dense = _dense()
    s = dense.sizes(_config("qwen3-0.6b"))
    assert s["head_dim"] == 128 and s["kv_heads"] == 8
    all_pos = dense.forward_flops(s, 128, head_positions=128)
    last = dense.forward_flops(s, 128, head_positions=1)
    assert all_pos - last == pytest.approx(2 * 127 * 1024 * 151936)


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# -- trace reduction --------------------------------------------------------------


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.measure([(0, 2), (1, 3)]) == 3
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_self_times_and_exposed_collectives():
    tr = xplane.Trace()
    tr.ops["/device:TPU:0"] = [
        (0, 100, "%while.1 = (...) while(...)"),
        (10, 30, "%fusion.2 = f32[] fusion(...)"),
        (30, 50, "%all-reduce.3 = f32[] all-reduce(...)"),
        (50, 70, "%fusion.4 = f32[] fusion(...)"),
        (120, 150, "%fusion.5 = f32[] fusion(...)"),
    ]
    # an asynchronous all-gather in flight from 60 to 90, under fusion.4 until 70
    tr.async_ops["/device:TPU:0"] = [(60, 90, "%all-gather-start.6 = (...) all-gather-start(...)"),
                                     (0, 5, "%copy-start.7 = (...) copy-start(...)")]
    tr.modules["/device:TPU:0"] = [(0, 100, "jit_round_fn(1)"), (120, 150, "jit_other(2)")]
    r = xplane.reduce(tr, (0, 200))
    assert r["busy_s"] == pytest.approx(130e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["modules_s"] == {"jit_round_fn": pytest.approx(100e-9), "jit_other": pytest.approx(30e-9)}
    ops = dict(r["device_ops"])
    assert ops["while.1"] == pytest.approx(40e-9)  # 100 less its three children
    # 30-50 all-reduce, 70-90 the all-gather no compute covers
    assert r["exposed_collective_s"] == pytest.approx(40e-9)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(70e-9)


def _recorded():
    path = TESTDATA / "round_tiny.xplane.pb"
    if not path.is_file():
        pytest.fail(f"missing {path}; record it with bench/tests/record_trace.py on a TPU")
    return path


def test_reducer_on_a_recorded_tpu_trace():
    import jax

    path = _recorded()
    assert path.stat().st_size < 1_000_000
    pd = jax.profiler.ProfileData.from_file(str(path))
    tr = xplane.Trace.from_profile(pd)
    window = tr.annotation("bench.window")
    assert window is not None
    r = xplane.reduce(tr, window)
    assert r["chips"] == 1
    # two rounds, each one run of the round program
    assert r["module_runs"].get("jit_round_fn") == 2
    # busy time, counted here independently: the union of every op's
    # interval inside the window, by a sweep over sorted boundaries
    ops = [(max(s, window[0]), min(e, window[1])) for s, e, _ in tr.ops["/device:TPU:0"]
           if e > window[0] and s < window[1]]
    edges = sorted({x for iv in ops for x in iv})
    covered = sum(b - a for a, b in zip(edges, edges[1:])
                  if any(s <= a and b <= e for s, e in ops))
    assert r["busy_s"] == pytest.approx(covered * 1e-9, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # self times add up to the busy time (events nest), idle to the rest
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["modules_s"]["jit_round_fn"] <= r["window_s"]


# -- discovery by name ------------------------------------------------------------


def test_a_cell_config_traffic_and_metric_added_as_files_are_found(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "limits").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "dummy-1b.json").write_text('{"name": "dummy-1b", "hidden_size": 7}')
    (tmp_path / "bench" / "traffic" / "dummy-mix.json").write_text('{"kind": "rounds", "batch_size": 5}')
    (tmp_path / "bench" / "limits" / "dummy-1b.mix.json").write_text('{"limits": {"x_gap": 0.5}}')
    (tmp_path / "bench" / "metrics" / "dummy_share.mix.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx['steps'] else None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "dummy-1b", "file": "bench/configs/dummy-1b.json"}],
        "workloads": [{"name": "dummy-1b.mix", "config": "dummy-1b", "traffic": "dummy-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}, {"name": "rate", "workloads": ["dummy-1b.mix"]},
                       {"name": "other_rate", "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "dummy_share.mix", "moves": "rate", "workloads": ["dummy-1b.mix"]},
                      {"name": "everywhere", "moves": "setup_s"},
                      {"name": "not_here", "moves": "other_rate"}],
    }))
    bench = spec.load_benchmark(tmp_path)
    cell = spec.find_cell(bench, "dummy-1b.mix")
    assert spec.load_config(tmp_path, bench, cell["config"])["hidden_size"] == 7
    assert spec.load_traffic(tmp_path, cell["traffic"])["batch_size"] == 5
    assert spec.load_limits(tmp_path, cell["name"]) == {"x_gap": 0.5}
    assert [m["name"] for m in spec.end_to_end_metrics(bench, cell["name"])] == ["setup_s", "rate"]
    assert [m["name"] for m in spec.per_layer_metrics(bench, cell["name"])] == [
        "dummy_share.mix", "everywhere"]
    read = spec.load_reader(tmp_path, "dummy_share.mix")
    assert read({"steps": [1]}) == 42.0 and read({"steps": []}) is None
    with pytest.raises(spec.SpecError):
        spec.find_cell(bench, "absent")
    with pytest.raises(spec.SpecError):
        spec.load_reader(tmp_path, "absent")


def test_every_declared_cell_and_metric_has_its_files():
    bench = spec.load_benchmark(REPO)
    for cell in bench["workloads"]:
        config = spec.load_config(REPO, bench, cell["config"])
        traffic = spec.load_traffic(REPO, cell["traffic"])
        assert spec.load_limits(REPO, cell["name"])
        assert (REPO / "bench" / "jobs" / f"{traffic['kind']}.py").is_file()
        assert (REPO / "bench" / "reference" / f"{config['reference']}.py").is_file()
        for m in spec.per_layer_metrics(bench, cell["name"]):
            assert callable(spec.load_reader(REPO, m["name"]))


# -- refusals -----------------------------------------------------------------------


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.rounds", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_fails_without_a_tpu():
    p = _run_cli(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
