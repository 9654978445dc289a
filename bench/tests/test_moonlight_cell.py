"""The Moonlight-16B-A3B configuration in the harness: its reference module's
hooks (sizes, bitwise-reproducible weights, operation counts by hand),
``expert_padding_share`` on hand-made counts, and a whole run of a tiny
Moonlight cell on the CPU.

    PYTHONPATH=src python -m pytest -q bench/tests/test_moonlight_cell.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import run  # noqa: E402
from bench.lib import spec  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tests.test_arch_hooks import _digest  # noqa: E402

SEED = 3000000019


def _ref():
    return spec.load_reference(REPO, "moonlight_16b_a3b")


def _config():
    return json.loads((REPO / "bench" / "configs" / "moonlight-16b-a3b.json").read_text())


def _tiny_config():
    """Every width cut to a CPU test's size; 16 experts, 2 held, top-4."""
    config = _config()
    config.update(name="tiny", hidden_size=64, intermediate_size=96, num_attention_heads=4,
                  num_key_value_heads=4, num_hidden_layers=3, vocab_size=512, kv_lora_rank=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, moe_intermediate_size=32,
                  n_routed_experts=2, num_experts_per_tok=4)
    config["deployment"] = dict(config["deployment"], n_routed_experts=16)
    run_as = config["run_as"]
    run_as["mla"] = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    run_as["moe"] = dict(run_as["moe"], num_experts=16, held_experts=2, top_k=4, d_ff_expert=32,
                         d_ff_shared=64)
    return config


def test_the_sizes_are_the_chips_share():
    s = _ref().sizes(_config())
    assert (s["experts"], s["held"], s["top_k"]) == (64, 8, 6)
    assert (s["layers"], s["dense_layers"], s["d_model"], s["vocab"]) == (27, 1, 2048, 163840)
    assert (s["d_expert"], s["d_shared"], s["d_ff"], s["kv_rank"]) == (1408, 2816, 11264, 512)


def test_the_published_counts_by_hand():
    ref = _ref()
    s = ref.sizes(_config())
    mla = 2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256 + 16 * 128 * 2048
    assert mla == 13_762_560
    dense = mla + 3 * 2048 * 11264
    assert dense == 82_968_576  # layer 0: 83.0 M
    # router, shared experts, and 6 x 8 / 64 = 0.75 of a held expert per token
    expert = mla + 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    assert expert == 37_683_200.0  # 37.7 M
    assert dense + 26 * expert == pytest.approx(1.0627e9, rel=1e-4)  # 1.06 B, not 2.32 B
    lora = 8 * ((2048 + 3072) + (2048 + 576) + (512 + 4096) + (2048 + 2048))
    attn = 2 * 16 * (192 + 128) * (128 * 129 // 2)
    layer = lambda w: 2 * (2 * 128 * w) + 3 * (2 * 128 * lora) + 3 * attn  # noqa: E731
    head = 2 * (2 * 2048 * 163840)
    want = layer(dense) + 26 * layer(expert) + head
    assert ref.lora_train_flops(s, 128, loss_positions=1) == pytest.approx(want, rel=1e-12)
    assert want / 128 == pytest.approx(4.31e9, rel=1e-2)  # about 4.3 GFLOP per trained token
    fwd = dense + 26 * expert + 27 * lora
    assert ref.forward_flops(s, 128, 1) == pytest.approx(
        2 * 128 * fwd + 27 * attn + 2 * 2048 * 163840, rel=1e-12)
    assert ref.input_grad_flops(s, 128) == pytest.approx(
        layer(dense) + 26 * layer(expert) - 27 * 2 * 128 * lora + head, rel=1e-12)


def test_the_tiny_counts_by_hand():
    ref = _ref()
    s = ref.sizes(_tiny_config())
    mla = 64 * 4 * 12 + 64 * (16 + 4) + 16 * 4 * 16 + 4 * 8 * 64
    dense, expert = mla + 3 * 64 * 96, mla + 64 * 16 + 3 * 64 * 64 + 4 * 2 / 16 * 3 * 64 * 32
    lora = 8 * ((64 + 48) + (64 + 20) + (16 + 64) + (32 + 64))
    attn = 2 * 4 * (12 + 8) * (16 * 17 // 2)
    fwd = dense + 2 * expert + 3 * lora
    assert ref.forward_flops(s, 16, 2) == 2 * 16 * fwd + 3 * attn + 2 * 2 * 64 * 512


def test_the_weights_are_bitwise_reproducible():
    ref, config = _ref(), _tiny_config()
    s = ref.sizes(config)
    a = ref.make_weights(SEED, s, config["run_as"])
    b = ref.make_weights(SEED, s, config["run_as"])
    c = ref.make_weights(SEED + 1, s, config["run_as"])
    assert _digest(a[0]) == _digest(b[0]) and _digest(a[1]) == _digest(b[1])
    assert _digest(a[0]) != _digest(c[0])
    params = a[0]
    assert str(params["layers"]["router_bias"].dtype) == "float32"
    assert str(params["layers"]["wq"].dtype) == "bfloat16"


class _Registry:
    def __init__(self, held, rows):
        self.values = {"fl.moe_held_assignments": held, "fl.moe_expert_rows": rows}

    def histogram(self, name):
        from repro.obs import Histogram

        h = Histogram()
        for v in self.values[name] or []:
            h.observe(v)
        return h


def test_expert_padding_share_reads_the_window_rounds(monkeypatch):
    import repro.obs

    read = spec.load_reader(REPO, "expert_padding_share")
    ctx = {"steps": [{"real_steps": 50}, {"real_steps": 52}]}
    # a set-up round, then two window rounds
    monkeypatch.setattr(repro.obs, "runtime_metrics",
                        _Registry([10.0, 900.0, 1000.0], [100.0, 8000.0, 8000.0]))
    assert read(ctx) == pytest.approx(100.0 * (1.0 - 1900.0 / 16000.0))
    for held, rows in (([], []), ([900.0], [8000.0]), ([1.0, 2.0], [0.0, 0.0])):
        monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry(held, rows))
        assert read(ctx) is None  # a dense program, too few rounds, nothing computed
    assert read({"steps": [{"loss": 1.0}]}) is None


def test_the_routed_job_reads_errors_that_a_norm_lets_cancel():
    """``rounds_routed`` compares the first moments by the norm of their
    difference too: moments of the right norms and the wrong signs read 0
    by ``moment_gap`` and 2 (the error's norm over the moments') by
    ``moment_diff``."""
    import numpy as np

    job = object.__new__(spec.load_job(REPO, "rounds_routed").Job)
    rng = np.random.default_rng(0)
    job.lora0 = {"a": np.zeros((4, 3, 2)), "b": np.zeros((4, 2, 5))}
    job.plan = {"gal": np.array([True, True, False, True])}
    m1 = {k: rng.normal(size=(3,) + v.shape) for k, v in job.lora0.items()}
    glob = {k: rng.normal(size=v.shape) for k, v in job.lora0.items()}
    want = {"loss": [2.0, 1.0, 0.5], "m1": m1, "global": [glob] * 3}
    got = dict(want, m1={k: -v for k, v in m1.items()})
    n = job.numbers(got, want)
    assert n["moment_gap"] == 0.0 and n["moment_diff"] == pytest.approx(2.0)
    assert n["first_loss_gap"] == 0.0 and n["update_gap"] == 0.0


# The tiny Moonlight cell's limits for the cell's job (rounds_routed), from
# CPU readings over 8 seeds at this size: sound at most first_loss_gap
# 1.1e-3, moment_gap 1.5e-2, moment_diff 5.0e-2, update_gap 2.0e-2; the
# float8 control at least moment_diff 0.30 (its other numbers overlap the
# sound ones); half of each batch left out at least 7.8e-3, 0.21, 0.67, 3.7e-2.
TINY_LIMITS = {"first_loss_gap": 3e-3, "moment_gap": 0.05, "moment_diff": 0.15,
               "update_gap": 0.03}


@pytest.fixture
def root(tmp_path):
    root = tiny.make_root(tmp_path, base="moonlight-16b-a3b", limits=TINY_LIMITS)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(_tiny_config()))
    # the Moonlight cell's job, in place of the qwen cell's that make_root borrows
    traffic = root / "bench" / "traffic" / "tiny.json"
    kind = json.loads((REPO / "bench" / "traffic" / "fl-k12-b2-moonlight.json").read_text())["kind"]
    traffic.write_text(json.dumps(dict(json.loads(traffic.read_text()), kind=kind)))
    return root


def test_a_sound_tiny_moonlight_run_is_correct(root, capsys):
    args = ["--workload", tiny.CELL, "--seed", str(SEED), "--seconds", "1", "--trace", "0"]
    assert run.main(args, root=root, require_tpu=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    from repro.obs import runtime_metrics

    held = runtime_metrics.histogram("fl.moe_held_assignments").recent
    rows = runtime_metrics.histogram("fl.moe_expert_rows").recent
    assert len(held) >= 4 and all(0 < h < r for h, r in zip(held, rows))


def test_the_float8_control_of_the_tiny_moonlight_cell_is_not_correct(root):
    from bench.lib import compare

    sys.path.insert(0, str(REPO / "src"))
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, tiny.CELL)
    config = spec.load_config(root, bench, cell["config"])
    traffic = spec.load_traffic(root, cell["traffic"])
    job = spec.load_job(root, traffic["kind"]).Job(
        config=config, traffic=traffic, seed=SEED,
        reference=spec.load_reference(root, config["reference"]), chips=1)
    job.setup()
    job.release()
    want = job.reference()
    ok, checks = compare.verdict(job.numbers(job.reference(mode="fp8"), want), TINY_LIMITS)
    assert not ok, checks
    ok, checks = compare.verdict(job.numbers(job.program_outputs(), want), TINY_LIMITS)
    assert ok, checks
