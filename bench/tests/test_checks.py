"""The check that decides ``correct``, run whole at a test's size on the CPU.

A sound run of a tiny cell (``tiny.py``) comes out correct. With the
timed path broken underneath the same run comes out not correct: in the
rounds, the round returning its state unchanged, half of every batch left
out of the loss; in the init phase, half of every batch left out of the
difficulty scores, a curriculum order altered where it is made, the
sensitivity probe without its perturbation. So does the control: the
reference computed with float8 operands, put in the program's place. The
limits are the tiny cells' own (``tiny.TINY_ROUNDS_LIMITS`` and
``tiny.TINY_INIT_LIMITS``, set from CPU readings at this size).

    PYTHONPATH=src python -m pytest -q bench/tests/test_checks.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import run  # noqa: E402
from bench.lib import compare, spec  # noqa: E402
from bench.tests import tiny  # noqa: E402

ARGS = ["--workload", tiny.CELL, "--seed", "3000000019", "--seconds", "1", "--trace", "0"]


def _result(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def test_a_sound_run_is_correct(root, capsys):
    assert run.main(ARGS, root=root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"compiles_in_window", "loss_gap", "moment_gap", "update_gap"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_a_round_that_returns_its_state_unchanged_is_caught(root, capsys, monkeypatch):
    from repro.optim import optimizers

    monkeypatch.setattr(optimizers, "adamw_update",
                        lambda grads, state, params, lr, mask=None, active=None, **_: (params, state))
    assert run.main(ARGS, root=root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is False
    assert res["checks"]["update_gap"]["value"] > res["checks"]["update_gap"]["limit"]


def test_half_of_each_batch_left_out_is_caught(root, capsys, monkeypatch):
    from repro.core import engine

    native = engine._masked_loss

    def half(loss_fn):
        masked = native(loss_fn)

        def fn(params, lora, batch, sv):
            B = sv.shape[-1]
            return masked(params, lora, batch, sv.at[..., B - B // 2:].set(0.0))

        return fn

    monkeypatch.setattr(engine, "_masked_loss", half)
    assert run.main(ARGS, root=root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is False, res["checks"]


def test_the_float8_control_is_not_correct(root):
    sys.path.insert(0, str(REPO / "src"))
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, tiny.CELL)
    config = spec.load_config(root, bench, cell["config"])
    traffic = spec.load_traffic(root, cell["traffic"])
    job = spec.load_job(root, traffic["kind"]).Job(
        config=config, traffic=traffic, seed=3000000019,
        reference=spec.load_reference(root, config["reference"]), chips=1)
    job.setup()
    job.release()
    want = job.reference()
    ok, checks = compare.verdict(job.numbers(job.reference(mode="fp8"), want),
                                 spec.load_limits(root, tiny.CELL))
    assert not ok, checks
    ok, checks = compare.verdict(job.numbers(job.program_outputs(), want),
                                 spec.load_limits(root, tiny.CELL))
    assert ok, checks


# -- the init cell ------------------------------------------------------------------

INIT_ARGS = ["--workload", "tiny.init", "--seed", "3000000019", "--seconds", "1", "--trace", "0"]


@pytest.fixture
def init_root(tmp_path):
    return tiny.make_root(tmp_path, kind="init")


def test_a_sound_init_run_is_correct(init_root, capsys):
    assert run.main(INIT_ARGS, root=init_root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["init_phase_s"]["value"] > 0


def test_half_of_each_batch_left_out_of_the_difficulty_scores_is_caught(init_root, capsys, monkeypatch):
    from repro.core import fisher

    native = fisher.batch_fisher_scores

    def half(loss_fn, params, lora, batches, sample_mask=None):
        B = sample_mask.shape[-1]
        return native(loss_fn, params, lora, batches, sample_mask.at[..., B - B // 2:].set(0.0))

    monkeypatch.setattr(fisher, "batch_fisher_scores", half)
    assert run.main(INIT_ARGS, root=init_root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is False
    assert res["checks"]["difficulty_gap"]["value"] > res["checks"]["difficulty_gap"]["limit"]


def test_a_sensitivity_probe_without_its_perturbation_is_caught(init_root, capsys, monkeypatch):
    from repro.core import gal

    monkeypatch.setattr(gal, "adversarial_perturbation", lambda g, gamma, p=2.0: 0.0 * g)
    assert run.main(INIT_ARGS, root=init_root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is False
    assert res["checks"]["sensitivity_gap"]["value"] > res["checks"]["sensitivity_gap"]["limit"]


def test_a_curriculum_order_altered_where_it_is_made_is_caught(init_root, capsys, monkeypatch):
    from repro.core import curriculum

    native = curriculum.order_batches
    monkeypatch.setattr(curriculum, "order_batches", lambda *a, **k: native(*a, **k)[::-1])
    assert run.main(INIT_ARGS, root=init_root, require_tpu=False) == 0
    res = _result(capsys)
    assert res["correct"] is False
    assert res["checks"]["order_mismatch"]["value"] > 0


def test_the_float8_control_of_the_init_phase_is_not_correct(init_root):
    sys.path.insert(0, str(REPO / "src"))
    bench = spec.load_benchmark(init_root)
    cell = spec.find_cell(bench, "tiny.init")
    config = spec.load_config(init_root, bench, cell["config"])
    traffic = spec.load_traffic(init_root, cell["traffic"])
    job = spec.load_job(init_root, traffic["kind"]).Job(
        config=config, traffic=traffic, seed=3000000019,
        reference=spec.load_reference(init_root, config["reference"]), chips=1)
    job.setup()
    job.release()
    limits = spec.load_limits(init_root, "tiny.init")
    want = job.reference()
    ok, checks = compare.verdict(job.numbers(job.reference(mode="fp8"), want), limits)
    assert not ok, checks
    ok, checks = compare.verdict(job.numbers(job.program_outputs(), want), limits)
    assert ok, checks
