"""Lane packing of a cohort's ragged curriculum steps.

``curriculum.pack_lanes`` lays each chosen client's run of steps whole into
one of ``L < k`` lanes, and the single-device engine's packed round program
trains those lanes instead of one lane per client padded to the longest.
Only the schedule of lane-steps changes: a packed round must reproduce the
unpacked round's global LoRA, client rows, Adam state and mean loss.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.config import FibecFedConfig, ModelConfig
from repro.core import curriculum as curr
from repro.core import engine as eng
from repro.data import dirichlet_partition, make_keyword_task
from repro.federated import CompressionConfig, make_runner
from repro.models import build_model
from repro.obs import Telemetry, runtime_metrics
from repro.train import make_loss_fn

CFG = ModelConfig(
    name="tiny-lm", family="dense", num_layers=2, d_model=32, num_heads=2,
    num_kv_heads=2, d_ff=64, vocab_size=256, head_dim=16, rope="full",
    norm="rmsnorm", mlp="swiglu", dtype="float32", lora_rank=2, max_seq_len=64,
)
# Dirichlet(0.5) shards of 72 samples over 6 clients at batch 2: cohorts of
# 4 whose step counts differ several-fold, so packing engages
FL = FibecFedConfig(
    num_devices=6, devices_per_round=4, rounds=4, batch_size=2,
    learning_rate=5e-3, fim_warmup_epochs=1, gal_fraction=0.5, sparse_ratio=0.5,
)
T = 10  # whole shards (alpha * rounds = 3.2)
ROUNDS = 3
# whole shards every round: the curriculum fraction stays 1
WHOLE = curr.CurriculumSchedule(strategy="none")


def _check_plan(runs, S, L, plan):
    lane_client, batch_idx, step_valid = plan
    k = len(runs)
    assert lane_client.shape == batch_idx.shape == step_valid.shape == (L, S)
    assert lane_client.dtype == batch_idx.dtype == np.int32
    assert step_valid.dtype == np.float32
    # no two lanes name one row at a step
    for s in range(S):
        assert len(set(lane_client[:, s].tolist())) == L
    for i, run in enumerate(runs):
        lanes, steps = np.nonzero((lane_client == i) & (step_valid > 0))
        # exactly one lane, one contiguous run, the client's steps in order
        assert len(set(lanes.tolist())) == 1
        assert np.array_equal(steps, np.arange(steps[0], steps[0] + len(run)))
        np.testing.assert_array_equal(batch_idx[lanes[0], steps], run)
    for lane in range(L):
        row = lane_client[lane]
        valid = step_valid[lane] > 0
        if not valid.any():  # an empty lane points at its scratch row
            assert np.all(row == k + lane)
            continue
        # after the lane's last client: inactive, on that client's row
        last = np.nonzero(valid)[0][-1]
        assert np.all(row[last:] == row[last])


@pytest.mark.parametrize("seed", range(6))
def test_pack_lanes_places_each_run_whole(seed):
    rng = np.random.default_rng(seed)
    k, S = int(rng.integers(2, 12)), int(2 ** rng.integers(2, 6))
    counts = rng.integers(1, S + 1, size=k)
    runs = [rng.integers(0, 50, size=c).astype(np.int32) for c in counts]
    L = curr.lane_count(WHOLE, counts, k, S)
    plan = curr.pack_lanes(runs, S, L)
    assert plan is not None  # the cohort is the population: it fits
    _check_plan(runs, S, L, plan)
    assert plan[2].sum() == counts.sum()


def test_pack_lanes_returns_none_on_a_misfit():
    runs = [np.arange(5), np.arange(5), np.arange(5)]
    assert curr.pack_lanes(runs, 8, 2) is None  # three runs of 5 in two lanes of 8
    assert curr.pack_lanes([np.arange(9)], 8, 1) is None  # longer than a lane
    _check_plan(runs, 8, 3, curr.pack_lanes(runs, 8, 3))


def test_lane_count_balanced_shards_keep_one_lane_per_client():
    assert curr.lane_count(WHOLE, [6] * 10, 10, 8) == 10
    assert curr.lane_count(WHOLE, [8] * 12, 10, 8) == 10
    # the k largest that fit in S decide, whichever cohort is drawn
    assert curr.lane_count(WHOLE, [15, 3, 7, 8, 15, 5, 3, 9, 6, 12, 10, 8], 10, 16) == 7
    assert curr.lane_count(WHOLE, [20, 4, 4, 4, 4], 2, 8) == 1
    assert curr.lane_count(WHOLE, [4, 4], 2, 8, local_epochs=2) == 2


def test_lane_count_is_the_largest_over_the_ramp():
    """One lane count per scan length for the whole job: the largest any
    round of the curriculum ramp asks, so the ramp never retraces the packed
    program within a step bucket."""
    ramp = curr.CurriculumSchedule(beta=0.6, alpha=0.8, total_rounds=100)
    qwen2 = [15, 3, 7, 8, 15, 5, 3, 9, 6, 12, 10, 8]  # batches per client
    qwen3 = [22, 4, 10, 12, 22, 8, 4, 13, 9, 18, 14, 12]
    # round 0 alone would pack qwen2's 60% shards into 4 lanes of 16, whole
    # shards (round 80 on) need 7
    t0 = [curr.num_selected_batches(ramp, 0, n) for n in qwen2]
    assert curr.lane_count(WHOLE, t0, 10, 16) == 4
    assert curr.lane_count(ramp, qwen2, 10, 16) == 7
    assert curr.lane_count(ramp, qwen2, 10, 8) == 7
    assert curr.lane_count(ramp, qwen3, 10, 32) == 5
    assert curr.lane_count(ramp, qwen3, 10, 16) == 8
    assert curr.lane_count(ramp, qwen3, 10, 1) == 0  # no count fits


def test_pack_lanes_keeps_the_epoch_major_order():
    sched = curr.CurriculumSchedule(total_rounds=10)
    orders = [np.array([3, 1, 0, 2]), np.array([1, 0]), np.array([0])]
    bi, sv = curr.step_plan(sched, 10, orders, local_epochs=2)
    runs = [b[v > 0] for b, v in zip(bi, sv)]
    np.testing.assert_array_equal(runs[0], [3, 1, 0, 2, 3, 1, 0, 2])
    S = bi.shape[1]
    plan = curr.pack_lanes(runs, S, 2)
    _check_plan(runs, S, 2, plan)


@pytest.fixture(scope="module")
def world():
    model = build_model(CFG)
    task = make_keyword_task(n_samples=72, seq_len=12, vocab_size=256, seed=0)
    parts = dirichlet_partition(task.data["label"], FL.num_devices, 0.5, seed=1)
    client_data = [
        {k: v[idx] for k, v in task.data.items() if k != "label"} for idx in parts
    ]
    return model, make_loss_fn(model), client_data


def _runner(world, compression, packed):
    model, loss_fn, client_data = world
    r = make_runner(
        "fibecfed", model, loss_fn, FL, client_data, optimizer="adamw",
        engine="vectorized", seed=7, compression=compression,
    )
    r.init_phase()
    if not packed:  # a lane count no cohort packs into fewer lanes than
        r._lane_count = lambda S: FL.devices_per_round
    return r


def _count(name):
    return runtime_metrics.counter(name).value


@pytest.mark.parametrize(
    "compression", [None, CompressionConfig(mode="int8")], ids=["raw", "int8"]
)
def test_packed_round_matches_unpacked(world, compression):
    packed0, unpacked0 = _count("fl.rounds_packed"), _count("fl.rounds_unpacked")
    r_pack = _runner(world, compression, packed=True)
    h_pack = [r_pack.run_round(T) for _ in range(ROUNDS)]
    assert _count("fl.rounds_packed") - packed0 == ROUNDS
    r_ref = _runner(world, compression, packed=False)
    h_ref = [r_ref.run_round(T) for _ in range(ROUNDS)]
    assert _count("fl.rounds_unpacked") - unpacked0 == ROUNDS

    assert r_pack._stacked_mask is not None and r_pack.gal_layers.any()
    for hp, hr in zip(h_pack, h_ref):
        assert hp["loss"] == pytest.approx(hr["loss"], rel=1e-5)
        assert hp["padded_steps"] == hr["padded_steps"]
    np.testing.assert_array_equal(
        r_pack.last_round_info["client_steps"], r_ref.last_round_info["client_steps"]
    )
    # AdamW's tolerance of tests/test_engine_equivalence.py: vmapping the
    # client step over L instead of k lanes reassociates float32 sums
    close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5
    )
    jax.tree.map(close, r_pack.global_lora, r_ref.global_lora)
    np.testing.assert_array_equal(
        r_pack.last_round_info["chosen"], r_ref.last_round_info["chosen"]
    )
    # every row: the rounds' chosen clients, and the others left as they were
    jax.tree.map(close, r_pack._stacked_lora, r_ref._stacked_lora)
    jax.tree.map(close, r_pack._stacked_opt, r_ref._stacked_opt)
    np.testing.assert_array_equal(
        np.asarray(r_pack._stacked_opt["t"]), np.asarray(r_ref._stacked_opt["t"])
    )


def test_a_cohort_that_does_not_fit_runs_unpacked(world):
    r = _runner(world, None, packed=True)
    hist = runtime_metrics.histogram("fl.round_scanned_steps")
    packed0, unpacked0 = _count("fl.rounds_packed"), _count("fl.rounds_unpacked")
    st = r.run_round(T)
    L = r._lane_count(int(st["padded_steps"]))
    assert L < FL.devices_per_round
    assert _count("fl.rounds_packed") - packed0 == 1
    assert hist.recent[-1] == L * st["padded_steps"]
    # one lane is too few for this cohort: the round takes the unpacked program
    r._lane_count = lambda S: 1
    st = r.run_round(T)
    assert _count("fl.rounds_packed") - packed0 == 1
    assert _count("fl.rounds_unpacked") - unpacked0 == 1
    assert hist.recent[-1] == FL.devices_per_round * st["padded_steps"]
    assert np.isfinite(st["loss"])


def test_a_ramp_compiles_one_packed_program_per_bucket(world):
    """Across the curriculum ramp the cohort's step counts change every few
    rounds, but the lane count is the bucket's: the packed program holds one
    trace per scan length (full participation, whose per-round lane counts
    would run 3, 4 and 5 at scan length 8)."""
    _, _, client_data = world
    fl = dataclasses.replace(FL, devices_per_round=FL.num_devices, rounds=T)
    tel = Telemetry()
    # a model of its own: programs are memoized per loss function, so this
    # packed program holds no other test's traces
    model = build_model(CFG)
    r = make_runner(
        "fibecfed", model, make_loss_fn(model), fl, client_data, optimizer="adamw",
        engine="vectorized", seed=7, telemetry=tel,
    )
    r.init_phase()
    packed0 = _count("fl.rounds_packed")
    buckets = {int(r.run_round(t)["padded_steps"]) for t in range(T + 1)}
    assert buckets == {8, 16}
    assert _count("fl.rounds_packed") - packed0 == T + 1
    traces = eng.trace_cache_size(r._packed_round_fn())
    assert traces == len(buckets)
    assert tel.metrics.gauge("jit.packed_round_fn_traces").value == traces
