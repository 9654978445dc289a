"""The vectorized round engine must reproduce the loop engine exactly.

The loop engine (one jitted call per (client, batch) step, host-side FedAvg)
is the semantic spec of Algorithm 1; the vectorized engine (stacked client
pytrees, scan-over-batches inside vmap-over-clients, fused aggregation) is
the fast path, and the sharded engine is the vectorized program with the
client axis sharded over a device mesh (stack and cohort padded to the
mesh's client-group count). Same seeds => same client sampling, same
curriculum orders, same update sequence — global LoRA trees, per-round
losses, and comm-bytes accounting must agree to float tolerance across full
init+tuning runs, on every mesh size (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to cover the
multi-device cases; CI's tier1-multidevice job does).
"""
import jax
import numpy as np
import pytest

from repro.config import FibecFedConfig, ModelConfig
from repro.data import dirichlet_partition, make_keyword_task
from repro.data.pipeline import stack_clients
from repro.federated import make_runner
from repro.launch.mesh import make_client_mesh
from repro.models import build_model
from repro.train import make_loss_fn

CFG = ModelConfig(
    name="tiny-lm", family="dense", num_layers=2, d_model=32, num_heads=2,
    num_kv_heads=2, d_ff=64, vocab_size=256, head_dim=16, rope="full",
    norm="rmsnorm", mlp="swiglu", dtype="float32", lora_rank=2, max_seq_len=64,
)
# 50 samples over 4 clients with batch 4 => ragged final batches on every
# client, so the padded fixed-shape path is exercised, not just the easy case
FL = FibecFedConfig(
    num_devices=4, devices_per_round=2, rounds=4, batch_size=4,
    learning_rate=5e-3, fim_warmup_epochs=1, gal_fraction=0.5, sparse_ratio=0.5,
)
ROUNDS = 2


@pytest.fixture(scope="module")
def world():
    model = build_model(CFG)
    task = make_keyword_task(n_samples=50, seq_len=12, vocab_size=256, seed=0)
    parts = dirichlet_partition(task.data["label"], FL.num_devices, 1.0, seed=0)
    client_data = [
        {k: v[idx] for k, v in task.data.items() if k != "label"} for idx in parts
    ]
    return model, make_loss_fn(model), client_data


# SGD in this world runs on an amplified float32 noise floor. Round 1 ends
# on a one-sample batch whose gradient norm is ~230 at lr 5e-3, so rounding
# differences grow by three to four orders of magnitude: nudging the frozen
# base weights by one ulp (6e-8 relative) moved the vectorized engine's
# global LoRA by 1.5e-4 to 6.4e-4 of each leaf's largest |value| (five
# nudges). Against a float64 referee both f32 engines sit in that band, and
# they differ from each other by 4.2e-4: reassociation, not drift. SGD
# comparisons therefore take an absolute tolerance of 2e-3 of the leaf's
# scale. AdamW normalizes each step, agrees to the 1e-6 level and keeps the
# tight tolerances.
SGD_LEAF_TOL = 2e-3


def _assert_lora_close(a, b, optimizer):
    a, b = np.asarray(a), np.asarray(b)
    if optimizer == "sgd":
        scale = max(float(np.max(np.abs(b))), 1e-30)
        np.testing.assert_allclose(a, b, atol=SGD_LEAF_TOL * scale, rtol=0)
    else:
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def _run(world, baseline, optimizer, engine, fused=False):
    model, loss_fn, client_data = world
    runner = make_runner(
        baseline, model, loss_fn, FL, client_data,
        optimizer=optimizer, fused_optimizer=fused, engine=engine, seed=7,
    )
    runner.init_phase()
    history = [runner.run_round(t) for t in range(ROUNDS)]
    return runner, history


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize(
    "baseline,optimizer",
    [("fibecfed", "adamw"), ("fedavg_lora", "sgd")],
)
def test_engines_equivalent(world, baseline, optimizer, fused):
    r_loop, h_loop = _run(world, baseline, optimizer, "loop", fused)
    r_vec, h_vec = _run(world, baseline, optimizer, "vectorized", fused)

    # same curriculum decisions
    for cl, cv in zip(r_loop.clients, r_vec.clients):
        np.testing.assert_array_equal(cl.order, cv.order)
    np.testing.assert_array_equal(r_loop.gal_layers, r_vec.gal_layers)

    # per-round losses and exact comm accounting
    for hl, hv in zip(h_loop, h_vec):
        assert hl["loss"] == pytest.approx(hv["loss"], rel=1e-4, abs=1e-5)
        assert hl["selected_batches"] == hv["selected_batches"]
    assert r_loop.comm_bytes_per_round == r_vec.comm_bytes_per_round

    # allclose global LoRA trees
    gl, gv = jax.tree.leaves(r_loop.global_lora), jax.tree.leaves(r_vec.global_lora)
    assert len(gl) == len(gv)
    for a, b in zip(gl, gv):
        _assert_lora_close(a, b, optimizer)

    # participating clients' host-side LoRA views track the stacked state
    for cl, cv in zip(r_loop.clients, r_vec.clients):
        for a, b in zip(jax.tree.leaves(cl.lora), jax.tree.leaves(cv.lora)):
            _assert_lora_close(a, b, optimizer)


def test_forced_kernel_round_matches_unfused(world):
    """fused_optimizer="force" pins the Pallas masked-update kernel path on
    every leaf (this world's tiny LoRA leaves would otherwise all take the
    sub-tile oracle fallback), so a full init+tuning run exercises the
    batched kernel inside the round program's vmap-over-clients + scan — and
    must still reproduce the unfused vectorized engine."""
    r_unf, h_unf = _run(world, "fibecfed", "adamw", "vectorized", False)
    r_krn, h_krn = _run(world, "fibecfed", "adamw", "vectorized", "force")
    for hu, hk in zip(h_unf, h_krn):
        assert hu["loss"] == pytest.approx(hk["loss"], rel=1e-4, abs=1e-5)
    for a, b in zip(
        jax.tree.leaves(r_unf.global_lora), jax.tree.leaves(r_krn.global_lora)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)


def test_reinit_after_donated_round(world):
    """Re-running init_phase after a round must (a) not touch the donated
    global_lora buffers and (b) re-score difficulty with each client's own
    trained LoRA — staying equivalent to the loop engine across the cycle."""
    model, loss_fn, client_data = world
    runners = {}
    for engine in ("loop", "vectorized"):
        r = make_runner(
            "fibecfed", model, loss_fn, FL, client_data, engine=engine, seed=5
        )
        r.init_phase()
        r.run_round(0)
        r.init_phase()
        stats = r.run_round(1)
        assert np.isfinite(stats["loss"])
        runners[engine] = (r, stats)
    r_loop, s_loop = runners["loop"]
    r_vec, s_vec = runners["vectorized"]
    for cl, cv in zip(r_loop.clients, r_vec.clients):
        # re-scored after an SGD round, so the LoRA being scored carries the
        # amplified f32 noise floor described at SGD_LEAF_TOL: against a
        # float64 referee the loop engine's scores are off by up to 1.4e-4
        # and the vectorized engine's by up to 2.8e-4 (4.2e-4 apart)
        np.testing.assert_allclose(cl.difficulty, cv.difficulty, rtol=2e-3)
        np.testing.assert_array_equal(cl.order, cv.order)
    assert s_loop["loss"] == pytest.approx(s_vec["loss"], rel=1e-4, abs=1e-5)


def test_unknown_engine_rejected(world):
    model, loss_fn, client_data = world
    with pytest.raises(ValueError):
        make_runner("fibecfed", model, loss_fn, FL, client_data, engine="turbo")


# --------------------------------------------------------------------------
# async engine (event-driven buffered aggregation)
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "baseline,optimizer,fused",
    [("fibecfed", "adamw", False), ("fedavg_lora", "sgd", False),
     ("fibecfed", "adamw", True)],
)
def test_async_equivalent_to_loop(world, baseline, optimizer, fused):
    """The degenerate async configuration IS synchronous FedAvg: homogeneous
    scenario (staleness 0, no dropout) with buffer size = cohort size must
    reproduce the loop engine — allclose LoRA trees and losses, identical
    comm accounting attributed per completion event."""
    r_loop, h_loop = _run(world, baseline, optimizer, "loop", fused)
    r_async, h_async = _run(world, baseline, optimizer, "async", fused)

    for cl, ca in zip(r_loop.clients, r_async.clients):
        np.testing.assert_array_equal(cl.order, ca.order)
    np.testing.assert_array_equal(r_loop.gal_layers, r_async.gal_layers)

    for hl, ha in zip(h_loop, h_async):
        assert hl["loss"] == pytest.approx(ha["loss"], rel=1e-4, abs=1e-5)
        assert hl["selected_batches"] == ha["selected_batches"]
        assert ha["staleness_mean"] == 0.0
        assert ha["dropped_clients"] == 0.0
    assert r_loop.comm_bytes_per_round == r_async.comm_bytes_per_round

    gl = jax.tree.leaves(r_loop.global_lora)
    ga = jax.tree.leaves(r_async.global_lora)
    assert len(gl) == len(ga)
    for a, b in zip(gl, ga):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)

    for cl, ca in zip(r_loop.clients, r_async.clients):
        for a, b in zip(jax.tree.leaves(cl.lora), jax.tree.leaves(ca.lora)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4
            )

    # the double buffer really retired the previous global version
    assert r_async._global.version == ROUNDS
    assert r_async._global.back is not None


def test_async_delta_merge_equivalent_to_loop(world):
    """merge_mode="delta" at server_lr=1 under the homogeneous scenario
    (staleness 0, full-cohort buffer) must coincide exactly with the
    buffered value merge — and hence with the loop engine. This is the
    delta-path equivalence contract: global += sum(w_i * (c_i - g)) with
    weights summing to 1 IS the weighted FedAvg."""
    from repro.federated import AsyncAggConfig

    model, loss_fn, client_data = world
    r_loop, h_loop = _run(world, "fibecfed", "adamw", "loop")
    r_delta = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", seed=7,
        async_cfg=AsyncAggConfig(merge_mode="delta", server_lr=1.0),
    )
    r_delta.init_phase()
    h_delta = [r_delta.run_round(t) for t in range(ROUNDS)]

    for hl, hd in zip(h_loop, h_delta):
        assert hl["loss"] == pytest.approx(hd["loss"], rel=1e-4, abs=1e-5)
        assert hd["staleness_mean"] == 0.0
    assert r_loop.comm_bytes_per_round == r_delta.comm_bytes_per_round
    for a, b in zip(
        jax.tree.leaves(r_loop.global_lora), jax.tree.leaves(r_delta.global_lora)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)


def test_async_adaptive_policies_inert_when_degenerate(world):
    """Adaptive knobs that are structurally inert in the homogeneous world —
    step adaptation (rel_speed 1 everywhere), buffer adaptation (no drops),
    and a staleness cutoff nothing exceeds — must leave the async engine
    bit-identical in behavior to its default configuration, i.e. still
    allclose to the loop engine."""
    from repro.federated import AsyncAggConfig

    model, loss_fn, client_data = world
    r_loop, h_loop = _run(world, "fibecfed", "adamw", "loop")
    r_ada = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", seed=7,
        async_cfg=AsyncAggConfig(
            adapt_steps=True, adapt_buffer=True, staleness_cutoff=0
        ),
    )
    r_ada.init_phase()
    h_ada = [r_ada.run_round(t) for t in range(ROUNDS)]
    for hl, ha in zip(h_loop, h_ada):
        assert hl["loss"] == pytest.approx(ha["loss"], rel=1e-4, abs=1e-5)
        assert hl["selected_batches"] == ha["selected_batches"]
        assert ha["stale_dropped"] == 0.0
    assert r_loop.comm_bytes_per_round == r_ada.comm_bytes_per_round
    for a, b in zip(
        jax.tree.leaves(r_loop.global_lora), jax.tree.leaves(r_ada.global_lora)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)


def test_async_adaptive_policies_straggler_run(world):
    """All adaptive policies at once under speed skew: the run stays finite,
    merged staleness respects the cutoff, the buffer stays within bounds,
    and step adaptation really shortens the straggler's local round."""
    from repro.core import curriculum as curr
    from repro.federated import AsyncAggConfig

    model, loss_fn, client_data = world
    cfg = AsyncAggConfig(
        buffer_size=2, merge_mode="delta", server_lr=0.8,
        staleness_cutoff=2, adapt_buffer=True, adapt_steps=True,
        sampling_bias=2.0,
    )
    runner = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", scenario="straggler",
        async_cfg=cfg, seed=7,
    )
    runner.init_phase()
    history = [runner.run_round(t) for t in range(8)]
    for h in history:
        assert np.isfinite(h["loss"])
        assert h["staleness_mean"] <= 2.0  # merged updates respect the cutoff
        assert 1.0 <= h["buffer_size"] <= 2.0

    # the step-adaptation policy really caps the slow client's plan
    sched = runner._scheduler
    plan, _ = runner._async_callbacks(FL.learning_rate, sched)
    slow_ci = int(np.argmax(sched.scenario.speed))
    fast_ci = int(np.argmin(sched.scenario.speed))
    assert sched.scenario.rel_speed(slow_ci) == 4.0
    full = len(
        curr.selected_batch_ids(runner.schedule, 0, runner.clients[slow_ci].order)
    )
    assert plan(slow_ci, 0) == max(1, int(np.ceil(full / 4.0)))
    full_fast = len(
        curr.selected_batch_ids(runner.schedule, 0, runner.clients[fast_ci].order)
    )
    assert plan(fast_ci, 0) == full_fast  # the fastest device is uncapped


def test_async_straggler_scenario_trains(world):
    """Under speed skew + a sub-cohort buffer the async engine merges early
    completions (finite losses, partial cohorts, staleness accrues) and
    never charges comm for clients that have not completed."""
    from repro.federated import AsyncAggConfig

    model, loss_fn, client_data = world
    runner = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", scenario="straggler",
        async_cfg=AsyncAggConfig(buffer_size=1), seed=7,
    )
    runner.init_phase()
    # enough serialized single-completion merges that some update dispatched
    # before an earlier merge is guaranteed to land late (staleness > 0)
    history = [runner.run_round(t) for t in range(10)]
    per_client = runner._gal_bytes_per_client()
    for h in history:
        assert np.isfinite(h["loss"])
        assert h["merged_clients"] == 1.0
        assert h["comm_bytes"] == per_client  # one completion, one round trip
    assert history[-1]["virtual_time"] > history[0]["virtual_time"]
    assert max(h["staleness_mean"] for h in history) > 0.0


def test_scenario_rejected_for_sync_engines(world):
    from repro.federated import AsyncAggConfig

    model, loss_fn, client_data = world
    with pytest.raises(ValueError):
        make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            engine="vectorized", scenario="straggler",
        )
    with pytest.raises(ValueError):
        make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            engine="loop", async_cfg=AsyncAggConfig(buffer_size=1),
        )


# --------------------------------------------------------------------------
# mesh-sharded engine
# --------------------------------------------------------------------------

# 53 samples over 5 clients: C indivisible by every multi-device mesh below,
# so the client-stack padding and the padded cohort (devices_per_round=3 is
# odd too) are exercised, not just the evenly-divisible case
FL5 = FibecFedConfig(
    num_devices=5, devices_per_round=3, rounds=4, batch_size=4,
    learning_rate=5e-3, fim_warmup_epochs=1, gal_fraction=0.5, sparse_ratio=0.5,
)


@pytest.fixture(scope="module")
def world5(world):
    model, loss_fn, _ = world  # share the model => shared compile memos
    task = make_keyword_task(n_samples=53, seq_len=12, vocab_size=256, seed=3)
    parts = dirichlet_partition(task.data["label"], FL5.num_devices, 1.0, seed=3)
    client_data = [
        {k: v[idx] for k, v in task.data.items() if k != "label"} for idx in parts
    ]
    return model, loss_fn, client_data


@pytest.mark.parametrize("n_devices", [1, 2, 8])
def test_sharded_equivalent_to_loop(world5, n_devices):
    """engine="sharded" must replay the loop engine exactly on every mesh
    size: allclose LoRA trees and losses, identical comm accounting."""
    if n_devices > len(jax.devices()):
        pytest.skip(
            f"needs {n_devices} XLA devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    model, loss_fn, client_data = world5
    mesh = make_client_mesh(n_devices)
    runners, history = {}, {}
    for engine, kw in (("loop", {}), ("sharded", {"mesh": mesh})):
        r = make_runner(
            "fibecfed", model, loss_fn, FL5, client_data,
            optimizer="adamw", engine=engine, seed=11, **kw,
        )
        r.init_phase()
        history[engine] = [r.run_round(t) for t in range(ROUNDS)]
        runners[engine] = r
    r_loop, r_sh = runners["loop"], runners["sharded"]

    for hl, hs in zip(history["loop"], history["sharded"]):
        assert hl["loss"] == pytest.approx(hs["loss"], rel=1e-4, abs=1e-5)
        assert hl["selected_batches"] == hs["selected_batches"]
    assert r_loop.comm_bytes_per_round == r_sh.comm_bytes_per_round

    for a, b in zip(
        jax.tree.leaves(r_loop.global_lora), jax.tree.leaves(r_sh.global_lora)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4)
    for cl, cs in zip(r_loop.clients, r_sh.clients):
        for a, b in zip(jax.tree.leaves(cl.lora), jax.tree.leaves(cs.lora)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4
            )

    # the stack really is padded and sharded on multi-device meshes
    C_stack = r_sh._sample_valid.shape[0]
    assert C_stack % n_devices == 0 and C_stack >= FL5.num_devices
    lead = jax.tree.leaves(r_sh._stacked_lora)[0]
    assert lead.sharding.mesh.shape.get("data") == n_devices


@pytest.mark.parametrize("fused", [False, True])
def test_sharded_matches_vectorized_bitwise_on_one_device(world5, fused):
    """On a 1-device mesh the sharded program is the vectorized program (the
    sharding constraints are no-ops), so the histories agree to float32
    determinism — a cheap guard that the shared round body didn't fork."""
    model, loss_fn, client_data = world5
    hist = {}
    for engine, kw in (("vectorized", {}), ("sharded", {"mesh": make_client_mesh(1)})):
        r = make_runner(
            "fibecfed", model, loss_fn, FL5, client_data,
            optimizer="sgd", fused_optimizer=fused, engine=engine, seed=2, **kw,
        )
        r.init_phase()
        hist[engine] = [r.run_round(t)["loss"] for t in range(ROUNDS)]
    assert hist["vectorized"] == pytest.approx(hist["sharded"], rel=1e-6)


def test_mesh_rejected_for_unsharded_engines(world5):
    model, loss_fn, client_data = world5
    with pytest.raises(ValueError):
        make_runner(
            "fibecfed", model, loss_fn, FL5, client_data,
            engine="vectorized", mesh=make_client_mesh(1),
        )


# --------------------------------------------------------------------------
# compressed uploads + resource-adaptive rank
# --------------------------------------------------------------------------


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _assert_close_trees(a, b, boundary_frac: float = 0.0):
    """allclose over trees; ``boundary_frac`` > 0 tolerates that fraction of
    elements violating the tight tolerance (top-k selection is boundary-
    brittle: the engines' deltas differ at float-associativity level, so a
    near-tied k-th magnitude can flip one element in or out — the flipped
    element is still bounded by the discarded-value scale)."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if boundary_frac == 0.0:
            np.testing.assert_allclose(x, y, atol=5e-5, rtol=1e-4)
            continue
        diff = np.abs(x - y)
        bad = diff > (5e-5 + 1e-4 * np.abs(y))
        assert bad.mean() <= boundary_frac, (bad.mean(), diff.max())
        assert diff.max() < 1e-2, diff.max()


def test_compression_none_is_exact_noop(world):
    """mode="none" (and full client_ranks) must route through the untouched
    PR 5 programs — bit-identical global trees, identical comm ints."""
    from repro.federated import CompressionConfig

    model, loss_fn, client_data = world
    r_base, _ = _run(world, "fibecfed", "adamw", "vectorized")
    r_none = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="vectorized", seed=7,
        compression=CompressionConfig(mode="none"),
        client_ranks=[CFG.lora_rank] * FL.num_devices,
    )
    r_none.init_phase()
    for t in range(ROUNDS):
        r_none.run_round(t)
    assert r_none.compression is None and r_none.client_ranks is None
    assert _leaves_equal(r_base.global_lora, r_none.global_lora)
    assert r_base.comm_bytes_per_round == r_none.comm_bytes_per_round
    assert r_base.comm_upload_bytes_per_round == r_none.comm_upload_bytes_per_round


@pytest.mark.parametrize(
    "comp_kw",
    [
        dict(mode="int8"),
        dict(mode="topk", topk_ratio=0.25, topk_values="int8"),
        dict(mode="topk", topk_ratio=0.25, topk_values="float", error_feedback=False),
    ],
)
def test_compressed_engines_equivalent(world, comp_kw):
    """loop (spec: host-side channel sim per client) and vectorized (fused
    in-program vmap'd kernel) must agree under every compression mode —
    same global trees, same EF residual evolution, same wire bytes."""
    from repro.federated import CompressionConfig

    model, loss_fn, client_data = world
    comp = CompressionConfig(**comp_kw)
    runners = {}
    for engine in ("loop", "vectorized"):
        r = make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine=engine, seed=7, compression=comp,
        )
        r.init_phase()
        for t in range(ROUNDS):
            r.run_round(t)
        runners[engine] = r
    r_loop, r_vec = runners["loop"], runners["vectorized"]
    frac = 0.02 if comp.use_thresh else 0.0
    _assert_close_trees(r_loop.global_lora, r_vec.global_lora, boundary_frac=frac)
    assert r_loop.comm_bytes_per_round == r_vec.comm_bytes_per_round
    assert r_loop.comm_upload_bytes_per_round == r_vec.comm_upload_bytes_per_round
    # the compressed push is strictly cheaper than the raw pull
    for total, up in zip(
        r_loop.comm_bytes_per_round, r_loop.comm_upload_bytes_per_round
    ):
        assert up < total - up
    if comp.error_feedback:
        stacked = [
            jax.tree.map(lambda x, ci=ci: x[ci], r_vec._stacked_residual)
            for ci in range(FL.num_devices)
        ]
        for cl, sr in zip(r_loop.clients, stacked):
            if cl.ef_residual is not None:
                _assert_close_trees(cl.ef_residual, sr, boundary_frac=frac)


def test_topk_full_ratio_float_matches_uncompressed(world):
    """ratio=1.0 float top-k keeps everything at full precision: the channel
    is the identity, so the run must match the uncompressed engine."""
    from repro.federated import CompressionConfig

    model, loss_fn, client_data = world
    r_base, _ = _run(world, "fibecfed", "adamw", "loop")
    r_id = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="loop", seed=7,
        compression=CompressionConfig(
            mode="topk", topk_ratio=1.0, topk_values="float", error_feedback=False
        ),
    )
    r_id.init_phase()
    for t in range(ROUNDS):
        r_id.run_round(t)
    _assert_close_trees(r_base.global_lora, r_id.global_lora)
    # but it still pays for indices on the wire
    assert r_id.comm_upload_bytes_per_round[0] > r_base.comm_upload_bytes_per_round[0]


def test_rank_heterogeneous_engines_equivalent(world):
    """Per-client ranks fold into the update masks: loop and vectorized must
    agree, low-rank clients' beyond-rank components never move, and the
    rank projection shrinks their wire bill."""
    model, loss_fn, client_data = world
    ranks = [CFG.lora_rank, 1, 1, CFG.lora_rank]
    runners = {}
    for engine in ("loop", "vectorized"):
        r = make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine=engine, seed=7, client_ranks=ranks,
        )
        r.init_phase()
        for t in range(ROUNDS):
            r.run_round(t)
        runners[engine] = r
    r_loop, r_vec = runners["loop"], runners["vectorized"]
    _assert_close_trees(r_loop.global_lora, r_vec.global_lora)
    assert r_loop.comm_bytes_per_round == r_vec.comm_bytes_per_round

    # a rank-1 client bills exactly rank/R of the full-rank round trip
    full = r_loop._client_comm_bytes(0)
    half = r_loop._client_comm_bytes(1)
    assert half[0] * CFG.lora_rank == full[0] * 1
    r_full, _ = _run(world, "fibecfed", "adamw", "loop")
    assert sum(r_loop.comm_bytes_per_round) <= sum(r_full.comm_bytes_per_round)


def test_async_compressed_matches_loop_compressed(world):
    """The degenerate async configuration stays synchronous FedAvg under
    compression (via async_cfg.compression), in both merge modes."""
    from repro.federated import AsyncAggConfig, CompressionConfig

    model, loss_fn, client_data = world
    comp = CompressionConfig(mode="topk", topk_ratio=0.25, topk_values="int8")
    r_loop = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="loop", seed=7, compression=comp,
    )
    r_loop.init_phase()
    for t in range(ROUNDS):
        r_loop.run_round(t)
    for mode_kw in (dict(), dict(merge_mode="delta", server_lr=1.0)):
        r_async = make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine="async", seed=7,
            async_cfg=AsyncAggConfig(compression=comp, **mode_kw),
        )
        r_async.init_phase()
        for t in range(ROUNDS):
            r_async.run_round(t)
        _assert_close_trees(
            r_loop.global_lora, r_async.global_lora, boundary_frac=0.02
        )
        assert r_loop.comm_bytes_per_round == r_async.comm_bytes_per_round
        assert (
            r_loop.comm_upload_bytes_per_round
            == r_async.comm_upload_bytes_per_round
        )


def test_constrained_scenario_derives_slow_ranks(world):
    """The "constrained" preset (slow_rank_fraction + bandwidth_factor)
    derives per-client ranks from the scenario's slow group and prices the
    bandwidth factor into round-trip time; the run stays finite."""
    model, loss_fn, client_data = world
    runner = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", scenario="constrained", seed=7,
    )
    runner.init_phase()
    history = [runner.run_round(t) for t in range(ROUNDS)]
    assert runner.client_ranks is not None
    assert np.any(runner.client_ranks < CFG.lora_rank)
    assert np.any(runner.client_ranks == CFG.lora_rank)
    for h in history:
        assert np.isfinite(h["loss"])


def test_stack_clients_pads_inert_rows():
    data = [
        {"tokens": np.arange(10, dtype=np.int32).reshape(5, 2)},
        {"tokens": np.arange(6, dtype=np.int32).reshape(3, 2)},
    ]
    stack = stack_clients(data, 2, pad_clients_to=4)
    assert stack.num_clients == 4
    assert stack.data["tokens"].shape[0] == 4
    # padding rows: no valid samples, zero sizes, finite data (client 0 copy)
    assert stack.sample_valid[2:].sum() == 0.0
    assert list(stack.n_batches) == [3, 2, 0, 0]
    assert list(stack.n_samples) == [5, 3, 0, 0]
    np.testing.assert_array_equal(stack.data["tokens"][2], stack.data["tokens"][0])
    # real rows unchanged vs the unpadded stack
    ref = stack_clients(data, 2)
    np.testing.assert_array_equal(stack.data["tokens"][:2], ref.data["tokens"])
    np.testing.assert_array_equal(stack.sample_valid[:2], ref.sample_valid)


# ---------------------------------------------------------------------------
# Client-state ownership: ClientStore refactor (in-memory default must be a
# pure refactor; out-of-core must be allclose with identical comm accounting;
# two-tier hierarchy must be exact at one edge).
# ---------------------------------------------------------------------------


def test_inmemory_store_default_bit_identical(world):
    """Passing an explicit InMemoryStore must be byte-for-byte the default:
    the store refactor is ownership-only, not a numerical change."""
    from repro.federated import InMemoryStore

    model, loss_fn, client_data = world
    runs = {}
    for store in (None, InMemoryStore()):
        r = make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine="vectorized", seed=7, store=store,
        )
        r.init_phase()
        h = [r.run_round(t) for t in range(ROUNDS)]
        runs[store is None] = (r, h)
    (r_def, h_def), (r_exp, h_exp) = runs[True], runs[False]
    for hd, he in zip(h_def, h_exp):
        assert hd["loss"] == he["loss"]
    assert _leaves_equal(r_def.global_lora, r_exp.global_lora)
    assert r_def.comm_bytes_per_round == r_exp.comm_bytes_per_round


@pytest.mark.parametrize("engine", ["loop", "vectorized", "async"])
def test_out_of_core_store_matches_in_memory(world, engine, tmp_path):
    """OutOfCoreStore with hot_slots < num_clients forces spill/reload every
    round; the run must stay allclose to the in-memory store with identical
    comm accounting, and cold files must actually land on disk."""
    import os

    from repro.federated import OutOfCoreStore

    model, loss_fn, client_data = world
    r_mem, h_mem = _run(world, "fibecfed", "adamw", engine)
    store = OutOfCoreStore(str(tmp_path), hot_slots=2)
    r_ooc = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine=engine, seed=7, store=store,
    )
    r_ooc.init_phase()
    h_ooc = [r_ooc.run_round(t) for t in range(ROUNDS)]

    for hm, ho in zip(h_mem, h_ooc):
        assert hm["loss"] == pytest.approx(ho["loss"], rel=1e-4, abs=1e-5)
        assert hm["selected_batches"] == ho["selected_batches"]
    _assert_close_trees(r_mem.global_lora, r_ooc.global_lora)
    assert r_mem.comm_bytes_per_round == r_ooc.comm_bytes_per_round

    # eviction really happened: cold state was spilled to flat-npz files
    spilled = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(spilled) >= FL.num_devices - 2

    # resident set stays bounded by the hot-set size
    store.flush()
    assert len(spilled) >= 2
    for ci in range(FL.num_devices):
        st = store.get(ci)
        for a, b in zip(jax.tree.leaves(st.lora), jax.tree.leaves(r_ooc.clients[ci].lora)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_out_of_core_rejected_for_sharded(world, tmp_path):
    from repro.federated import OutOfCoreStore

    model, loss_fn, client_data = world
    with pytest.raises(ValueError, match="sharded"):
        make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine="sharded", seed=7,
            store=OutOfCoreStore(str(tmp_path), hot_slots=2),
        )


def test_hierarchy_rejected_for_sync_engines(world):
    model, loss_fn, client_data = world
    with pytest.raises(ValueError, match="async"):
        make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine="vectorized", seed=7, hierarchy=2,
        )


def test_hierarchy_single_edge_bit_exact(world):
    """One edge is the flat merge routed through an edge summary: contracting
    a single partial sum with weight 1.0 is the identity, so the two-tier run
    must be bit-identical to the flat async engine."""
    model, loss_fn, client_data = world
    r_flat, h_flat = _run(world, "fibecfed", "adamw", "async")
    r_edge = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", seed=7, hierarchy=1,
    )
    r_edge.init_phase()
    h_edge = [r_edge.run_round(t) for t in range(ROUNDS)]
    for hf, he in zip(h_flat, h_edge):
        assert hf["loss"] == he["loss"]
    assert _leaves_equal(r_flat.global_lora, r_edge.global_lora)
    assert r_flat.comm_bytes_per_round == r_edge.comm_bytes_per_round


@pytest.mark.parametrize("num_edges", [2, 3])
def test_hierarchy_multi_edge_allclose(world, num_edges):
    """Multiple edges reassociate the weighted sum (client partials are
    reduced per edge before the server contraction): allclose to flat, with
    the wire bill unchanged (edge aggregation is lossless)."""
    model, loss_fn, client_data = world
    r_flat, h_flat = _run(world, "fibecfed", "adamw", "async")
    r_edge = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", seed=7, hierarchy=num_edges,
    )
    r_edge.init_phase()
    h_edge = [r_edge.run_round(t) for t in range(ROUNDS)]
    for hf, he in zip(h_flat, h_edge):
        assert hf["loss"] == pytest.approx(he["loss"], rel=1e-4, abs=1e-5)
    _assert_close_trees(r_flat.global_lora, r_edge.global_lora)
    assert r_flat.comm_bytes_per_round == r_edge.comm_bytes_per_round


@pytest.mark.parametrize(
    "num_edges,assignments",
    [
        (3, (0, 0, 1, 2)),  # uneven: one edge holds half the population
        (4, (2, 0, 0, 3)),  # uneven + empty edge 1 + non-contiguous regions
    ],
    ids=["E3-lopsided", "E4-empty-edge"],
)
def test_hierarchy_uneven_assignments_allclose(world, num_edges, assignments):
    """Explicit client→edge maps (uneven region sizes, empty edges, ids out
    of block order) only reassociate the weighted sum: allclose to the flat
    merge, with per-client comm accounting untouched by the topology."""
    from repro.federated import HierarchyConfig

    model, loss_fn, client_data = world
    r_flat, h_flat = _run(world, "fibecfed", "adamw", "async")
    r_edge = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", seed=7,
        hierarchy=HierarchyConfig(num_edges=num_edges, assignments=assignments),
    )
    r_edge.init_phase()
    h_edge = [r_edge.run_round(t) for t in range(ROUNDS)]
    for hf, he in zip(h_flat, h_edge):
        assert hf["loss"] == pytest.approx(he["loss"], rel=1e-4, abs=1e-5)
    _assert_close_trees(r_flat.global_lora, r_edge.global_lora)
    assert r_flat.comm_bytes_per_round == r_edge.comm_bytes_per_round
    assert r_flat.comm_upload_bytes_per_round == r_edge.comm_upload_bytes_per_round


def test_hierarchy_assignment_validation():
    """Malformed client→edge maps fail at construction or reduce time, not
    silently mis-route updates."""
    from repro.federated import HierarchyConfig, edge_reduce
    from repro.federated.hierarchy import build_edge_summary_fn

    with pytest.raises(ValueError, match=r"\[0, 2\)"):
        HierarchyConfig(num_edges=2, assignments=(0, 2, 1))
    with pytest.raises(ValueError, match=r"\[0, 3\)"):
        HierarchyConfig(num_edges=3, assignments=(0, -1, 1))
    with pytest.raises(ValueError, match="1-D"):
        HierarchyConfig(num_edges=2, assignments=((0, 1), (1, 0)))
    # config normalizes to a hashable tuple (frozen dataclass stays usable
    # as a dict key)
    cfg = HierarchyConfig(num_edges=3, assignments=np.array([0, 2, 1]))
    assert cfg.assignments == (0, 2, 1)
    assert hash(cfg) == hash(HierarchyConfig(num_edges=3, assignments=(0, 2, 1)))
    # the map must cover the whole population at reduce time
    fn = build_edge_summary_fn()
    payloads = [{"a": np.ones(2, np.float32)}] * 2
    with pytest.raises(ValueError, match="map all 4 clients"):
        edge_reduce(
            fn, payloads, np.ones(2, np.float32), [0, 1],
            num_clients=4, num_edges=2, assignments=(0, 1),
        )


def test_ef_residual_survives_eviction(world, tmp_path):
    """Error-feedback residuals are client state: evicting a client to disk
    mid-run and reloading it must leave the EF telescoping unchanged vs the
    in-memory run (same residual trees, same global model)."""
    from repro.federated import CompressionConfig, OutOfCoreStore

    model, loss_fn, client_data = world
    comp = CompressionConfig(
        mode="topk", topk_ratio=0.25, topk_values="int8", error_feedback=True
    )
    runs = {}
    for key, store in (
        ("mem", None),
        ("ooc", OutOfCoreStore(str(tmp_path), hot_slots=1)),
    ):
        r = make_runner(
            "fibecfed", model, loss_fn, FL, client_data,
            optimizer="adamw", engine="loop", seed=7,
            compression=comp, store=store,
        )
        r.init_phase()
        for t in range(ROUNDS):
            r.run_round(t)
        runs[key] = r
    r_mem, r_ooc = runs["mem"], runs["ooc"]
    _assert_close_trees(r_mem.global_lora, r_ooc.global_lora)
    assert r_mem.comm_bytes_per_round == r_ooc.comm_bytes_per_round
    assert r_mem.comm_upload_bytes_per_round == r_ooc.comm_upload_bytes_per_round
    seen = 0
    for cm, co in zip(r_mem.clients, r_ooc.clients):
        if cm.ef_residual is None:
            assert co.ef_residual is None
            continue
        seen += 1
        _assert_close_trees(cm.ef_residual, co.ef_residual)
    assert seen > 0
