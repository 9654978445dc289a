"""Observability layer: metrics math, span well-formedness, exporters,
retrace counters, bit-identity of the disabled path, and reconciliation of
exported traces against the engines' own accounting.

The two reconciliation tests are the PR's acceptance contract: a straggler
async run's virtual upload spans must sum to exactly the runner's wire-format
upload accounting, and a mixed multi-adapter serve session's span/counter
totals must match the engine's ``stats`` dict — the trace is bookkeeping,
not an estimate.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.config import FibecFedConfig, ModelConfig
from repro.configs import ARCHS
from repro.data import dirichlet_partition, make_keyword_task
from repro.federated import AsyncAggConfig, make_runner
from repro.launch.mesh import make_client_mesh
from repro.models import build_model
from repro.obs import (
    NULL_TELEMETRY,
    MetricsRegistry,
    NullRegistry,
    NullTelemetry,
    SchemaError,
    Telemetry,
    Tracer,
    VIRTUAL,
    WALL,
    check_spans,
    ensure,
    runtime_metrics,
    validate_event,
    validate_jsonl,
    write_perfetto,
)
from repro.obs.metrics import NULL_METRIC, _bucket_exponent
from repro.serve import Request, SamplingParams, ServeEngine, make_prompt_batch
from repro.train import make_loss_fn

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("x")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("x") is c  # same object on re-get
    g = reg.gauge("y")
    g.set(4)
    g.set(1.5)
    assert g.value == 1.5


def test_histogram_math():
    h = MetricsRegistry().histogram("h")
    for v in (0.5, 1.0, 3.0, 4.0, -1.0):
        h.observe(v)
    assert h.count == 5
    assert h.total == pytest.approx(7.5)
    assert h.mean == pytest.approx(1.5)
    assert h.vmin == -1.0 and h.vmax == 4.0
    # 0.5 -> 2**-1, 1.0 -> 2**0, 3.0 -> (2**1, 2**2], 4.0 -> 2**2 exactly
    assert h.buckets == {"-1": 1, "0": 1, "2": 2, "-inf": 1}
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["buckets"]["2"] == 2
    assert json.loads(json.dumps(snap)) == snap  # JSON-clean


def test_bucket_edges_powers_of_two():
    # exact powers of two land in their own exponent; epsilon above moves up
    assert _bucket_exponent(2.0) == "1"
    assert _bucket_exponent(2.0 + 1e-9) == "2"
    assert _bucket_exponent(1.0) == "0"
    assert _bucket_exponent(0.0) == "-inf"
    assert _bucket_exponent(-5.0) == "-inf"
    for e in range(-8, 9):
        v = math.ldexp(1.0, e)
        assert _bucket_exponent(v) == str(e)
        assert _bucket_exponent(v * 1.001) == str(e + 1)


def test_metric_name_bound_to_one_kind():
    reg = MetricsRegistry()
    reg.counter("n")
    with pytest.raises(ValueError):
        reg.gauge("n")
    with pytest.raises(ValueError):
        reg.histogram("n")


def test_registry_snapshot_and_reset():
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7)
    reg.histogram("h").observe(1)
    snap = reg.snapshot()
    assert snap["counters"] == {"c": 2.0}
    assert snap["gauges"] == {"g": 7.0}
    assert snap["histograms"]["h"]["count"] == 1
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_null_registry_is_inert():
    reg = NullRegistry()
    assert reg.counter("a") is NULL_METRIC
    reg.counter("a").inc(5)
    reg.gauge("b").set(1)
    reg.histogram("c").observe(2)
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# tracer + span well-formedness
# ---------------------------------------------------------------------------


def test_tracer_span_contextmanager_records_args():
    tr = Tracer()
    with tr.span("work", cat="t", track="host", args={"a": 1}) as sargs:
        sargs["b"] = 2
    (ev,) = tr.events
    assert ev["type"] == "span" and ev["name"] == "work"
    assert ev["clock"] == WALL and ev["args"] == {"a": 1, "b": 2}
    assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0


def test_tracer_add_span_virtual_and_clamping():
    tr = Tracer()
    tr.add_span("up", start=3.0, end=5.0, clock=VIRTUAL, track="client/0")
    tr.add_span("zero", start=5.0, end=4.0, clock=VIRTUAL, track="client/0")
    assert tr.events[0]["ts"] == 3.0 and tr.events[0]["dur"] == 2.0
    assert tr.events[1]["dur"] == 0.0  # end < start clamps, never negative
    with pytest.raises(ValueError):
        tr.add_span("bad", start=0, end=1, clock="lamport")
    with pytest.raises(ValueError):
        tr.instant("bad", clock="lamport")


def test_check_spans_accepts_nesting_and_disjoint():
    tr = Tracer()
    tr.add_span("outer", start=0.0, end=10.0, clock=VIRTUAL, track="a")
    tr.add_span("inner", start=2.0, end=5.0, clock=VIRTUAL, track="a")
    tr.add_span("later", start=10.0, end=12.0, clock=VIRTUAL, track="a")
    # same interval on a DIFFERENT track never interacts
    tr.add_span("other", start=1.0, end=11.0, clock=VIRTUAL, track="b")
    check_spans(tr.events)


def test_check_spans_rejects_partial_overlap():
    tr = Tracer()
    tr.add_span("a", start=0.0, end=5.0, clock=VIRTUAL, track="a")
    tr.add_span("b", start=3.0, end=8.0, clock=VIRTUAL, track="a")
    with pytest.raises(ValueError, match="partially overlaps"):
        check_spans(tr.events)
    # the same pair split across clocks is fine
    tr2 = Tracer()
    tr2.add_span("a", start=0.0, end=5.0, clock=VIRTUAL, track="a")
    tr2.add_span("b", start=3.0, end=8.0, clock=WALL, track="a")
    check_spans(tr2.events)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _sample_telemetry() -> Telemetry:
    tel = Telemetry(run_id="t", meta={"k": "v"})
    with tel.span("host_work", cat="test"):
        pass
    tel.tracer.add_span(
        "virt", start=1.0, end=2.0, clock=VIRTUAL, track="client/1",
        args={"upload_bytes": 10},
    )
    tel.instant("mark", cat="test")
    tel.metrics.counter("c").inc(3)
    tel.metrics.histogram("h").observe(2.0)
    return tel


def test_jsonl_round_trip_validates(tmp_path):
    tel = _sample_telemetry()
    path = str(tmp_path / "trace.jsonl")
    n = tel.export_jsonl(path)
    counts = validate_jsonl(path)
    assert counts == {"manifest": 1, "span": 2, "instant": 1, "metrics": 1}
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == n
    assert lines[0]["type"] == "manifest" and lines[0]["run_id"] == "t"
    assert lines[-1]["snapshot"]["counters"]["c"] == 3.0
    assert "runtime" in lines[-1]["snapshot"]


def test_jsonl_validation_rejects_malformed(tmp_path):
    with pytest.raises(SchemaError):
        validate_event({"type": "span", "name": "x"})  # missing fields
    with pytest.raises(SchemaError):
        validate_event(
            {"type": "span", "name": "x", "cat": "c", "track": "t",
             "clock": "lamport", "ts": 0, "dur": 0, "args": {}}
        )
    with pytest.raises(SchemaError):
        validate_event(
            {"type": "instant", "name": "x", "cat": "c", "track": "t",
             "clock": WALL, "ts": -1.0, "args": {}}
        )
    # a file whose first line is not the manifest fails as a whole
    p = tmp_path / "bad.jsonl"
    p.write_text('{"type": "metrics", "snapshot": {}}\n')
    with pytest.raises(SchemaError, match="manifest"):
        validate_jsonl(str(p))


def test_perfetto_export_loads_and_separates_clocks(tmp_path):
    tel = _sample_telemetry()
    path = str(tmp_path / "trace.json")
    tel.export_perfetto(path)
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    # wall span on pid 1, virtual span on pid 2, microsecond timestamps
    assert {e["pid"] for e in xs} == {1, 2}
    virt = next(e for e in xs if e["pid"] == 2)
    assert virt["ts"] == pytest.approx(1e6) and virt["dur"] == pytest.approx(1e6)
    assert virt["args"]["upload_bytes"] == 10
    assert any(e.get("ph") == "i" for e in evs)
    names = {
        e["args"]["name"] for e in evs
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert len(names) == 2  # both clock-domain processes labeled


# ---------------------------------------------------------------------------
# telemetry facade + runtime (retrace) counters
# ---------------------------------------------------------------------------


def test_ensure_normalizes_none():
    assert ensure(None) is NULL_TELEMETRY
    tel = Telemetry()
    assert ensure(tel) is tel
    assert isinstance(NULL_TELEMETRY, NullTelemetry)
    assert not NULL_TELEMETRY.enabled


def test_null_telemetry_is_inert():
    with NULL_TELEMETRY.span("x", cat="y", args={"a": 1}) as sargs:
        sargs["b"] = 2  # writable scratch, recorded nowhere
    NULL_TELEMETRY.instant("x")
    assert NULL_TELEMETRY.tracer.events == []
    assert NULL_TELEMETRY.snapshot() == {}
    with pytest.raises(RuntimeError):
        NULL_TELEMETRY.export_jsonl("/dev/null")
    with pytest.raises(RuntimeError):
        NULL_TELEMETRY.export_perfetto("/dev/null")


def test_memo_counts_program_builds_once_per_key():
    from repro.core.fibecfed import _memo, clear_compile_caches

    builds = runtime_metrics.counter("jit.program_builds")
    key = ("test_obs-unique-key", id(object()))
    before = builds.value
    assert _memo(key, lambda: "prog") == "prog"
    assert builds.value == before + 1
    assert _memo(key, lambda: "other") == "prog"  # hit: no build, no count
    assert builds.value == before + 1

    clears = runtime_metrics.counter("jit.cache_clears")
    c0 = clears.value
    clear_compile_caches()
    assert clears.value == c0 + 1
    # the cleared memo re-builds (and re-counts) on next use
    assert _memo(key, lambda: "rebuilt") == "rebuilt"
    assert builds.value == before + 2


def test_trace_cache_size_reads_jit_cache():
    from repro.core.engine import trace_cache_size

    fn = jax.jit(lambda x: x + 1)
    assert trace_cache_size(fn) == 0
    fn(jax.numpy.float32(1.0))
    assert trace_cache_size(fn) == 1
    fn(jax.numpy.zeros((2,), jax.numpy.float32))  # new signature
    assert trace_cache_size(fn) == 2
    assert trace_cache_size(object()) == 0  # non-jit: safe zero


# ---------------------------------------------------------------------------
# FL engines: disabled telemetry is bit-identical; enabled spans reconcile
# ---------------------------------------------------------------------------

CFG = ModelConfig(
    name="obs-lm", family="dense", num_layers=2, d_model=32, num_heads=2,
    num_kv_heads=2, d_ff=64, vocab_size=256, head_dim=16, rope="full",
    norm="rmsnorm", mlp="swiglu", dtype="float32", lora_rank=2, max_seq_len=64,
)
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


def _run_fl(world, engine, telemetry=None, rounds=ROUNDS, **kw):
    model, loss_fn, client_data = world
    runner = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine=engine, seed=7, telemetry=telemetry, **kw,
    )
    runner.init_phase()
    history = [runner.run_round(t) for t in range(rounds)]
    return runner, history


def _bitwise_equal_trees(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize(
    "engine,kw",
    [
        ("loop", {}),
        ("vectorized", {}),
        ("sharded", {"mesh": "1"}),
        ("async", {}),
        ("async", {"scenario": "straggler",
                   "async_cfg": AsyncAggConfig(buffer_size=2)}),
    ],
)
def test_enabled_telemetry_is_bit_identical(world, engine, kw):
    """The no-op recorder contract, from the other side: ENABLING telemetry
    must not change a single bit of any engine's run — spans and counters
    observe dispatch boundaries, never the numerics or the RNG streams."""
    kw = dict(kw)
    if kw.get("mesh") == "1":
        kw["mesh"] = make_client_mesh(1)
    r_off, h_off = _run_fl(world, engine, telemetry=None, **kw)
    tel = Telemetry(run_id=f"bitid/{engine}")
    r_on, h_on = _run_fl(world, engine, telemetry=tel, **kw)

    for ho, hn in zip(h_off, h_on):
        assert ho == hn  # every stat float, bitwise
    assert r_off.comm_bytes_per_round == r_on.comm_bytes_per_round
    assert r_off.comm_upload_bytes_per_round == r_on.comm_upload_bytes_per_round
    _bitwise_equal_trees(r_off.global_lora, r_on.global_lora)

    # and the enabled side actually recorded a well-formed trace
    events = tel.tracer.events
    check_spans(events)
    assert sum(1 for e in events if e["name"] == "round") == ROUNDS
    assert sum(1 for e in events if e["name"] == "init_phase") == 1
    snap = tel.snapshot()
    assert snap["counters"]["fl.rounds"] == ROUNDS
    assert snap["counters"]["fl.comm_bytes"] == sum(r_on.comm_bytes_per_round)


def test_init_phase_spans_nest_under_init(world):
    tel = Telemetry()
    _run_fl(world, "vectorized", telemetry=tel, rounds=0)
    spans = {e["name"]: e for e in tel.tracer.events if e["type"] == "span"}
    for name in ("difficulty", "sensitivity", "fim_warmup"):
        inner, outer = spans[name], spans["init_phase"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    # round spans carry their loss arg for trace-side postmortems
    tel2 = Telemetry()
    _run_fl(world, "vectorized", telemetry=tel2, rounds=1)
    rd = next(e for e in tel2.tracer.events if e["name"] == "round")
    assert np.isfinite(rd["args"]["loss"]) and rd["args"]["t"] == 0


ROUND_PHASES = ("plan", "put", "dispatch", "wait", "account")
INIT_SUB_SPANS = {
    "difficulty": ("difficulty_read",),
    "sensitivity": ("sensitivity_client", "sensitivity_read"),
    "fim_warmup": ("fim_gather", "fim_program", "fim_select", "fim_slice"),
}


def _spans(tel, name):
    return [e for e in tel.tracer.events if e["type"] == "span" and e["name"] == name]


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9)


@pytest.mark.parametrize("engine,kw", [("vectorized", {}), ("sharded", {"mesh": "1"})])
def test_round_phase_spans_nest_under_round(world, engine, kw):
    kw = {"mesh": make_client_mesh(1)} if kw else {}
    tel = Telemetry()
    _run_fl(world, engine, telemetry=tel, **kw)
    check_spans(tel.tracer.events)
    rounds = _spans(tel, "round")
    assert len(rounds) == ROUNDS
    for p in ROUND_PHASES:
        phase = _spans(tel, f"round_{p}")
        assert len(phase) == ROUNDS
        for ph, rd in zip(phase, rounds):
            assert ph["track"] == rd["track"] == "server"
            assert _inside(ph, rd)


def test_round_phase_spans_sum_to_at_most_the_round(world):
    tel = Telemetry()
    _run_fl(world, "vectorized", telemetry=tel)
    phases = [_spans(tel, f"round_{p}") for p in ROUND_PHASES]
    for i, rd in enumerate(_spans(tel, "round")):
        total = sum(ph[i]["dur"] for ph in phases)
        assert 0.0 < total <= rd["dur"]
        # in order, without overlap
        starts = [ph[i]["ts"] for ph in phases]
        assert starts == sorted(starts)


def _assert_phase_histograms_count_every_round(runner):
    hists = [runtime_metrics.histogram(f"fl.round_{p}_s") for p in ROUND_PHASES]
    for t in range(3):
        before = [(h.count, len(h.recent)) for h in hists]
        runner.run_round(t)
        for h, (count, n) in zip(hists, before):
            assert h.count == count + 1
            assert len(h.recent) == min(n + 1, h.RECENT)
            assert h.recent[-1] >= 0.0
    assert runtime_metrics.snapshot()["histograms"]["fl.round_wait_s"]["count"] == hists[3].count


def test_round_phase_histograms_count_every_round_with_telemetry_off(world):
    runner, _ = _run_fl(world, "vectorized", telemetry=None, rounds=0)
    _assert_phase_histograms_count_every_round(runner)


def test_out_of_core_rounds_record_the_phase_histograms(world, tmp_path):
    """The out-of-core store's rounds run the same five phases."""
    from repro.federated import OutOfCoreStore

    store = OutOfCoreStore(str(tmp_path), hot_slots=2)
    runner, _ = _run_fl(world, "vectorized", telemetry=None, rounds=0, store=store)
    _assert_phase_histograms_count_every_round(runner)


def test_histogram_recent_keeps_the_last_observations():
    h = MetricsRegistry().histogram("h")
    for v in range(h.RECENT + 10):
        h.observe(v)
    assert len(h.recent) == h.RECENT == 1024
    assert list(h.recent)[-3:] == [h.RECENT + 7, h.RECENT + 8, h.RECENT + 9]
    assert h.recent[0] == 10.0
    assert h.count == h.RECENT + 10
    assert "recent" not in h.snapshot()


def test_init_sub_spans_nest_under_their_phases(world):
    tel = Telemetry()
    runner, _ = _run_fl(world, "vectorized", telemetry=tel, rounds=0)
    runner.init_phase()
    check_spans(tel.tracer.events)
    inits = _spans(tel, "init_phase")
    assert len(inits) == 2
    for phase, subs in INIT_SUB_SPANS.items():
        outer = _spans(tel, phase)
        assert len(outer) == len(inits)  # once per init_phase
        assert all(_inside(o, i) for o, i in zip(outer, inits))
        for sub in subs:
            inner = _spans(tel, sub)
            assert inner and all(any(_inside(e, o) for o in outer) for e in inner)
    clients = _spans(tel, "sensitivity_client")
    assert [e["args"]["ci"] for e in clients] == list(range(FL.num_devices)) * 2
    for rd in _spans(tel, "sensitivity_read"):
        assert sum(_inside(rd, c) for c in clients) == 1


def test_gc_collections_recorded_until_close():
    import gc

    tel = Telemetry()
    gc.collect()
    (ev,) = _spans(tel, "gc")
    assert ev["track"] == "gc" and ev["clock"] == WALL
    assert ev["args"]["generation"] == 2 and ev["args"]["collected"] >= 0
    tel.close()
    tel.close()  # idempotent
    gc.collect()
    assert len(_spans(tel, "gc")) == 1


def test_gc_hook_goes_with_its_telemetry():
    import gc

    n = len(gc.callbacks)
    tel = Telemetry()
    assert len(gc.callbacks) == n + 1
    del tel
    gc.collect()
    assert len(gc.callbacks) == n


def _scope_names(lowered) -> set:
    """Every name in the op locations of a lowered program, with JAX's
    transform wrappers (``vmap(jvp(lm_head))``) taken off."""
    import re

    names = set()
    for loc in re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)):
        for part in loc.split("/"):
            names.add(re.sub(r"^(?:\w+\()+|\)+$", "", part))
    return names


@pytest.fixture(scope="module")
def lowered_programs(world):
    import jax.numpy as jnp

    runner, _ = _run_fl(world, "vectorized", rounds=0)
    r = runner
    kp, S, E = r._cohort_pad, 2, FL.fim_warmup_epochs
    round_args = (
        r.params, r.global_lora, r._stacked_lora, r._stacked_opt, r._stacked_mask,
        r._gal_mask_tree, r._stack_data, r._sample_valid,
        jnp.arange(kp, dtype=jnp.int32), jnp.zeros((kp, S), jnp.int32),
        jnp.ones((kp, S), jnp.float32), jnp.full((kp,), 1.0 / kp, jnp.float32),
        jnp.float32(FL.learning_rate),
    )
    wdata = {k: v[:, :E] for k, v in r._stack_data.items()}
    client = r.clients[0]
    return {
        "round": r._round_fn().lower(*round_args),
        "difficulty": r._difficulty_fn().lower(
            r.params, r._stacked_lora, r._stack_data, r._sample_valid),
        "fim": r._fim_warmup_fn().lower(
            r.params, r._stacked_lora, wdata, r._sample_valid[:, :E]),
        "sensitivity": r._sensitivity_fn().lower(
            r.params, client.lora, r._client_batch(client, client.batches[0])),
    }


@pytest.mark.parametrize("program,scopes", [
    ("round", ("gather", "merge_in", "client_train", "attention", "mlp", "lm_head",
               "loss", "adamw", "fedavg", "scatter")),
    ("difficulty", ("difficulty_grads", "attention", "mlp", "lm_head", "loss")),
    ("fim", ("fim_warmup_program", "lm_head", "loss")),
    ("sensitivity", ("sensitivity_probe", "attention")),
])
def test_programs_name_their_scopes(lowered_programs, program, scopes):
    names = _scope_names(lowered_programs[program])
    assert set(scopes) <= names, sorted(set(scopes) - names)


def test_profiler_trace_holds_round_phases(world, tmp_path):
    """With telemetry off, a profiler trace around a round holds the round
    and its phases on the host plane, the clock of the device's ops."""
    import glob

    runner, _ = _run_fl(world, "vectorized", telemetry=None, rounds=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        runner.run_round(1)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    host = {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {"round"} | {f"round_{p}" for p in ROUND_PHASES} <= host


def test_async_straggler_trace_reconciles_with_comm_accounting(world, tmp_path):
    """The acceptance contract: a straggler async run's virtual-clock spans
    must reconcile EXACTLY with the runner's own accounting — upload-span
    bytes vs wire-format upload bytes, dispatch-span download bytes vs the
    pull side, merges/completions/staleness vs the per-round stats."""
    tel = Telemetry(run_id="straggler")
    rounds = 6
    r, hist = _run_fl(
        world, "async", telemetry=tel, rounds=rounds,
        scenario="straggler", async_cfg=AsyncAggConfig(buffer_size=2),
    )
    events = tel.tracer.events
    check_spans(events)

    spans = [e for e in events if e["type"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # every completion decomposes into dispatch -> compute -> upload -> buffer
    n_completions = len(by_name["upload"])
    assert (
        len(by_name["dispatch"]) == len(by_name["compute"])
        == len(by_name["buffer"]) == n_completions
    )
    for name in ("dispatch", "compute", "upload", "buffer"):
        assert all(s["clock"] == VIRTUAL for s in by_name[name])

    # exact byte reconciliation: the buffer empties at every flush, so span
    # totals equal the per-round comm sums (no estimate, no tolerance)
    up_spans = sum(s["args"]["upload_bytes"] for s in by_name["upload"])
    down_spans = sum(s["args"]["download_bytes"] for s in by_name["dispatch"])
    assert up_spans == sum(r.comm_upload_bytes_per_round)
    assert down_spans == sum(r.comm_bytes_per_round) - sum(
        r.comm_upload_bytes_per_round
    )

    snap = tel.snapshot()
    c = snap["counters"]
    assert c["async.completions"] == n_completions
    assert c["async.merges"] == rounds
    merged = sum(h["merged_clients"] for h in hist)
    assert snap["histograms"]["async.staleness"]["count"] == merged
    assert c["fl.comm_upload_bytes"] == up_spans

    # the whole thing exports and validates
    jsonl = str(tmp_path / "trace.jsonl")
    tel.export_jsonl(jsonl)
    validate_jsonl(jsonl)
    perfetto = str(tmp_path / "trace.json")
    tel.export_perfetto(perfetto)
    doc = json.load(open(perfetto))
    assert any(e.get("pid") == 2 for e in doc["traceEvents"])  # virtual lanes


def test_observed_pacing_caps_straggler_after_observation(world):
    """pace_mode="observed": after a few merges the EMA has seen the slow
    cohort and adapt_steps caps its plan from measurements alone — no
    scenario oracle consulted."""
    from repro.core import curriculum as curr

    model, loss_fn, client_data = world
    runner = make_runner(
        "fibecfed", model, loss_fn, FL, client_data,
        optimizer="adamw", engine="async", scenario="straggler", seed=7,
        async_cfg=AsyncAggConfig(
            buffer_size=2, adapt_steps=True, pace_mode="observed"
        ),
    )
    runner.init_phase()
    for t in range(8):
        assert np.isfinite(runner.run_round(t)["loss"])
    sched = runner._scheduler
    slow_ci = int(np.argmax(sched.scenario.speed))
    assert sched.observed_rel_speed(slow_ci) > 1.5  # skew was measured
    plan, _ = runner._async_callbacks(FL.learning_rate, sched)
    full = runner.fl.local_epochs * len(
        curr.selected_batch_ids(runner.schedule, 8, runner.clients[slow_ci].order)
    )
    assert plan(slow_ci, 8) < full  # and it really shortens the local round


# ---------------------------------------------------------------------------
# serving engine: bit-identity + trace/stats reconciliation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_world():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(3)
    params = model.init_params(rng)
    lora = model.init_lora(rng)
    extra = [model.init_lora(jax.random.fold_in(rng, i)) for i in (1, 2)]
    tokens = np.asarray(make_prompt_batch(cfg, rng, 5, 8)["tokens"])
    return model, params, lora, extra, tokens


def _serve_session(model, params, lora, extra, tokens, telemetry=None):
    eng = ServeEngine(
        model, params, lora, adapters=extra, cache_len=32, num_slots=2,
        max_new_cap=8, telemetry=telemetry,
    )
    samplings = [
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=3),
        SamplingParams(max_new_tokens=6, temperature=0.5, seed=3),
        SamplingParams(max_new_tokens=4),
        SamplingParams(max_new_tokens=6),
    ]
    rids = [
        eng.submit(Request(tokens=tokens[i], sampling=sp, adapter_id=i % 3))
        for i, sp in enumerate(samplings)
    ]
    comps = {c.request_id: c for c in eng.drain()}
    return eng, rids, comps


def test_serve_telemetry_bit_identical_and_reconciles(serve_world, tmp_path):
    model, params, lora, extra, tokens = serve_world
    e_off, rids_off, c_off = _serve_session(model, params, lora, extra, tokens)
    tel = Telemetry(run_id="serve")
    e_on, rids_on, c_on = _serve_session(
        model, params, lora, extra, tokens, telemetry=tel
    )

    # bit-identity: same tokens, same finish reasons, same engine stats
    assert rids_off == rids_on
    for rid in rids_off:
        np.testing.assert_array_equal(c_off[rid].tokens, c_on[rid].tokens)
        assert c_off[rid].finish_reason == c_on[rid].finish_reason
    assert e_off.stats == e_on.stats

    # trace/stats reconciliation on the enabled engine
    events = tel.tracer.events
    check_spans(events)
    spans = [e for e in events if e["type"] == "span"]
    segs = [s for s in spans if s["name"] == "segment"]
    assert len(segs) == e_on.stats["segment_calls"]
    assert sum(s["args"]["nsteps"] for s in segs) == e_on.stats[
        "jitted_decode_steps"
    ]
    assert (
        sum(1 for s in spans if s["name"] == "prefill")
        == e_on.stats["prefill_calls"]
    )
    assert sum(1 for e in events if e["name"] == "submit") == len(rids_on)

    snap = tel.snapshot()
    c = snap["counters"]
    assert c["serve.submitted"] == len(rids_on)
    assert c["serve.completed"] == e_on.stats["completed"]
    assert c["serve.decode_steps"] == e_on.stats["jitted_decode_steps"]
    assert c["serve.tokens_emitted"] == sum(x.steps for x in c_on.values())
    assert snap["histograms"]["serve.ttft_s"]["count"] == e_on.stats["admitted"]
    assert snap["histograms"]["serve.queue_s"]["count"] == e_on.stats["admitted"]
    assert (
        snap["histograms"]["serve.tokens_per_completion"]["count"]
        == e_on.stats["completed"]
    )
    assert snap["gauges"]["serve.useful_tokens_per_s"] > 0.0
    assert snap["gauges"]["serve.slots_free"] == e_on.scheduler.free

    jsonl = str(tmp_path / "serve.jsonl")
    tel.export_jsonl(jsonl)
    validate_jsonl(jsonl)
    tel.export_perfetto(str(tmp_path / "serve.json"))
    json.load(open(tmp_path / "serve.json"))


def test_serve_reset_keeps_telemetry(serve_world):
    model, params, lora, extra, tokens = serve_world
    tel = Telemetry()
    eng = ServeEngine(
        model, params, lora, adapters=extra, cache_len=32, num_slots=2,
        max_new_cap=8, telemetry=tel,
    )
    eng.submit(Request(tokens=tokens[0], sampling=SamplingParams(max_new_tokens=2)))
    eng.drain()
    eng.reset()
    assert eng.tel is tel and eng.scheduler.tel is tel
    before = tel.metrics.counter("serve.submitted").value
    eng.submit(Request(tokens=tokens[1], sampling=SamplingParams(max_new_tokens=2)))
    eng.drain()
    assert tel.metrics.counter("serve.submitted").value == before + 1


# ---------------------------------------------------------------------------
# trace_summary CLI (the CI artifact gate)
# ---------------------------------------------------------------------------


def test_trace_summary_cli(tmp_path, capsys):
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "trace_summary",
        pathlib.Path(__file__).resolve().parent.parent
        / "scripts" / "trace_summary.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    tel = _sample_telemetry()
    good = str(tmp_path / "good.jsonl")
    tel.export_jsonl(good)
    assert mod.main([good, "--metrics", "--require-spans", "2"]) == 0
    out = capsys.readouterr().out
    assert "upload_bytes=10" in out and "run_id: t" in out

    assert mod.main([good, "--require-spans", "99"]) == 1
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "nope"}\n')
    assert mod.main([str(bad)]) == 2
