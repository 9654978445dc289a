"""Serving benchmark: jitted continuous-batching engine vs the seed loop.

Workload: a mixed multi-tenant batch — 16 requests over 4 LoRA adapters,
mixed prompt lengths (8 / 16) and per-request token budgets (8 / 32),
greedy decode with no EOS so every count below is deterministic.

Two engines serve the identical workload:

- ``reference`` — the seed :class:`repro.serve.ReferenceEngine` (host-side
  decode loop, one adapter at a time). Multi-tenancy forces it to shard the
  workload into per-(adapter, prompt-length) groups served sequentially,
  and each group barriers on its longest request, so short requests pay
  for long ones. Its TTFT is completion-observed: the blocking
  ``generate()`` only exposes tokens when the whole group returns.
- ``continuous`` — :class:`repro.serve.ServeEngine` submit/drain: all 16
  requests queue up front, a slot pool of 8 admits them into freed cache
  slots between jitted decode segments, and every resident request routes
  to its own adapter inside one batched decode step.

Throughput counts *useful* tokens only (each request's own budget; the
reference's barrier-waste decodes cost time but earn nothing), so the
speedup is end-to-end serving throughput on equal delivered work. Decoded
tokens are asserted equal between engines before anything is timed.

Two metrics go to the JSON gate (``scripts/bench_compare.py``):

- ``tokens_per_s/continuous_over_reference`` — measured wall-time speedup
  (machine-dependent; the CI compare is warn-only);
- ``host_dispatches_per_token/reference_over_continuous`` — host→device
  round-trips per useful token, reference over continuous. The reference
  loop pays ``2 + 2*max_new`` dispatches per group (prefill + sample, then
  decode + sample per token); the continuous engine pays 3 per admitted
  prefill group (prefill, first-token sample, admit scatter) plus one per
  jitted segment. Both counts are deterministic functions of the fixed
  workload — no device count or machine can change them — so the ratio
  rides in ``speedups_device_independent`` and always gates.

Usage:  PYTHONPATH=src python benchmarks/serve_bench.py [--json PATH]
Env: REPRO_BENCH_HOST_DEVICES forces the XLA host device count (set before
     jax initializes; the CI recipe is REPRO_BENCH_HOST_DEVICES=8 to match
     the tier1-multidevice regime the committed baseline records).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# must run before jax locks the device count (same idiom as fl_round_bench)
_HOST_DEVICES = os.environ.get("REPRO_BENCH_HOST_DEVICES")
if _HOST_DEVICES and "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_HOST_DEVICES}"
    ).strip()

import jax
import numpy as np

from repro.config import ModelConfig
from repro.models import build_model
from repro.serve import (
    ReferenceEngine,
    Request,
    SamplingParams,
    ServeEngine,
    batch_from_requests,
    make_prompt_batch,
)

SERVE_LM = ModelConfig(
    name="serve-lm", family="dense", num_layers=4, d_model=64, num_heads=4,
    num_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16, rope="full",
    norm="rmsnorm", mlp="swiglu", dtype="float32", lora_rank=4, max_seq_len=64,
)

NUM_REQUESTS = 16
NUM_ADAPTERS = 4
NUM_SLOTS = 8
PROMPT_LENS = (8, 16)
MAX_NEW = (8, 32)
CACHE_LEN = max(PROMPT_LENS) + max(MAX_NEW)


def build_workload(model):
    """16 requests: first half prompt-len 8, second half 16; budgets 8 then
    32 within each half (so every reference group mixes both and barriers);
    adapters round-robin over the registry."""
    rng = jax.random.PRNGKey(0)
    half = NUM_REQUESTS // 2
    toks = {
        L: np.asarray(make_prompt_batch(model.cfg, jax.random.fold_in(rng, L),
                                        half, L)["tokens"])
        for L in PROMPT_LENS
    }
    reqs = []
    for i in range(NUM_REQUESTS):
        L = PROMPT_LENS[0] if i < half else PROMPT_LENS[1]
        mn = MAX_NEW[0] if (i % half) < half // 2 else MAX_NEW[1]
        reqs.append(Request(
            tokens=toks[L][i % half],
            sampling=SamplingParams(max_new_tokens=mn, temperature=0.0),
            adapter_id=i % NUM_ADAPTERS,
        ))
    return reqs


def reference_groups(reqs):
    """Schedule for the seed engine: one blocking generate() per
    (adapter, prompt-length) group, barriered on the group's longest
    budget. Returns [(adapter_id, [request, ...], group_max_new)]."""
    groups = {}
    for r in reqs:
        groups.setdefault((r.adapter_id, len(r.tokens)), []).append(r)
    return [
        (a, rs, max(r.sampling.max_new_tokens for r in rs))
        for (a, _L), rs in sorted(groups.items())
    ]


def run_reference(engine, adapters, groups):
    """Serve every group sequentially; returns (wall_s, ttfts, tokens)."""
    ttfts, tokens = [], {}
    t0 = time.perf_counter()
    for adapter_id, rs, group_max in groups:
        engine.lora = adapters[adapter_id]
        res = engine.generate(
            batch_from_requests(rs), max_new_tokens=group_max
        )
        # blocking API: callers see nothing until the group returns
        t_done = time.perf_counter() - t0
        for row, r in zip(res.tokens, rs):
            ttfts.append(t_done)
            tokens[id(r)] = row[: r.sampling.max_new_tokens].copy()
    return time.perf_counter() - t0, ttfts, tokens


def run_continuous(engine, reqs):
    """Submit everything up front, drain; returns (wall_s, ttfts, tokens,
    stats snapshot)."""
    engine.reset()
    t0 = time.perf_counter()
    by_rid = {}
    for r in reqs:
        rid = engine.submit(Request(
            tokens=r.tokens, sampling=r.sampling, adapter_id=r.adapter_id
        ))
        by_rid[rid] = r
    comps = engine.drain()
    wall = time.perf_counter() - t0
    ttfts = [c.ttft_s for c in comps]
    tokens = {id(by_rid[c.request_id]): c.tokens for c in comps}
    return wall, ttfts, tokens, dict(engine.stats)


def bench_all(trace_dir=None):
    from repro.obs import Telemetry, validate_jsonl

    model = build_model(SERVE_LM)
    rng = jax.random.PRNGKey(7)
    params = model.init_params(rng)
    adapters = [model.init_lora(jax.random.fold_in(rng, i))
                for i in range(NUM_ADAPTERS)]
    reqs = build_workload(model)
    groups = reference_groups(reqs)
    useful = sum(r.sampling.max_new_tokens for r in reqs)

    ref = ReferenceEngine(model, params, adapters[0], cache_len=CACHE_LEN)
    # the continuous engine runs with telemetry ENABLED: the timed pass below
    # doubles as the overhead budget check (spans/counters must stay well
    # under the gate's noise floor) and the token-equality assert proves the
    # instrumented path is bit-identical to the un-instrumented reference
    tel = Telemetry(
        run_id="serve_bench",
        meta={"requests": NUM_REQUESTS, "adapters": NUM_ADAPTERS,
              "num_slots": NUM_SLOTS},
    )
    cont = ServeEngine(
        model, params, adapters[0], adapters=adapters[1:],
        cache_len=CACHE_LEN, num_slots=NUM_SLOTS, max_new_cap=max(MAX_NEW),
        telemetry=tel,
    )

    # warmup (compile both paths), and check the engines agree token-for-token
    _, _, ref_tok = run_reference(ref, adapters, groups)
    _, _, cont_tok, _ = run_continuous(cont, reqs)
    for r in reqs:
        if not np.array_equal(ref_tok[id(r)], cont_tok[id(r)]):
            raise AssertionError(
                f"engines disagree on adapter {r.adapter_id} "
                f"prompt_len {len(r.tokens)}"
            )

    ref_s, ref_ttfts, _ = run_reference(ref, adapters, groups)
    cont_s, cont_ttfts, _, stats = run_continuous(cont, reqs)

    # deterministic host->device round-trip counts (see module docstring)
    ref_disp = sum(2 + 2 * gmax for _a, _rs, gmax in groups)
    cont_disp = 3 * stats["prefill_calls"] + stats["segment_calls"]

    results = {
        "reference": {
            "wall_s": ref_s,
            "tokens_per_s": useful / ref_s,
            "ttft_mean_s": float(np.mean(ref_ttfts)),
            "host_dispatches": ref_disp,
            "groups": len(groups),
        },
        "continuous": {
            "wall_s": cont_s,
            "tokens_per_s": useful / cont_s,
            "ttft_mean_s": float(np.mean(cont_ttfts)),
            "host_dispatches": cont_disp,
            "prefill_calls": stats["prefill_calls"],
            "segment_calls": stats["segment_calls"],
            "jitted_decode_steps": stats["jitted_decode_steps"],
        },
    }
    speedups = {
        "tokens_per_s/continuous_over_reference": ref_s / cont_s,
        "ttft/reference_over_continuous": float(
            np.mean(ref_ttfts) / max(np.mean(cont_ttfts), 1e-9)
        ),
    }
    indep = {
        "host_dispatches_per_token/reference_over_continuous":
            (ref_disp / useful) / (cont_disp / useful),
    }
    rows = [
        f"serve/reference,{1e3 * ref_s:.0f},"
        f"tok_per_s={useful / ref_s:.0f};dispatches={ref_disp}",
        f"serve/continuous,{1e3 * cont_s:.0f},"
        f"tok_per_s={useful / cont_s:.0f};dispatches={cont_disp};"
        f"speedup={ref_s / cont_s:.2f}x",
    ]
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        jsonl = os.path.join(trace_dir, "trace.jsonl")
        tel.export_jsonl(jsonl)
        validate_jsonl(jsonl)
        tel.export_perfetto(os.path.join(trace_dir, "trace.json"))
        print(f"# wrote {trace_dir}/trace.jsonl + trace.json", file=sys.stderr)
    return rows, speedups, indep, results, tel.snapshot()


def write_json(path: str, speedups: dict, indep: dict, results: dict,
               metrics_snapshot: dict = None) -> None:
    payload = {
        "bench": "serve",
        "num_xla_devices": len(jax.devices()),
        "workload": {
            "requests": NUM_REQUESTS,
            "adapters": NUM_ADAPTERS,
            "num_slots": NUM_SLOTS,
            "prompt_lens": list(PROMPT_LENS),
            "max_new_tokens": list(MAX_NEW),
            "useful_tokens": sum(
                (MAX_NEW[0] if (i % (NUM_REQUESTS // 2)) < NUM_REQUESTS // 4
                 else MAX_NEW[1])
                for i in range(NUM_REQUESTS)
            ),
        },
        "engine_metrics": results,
        "speedups": speedups,
        "speedups_device_independent": indep,
        # informational; bench_compare passes it through without gating
        "metrics_snapshot": metrics_snapshot or {},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def run() -> list:
    """benchmarks.run harness entry point."""
    return bench_all()[0]


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable results (e.g. BENCH_serve.json)",
    )
    ap.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="write the continuous engine's trace.jsonl + Perfetto"
             " trace.json there (inspect with scripts/trace_summary.py)",
    )
    args = ap.parse_args()
    rows, speedups, indep, results, snap = bench_all(trace_dir=args.trace_dir)
    for row in rows:
        print(row)
    if args.json:
        write_json(args.json, speedups, indep, results, snap)
        print(f"# wrote {args.json}", file=sys.stderr)
