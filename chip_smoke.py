#!/usr/bin/env python3
"""Bring-up check: federated LoRA rounds at qwen2-0.5b published widths on a TPU.

Drives the system's main path through the entry points a user calls --
``make_runner(...)`` -> ``init_phase()`` -> ``run_round(t)`` on the
vectorized engine -- with ``ARCHS["qwen2-0.5b"]`` at its published widths in
bfloat16 (random weights from ``SEED``), then serves the trained adapter
with ``ServeEngine``. Every check is fatal: a failed check makes the script
exit non-zero without printing the result line.

  python chip_smoke.py             one chip: the main path and six checks
  python chip_smoke.py --chips 4   engine="sharded" (init phase and rounds) on
                                   a four-chip client mesh against the
                                   vectorized engine on one chip, and nothing
                                   else

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
with the device as JAX reports it. The ``phase`` lines before it (compile
seconds, run seconds, losses, peak device memory) are informational, not
measurements. ``--reduced`` rehearses the script on a CPU at a tiny width:
it lifts the TPU requirement and is never used on the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0  # weights, data, partition and cohort sampling

# Tolerances, each with its reason. bfloat16 keeps 8 significant bits (a
# relative rounding step of 2^-8 = 3.9e-3 per operation); the engines under
# comparison round in different places (fusion decisions differ between a
# vmapped and an unvmapped program), so bf16 forward/backward results agree
# to a small multiple of that step, not bit for bit.
LOSS_RTOL = 1e-2  # per-round mean loss, engine vs engine
# Relative Frobenius distance of each GAL leaf's round update between two
# engines. The oracle pairs train with SGD, whose update is linear in the
# gradient, so gradient rounding shows up at its own size. (Adam's first
# steps are lr * sign(g): bf16 rounding flips the sign of near-zero gradient
# entries and turns that noise into O(1) differences there.) Measured on a
# v5e at these shapes, loop against vectorized: 2.79e-2 in bf16 (worst on the
# wq/wk ``b`` factors, whose gradients pass the softmax backward) and 3.3e-6
# with the base in float32 at "highest" precision, so the bf16 gap is where
# the programs round. Faults planted in the loop round read 6.8e-2 (one
# cohort client's FedAvg weight x1.25), 0.23 (x2) and 0.25 (that client's one
# curriculum step dropped); the limit sits between the sound and the
# smallest fault reading.
UPDATE_RTOL = 4e-2
# An update smaller than this fraction of its leaf is measured against the
# leaf instead: with b = 0 at init, SGD barely moves the LoRA ``a`` factors in
# one round, and their updates sit at the float32 rounding of the leaf itself
# (which the engines' differently ordered FedAvg sums round differently).
UPDATE_FLOOR = 1e-4
# fused vs unfused AdamW: same gradients, the kernel recomputes the same f32
# elementwise update, so only f32 rounding separates them
FUSED_RTOL = 1e-3
# the init-phase scores both oracle engines compute from the same inputs in
# bf16: per-batch Fisher difficulty (sums of squared gradients) and
# momentum-FIM diagonals (squared gradients, so twice the gradients'
# relative rounding: 3.2e-2 measured on a v5e)
SCORE_RTOL = 5e-2
# bf16 model vs the same forward with float32 params at "highest" matmul
# precision. The loss at the label token sits near log(vocab) ~ 12 whatever
# the weights, so it is checked, but the logits carry the comparison: max
# |logit difference| over max |logit|, every position of the batch. Measured
# on a v5e: loss 9.7e-5, logits 1.67e-2 (24 layers of bf16 rounding).
F32_LOSS_RTOL = 1e-3
F32_LOGITS_RTOL = 3e-2
# serve prefill vs full forward at the prompt's last position: same weights,
# two bf16 code paths (cache-filling scan with per-slot adapters vs the
# training forward); max |logit difference| over max |logit|
LOGITS_RTOL = 2e-2


class CheckFailed(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


class Checks:
    """Records every tolerance check; the script fails if any failed."""

    def __init__(self):
        self.failed = []

    def within(self, name: str, value: float, limit: float) -> None:
        ok = bool(value <= limit)  # NaN fails
        print(f"check {name}: {value:.3e} <= {limit:.1e} {'ok' if ok else 'FAILED'}",
              flush=True)
        if not ok:
            self.failed.append(f"{name}: {value:.3e} > {limit:.1e}")


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny widths for a CPU rehearsal (never on the chip)")
    return ap.parse_args()


def main() -> int:
    args = parse_args()
    import jax

    dev = jax.devices()[0]
    if not args.reduced and dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices; JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    smoke = Smoke(args)
    if args.chips == 4:
        smoke.sharded_vs_vectorized()
    else:
        smoke.one_chip()
    for d in jax.devices()[: args.chips]:
        stats = d.memory_stats() or {}
        print(f"{d}: " + " ".join(
            f"{k}={stats.get(k, 'not reported')}"
            for k in ("peak_bytes_in_use", "bytes_limit", "largest_alloc_size")
        ), flush=True)
    if smoke.checks.failed:
        print("FAILED: " + "; ".join(smoke.checks.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": args.chips,
    }}))
    return 0


class Smoke:
    def __init__(self, args):
        import jax
        import numpy as np

        from repro.config import FibecFedConfig
        from repro.configs import ARCHS
        from repro.data import dirichlet_partition, make_keyword_task
        from repro.models import build_model
        from repro.train import make_loss_fn

        self.args = args
        self.dev = jax.devices()[0]
        self.checks = Checks()
        self._compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

        cfg = ARCHS["qwen2-0.5b"]
        seq_len, n_samples = 128, 128
        if args.reduced:
            cfg = cfg.reduced(dtype="bfloat16", lora_rank=cfg.lora_rank)
            seq_len, n_samples = 32, 64
        self.cfg = cfg
        self.model = build_model(cfg)
        self.loss_fn = make_loss_fn(self.model)
        task = make_keyword_task(
            n_samples=n_samples, seq_len=seq_len, vocab_size=cfg.vocab_size,
            seed=SEED,
        )
        self.fl = FibecFedConfig(
            num_devices=8, devices_per_round=4, batch_size=4,
            fim_warmup_epochs=1, seed=SEED,
        )
        B = self.fl.batch_size
        parts = dirichlet_partition(
            task.data["label"], self.fl.num_devices, alpha=1.0, seed=SEED,
            min_per_client=B,
        )
        # whole batches only: the loop engine compiles its per-batch programs
        # once per batch shape, and each compile at published widths costs
        # tens of seconds (the padded path is covered by the unit tests)
        self.clients = [
            {k: v[p[: len(p) // B * B]] for k, v in task.data.items() if k != "label"}
            for p in parts
        ]
        # the runners' own LoRA init (FibecFed folds 1 into the seed key)
        self.init_lora = jax.tree.map(
            np.asarray,
            self.model.init_lora(jax.random.fold_in(jax.random.PRNGKey(SEED), 1)),
        )
        print(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
              f"vocab={cfg.vocab_size} dtype={cfg.dtype} lora_rank={cfg.lora_rank}; "
              f"{len(self.clients)} clients, sizes "
              f"{[len(c['tokens']) for c in self.clients]}, seq_len={seq_len}",
              flush=True)

    # -- bookkeeping ---------------------------------------------------------

    # lowering and XLA compilation of top-level programs (tracing is left
    # out: nested jits report their own trace events inside the outer one)
    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def _on_event(self, event, duration, **_):
        if event in self._COMPILE_EVENTS:
            self._compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        """Prints ``phase <name>: compile_s run_s ...`` for the block; the
        block adds its own fields (losses) to the yielded dict."""
        info = {}
        c0, t0 = self._compile_s, time.perf_counter()
        yield info
        wall = time.perf_counter() - t0
        comp = self._compile_s - c0
        extra = " ".join(f"{k}={v}" for k, v in info.items())
        print(f"phase {name}: compile_s={comp:.1f} run_s={wall - comp:.1f} {extra}",
              flush=True)

    def runner(self, engine: str, *, optimizer="adamw", fused=False, fl=None,
               mesh=None, params=None):
        """A fibecfed runner over the smoke's world. ``params``: base weights
        to use instead of the runner's own copy (identical, from the same
        seed), so that one copy of the frozen base stays on the chip."""
        from repro.federated import make_runner

        r = make_runner(
            "fibecfed", self.model, self.loss_fn, fl or self.fl, self.clients,
            seed=SEED, optimizer=optimizer, fused_optimizer=fused,
            engine=engine, mesh=mesh,
        )
        if params is not None:
            r.params = params
        return r

    def init(self, runner, name: str) -> None:
        with self.phase(f"{name}/init_phase"):
            runner.init_phase()

    def rounds(self, runner, name: str, n: int):
        """``n`` rounds; returns per-round losses and a host copy of the
        global LoRA after each round."""
        import jax
        import numpy as np

        losses, globals_ = [], []
        for t in range(n):
            with self.phase(f"{name}/round{t}") as info:
                stats = runner.run_round(t)
                info["loss"] = f"{stats['loss']:.6f}"
                if "padded_steps" in stats:
                    info["padded_steps"] = int(stats["padded_steps"])
            require(np.isfinite(stats["loss"]), f"{name} round {t} loss is not finite")
            losses.append(stats["loss"])
            # host copy now: the next round donates these buffers
            globals_.append(jax.tree.map(np.asarray, runner.global_lora))
        self.last_stats = stats
        return losses, globals_

    def update_errors(self, name, got, want, gal_layers, limit):
        """Per-leaf distance of the GAL layers of two global LoRAs, relative
        to the round's update (global LoRA minus init; see UPDATE_FLOOR)."""
        import jax
        import numpy as np

        sel = np.asarray(gal_layers, bool)
        worst = 0.0
        for (path, g), w, i in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree.leaves(want), jax.tree.leaves(self.init_lora),
        ):
            g = np.asarray(g, np.float64)[sel]
            w = np.asarray(w, np.float64)[sel]
            scale = max(np.linalg.norm(w - i[sel]), UPDATE_FLOOR * np.linalg.norm(w))
            err = float(np.linalg.norm(g - w) / max(scale, 1e-30))
            worst = max(worst, err)
            print(f"  {name} {jax.tree_util.keystr(path)}: update rel err {err:.3e}")
        self.checks.within(f"{name} GAL LoRA update", worst, limit)

    # -- one chip --------------------------------------------------------------

    def one_chip(self):
        import jax
        import numpy as np

        cfg, dev = self.cfg, self.dev

        # main path: fibecfed, AdamW, vectorized engine, three rounds
        main = self.runner("vectorized")
        self.init(main, "main")
        losses, globals_ = self.rounds(main, "main", 3)
        leaves = jax.tree.leaves(main.params)
        require({str(x.dtype) for x in leaves} == {"bfloat16"},
                "base params are not all bfloat16")
        require(all(x.devices() == {dev} for x in leaves),
                f"base params do not all live on {dev}")
        hd = cfg.resolved_head_dim
        require(main.params["layers"]["wq"].shape
                == (cfg.num_layers, cfg.d_model, cfg.num_heads * hd),
                "wq is not at the configured width")
        if not self.args.reduced:
            require((cfg.num_layers, cfg.d_model) == (24, 896),
                    "not qwen2-0.5b's published widths")
        print(f"check main: losses {['%.6f' % v for v in losses]} finite, params "
              f"bfloat16 on {dev.device_kind}, wq {main.params['layers']['wq'].shape}",
              flush=True)
        gal_layers = np.asarray(main.gal_layers)
        del leaves

        self.fused_round(main, losses[0], globals_[0], gal_layers)
        self.f32_reference(main, globals_[-1])
        self.serve(main, globals_[-1])
        del main
        gc.collect()
        self.loop_oracle()

    def fused_round(self, main, loss0, global0, gal_layers):
        """Check 5: one round with the fused Pallas masked-update kernels."""
        import jax
        import numpy as np

        from repro.kernels.ops import MIN_KERNEL_LEAF

        fused = self.runner("vectorized", fused=True, params=main.params)
        self.init(fused, "fused")
        flosses, fglobals = self.rounds(fused, "fused", 1)
        require(np.array_equal(np.asarray(fused.gal_layers), gal_layers),
                "fused runner chose other GAL layers from the same init programs")
        self.checks.within("fused vs unfused round-0 loss",
                           abs(flosses[0] - loss0) / abs(loss0), LOSS_RTOL)
        self.update_errors("fused vs unfused", fglobals[0], global0, gal_layers,
                           FUSED_RTOL)
        sizes = [x.size for x in jax.tree.leaves(self.init_lora)]
        n_kernel = sum(s >= MIN_KERNEL_LEAF for s in sizes)
        print(f"kernel leaves: {n_kernel} of {len(sizes)} LoRA leaves take the "
              f"masked-update kernel, {len(sizes) - n_kernel} the sub-tile oracle "
              f"(MIN_KERNEL_LEAF={MIN_KERNEL_LEAF})", flush=True)
        with self.phase("fused/round_program"):
            compiled = round_program(fused, int(self.last_stats["padded_steps"]))
        mem = compiled.memory_analysis()
        if mem is not None:
            print(f"fused round program: arguments {mem.argument_size_in_bytes} B, "
                  f"temporaries {mem.temp_size_in_bytes} B", flush=True)
        has_kernel = "tpu_custom_call" in compiled.as_text()
        print(f"check fused round program contains tpu_custom_call: {has_kernel}",
              flush=True)
        if not self.args.reduced:
            require(has_kernel, "the fused round program has no tpu_custom_call")
        del fused
        gc.collect()

    def loop_oracle(self):
        """Check 3: round 0 on engine="loop" (the semantic spec) against
        engine="vectorized", both SGD, from the same seed. Both run their
        own init phase; the loop runner then takes the vectorized runner's
        decisions (``take_init_decisions``).
        """
        vec = self.runner("vectorized", optimizer="sgd")
        self.init(vec, "oracle-vectorized")
        loop = self.runner("loop", optimizer="sgd", params=vec.params)
        self.init(loop, "oracle-loop")
        self.take_init_decisions("loop", loop, vec)

        vlosses, vglobals = self.rounds(vec, "oracle-vectorized", 1)
        llosses, lglobals = self.rounds(loop, "oracle-loop", 1)
        self.checks.within("loop vs vectorized round-0 loss",
                           abs(llosses[0] - vlosses[0]) / abs(vlosses[0]), LOSS_RTOL)
        self.update_errors("loop vs vectorized", lglobals[0], vglobals[0],
                           vec.gal_layers, UPDATE_RTOL)
        del vec, loop
        gc.collect()

    def take_init_decisions(self, name, runner, oracle):
        """Compares the init-phase scores ``runner`` and ``oracle`` computed
        with their own programs, then hands ``runner`` the oracle's discrete
        decisions (curriculum orders, neuron masks, GAL layers), so that the
        rounds compare round programs and not which side of a near-tie bf16
        rounding put a batch or a neuron on."""
        import jax
        import numpy as np

        diff_err = max(
            float(np.max(np.abs(rc.difficulty - oc.difficulty))
                  / max(np.max(np.abs(oc.difficulty)), 1e-30))
            for rc, oc in zip(runner.clients, oracle.clients)
        )
        self.checks.within(f"{name} vs vectorized difficulty scores", diff_err,
                           SCORE_RTOL)
        fim_err = max(
            rel_fro(a, b)
            for rc, oc in zip(runner.clients, oracle.clients)
            for a, b in zip(jax.tree.leaves(rc.fim), jax.tree.leaves(oc.fim))
        )
        self.checks.within(f"{name} vs vectorized FIM diagonals", fim_err, SCORE_RTOL)
        flips = share_init_decisions(runner, oracle)
        print(f"init decisions the {name} runner took over: {flips}", flush=True)

    def f32_reference(self, main, trained):
        """Check 4: one client batch through the bf16 model against the same
        forward with params cast to float32 at "highest" matmul precision:
        the loss and the logits at every position."""
        import jax
        import jax.numpy as jnp

        batch = {k: jnp.asarray(v[: self.fl.batch_size])
                 for k, v in self.clients[0].items()}
        lora = jax.tree.map(jnp.asarray, trained)

        @jax.jit
        def loss_and_logits(p, l, b):
            logits, _ = self.model.forward(p, l, b)
            return self.loss_fn(p, l, b), logits.astype(jnp.float32)

        with self.phase("f32_reference") as info:
            got, got_logits = loss_and_logits(main.params, lora, batch)
            params32 = jax.tree.map(lambda x: x.astype(jnp.float32), main.params)
            with jax.default_matmul_precision("highest"):
                ref, ref_logits = loss_and_logits(params32, lora, batch)
            del params32
            logits_err = float(jnp.max(jnp.abs(got_logits - ref_logits))
                               / jnp.max(jnp.abs(ref_logits)))
            got, ref = float(got), float(ref)
            info["loss_bf16"] = f"{got:.6f}"
            info["loss_f32"] = f"{ref:.6f}"
        self.checks.within("bf16 vs f32 reference loss", abs(got - ref) / abs(ref),
                           F32_LOSS_RTOL)
        self.checks.within("bf16 vs f32 reference logits", logits_err, F32_LOGITS_RTOL)

    def serve(self, main, trained):
        """Check 6: the trained adapter and the init adapter served side by
        side; prefill logits against the full forward."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.lora import gather_adapter_slots, stack_adapter_trees
        from repro.serve import Request, SamplingParams, ServeEngine

        prompt_len, new_tokens, n_req = 64, 16, 4
        cache_len = prompt_len + new_tokens
        trained = jax.tree.map(jnp.asarray, trained)
        init = jax.tree.map(jnp.asarray, self.init_lora)
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(SEED + 1), (n_req, prompt_len), 0,
            self.cfg.vocab_size, jnp.int32))
        eng = ServeEngine(self.model, main.params, trained, adapters=[init],
                          num_slots=4, cache_len=cache_len, max_new_cap=new_tokens)
        with self.phase("serve") as info:
            for i in range(n_req):
                eng.submit(Request(
                    tokens=prompts[i], adapter_id=i % 2,
                    sampling=SamplingParams(max_new_tokens=new_tokens),
                ))
            comps = eng.drain()
            info["completed"] = len(comps)
            info["tokens"] = sum(c.steps for c in comps)
        require(len(comps) == n_req, f"{len(comps)} of {n_req} requests completed")
        for c in comps:
            require(c.steps == new_tokens and c.finish_reason == "length",
                    f"request {c.request_id} stopped after {c.steps} tokens "
                    f"({c.finish_reason})")
        print(f"check serve: {n_req} requests over 2 adapters, {new_tokens} tokens each",
              flush=True)

        # request 0's prefill, through the per-slot adapter path the engine uses
        batch = {"tokens": jnp.asarray(prompts[:1])}
        slots = gather_adapter_slots(
            self.cfg, stack_adapter_trees([trained, init]), jnp.zeros((1,), jnp.int32))
        with self.phase("serve/prefill_vs_forward"):
            pre, _, _ = jax.jit(
                lambda p, l, b: self.model.prefill(p, l, b, cache_len))(
                main.params, slots, batch)
            fwd, _ = jax.jit(self.model.forward)(main.params, trained, batch)
            pre = np.asarray(pre[0, -1], np.float64)
            fwd = np.asarray(fwd[0, -1], np.float64)
        self.checks.within("prefill vs forward last-position logits",
                           float(np.max(np.abs(pre - fwd)) / np.max(np.abs(fwd))),
                           LOGITS_RTOL)

    # -- four chips --------------------------------------------------------------

    def sharded_vs_vectorized(self):
        """engine="sharded" on a four-chip client mesh against the vectorized
        engine on one chip: the fibecfed preset, same seed, same world,
        cohort of 8, SGD (see UPDATE_RTOL). Each runs its own init phase (the
        sharded difficulty and FIM-warmup programs among them); the sharded
        runner then takes the vectorized runner's decisions
        (``take_init_decisions``) and both run three neuron-masked rounds."""
        import dataclasses

        import jax
        import numpy as np

        from repro.launch.mesh import make_client_mesh

        fl = dataclasses.replace(self.fl, devices_per_round=8)
        mesh = make_client_mesh(4)
        vec = self.runner("vectorized", optimizer="sgd", fl=fl)
        self.init(vec, "vectorized")
        shd = self.runner("sharded", optimizer="sgd", fl=fl, mesh=mesh)
        self.init(shd, "sharded")
        self.take_init_decisions("sharded", shd, vec)
        slosses, sglobals = self.rounds(shd, "sharded", 3)
        devices = {d for x in jax.tree.leaves(shd._stacked_lora)
                   for d in x.sharding.device_set}
        rows = {s.data.shape[0] for x in jax.tree.leaves(shd._stacked_lora)
                for s in x.addressable_shards}
        n_stack = jax.tree.leaves(shd._stacked_lora)[0].shape[0]
        print(f"sharded client stack: {n_stack} clients over {len(devices)} devices, "
              f"{sorted(rows)} rows per shard", flush=True)
        require(len(devices) == 4, f"the stacked client trees sit on {len(devices)} devices")
        require(rows == {n_stack // 4}, "the client stack is not split four ways")
        gal_layers = np.asarray(shd.gal_layers)
        del shd
        gc.collect()

        vlosses, vglobals = self.rounds(vec, "vectorized", 3)
        vdevs = {d for x in jax.tree.leaves(vec._stacked_lora)
                 for d in x.sharding.device_set}
        require(vdevs == {self.dev}, f"the vectorized stack sits on {vdevs}")
        for t, (a, b) in enumerate(zip(slosses, vlosses)):
            self.checks.within(f"sharded vs vectorized round-{t} loss",
                               abs(a - b) / abs(b), LOSS_RTOL)
        self.update_errors("sharded vs vectorized", sglobals[-1], vglobals[-1],
                           gal_layers, UPDATE_RTOL)


def rel_fro(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def share_init_decisions(dst, src) -> dict:
    """Install ``src``'s curriculum orders, neuron masks and GAL layers on
    ``dst`` -- a loop runner's per-client masks, or a stacked engine's mask
    stack placed on ``dst``'s mesh; returns how many of each differed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine as eng
    from repro.lora import gal_mask_tree

    flips = {"orders": 0, "mask_entries": 0,
             "gal_layers": int(np.sum(np.asarray(dst.gal_layers)
                                      != np.asarray(src.gal_layers)))}
    for dc, sc in zip(dst.clients, src.clients):
        flips["orders"] += int(not np.array_equal(dc.order, sc.order))
        flips["mask_entries"] += sum(
            int(np.sum(np.asarray(a) != np.asarray(b)))
            for a, b in zip(jax.tree.leaves(dc.neuron_mask),
                            jax.tree.leaves(sc.neuron_mask)))
        dc.order = np.array(sc.order)
        dc.neuron_mask = jax.tree.map(jnp.array, sc.neuron_mask)
    if dst._stacked_engine:
        # the stacked round reads the mask stack, not the per-client views
        require(jax.tree.map(jnp.shape, dst._stacked_mask)
                == jax.tree.map(jnp.shape, src._stacked_mask),
                "the two runners stack their clients differently")
        mask = jax.tree.map(jnp.array, src._stacked_mask)
        if dst.mesh is not None:
            mask = jax.device_put(mask, eng.client_sharding(dst.mesh))
        dst._stacked_mask = mask
        for ci, dc in enumerate(dst.clients):
            dc.neuron_mask = jax.tree.map(lambda x, ci=ci: x[ci], mask)
    if flips["gal_layers"]:
        dst.gal_layers = np.array(src.gal_layers)
        tree = gal_mask_tree(dst.cfg, dst.global_lora, dst.gal_layers)
        if dst.mesh is not None:
            tree = jax.device_put(tree, eng.replicated_sharding(dst.mesh))
        dst._gal_mask_tree = tree
        dst._gal_leaf_cache = None
        dst._comm_bytes_cache = {}
    return flips


def round_program(runner, padded_steps: int):
    """The runner's compiled round program at the shapes its last round ran
    with (``padded_steps`` curriculum steps); a compile-cache hit."""
    import jax
    import jax.numpy as jnp

    k = min(runner.fl.devices_per_round, len(runner.clients))
    spec = lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))  # noqa: E731
    args = jax.tree.map(spec, (
        runner.params, runner.global_lora, runner._stacked_lora, runner._stacked_opt,
        runner._stacked_mask, runner._gal_mask_tree, runner._stack_data,
        runner._sample_valid,
    )) + (
        jax.ShapeDtypeStruct((k,), jnp.int32),
        jax.ShapeDtypeStruct((k, padded_steps), jnp.int32),
        jax.ShapeDtypeStruct((k, padded_steps), jnp.float32),
        jax.ShapeDtypeStruct((k,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    return runner._round_fn().lower(*args).compile()


if __name__ == "__main__":
    sys.exit(main())
