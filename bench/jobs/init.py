"""The init phase, back to back: ``FibecFed.init_phase()`` in a closed loop,
as each new federated job over this population starts.

Set-up runs it once (the first call loads or compiles its programs). Each
window call scores every client's batches (per-sample Fisher difficulty),
probes each client's layer sensitivity, selects the GAL layers, and warms
the momentum Fisher diagonal into neuron masks. The reference recomputes
the last call's scores and decisions after the window.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np

from bench.lib import compare
from bench.lib import fl_reference
from bench.lib.fljob import FLJob, host

SPANS = ("difficulty", "sensitivity", "fim_warmup")


class Job(FLJob):
    telemetry = True  # the program's init-phase spans feed the per-layer metrics

    def setup(self) -> None:
        self.runner.init_phase()
        self.orders = [np.asarray(c.order) for c in self.runner.clients]

    def step(self) -> Dict[str, Any]:
        events = self.tel.tracer.events
        n0 = len(events)
        self.runner.init_phase()
        spans = {e["name"]: e["dur"] for e in events[n0:]
                 if e.get("type") == "span" and e["name"] in SPANS}
        return {"spans": spans}

    def end_to_end(self, window_s: float, steps: List[Dict[str, Any]]) -> Dict[str, float]:
        return {"init_phase_s": window_s / len(steps)}

    def required_flops(self, steps: List[Dict[str, Any]]) -> float:
        """Per call: a per-sample LoRA gradient of every sample (difficulty)
        and of each warm-up batch's samples (FIM); for the sensitivity probe,
        the gradient with respect to the embeddings of each client's easiest
        batch plus two forward passes of it without the head."""
        s, T, B = self.sizes, self.seq_len, self.traffic["batch_size"]
        ref = self.ref
        grad = ref.lora_train_flops(s, T, loss_positions=1)
        probe = ref.input_grad_flops(s, T, loss_positions=1) + 2 * ref.forward_flops(s, T, 0)
        E = self.traffic["fim_warmup_epochs"]
        total = 0.0
        for n, order in zip(self.shards, self.orders):
            size = lambda b: min(B, int(n) - int(b) * B)  # noqa: E731
            warm = sum(size(order[min(e, len(order) - 1)]) for e in range(E))
            total += int(n) * grad + warm * grad + size(order[0]) * probe
        return total * len(steps)

    def release(self) -> None:
        r = self.runner
        self.got = {
            "difficulty": [np.asarray(c.difficulty, np.float64) for c in r.clients],
            "order": [np.asarray(c.order) for c in r.clients],
            "fim": [host(c.fim) for c in r.clients],
            "keep": [{t: np.asarray(ab["b"][:, 0, :]) for t, ab in c.neuron_mask["layers"].items()}
                     for c in r.clients],
            "sensitivity": [np.asarray(c.layer_scores, np.float64) for c in r.clients],
            "gal": np.asarray(r.gal_layers, bool),
        }
        super().release()

    # -- the comparison with the reference --------------------------------------

    def reference(self, mode: str = "f32") -> Dict[str, Any]:
        tr = self.traffic
        return fl_reference.run_init(
            self.ref, self.config, self.params, self.lora0, self.clients, self.got["order"],
            batch_size=tr["batch_size"], fim_epochs=tr["fim_warmup_epochs"],
            momentum=self.fl.fim_momentum, gamma=self.fl.noise_budget,
            gal_fraction=tr["gal_fraction"], sparse_ratio=tr["sparse_ratio"], mode=mode)

    def faults(self) -> Dict[str, Dict[str, Any]]:
        return {}

    def program_outputs(self) -> Dict[str, Any]:
        return self.got

    def numbers(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """The scores against the reference: each client's batch
        difficulties by the worst client (its largest gap over its largest
        reference value); the layer sensitivities of all clients together
        (the norm of the difference over the reference's norm: one score
        reads the difference of two bf16 forward passes, so single scores
        are noisy); the Fisher diagonals by the worst leaf, all clients
        stacked (``compare.diff_gap``). The decisions against the scores they
        were made from (the program's own): the share of clients whose order,
        of neuron-mask entries and of GAL layers that the decision rules
        would have made otherwise."""
        tr = self.traffic

        def worst(a, b):
            return max(float(np.max(np.abs(x - y)) / max(np.max(np.abs(y)), 1e-30))
                       for x, y in zip(a, b))

        stack = lambda fims: [np.stack(xs) for xs in zip(*[jax.tree.leaves(f) for f in fims])]  # noqa: E731
        keeps = lambda ks: np.concatenate([np.ravel(k[t]) for k in ks for t in sorted(k)])  # noqa: E731
        decided = [fl_reference.decide_keep(f, tr["sparse_ratio"]) for f in got["fim"]]
        gal = fl_reference.decide_gal(got["sensitivity"], self.shards, tr["gal_fraction"])
        sens, sens_ref = np.stack(got["sensitivity"]), np.stack(want["sensitivity"])
        return {
            "difficulty_gap": worst(got["difficulty"], want["difficulty"]),
            "fim_gap": compare.diff_gap(stack(got["fim"]), stack(want["fim"])),
            "sensitivity_gap": float(np.linalg.norm(sens - sens_ref) / np.linalg.norm(sens_ref)),
            "order_mismatch": float(np.mean([
                not np.array_equal(o, fl_reference.decide_order(d))
                for o, d in zip(got["order"], got["difficulty"])])),
            "mask_mismatch": float(np.mean(keeps(got["keep"]) != keeps(decided))),
            "gal_mismatch": float(np.mean(got["gal"] != gal)),
        }

    def check(self) -> Dict[str, float]:
        return self.numbers(self.got, self.reference())
