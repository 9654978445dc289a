"""Steady federated rounds: ``FibecFed.run_round`` in a closed loop.

Set-up runs the init phase once, warms the round program at every curriculum
step bucket a cohort of this population can reach, and drives the first
``check_rounds`` rounds, whose results the reference recomputes after the
window. The window then runs synchronous rounds, each starting when the last
returns, at the fixed round ``round_t`` >= alpha * T, where every client
trains its whole shard.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare
from bench.lib import fl_reference
from bench.lib.fljob import FLJob, host


def _bucket(n: int) -> int:
    """The round program's step count for a cohort whose largest shard has
    ``n`` batches: the next power of two (``repro.data.pipeline.bucket_size``)."""
    return 1 << max(0, int(n) - 1).bit_length()


class Job(FLJob):
    def setup(self) -> None:
        tr, r = self.traffic, self.runner
        self.t = tr["round_t"]
        if self.t < tr["alpha"] * tr["rounds_total"]:
            raise SystemExit("round_t must be >= alpha * rounds_total: whole shards train")
        r.init_phase()
        self.plan = {
            "gal": np.asarray(r.gal_layers, bool),
            "order": [np.asarray(c.order) for c in r.clients],
            "keep": [{t: np.asarray(ab["b"][:, 0, :]) for t, ab in c.neuron_mask["layers"].items()}
                     for c in r.clients],
        }
        self.warm()
        self.checked = []
        for i in range(tr["check_rounds"]):
            st = r.run_round(self.t)
            chosen = np.asarray(r.last_round_info["chosen"])
            rec = {"chosen": chosen, "loss": st["loss"], "global": host(r.global_lora)}
            if i == 0:  # the optimizer's first moments of the round's clients
                rec["m1"] = host(jax.tree.map(lambda x: x[jnp.asarray(chosen)], r._stacked_opt["m"]))
            self.checked.append(rec)

    def reachable_buckets(self) -> List[int]:
        """Step buckets of every cohort this population can draw: the largest
        shard in a cohort of ``k`` is at least the k-th smallest."""
        k = min(self.traffic["cohort"], len(self.batches))
        floor = np.sort(self.batches)[k - 1]
        return sorted({_bucket(n) for n in self.batches if n >= floor})

    def warm(self) -> None:
        """One call of the round program per reachable bucket, on copies of
        the client state and with every step inactive, so that the window
        compiles nothing and the runner's state is untouched."""
        r = self.runner
        kp = r._cohort_pad
        fn = r._round_fn()
        copy = lambda tree: jax.tree.map(lambda x: x.copy(), tree)  # noqa: E731
        w = np.zeros(kp, np.float32)
        w[0] = 1.0
        for S in self.reachable_buckets():
            out = fn(r.params, copy(r.global_lora), copy(r._stacked_lora), copy(r._stacked_opt),
                     r._stacked_mask, r._gal_mask_tree, r._stack_data, r._sample_valid,
                     jnp.asarray(np.arange(kp), jnp.int32),
                     jnp.asarray(np.zeros((kp, S), np.int32)),
                     jnp.asarray(np.zeros((kp, S), np.float32)),
                     jnp.asarray(w), jnp.float32(self.fl.learning_rate))
            jax.block_until_ready(out)

    def step(self) -> Dict[str, Any]:
        st = self.runner.run_round(self.t)
        info = self.runner.last_round_info
        chosen, steps = np.asarray(info["chosen"]), np.asarray(info["client_steps"])
        if not np.array_equal(steps, self.batches[chosen]):
            raise SystemExit("a client trained less than its whole shard")
        return {"loss": st["loss"], "samples": int(self.shards[chosen].sum()),
                "real_steps": int(steps.sum())}

    def end_to_end(self, window_s: float, steps: List[Dict[str, Any]]) -> Dict[str, float]:
        tokens = sum(s["samples"] for s in steps) * self.seq_len
        return {"train_tokens_per_s": tokens / window_s}

    def required_flops(self, steps: List[Dict[str, Any]]) -> float:
        per_seq = self.ref.lora_train_flops(self.sizes, self.seq_len, loss_positions=1)
        return per_seq * sum(s["samples"] for s in steps)

    # -- the comparison with the reference --------------------------------------

    def reference(self, mode: str = "f32", half_batch: bool = False) -> Dict[str, Any]:
        return fl_reference.run_rounds(
            self.ref, self.config, self.params, self.lora0, self.clients, self.plan,
            [c["chosen"] for c in self.checked], batch_size=self.traffic["batch_size"],
            lr=self.fl.learning_rate, mode=mode, half_batch=half_batch)

    def faults(self) -> Dict[str, Dict[str, Any]]:
        """The reference with a fault planted, in the program's place: half
        of every batch left out, the mean taken over the rest."""
        return {"half_batch": self.reference(half_batch=True)}

    def program_outputs(self) -> Dict[str, Any]:
        return {"loss": [c["loss"] for c in self.checked], "m1": self.checked[0]["m1"],
                "global": [c["global"] for c in self.checked]}

    def numbers(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """``loss_gap``: the worst round's mean loss, relative; ``moment_gap``:
        the first round's Adam first moments of the cohort; ``update_gap``:
        the global LoRA's change over the checked rounds on the GAL layers.
        Both by the worst leaf's norm (``compare.norm_gap``), over leaves the
        reference moves."""
        m_got, m_want = jax.tree.leaves(got["m1"]), jax.tree.leaves(want["m1"])
        moving = compare.moving_leaves(m_want)
        d = self._gal_change
        return {
            "loss_gap": max(compare.rel_gap(a, b) for a, b in zip(got["loss"], want["loss"])),
            "moment_gap": compare.norm_gap(m_got, m_want, moving),
            "update_gap": compare.norm_gap(d(got["global"][-1]), d(want["global"][-1]), moving),
        }

    def _gal_change(self, lora) -> List[np.ndarray]:
        """Each leaf's change from the initial LoRA, on the GAL layers."""
        gal = self.plan["gal"]
        return [np.asarray(x, np.float64)[gal] - np.asarray(x0, np.float64)[gal]
                for x, x0 in zip(jax.tree.leaves(lora), jax.tree.leaves(self.lora0))]

    def detail(self, got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, Any]:
        """Per leaf and per round, what ``numbers`` takes the worst of."""
        leaf = lambda g, w: [round(compare.norm_gap([a], [b]), 6) for a, b in zip(g, w)]  # noqa: E731
        d = self._gal_change
        return {
            "loss": [compare.rel_gap(a, b) for a, b in zip(got["loss"], want["loss"])],
            "moment": leaf(jax.tree.leaves(got["m1"]), jax.tree.leaves(want["m1"])),
            "update": [leaf(d(got["global"][r]), d(want["global"][r])) for r in range(len(got["global"]))],
            "update_norms": compare.leaf_norms(d(want["global"][-1])),
        }

    def check(self) -> Dict[str, float]:
        return self.numbers(self.program_outputs(), self.reference())
