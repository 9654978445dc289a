"""The federated job every cell drives: a ``FibecFed`` runner built through
``make_runner`` over the benchmark's own weights and data.

The architecture comes from the configuration's reference module (``ref``):
its ``sizes``, its ``make_weights`` and its operation counts. Nothing here
knows a layout."""
from __future__ import annotations

import dataclasses
import typing
from typing import Any, Dict

import jax
import numpy as np

from bench.lib import traffic as gen
from bench.lib.weights import check_layout

# published config.json keys -> the program's ModelConfig fields
HF_TO_MODEL = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(config: Dict[str, Any]):
    from repro.config import ModelConfig

    kw = {dst: config[src] for src, dst in HF_TO_MODEL.items()}
    kw.update(config["run_as"])
    return _from_dict(ModelConfig, dict(kw, name=config["name"]))


def _from_dict(cls, kw: Dict[str, Any]):
    """``cls(**kw)``, where a dict under a field whose type is a dataclass (or
    an ``Optional`` of one) becomes that dataclass, by the same rule. A key
    that is no field raises ``TypeError``."""
    hints = typing.get_type_hints(cls)
    out = {}
    for k, v in kw.items():
        hint = hints.get(k)
        sub = next((t for t in (hint, *typing.get_args(hint)) if dataclasses.is_dataclass(t)), None)
        out[k] = _from_dict(sub, v) if sub is not None and isinstance(v, dict) else v
    return cls(**out)


def host(tree):
    return jax.tree.map(np.asarray, tree)


class FLJob:
    """Builds the runner. Subclasses (``bench/jobs/<kind>.py``) add the
    set-up that kind needs, one unit of window work (``step``), the
    end-to-end metrics and the comparison with the reference."""

    telemetry = False  # spans for per-layer metrics that read them

    def __init__(self, *, config, traffic, seed: int, reference, chips: int):
        from repro.config import FibecFedConfig
        from repro.federated import make_runner
        from repro.models import build_model
        from repro.obs import Telemetry
        from repro.train import make_loss_fn

        self.config, self.traffic, self.seed, self.ref = config, traffic, seed, reference
        self.chips = chips
        self.cfg = model_config(config)
        self.sizes = reference.sizes(config)
        self.seq_len = traffic["seq_len"]
        self.shards = gen.shard_sizes(traffic)
        self.batches = np.asarray([gen.batches_of(n, traffic["batch_size"]) for n in self.shards])

        model = build_model(self.cfg)
        key = jax.random.PRNGKey(0)
        params, lora = reference.make_weights(seed, self.sizes, config["run_as"])
        check_layout(params, jax.eval_shape(model.init_params, key), "base weight")
        check_layout(lora, jax.eval_shape(model.init_lora, key), "LoRA")
        self.params = params
        self.lora0 = host(lora)  # the reference's copy: the program donates its own
        # handed over once: the program keeps its models (and so these
        # closures) in a process-wide cache, which must not keep the weights
        given = {"params": params, "lora": lora}
        model = dataclasses.replace(
            model, init_params=lambda _k: given.pop("params"),
            init_lora=lambda _k: given.pop("lora"))
        self.clients = gen.make_clients(traffic, self.cfg.vocab_size, seed)
        fl = FibecFedConfig(
            num_devices=traffic["population"],
            devices_per_round=traffic["cohort"],
            rounds=traffic["rounds_total"],
            local_epochs=1,
            batch_size=traffic["batch_size"],
            learning_rate=traffic["learning_rate"],
            curriculum="linear",
            beta_initial_ratio=traffic["beta"],
            alpha_full_data=traffic["alpha"],
            gal_fraction=traffic["gal_fraction"],
            fim_warmup_epochs=traffic["fim_warmup_epochs"],
            sparse_ratio=traffic["sparse_ratio"],
            dirichlet_alpha=traffic["dirichlet_alpha"],
            seed=traffic["cohort_seed"],
        )
        self.fl = fl
        mesh = None
        if traffic["engine"] == "sharded":
            from repro.launch.mesh import make_client_mesh

            mesh = make_client_mesh(chips)
        self.tel = Telemetry() if self.telemetry else None
        self.runner = make_runner(
            "fibecfed", model, make_loss_fn(model), fl, self.clients,
            seed=traffic["cohort_seed"], optimizer=traffic["optimizer"],
            engine=traffic["engine"], mesh=mesh, telemetry=self.tel,
        )

    def host_spans(self):
        """The program's wall-clock spans as ``(name, start, end)`` on
        ``time.perf_counter``'s clock."""
        if self.tel is None:
            return []
        epoch = self.tel.tracer.epoch
        return [(e["name"], epoch + e["ts"], epoch + e["ts"] + e["dur"])
                for e in self.tel.tracer.events if e.get("type") == "span" and e.get("clock") == "wall"]

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.runner = None
