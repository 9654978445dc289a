"""The configuration's reference module as the one place where the harness
learns an architecture: its sizes, weights and operation counts.

    PYTHONPATH=src python -m pytest -q bench/tests/test_arch_hooks.py
"""
from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.lib import spec  # noqa: E402
from bench.lib.fljob import model_config  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 3000000019
RECORDING = REPO / "bench" / "tests" / "arch" / "recording_decoder.py"


def _digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)}|{x.dtype}|{x.shape}|".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def _tiny_config():
    config = json.loads((REPO / "bench" / "configs" / "qwen2-0.5b.json").read_text())
    config.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
    return config


def _tiny(tmp_path, **kw):
    root = tiny.make_root(tmp_path, **kw)
    bench = spec.load_benchmark(root)
    cell = bench["workloads"][0]
    return root, spec.load_config(root, bench, cell["config"]), spec.load_traffic(root, cell["traffic"])


# Recorded on the parent commit of the hooks, from ``bench/lib/weights.make_weights``
# (then the only weights) at the tiny root's sizes and seed 3000000019, on the
# CPU: sha256 over every leaf's path, type, shape and bytes, base then LoRA.
PARENT_WEIGHTS = {
    "qwen2-0.5b": ("b00b2af9bec39b995eb8c1d179888ea23d5442c64dce92f28bc112fe011ec5a5",
                   "d388fdc7bc3b40e0c79e95f4604641d4a6e035c61286f35e16fdf4292c1b892a"),
    "qwen3-0.6b": ("050c9f3c8a8ca3c546561a127fe40f939b31d1b577ac92b79ed5b5fe3532d14e",
                   "eaf312855ca3c3c754f275863ba5cf815f75976e7da6d42e660166924af2e78e"),
}


@pytest.mark.parametrize("base", sorted(PARENT_WEIGHTS))
def test_the_dense_hooks_make_the_parents_weights_bit_for_bit(tmp_path, base):
    root, config, _ = _tiny(tmp_path, base=base)
    ref = spec.load_reference(root, config["reference"])
    params, lora = ref.make_weights(SEED, ref.sizes(config), config["run_as"])
    assert (_digest(params), _digest(lora)) == PARENT_WEIGHTS[base]


# A job's required operations on the parent commit, at the tiny root's sizes,
# seed 3000000019, after set-up and one window step (CPU): the rounds step
# trained 18 samples; the init count follows the curriculum orders.
PARENT_REQUIRED_FLOPS = {"rounds": 103440384.0, "init": 283377664.0}


@pytest.mark.parametrize("kind", sorted(PARENT_REQUIRED_FLOPS))
def test_the_jobs_take_the_architecture_from_the_reference_module(tmp_path, kind):
    import jax
    import numpy as np

    root, config, traffic = _tiny(tmp_path, kind=kind, reference=RECORDING)
    ref = spec.load_reference(root, config["reference"])
    job = spec.load_job(root, traffic["kind"]).Job(config=config, traffic=traffic, seed=SEED,
                                                   reference=ref, chips=1)
    assert job.sizes["arch"] == "recording"
    assert ref.CALLS == ["sizes", "make_weights"]
    params, lora = ref.MADE[0]
    assert job.params is params
    for a, b in zip(jax.tree.leaves(job.runner.params), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(job.lora0), jax.tree.leaves(lora)):
        assert np.array_equal(a, np.asarray(b))
    job.setup()
    steps = [job.step()]
    del ref.CALLS[:]
    got = job.required_flops(steps)
    assert "lora_train_flops" in ref.CALLS
    # the recording module's counts are twice the dense ones: the same
    # count as the parent's, doubled, to the last digit
    assert got == ref.FLOPS_SCALE * PARENT_REQUIRED_FLOPS[kind]


def test_a_sub_config_in_run_as_becomes_its_dataclass():
    from repro.config import MoEConfig

    config = _tiny_config()
    config["run_as"]["moe"] = {"num_experts": 8, "top_k": 2, "d_ff_expert": 32}
    cfg = model_config(config)
    assert isinstance(cfg.moe, MoEConfig)
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_ff_expert) == (8, 2, 32)
    assert cfg.ssm is None and cfg.qkv_bias is True


def test_an_unknown_key_in_a_sub_config_raises():
    config = _tiny_config()
    config["run_as"]["moe"] = {"num_experts": 8, "n_routed_experts": 64}
    with pytest.raises(TypeError, match="n_routed_experts"):
        model_config(config)


@pytest.mark.parametrize("hook", spec.ARCH_HOOKS)
def test_a_reference_module_without_a_hook_is_refused(tmp_path, hook):
    refs = tmp_path / "bench" / "reference"
    refs.mkdir(parents=True)
    others = "".join(f"def {h}(*a):\n    return 0\n\n" for h in spec.ARCH_HOOKS if h != hook)
    (refs / "partial.py").write_text(others)
    with pytest.raises(spec.SpecError, match=hook):
        spec.load_reference(tmp_path, "partial")
