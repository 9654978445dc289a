"""Distributed step semantics on a 1x1 host mesh (structure, not scale):
the FibecFed train step's merge/mask/aggregate algebra must be exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.launch import shardings as shd
from repro.launch.steps import build_train_step, make_train_state
from repro.lora import gal_mask_tree, lora_num_logical_layers
from repro.models import build_model

CFG = ModelConfig(
    name="tiny-lm", family="dense", num_layers=2, d_model=32, num_heads=2,
    num_kv_heads=2, d_ff=64, vocab_size=256, head_dim=16, dtype="float32",
    lora_rank=2, max_seq_len=64,
)


@pytest.fixture(scope="module")
def world(rng):
    model = build_model(CFG)
    params = model.init_params(rng)
    n_groups = 2
    state = make_train_state(model, rng, n_groups)
    gal = np.array([True, False])
    state["gal_mask"] = gal_mask_tree(CFG, state["gal_lora"], gal)
    state["local_mask"] = jax.tree.map(jnp.ones_like, state["local_mask"])
    batch = {"tokens": jax.random.randint(rng, (4, 16), 0, CFG.vocab_size)}
    return model, params, state, batch, gal


def test_train_step_runs_and_loss_finite(world):
    model, params, state, batch, gal = world
    step = jax.jit(build_train_step(model, n_groups=2))
    new_state, metrics = step(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state["step"]) == 1


def test_gal_updates_are_shared_local_are_not(world):
    model, params, state, batch, gal = world
    step = jax.jit(build_train_step(model, n_groups=2))
    new_state, _ = step(params, state, batch)
    # GAL layer (0): gal_lora changed, local_lora unchanged (masked out)
    gal_b = new_state["gal_lora"]["layers"]["wq"]["b"]
    old_gal_b = state["gal_lora"]["layers"]["wq"]["b"]
    assert float(jnp.max(jnp.abs(gal_b[0] - old_gal_b[0]))) > 0.0
    # non-GAL layer (1) of gal_lora frozen
    np.testing.assert_allclose(np.asarray(gal_b[1]), np.asarray(old_gal_b[1]))
    # local lora: non-GAL layer changed per client, GAL layer frozen
    loc_b = new_state["local_lora"]["layers"]["wq"]["b"]
    old_loc_b = state["local_lora"]["layers"]["wq"]["b"]
    np.testing.assert_allclose(np.asarray(loc_b[:, 0]), np.asarray(old_loc_b[:, 0]))
    assert float(jnp.max(jnp.abs(loc_b[:, 1] - old_loc_b[:, 1]))) > 0.0


def test_local_updates_differ_across_clients(world):
    model, params, state, batch, gal = world
    step = jax.jit(build_train_step(model, n_groups=2))
    new_state, _ = step(params, state, batch)
    loc_b = new_state["local_lora"]["layers"]["wq"]["b"]
    # different client data -> different local updates on the non-GAL layer
    diff = float(jnp.max(jnp.abs(loc_b[0, 1] - loc_b[1, 1])))
    assert diff > 0.0


def test_sharding_specs_cover_all_leaves(rng):
    from repro.configs import ARCHS

    for arch in ["qwen2-0.5b", "granite-moe-3b-a800m", "mamba2-1.3b", "zamba2-7b", "whisper-large-v3"]:
        cfg = ARCHS[arch].reduced()
        model = build_model(cfg)
        params = jax.eval_shape(model.init_params, rng)
        lora = jax.eval_shape(model.init_lora, rng)
        from repro.utils import tree_map_with_path_str

        tree_map_with_path_str(
            lambda p, l: shd.base_param_spec(p, l), params
        )  # no exception = every leaf matched
        tree_map_with_path_str(lambda p, l: shd.lora_spec(p, l), lora)


def test_spec_restrict_drops_missing_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = P(("pod", "data"), None, "model")
    r = shd._restrict(spec, mesh)
    assert r == P(("data",), None, "model")


def test_production_mesh_names_missing_devices():
    """A host that cannot form the 16x16 production mesh gets an error that
    names its device count; the launchers no longer fall back to a reduced
    config on their own."""
    from repro.launch.mesh import make_production_mesh

    with pytest.raises(ValueError, match=f"Number of devices {len(jax.devices())}"):
        make_production_mesh()
