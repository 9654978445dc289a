"""Moonlight-16B-A3B (latent attention, sigmoid-routed held experts) against
its plain reference, ``bench/reference/moonlight_16b_a3b.py``, at a tiny
size on the CPU: a dense layer 0 and one expert layer, 16 experts of which
2 are held (the published 8 of 64 per share), seeded random weights.

The program runs in float32 here, so it meets the float32 reference up to
the rounding of a different order of sums; the same program in bfloat16
misses by a percent (``test_bfloat16_fails_the_tolerance``).
"""
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench.lib.fljob import model_config  # noqa: E402
from bench.lib.weights import check_layout  # noqa: E402
from repro.config import FibecFedConfig  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.federated import make_runner  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.moe import apply_held_moe, route_sigmoid  # noqa: E402
from repro.obs import runtime_metrics  # noqa: E402
from repro.train import make_loss_fn  # noqa: E402

_SPEC = importlib.util.spec_from_file_location(
    "moonlight_reference", REPO / "bench" / "reference" / "moonlight_16b_a3b.py"
)
ref = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(ref)

# Float32 program against the float32 reference: the two sum in other
# orders (einsum groupings, the experts one at a time against all at once),
# which moves results by about 1e-6 relative (measured 2e-7 on logits and
# up to 8e-7 on LoRA gradients). The same program in bfloat16 misses by
# 1e-2, so 1e-5 tells the two apart with room on both sides.
RTOL = 1e-5
SEED = 3000000019


def tiny_config(dtype="float32", held=2):
    """The published configuration file cut to a test's size (every width
    shrunk, 16 experts with ``held`` held, top-4)."""
    config = json.loads((REPO / "bench" / "configs" / "moonlight-16b-a3b.json").read_text())
    config.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                  num_key_value_heads=4, num_hidden_layers=2, vocab_size=256,
                  kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                  moe_intermediate_size=32, n_routed_experts=held, num_experts_per_tok=4)
    config["deployment"] = dict(config["deployment"], n_routed_experts=16)
    run = config["run_as"]
    run.update(dtype=dtype, lora_rank=4)
    run["mla"] = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    run["moe"] = dict(run["moe"], num_experts=16, held_experts=held, top_k=4, d_ff_expert=32,
                      d_ff_shared=64)
    return config


def _model(config):
    return build_model(model_config(config))


def _weights(config, seed=SEED):
    """The reference's weights, with LoRA ``b`` moved off zero so that every
    target's delta reaches the output."""
    params, lora = ref.make_weights(seed, ref.sizes(config), config["run_as"])
    key = jax.random.PRNGKey(seed % 1000)
    lora = jax.tree.map(lambda x: x + 0.05 * jax.random.normal(key, x.shape), lora)
    return params, lora


def _batch(seed=0, B=3, T=12):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"tokens": jax.random.randint(k1, (B, T), 0, 256),
            "label_token": jax.random.randint(k2, (B,), 0, 256)}


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.ravel(a - b)) / jnp.linalg.norm(jnp.ravel(b)))


def _gaps(dtype):
    config = tiny_config(dtype)
    model = _model(config)
    params, lora = _weights(config)
    batch, valid = _batch(), jnp.array([1.0, 1.0, 0.0])
    loss_fn = make_loss_fn(model)
    lp, gp = jax.value_and_grad(lambda lo: loss_fn.masked(params, lo, batch, valid))(lora)
    m, prec = ref.Model(config), ref.Precision("f32")
    lr, gr = jax.value_and_grad(lambda lo: m.batch_loss(prec, params, lo, batch, valid))(lora)
    logits, _ = model.forward(params, lora, batch)
    want = m.last_logits(prec, params, lora, batch["tokens"])
    return {"logits": _rel(logits[:, -1], want), "loss": abs(float(lp - lr)) / abs(float(lr)),
            "grads": max(_rel(a, b) for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)))}


def test_the_reference_weights_fit_the_programs_layout():
    config = tiny_config()
    model = _model(config)
    params, lora = ref.make_weights(SEED, ref.sizes(config), config["run_as"])
    check_layout(params, jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), "base")
    check_layout(lora, jax.eval_shape(model.init_lora, jax.random.PRNGKey(0)), "LoRA")
    assert sorted(lora["layers"]) == ["wkv_a", "wkv_b", "wo", "wq"]
    assert all(x.shape[0] == 2 for x in jax.tree.leaves(lora))  # one stack of every layer
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) > 0


def test_logits_and_lora_gradients_match_the_reference():
    gaps = _gaps("float32")
    assert max(gaps.values()) < RTOL, gaps


def test_bfloat16_fails_the_tolerance():
    gaps = _gaps("bfloat16")
    assert min(gaps["logits"], gaps["grads"]) > 10 * RTOL, gaps


def _layer(config, held, seed=1, T=24):
    """One expert layer's weights (reference layout, float32) and a normed
    input (1, T, D)."""
    params, _ = ref.make_weights(seed, ref.sizes(config), config["run_as"])
    w = jax.tree.map(lambda x: jnp.asarray(x[0], jnp.float32), params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, T, config["hidden_size"]))
    return w, x


def _mcfg(held, **kw):
    return dataclasses.replace(model_config(tiny_config()).moe, held_experts=held, **kw)


def test_the_router_selects_with_the_bias_and_weights_without_it():
    mcfg = _mcfg(2, top_k=2, routed_scale=2.446)
    x = jnp.ones((1, 4))
    w = jnp.zeros((4, 16)).at[:, :3].set(jnp.array([0.3, 0.2, 0.1]))  # scores e0 > e1 > e2
    idx, weight = route_sigmoid(x, w, jnp.zeros(16), mcfg)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    bias = jnp.zeros(16).at[2].set(1.0)  # lifts e2 over e1 in the selection
    idx, weight = route_sigmoid(x, w, bias, mcfg)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    s = jax.nn.sigmoid(jnp.array([1.2, 0.4]))  # the unbiased scores of e0 and e2
    want = 2.446 * s / s.sum()
    got = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(weight[0]).tolist()))
    np.testing.assert_allclose([got[0], got[2]], want, rtol=1e-6)
    assert float(weight.sum()) == pytest.approx(2.446, rel=1e-6)


def test_the_layer_is_dropless_when_every_token_picks_one_held_expert():
    """A capacity-bounded dispatch would drop most of these tokens."""
    config = tiny_config()
    w, x = _layer(config, 2)
    w["router_bias"] = jnp.zeros(16).at[0].set(50.0).at[1].set(-50.0)
    m, prec = ref.Model(config), ref.Precision("f32")
    y, counts = apply_held_moe(m.rms(x, w["mlp_norm_w"]), w, _mcfg(2))
    want, n = m.experts(prec, x, w)  # the residual block: x + its feed-forward
    np.testing.assert_allclose(np.asarray(y), np.asarray(want - x), rtol=RTOL, atol=1e-6)
    assert float(counts["held_assignments"]) == float(n[0]) == x.shape[1]  # every token, once
    assert float(counts["expert_rows"]) == x.shape[0] * x.shape[1] * 2  # both held, every token


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each share holds 2 of the 16 experts (its experts first: the routing
    is the same up to the order of the router's columns); their routed parts
    summed, with the shared experts once, are the reference's layer with
    every expert held."""
    uncut = tiny_config(held=16)
    w, x = _layer(uncut, 16)
    m, prec = ref.Model(uncut), ref.Precision("f32")
    want, _ = m.experts(prec, x, w)  # the residual block: x + its feed-forward
    x_normed = m.rms(x, w["mlp_norm_w"])
    shared = m.swiglu(prec, x_normed, w["s_gate"], w["s_up"], w["s_down"])
    mcfg = _mcfg(2)
    total, assigned = shared, 0.0
    for share in range(8):
        mine = np.arange(2 * share, 2 * share + 2)
        order = np.concatenate([mine, np.setdiff1d(np.arange(16), mine)])
        p = dict(w, router=w["router"][:, order], router_bias=w["router_bias"][order],
                 **{k: w[k][mine] for k in ("e_gate", "e_up", "e_down")})
        y, counts = apply_held_moe(x_normed, p, mcfg)
        total, assigned = total + (y - shared), assigned + float(counts["held_assignments"])
    np.testing.assert_allclose(np.asarray(x + total), np.asarray(want), rtol=RTOL, atol=1e-6)
    assert assigned == x.shape[1] * mcfg.top_k  # every pick lands in one share


def test_serving_an_mla_model_is_refused():
    model = build_model(ARCHS["moonlight-16b-a3b"].reduced())
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.init_cache(1, 8)
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.prefill(None, {"layers": None}, {"tokens": jnp.zeros((1, 4), jnp.int32)}, 8)


# -- the federated rounds ----------------------------------------------------------

# the whole population each round, so the 5-batch shard sets an 8-step scan
# that the others pack beside in 2 lanes
FL = FibecFedConfig(num_devices=4, devices_per_round=4, rounds=4, batch_size=2,
                    learning_rate=5e-3, fim_warmup_epochs=1, gal_fraction=0.5,
                    sparse_ratio=0.5)
T_ROUND = 10  # whole shards


@pytest.fixture(scope="module")
def world():
    config = tiny_config()
    model = _model(config)
    params, lora = _weights(config)
    given = {"params": params, "lora": lora}
    # a copy of the LoRA per runner: the round program donates the global one
    model = dataclasses.replace(model, init_params=lambda _k: given["params"],
                                init_lora=lambda _k: jax.tree.map(jnp.copy, given["lora"]))
    rng = np.random.default_rng(0)
    clients = []
    for n in (9, 3, 6, 4):  # skewed shards: the cohort packs into fewer lanes
        clients.append({"tokens": rng.integers(0, 256, (n, 8)).astype(np.int32),
                        "label_token": rng.integers(0, 256, n).astype(np.int32)})
    return config, model, make_loss_fn(model), clients


def _runner(world, engine, **kw):
    _, model, loss_fn, clients = world
    r = make_runner("fibecfed", model, loss_fn, FL, clients, optimizer="adamw",
                    engine=engine, seed=7, **kw)
    return r


def test_the_loop_engine_matches_packed_vectorized_rounds(world):
    packed0 = runtime_metrics.counter("fl.rounds_packed").value
    r_vec = _runner(world, "vectorized")
    r_vec.init_phase()
    st_vec = r_vec.run_round(T_ROUND)
    assert runtime_metrics.counter("fl.rounds_packed").value == packed0 + 1
    r_loop = _runner(world, "loop")
    r_loop.init_phase()
    st_loop = r_loop.run_round(T_ROUND)
    np.testing.assert_array_equal(r_vec.last_round_info["chosen"], r_loop.last_round_info["chosen"])
    assert st_vec["loss"] == pytest.approx(st_loop["loss"], rel=1e-5)
    # AdamW's tolerance of tests/test_engine_equivalence.py
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                         rtol=1e-4, atol=5e-5),
                 r_vec.global_lora, r_loop.global_lora)


def test_chunked_init_programs_match_the_all_clients_ones(world):
    full = _runner(world, "vectorized")
    full.init_phase()
    assert full.init_chunks == {}  # no memory stated on the CPU: the all-clients programs
    chunked = _runner(world, "vectorized")
    chunked._init_bytes_limit = 1  # no program fits: one client at a time
    chunked.init_phase()
    assert chunked.init_chunks == {"difficulty": 1, "fim_warmup": 1}
    # the same sums, batched over 4 clients or over 1: float32 reorders them
    # (measured up to 3e-5 relative on the smallest Fisher entries)
    for a, b in zip(full.clients, chunked.clients):
        np.testing.assert_allclose(a.difficulty, b.difficulty, rtol=1e-5)
        np.testing.assert_array_equal(a.order, b.order)
        for x, y in zip(jax.tree.leaves(a.fim), jax.tree.leaves(b.fim)):
            np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5 * float(np.abs(x).max()))
    np.testing.assert_array_equal(full.gal_layers, chunked.gal_layers)


def test_the_held_assignment_counter_matches_the_reference(world):
    """One round at learning rate 0 (the LoRA stays where it is, so the
    reference counts every batch at the initial weights): the round's
    ``fl.moe_held_assignments`` is the reference's count over the valid
    samples of every batch the cohort trained, and ``fl.moe_expert_rows``
    every held expert on every token of every lane-step the program ran."""
    config, _, _, clients = world
    r = _runner(world, "vectorized")
    r.init_phase()
    r.run_round(T_ROUND, lr=0.0)
    held = runtime_metrics.histogram("fl.moe_held_assignments").recent[-1]
    rows = runtime_metrics.histogram("fl.moe_expert_rows").recent[-1]
    scanned = runtime_metrics.histogram("fl.round_scanned_steps").recent[-1]
    m, prec = ref.Model(config), ref.Precision("f32")
    params, lora = r.params, r.global_lora
    want = 0.0
    for ci in r.last_round_info["chosen"]:
        c = clients[int(ci)]
        _, _, n = m.forward(prec, params, lora, jnp.asarray(c["tokens"]))
        want += float(n.sum())  # each sample trains once in a whole-shard round
    assert held == want
    assert rows == scanned * FL.batch_size * 8 * 2 * 1  # lane-steps x B x T x held x expert layers
