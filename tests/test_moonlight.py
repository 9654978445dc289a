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
from repro.models.moe import (  # noqa: E402
    apply_held_moe, expert_capacities, route_sigmoid,
)
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
    # held expert 0 over every token; held expert 1, picked by none, over no row
    assert float(counts["expert_rows"]) == x.shape[0] * x.shape[1]
    assert float(counts["dense_fallbacks"]) == 1.0


def _bias_for(w, x, mcfg, above: int, at_most: int):
    """A correction bias under which held expert 0 takes more than ``above``
    and at most ``at_most`` of the tokens of ``x``, and held expert 1 none:
    the largest held load, by bisection on expert 0's bias."""
    base = w["router_bias"].at[1].set(-50.0)
    lo, hi = -50.0, 50.0
    for _ in range(60):
        mid = (lo + hi) / 2
        idx, _ = route_sigmoid(x, w["router"], base.at[0].set(mid), mcfg)
        load = int(jnp.sum(idx == 0))
        if above < load <= at_most:
            return base.at[0].set(mid)
        lo, hi = (mid, hi) if load <= above else (lo, mid)
    raise AssertionError(f"no bias puts expert 0's load in ({above}, {at_most}]")


def _dense_experts(x, p, mcfg):
    """The held experts over every token, gated (the layer before the
    ladder, and the ladder's last branch for every expert)."""
    idx, weight = route_sigmoid(x, p["router"], p["router_bias"], mcfg)
    gate = jnp.einsum("bske,bsk->bse", jax.nn.one_hot(idx, mcfg.held, dtype=jnp.float32), weight)
    g = jnp.einsum("bsd,edf->bsef", x, p["e_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, p["e_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsef,efd->bsd", h * gate[..., None].astype(x.dtype), p["e_down"])


def _value_and_input_grad(f, x, seed=5):
    """``f(x)`` and the gradient of its product with a fixed random
    cotangent w.r.t. ``x`` (through the expert rows and the gates), in
    float32."""
    y, vjp = jax.vjp(f, x)
    ct = jax.random.normal(jax.random.PRNGKey(seed), y.shape).astype(y.dtype)
    return y.astype(jnp.float32), vjp(ct)[0].astype(jnp.float32)


T_LADDER = 256  # Moonlight's tokens per lane (batch 2 x 128): rungs 32, 64, 128
LOADS = (0, 32, 64, 128, T_LADDER)  # branch i takes a load in (LOADS[i], LOADS[i + 1]]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("branch", [0, 1, 2, 3], ids=["rung32", "rung64", "rung128", "dense"])
def test_each_rung_and_the_dense_fallback_match_the_dense_layer(branch, dtype):
    """Each branch of the capacity ladder against the held experts run
    densely, forced by the correction bias (held expert 0 at a load of the
    branch, held expert 1 picked by no token: no rows). In float32 they
    differ by the order of sums alone; in bfloat16 the ladder is no further
    from the float32 layer than the dense layer in bfloat16 is (both round;
    the ladder sums the experts' outputs in float32 and rounds once, as the
    dense contraction does)."""
    assert expert_capacities(T_LADDER) == LOADS[1:-1]
    mcfg = _mcfg(2, shared_expert=False)
    w, x = _layer(tiny_config(), 2, T=T_LADDER)
    dt = jnp.dtype(dtype)
    w = {k: v if k == "router_bias" else v.astype(dt) for k, v in w.items()}
    x = x.astype(dt)
    w["router_bias"] = _bias_for(w, x, mcfg, LOADS[branch], LOADS[branch + 1])
    counts = apply_held_moe(x, w, mcfg)[1]
    assert float(counts["expert_rows"]) == LOADS[branch + 1]  # expert 0's C (or T), expert 1's 0
    assert float(counts["dense_fallbacks"]) == float(branch == 3)
    y, g = _value_and_input_grad(lambda x: apply_held_moe(x, w, mcfg)[0], x)
    yd, gd = _value_and_input_grad(lambda x: _dense_experts(x, w, mcfg), x)
    if dtype == "float32":
        assert _rel(y, yd) < RTOL and _rel(g, gd) < RTOL, (_rel(y, yd), _rel(g, gd))
        return
    w32 = jax.tree.map(lambda v: v.astype(jnp.float32), w)
    y32, g32 = _value_and_input_grad(lambda x: _dense_experts(x, w32, mcfg), x.astype(jnp.float32))
    for got, dense, want in ((y, yd, y32), (g, gd, g32)):
        assert 0 < _rel(dense, want) and _rel(got, want) <= 1.1 * _rel(dense, want), (
            _rel(got, want), _rel(dense, want))


def test_every_lane_takes_the_rung_of_the_heaviest_lane():
    """Under ``vmap``, three lanes whose loads alone would take three rungs
    (one of them no rows) all take the heaviest lane's, and each matches the
    dense layer."""
    mcfg = _mcfg(2, shared_expert=False)
    w, heavy = _layer(tiny_config(), 2, T=T_LADDER)
    w["router_bias"] = _bias_for(w, heavy, mcfg, 64, 128)
    idx, _ = route_sigmoid(heavy, w["router"], w["router_bias"], mcfg)
    idle = heavy[:, int(jnp.argmin(jnp.any(idx[0] == 0, axis=-1)))]  # a token on no held expert
    light = heavy.at[:, T_LADDER // 2:].set(idle)  # about half the heavy lane's load
    lanes = jnp.stack([light, heavy, jnp.broadcast_to(idle, heavy.shape)])  # (3, 1, T, D)
    alone = [float(apply_held_moe(lane, w, mcfg)[1]["expert_rows"]) for lane in lanes]
    assert alone == [64.0, 128.0, 0.0], alone
    y, counts = jax.vmap(lambda x: apply_held_moe(x, w, mcfg))(lanes)
    np.testing.assert_array_equal(np.asarray(counts["expert_rows"]), [alone[1]] * 3)
    np.testing.assert_array_equal(np.asarray(counts["dense_fallbacks"]), [0.0] * 3)
    y, g = jax.vmap(lambda x: _value_and_input_grad(lambda x: apply_held_moe(x, w, mcfg)[0], x))(
        lanes)
    for i, lane in enumerate(lanes[:2]):  # the idle lane's layer output is all zeros
        yd, gd = _value_and_input_grad(lambda x: _dense_experts(x, w, mcfg), lane)
        assert _rel(y[i], yd) < RTOL and _rel(g[i], gd) < RTOL, (i, _rel(y[i], yd), _rel(g[i], gd))
    assert not jnp.any(y[2]) and not jnp.any(g[2])


@pytest.mark.parametrize("branch", [1, 3], ids=["rung64", "dense"])
def test_the_vmapped_remat_gradient_keeps_the_ladder_a_conditional(branch):
    """``jit(vmap(grad(checkpoint(layer))))`` compiles the ladder to HLO
    ``conditional``s, not selects that run every branch, and the counts say
    which branch ran."""
    mcfg = _mcfg(2)
    w, x = _layer(tiny_config(), 2, T=T_LADDER)
    w["router_bias"] = _bias_for(w, x, mcfg, LOADS[branch], LOADS[branch + 1])

    def f(x):
        y, counts = apply_held_moe(x, w, mcfg)
        return jnp.sum(jnp.square(y)), counts

    fn = jax.jit(jax.vmap(jax.grad(jax.checkpoint(f), has_aux=True)))
    lanes = jnp.stack([x, x[:, ::-1]])
    hlo = fn.lower(lanes).compile().as_text()
    assert hlo.count(" conditional(") >= 2 * mcfg.held  # a forward and a backward switch per expert
    _, counts = fn(lanes)
    np.testing.assert_array_equal(np.asarray(counts["expert_rows"]), [float(LOADS[branch + 1])] * 2)
    np.testing.assert_array_equal(np.asarray(counts["dense_fallbacks"]), [float(branch == 3)] * 2)


@pytest.mark.parametrize("branch", [0, 3], ids=["rung32", "dense"])
def test_the_hessian_vector_product_matches_the_dense_layer(branch):
    """Forward over reverse (as ``gal.make_lora_hvp``): the custom gradient
    is differentiated as plain code, and meets the dense layer's."""
    mcfg = _mcfg(2, shared_expert=False)
    w, x = _layer(tiny_config(), 2, T=T_LADDER)
    w["router_bias"] = _bias_for(w, x, mcfg, LOADS[branch], LOADS[branch + 1])
    v = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def hvp(layer):
        grad = jax.grad(lambda x: jnp.sum(jnp.square(layer(x))))
        return jax.jvp(grad, (x,), (v,))[1]

    got = hvp(lambda x: apply_held_moe(x, w, mcfg)[0])
    want = hvp(lambda x: _dense_experts(x, w, mcfg))
    assert _rel(got, want) < RTOL, _rel(got, want)


def test_a_gradient_for_the_held_experts_weights_is_refused():
    """The held experts are frozen: their weights get no gradient, and asking
    for one fails rather than returning zeros."""
    mcfg = _mcfg(2)
    w, x = _layer(tiny_config(), 2)
    with pytest.raises(NotImplementedError, match="frozen"):
        jax.grad(lambda e: jnp.sum(apply_held_moe(x, dict(w, e_up=e), mcfg)[0]))(w["e_up"])


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


def test_an_out_of_core_round_keeps_the_expert_counts(world, tmp_path):
    """The out-of-core store runs the same round program over a stack of
    the cohort, so its rounds record the held-expert counts too: at learning
    rate 0, the same (token, held expert) assignments as the in-memory
    round, whose lane packing changes none of them."""
    from repro.federated import OutOfCoreStore

    hist = runtime_metrics.histogram("fl.moe_held_assignments")
    held = {}
    for key, kw in (("mem", {}), ("ooc", {"store": OutOfCoreStore(str(tmp_path), hot_slots=2)})):
        r = _runner(world, "vectorized", **kw)
        r.init_phase()
        count = hist.count
        r.run_round(T_ROUND, lr=0.0)
        assert hist.count == count + 1
        held[key] = hist.recent[-1]
    assert held["ooc"] == held["mem"] > 0


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


def _held_loads(m, prec, params, lora, tokens):
    """The reference's load on each held expert (held,) over ``tokens``
    (B, T): the tokens whose top-k picks it, in the tiny model's one expert
    layer (after its one dense layer)."""
    assert m.n_dense == 1 and len(jax.tree.leaves(params["layers"])[0]) == 1
    lo = lora["layers"]
    w0 = ref._f32(jax.tree.map(lambda x: x[0], params["dense_layers"]))
    w1 = ref._f32(jax.tree.map(lambda x: x[0], params["layers"]))
    h = params["embed"][tokens].astype(jnp.float32)
    h = m.dense(prec, m.attention(prec, h, w0, jax.tree.map(lambda x: x[0], lo)), w0)
    h = m.attention(prec, h, w1, jax.tree.map(lambda x: x[1], lo))
    picks, _ = m.route(prec, m.rms(h, w1["mlp_norm_w"]), w1)
    return np.asarray([int(jnp.sum(picks == e)) for e in range(m.held)])


def test_the_held_assignment_counter_matches_the_reference(world):
    """One round at learning rate 0 (the LoRA stays where it is, so the
    reference counts every batch at the initial weights): the round's
    ``fl.moe_held_assignments`` is the reference's count over the valid
    samples of every batch the cohort trained. At every step of the round
    program each held expert's rung is the smallest of the ladder at 16
    tokens, 0, 8 or 16 rows (16: the dense branch), that holds the
    reference's load on it in every lane; ``fl.moe_expert_rows`` is the sum
    of those rungs over the experts and lanes of every step, and
    ``fl.moe_dense_fallbacks`` the (lane, expert) pairs at the dense branch."""
    config, _, _, clients = world
    r = _runner(world, "vectorized")
    r.init_phase()
    plans = []
    round_fn = r._round_fn

    def capture(packed=False):
        fn = round_fn(packed=packed)
        if not packed:
            return fn

        def call(*args):
            plans.append(tuple(np.asarray(a) for a in args[8:12]))  # chosen, the lane plan
            return fn(*args)

        return call

    r._round_fn = capture
    r.run_round(T_ROUND, lr=0.0)
    held = runtime_metrics.histogram("fl.moe_held_assignments").recent[-1]
    rows = runtime_metrics.histogram("fl.moe_expert_rows").recent[-1]
    dense = runtime_metrics.histogram("fl.moe_dense_fallbacks").recent[-1]
    m, prec = ref.Model(config), ref.Precision("f32")
    params, lora = r.params, r.global_lora
    want = 0.0
    for ci in r.last_round_info["chosen"]:
        c = clients[int(ci)]
        _, _, n = m.forward(prec, params, lora, jnp.asarray(c["tokens"]))
        want += float(n.sum())  # each sample trains once in a whole-shard round
    assert held == want

    (chosen, lane_client, batch_idx, _), = plans  # one packed round
    assert (lane_client < len(chosen)).all()  # no lane trains a scratch row (zero LoRA)
    tokens = np.asarray(r._stack_data["tokens"])  # (clients, batches, B, T)
    caps = np.asarray((0,) + expert_capacities(tokens[0, 0].size) + (tokens[0, 0].size,))
    assert caps.tolist() == [0, 8, 16]
    rungs = []  # (step, held expert)
    for lc, bidx in zip(lane_client.T, batch_idx.T):  # every step, its lanes
        loads = np.max([_held_loads(m, prec, params, lora, jnp.asarray(tokens[chosen[c], b]))
                        for c, b in zip(lc, bidx)], axis=0)  # (held,): each expert's heaviest lane
        rungs.append(np.searchsorted(caps, loads))  # the smallest rung no less than the load
    rungs, lanes = np.asarray(rungs), lane_client.shape[0]
    assert {1, 2} <= set(rungs.ravel())  # a rung of 8 rows and the dense branch both ran
    assert rows == lanes * caps[rungs].sum()
    assert dense == lanes * np.sum(rungs == len(caps) - 1)
