"""Weights of a dense decoder, made on the device from the seed in one call.

The benchmark makes the frozen base (bfloat16, the type it is served in) and
the initial LoRA factors itself and hands both to the program, so that the
plain reference computes from weights the program did not make. The tree
layout is the program's (``repro.models.transformer.init_decoder``,
``repro.lora.init_lora``); ``check_layout`` refuses a program whose layout
differs.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

LORA_TARGETS = ("wk", "wo", "wq", "wv")


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng([int(seed) & (2**64 - 1), 11]).integers(0, 2**31 - 1))


def _dims(s: Dict[str, Any]) -> Dict[str, tuple]:
    d, q, kv = s["d_model"], s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}


def _make(key, s: Dict[str, Any], qkv_bias: bool, qk_norm: bool, dtype):
    L, d, f, V, hd, r = s["layers"], s["d_model"], s["d_ff"], s["vocab"], s["head_dim"], s["rank"]
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def dense(d_in, d_out):
        return normal((L, d_in, d_out), 1.0 / math.sqrt(d_in))

    def norm_w(shape):
        return 1.0 + normal(shape, 0.1)

    layers = {t: dense(*io) for t, io in _dims(s).items()}
    if qkv_bias:
        for t, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")):
            layers[b] = normal((L, _dims(s)[t][1]), 0.1)
    if qk_norm:
        layers["q_norm_w"] = norm_w((L, hd))
        layers["k_norm_w"] = norm_w((L, hd))
    layers["attn_norm_w"] = norm_w((L, d))
    layers["mlp_norm_w"] = norm_w((L, d))
    layers["w_gate"] = dense(d, f)
    layers["w_up"] = dense(d, f)
    layers["w_down"] = dense(f, d)
    params = {"embed": normal((V, d), 0.02), "layers": layers, "final_norm_w": norm_w((d,))}
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    # LoRA: a ~ N(0, 1/r^2), b = 0, float32 (the standard init)
    lora = {"layers": {
        t: {"a": normal((L, io[0], r), 1.0 / r), "b": jnp.zeros((L, r, io[1]), jnp.float32)}
        for t, io in sorted(_dims(s).items())
    }}
    return params, lora


def make_weights(seed: int, s: Dict[str, Any], *, qkv_bias: bool, qk_norm: bool,
                 dtype: str = "bfloat16"):
    """``(params, lora)`` on the default device, from ``seed``."""
    fn = jax.jit(lambda k: _make(k, s, qkv_bias, qk_norm, jnp.dtype(dtype)))
    return fn(jax.random.PRNGKey(jax_seed(seed)))


def check_layout(ours, theirs, what: str) -> None:
    """Refuses weights whose tree, shapes or types differ from the
    program's own initializer's (``jax.eval_shape`` of it)."""
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), ours)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), theirs)
    if a != b:
        raise SystemExit(f"the benchmark's {what} layout differs from the program's:\n{a}\n{b}")
