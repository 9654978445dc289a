"""Mixture-of-Experts layers.

Two routers (``MoEConfig.router``):

- ``"softmax"``: capacity-based grouped dispatch (expert parallel). Tokens
  are routed within *groups* of ``router_group_size`` tokens so the
  dispatch one-hot tensor stays small: capacity per expert per group is
  ``ceil(G * top_k * cf / E)``. Dispatch/combine are einsums against a
  ``(B, n_groups, G, E, C)`` mask — under pjit with experts sharded on the
  ``model`` axis and tokens on ``data`` this lowers to the canonical
  all-to-all expert-parallel schedule (MaxText-style "dropping" strategy).
  Aux load-balance loss is returned for training-mode monitoring.
- ``"sigmoid_bias"`` (DeepSeek-V3, :func:`apply_held_moe`): top-k of sigmoid
  scores plus a frozen correction bias, dropless, over a layer that holds
  only experts ``0..held-1`` of the router's ``num_experts`` (a chip's share
  of an expert-parallel split) and computes their part of the result. Each
  held expert runs over the rows of the tokens that picked it, in a buffer
  of the call's smallest capacity rung that holds its load
  (:func:`expert_capacities`), the rung made the same in every ``vmap``
  lane; past the last rung the expert runs over every token, gated.

The routed experts, the shared expert and the router are all part of the
*frozen base model* for FibecFed: LoRA trains the attention projections
only (``repro.lora``).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.models.layers import init_stacked_dense


def init_moe(rng, n_layers: int, d_model: int, mcfg: MoEConfig, dtype):
    """Stacked router and expert weights. The sigmoid router adds its
    correction bias (float32, zero) and keeps only the held experts'
    weights."""
    r = jax.random.split(rng, 7)
    E, Fe = mcfg.num_experts, mcfg.d_ff_expert
    held = mcfg.held if mcfg.router == "sigmoid_bias" else E
    p = {
        "router": init_stacked_dense(r[0], n_layers, d_model, E, dtype, scale=0.02),
        "e_gate": (
            jax.random.normal(r[1], (n_layers, held, d_model, Fe), jnp.float32)
            / math.sqrt(d_model)
        ).astype(dtype),
        "e_up": (
            jax.random.normal(r[2], (n_layers, held, d_model, Fe), jnp.float32)
            / math.sqrt(d_model)
        ).astype(dtype),
        "e_down": (
            jax.random.normal(r[3], (n_layers, held, Fe, d_model), jnp.float32)
            / math.sqrt(Fe)
        ).astype(dtype),
    }
    if mcfg.router == "sigmoid_bias":
        p["router_bias"] = jnp.zeros((n_layers, E), jnp.float32)
    if mcfg.shared_expert:
        Fs = mcfg.d_ff_shared
        p["s_gate"] = init_stacked_dense(r[4], n_layers, d_model, Fs, dtype)
        p["s_up"] = init_stacked_dense(r[5], n_layers, d_model, Fs, dtype)
        p["s_down"] = init_stacked_dense(r[6], n_layers, Fs, d_model, dtype)
    return p


def capacity(group: int, mcfg: MoEConfig) -> int:
    c = math.ceil(group * mcfg.top_k * mcfg.capacity_factor / mcfg.num_experts)
    return max(int(c), 1)


def route(
    x: jax.Array,
    router_w: jax.Array,
    mcfg: MoEConfig,
    sample_weight: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (..., G, D) groups of tokens. Returns (dispatch, combine, aux_loss).

    dispatch: (..., G, E, C) bool-ish mask; combine: same shape, f32 weights.

    ``sample_weight`` (B,) restricts the load-balance aux loss to valid
    samples when x is a (B, n_groups, G, D) training batch whose groups never
    span samples — the aux mean over the batch axis becomes weight-averaged,
    so padded fixed-shape batches reproduce their ragged originals exactly.
    Routing itself is per-sample and needs no masking.
    """
    E = mcfg.num_experts
    G = x.shape[-2]
    C = capacity(G, mcfg)
    logits = jnp.einsum("...gd,de->...ge", x, router_w.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (...,G,E)

    # top-k gating
    gate_vals, gate_idx = jax.lax.top_k(probs, mcfg.top_k)  # (...,G,K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # expert one-hot per k-choice: (...,G,K,E)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    # position of each (token, k) inside its expert queue, ordered by token
    # then by k: cumulative count over the flattened (G*K) axis.
    flat = onehot.reshape(*onehot.shape[:-3], G * mcfg.top_k, E)
    pos = jnp.cumsum(flat, axis=-2) - flat  # (...,G*K,E)
    pos = pos.reshape(onehot.shape)
    within_cap = pos < C
    slot_onehot = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    # (...,G,K,E,C)
    dispatch_k = onehot[..., None] * slot_onehot * within_cap[..., None]
    combine_k = dispatch_k * gate_vals[..., None, None]
    dispatch = jnp.sum(dispatch_k, axis=-3)  # (...,G,E,C)
    combine = jnp.sum(combine_k, axis=-3)

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=-2)  # (...,E) avg router prob
    ce = jnp.mean(jnp.sum(onehot, axis=-2), axis=-2) / mcfg.top_k  # frac routed
    per_group = jnp.sum(me * ce, axis=-1)  # (B, n_groups) for train batches
    if sample_weight is None:
        aux = jnp.mean(per_group) * E * mcfg.aux_loss_weight
    else:
        assert per_group.ndim == 2, "sample_weight needs (B, n_groups, G, D) tokens"
        sw = sample_weight.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(sw), 1.0) * per_group.shape[-1]
        aux = jnp.sum(per_group * sw[:, None]) / denom * E * mcfg.aux_loss_weight
    return dispatch, combine, aux


def apply_moe(
    x: jax.Array,
    p,
    mcfg: MoEConfig,
    *,
    token_parallel: bool = False,
    sample_weight: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D); p holds the per-layer slice. Returns (y, aux_loss).

    ``sample_weight`` (B,) makes the aux loss ignore padding samples (see
    :func:`route`); it never changes routing or outputs."""
    B, S, D = x.shape
    if S == 1:
        # decode: route the whole batch as one group (mixes samples, so the
        # per-sample aux weighting does not apply)
        xg = x.reshape(1, 1, B, D)
        sample_weight = None
    else:
        G = min(mcfg.router_group_size, S)
        assert S % G == 0, (S, G)
        xg = x.reshape(B, S // G, G, D)
    # NOTE §Perf-B: constraining the token groups onto the model axis here
    # was REFUTED — the S-sharding propagates into attention and replicates
    # the score buffers (8x traffic). The winning variant replicates uneven
    # expert weights only (shardings.py moe_token_parallel) and lets GSPMD
    # place the FFN; apply_moe itself stays constraint-free.
    del token_parallel
    dispatch, combine, aux = route(xg, p["router"], mcfg, sample_weight=sample_weight)
    xe = jnp.einsum("bngec,bngd->ebncd", dispatch.astype(x.dtype), xg)
    # expert FFN (SwiGLU) — e is leading so pjit shards experts on `model`
    g = jnp.einsum("ebncd,edf->ebncf", xe, p["e_gate"])
    u = jnp.einsum("ebncd,edf->ebncf", xe, p["e_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    ye = jnp.einsum("ebncf,efd->ebncd", h, p["e_down"])
    y = jnp.einsum("ebncd,bngec->bngd", ye, combine.astype(x.dtype))
    y = y.reshape(B, S, D)

    if mcfg.shared_expert:
        g = jnp.einsum("bsd,df->bsf", x, p["s_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["s_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = y + jnp.einsum("bsf,fd->bsd", h, p["s_down"])
    return y, aux


# ---------------------------------------------------------------------------
# sigmoid router with a correction bias, over a layer of held experts
# ---------------------------------------------------------------------------


def route_sigmoid(x: jax.Array, router_w: jax.Array, bias: jax.Array, mcfg: MoEConfig):
    """DeepSeek-V3's ``noaux_tc`` router (one expert group). x: (..., D).

    Returns ``(idx, weight)``, each (..., top_k): the top-k experts of
    ``sigmoid(x W_r) + bias`` and their *unbiased* sigmoid scores,
    normalised over the k and scaled by ``routed_scale``.
    Scores are computed in float32 at full precision: the selection is
    discrete, and a rounded score flips it."""
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), mcfg.top_k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, weight / jnp.sum(weight, axis=-1, keepdims=True) * mcfg.routed_scale


def expert_capacities(tokens: int) -> Tuple[int, ...]:
    """The capacity ladder of a held-expert call over ``tokens`` tokens: the
    rows of each rung, T/8, T/4 and T/2 rounded up to a multiple of 8, those
    under T (at T = 256: 32, 64, 128)."""
    rungs = []
    for part in (8, 4, 2):
        c = 8 * math.ceil(tokens / (8 * part))
        if c < tokens and c not in rungs:
            rungs.append(c)
    return tuple(rungs)


@jax.custom_batching.custom_vmap
def _lane_max(n: jax.Array) -> jax.Array:
    """``n``; under ``vmap``, its elementwise max over the mapped axis,
    unbatched (once per nested ``vmap``). A rung picked from it is the same
    in every lane, so the ``lax.switch`` it indexes stays a conditional: a
    batched index would turn it into a select that runs every branch."""
    return n


@_lane_max.def_vmap
def _lane_max_rule(axis_size, in_batched, n):
    del axis_size
    return _lane_max(jnp.max(n, axis=0) if in_batched[0] else n), False


def _slots(picked: jax.Array, gate: jax.Array, capacity: int, dtype):
    """One held expert's rows: the (C, T) one-hot whose row c marks the token
    of its (c + 1)-th pick in token order (rows past its load are zero), and
    each row's gate (C,)."""
    before = jnp.cumsum(picked) - picked
    hit = (before[None, :] == jnp.arange(capacity)[:, None]) & (picked[None, :] > 0)
    return hit.astype(dtype), jnp.sum(jnp.where(hit, gate[None, :], 0.0), axis=1)


def _take(slots: jax.Array, v: jax.Array) -> jax.Array:
    """``slots @ v``: each row's token of ``v`` (T, ...), exactly (a one-hot
    product, where the dot does not round float32 operands)."""
    f32 = slots.dtype == jnp.float32
    return jnp.dot(slots, v.astype(slots.dtype),
                   precision=jax.lax.Precision.HIGHEST if f32 else None)


def _put(slots: jax.Array, rows: jax.Array) -> jax.Array:
    """``slots.T @ rows``: float32 ``rows`` (C, D) back at their tokens (T, D),
    zero where a token holds none. In a narrower dtype the one-hot product
    takes each row as a high and a low part, so a row keeps 16 bits of
    mantissa, far below the rounding of the layer's result."""
    if slots.dtype == jnp.float32:
        return jnp.dot(slots.T, rows, precision=jax.lax.Precision.HIGHEST)
    hi = rows.astype(slots.dtype)
    lo = (rows - hi.astype(jnp.float32)).astype(slots.dtype)
    return (jnp.dot(slots.T, hi, preferred_element_type=jnp.float32)
            + jnp.dot(slots.T, lo, preferred_element_type=jnp.float32))


def _swiglu(x, gate, wg, wu, wd):
    """One expert's gated SwiGLU over rows ``x`` (R, D), ``gate`` (R,)
    float32: (R, D) float32. In the model's dtype, the gate on ``h`` and the
    down projection accumulated in float32, as the dense layer."""
    g = jnp.dot(x, wg)
    u = jnp.dot(x, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.dot(h * gate.astype(x.dtype)[:, None], wd, preferred_element_type=jnp.float32)


def _expert_fwd(capacity: int, x, gate, picked, wg, wu, wd):
    """One held expert's part of the layer, (T, D) float32: none (no
    picks), over ``capacity`` rows, or over every token (``capacity`` T)."""
    if capacity == 0:
        return jnp.zeros(x.shape, jnp.float32)
    if capacity == x.shape[0]:
        return _swiglu(x, gate, wg, wu, wd)
    slots, row_gate = _slots(picked, gate, capacity, x.dtype)
    return _put(slots, _swiglu(_take(slots, x), row_gate, wg, wu, wd))


def _expert_vjp(capacity: int, x, gate, picked, dy, wg, wu, wd):
    """:func:`_expert_fwd`'s input gradients ``(dx (T, D), dgate (T,))`` for
    its output's cotangent ``dy``, its forward pass recomputed."""
    _, vjp = jax.vjp(lambda x, gate: _expert_fwd(capacity, x, gate, picked, wg, wu, wd), x, gate)
    return vjp(dy.astype(jnp.float32))


def _held_experts_apply(capacities, x, gate, picked, rung, wg, wu, wd):
    """The held experts' part of the layer over tokens ``x`` (T, D): expert
    ``e`` over ``capacities[rung[e]]`` rows (:func:`_expert_fwd`), one
    ``lax.switch`` per expert, summed in float32 and rounded once."""
    y = jnp.zeros(x.shape, jnp.float32)
    branches = [functools.partial(_expert_fwd, c) for c in capacities]
    for e in range(wg.shape[0]):
        y = y + jax.lax.switch(rung[e], branches, x, gate[:, e], picked[:, e],
                               wg[e], wu[e], wd[e])
    return y.astype(x.dtype)


def _held_experts_fwd(capacities, x, gate, picked, rung, wg, wu, wd):
    if wg.perturbed or wu.perturbed or wd.perturbed:
        raise NotImplementedError("the held experts are frozen: no gradient w.r.t. their weights")
    args = tuple(a.value for a in (x, gate, picked, rung, wg, wu, wd))
    return _held_experts_apply(capacities, *args), args


def _held_experts_bwd(capacities, res, dy):
    x, gate, picked, rung, wg, wu, wd = res
    if isinstance(dy, jax.custom_derivatives.SymbolicZero):
        return (None,) * 7
    dx = jnp.zeros(x.shape, jnp.float32)
    dgate = []
    branches = [functools.partial(_expert_vjp, c) for c in capacities]
    for e in range(wg.shape[0]):
        dx_e, dgate_e = jax.lax.switch(rung[e], branches, x, gate[:, e], picked[:, e], dy,
                                       wg[e], wu[e], wd[e])
        dx = dx + dx_e
        dgate.append(dgate_e)
    return dx.astype(x.dtype), jnp.stack(dgate, axis=-1), None, None, None, None, None


_held_experts = jax.custom_vjp(_held_experts_apply, nondiff_argnums=(0,))
_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd, symbolic_zeros=True)


def apply_held_moe(
    x: jax.Array,
    p,
    mcfg: MoEConfig,
    *,
    sample_weight: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The sigmoid-routed expert layer of a chip that holds experts
    ``0..held-1``. x: (B, S, D); p the per-layer slice. Returns ``(y,
    counts)``.

    The router scores all ``num_experts``; every (token, expert) assignment
    that lands on a held expert is computed, whatever the skew (dropless),
    and the absent experts contribute nothing. Each held expert runs over
    its own capacity rung, the fewest rows of 0, :func:`expert_capacities`
    (T = B * S) and T that hold its load, the load's max also taken over
    every ``vmap`` lane (:func:`_lane_max`): a ``lax.switch`` per expert over
    no rows, the rows of the tokens that picked it (gathered and put back by
    one-hot products), or every token with a gate that is zero where the
    token did not pick it (the dense branch). Each computes the same
    assignments with the same weights and gates; the experts' outputs sum
    in float32 and round once. The gradient reaches ``x`` through the rows
    and the gate (a custom VJP that recomputes the taken branch; the held
    experts' weights are frozen and get none). The shared expert then runs
    on every token. ``counts`` (float32 scalars, no gradient):
    ``held_assignments``, the (token, held expert) assignments, each sample
    weighted by ``sample_weight`` (B,) when given; ``expert_rows``, the rows
    the held experts computed; ``dense_fallbacks``, the held experts that
    ran over every token."""
    held = mcfg.held
    B, S, D = x.shape
    with jax.named_scope("router"):
        idx, weight = route_sigmoid(x, p["router"], p["router_bias"], mcfg)  # (B,S,K)
        onehot = jax.nn.one_hot(idx, held, dtype=jnp.float32)  # (B,S,K,held)
        gate = jnp.einsum("bske,bsk->bse", onehot, weight)  # picked: weight; else 0
        # a token's k picks are distinct: 1 where it picked the held expert
        picked = jnp.sum(jax.nn.one_hot(idx, held, dtype=jnp.int32), axis=2)  # (B,S,held)
        per_sample = jnp.sum(onehot, axis=(1, 2, 3))  # (B,)
        if sample_weight is not None:
            per_sample = per_sample * sample_weight.astype(jnp.float32)
        count = jax.lax.stop_gradient(jnp.sum(per_sample))
        caps = (0,) + expert_capacities(B * S) + (B * S,)
        load = _lane_max(jnp.sum(picked, axis=(0, 1)))  # (held,)
        # each expert's smallest rung that holds its load
        rung = jnp.sum(jnp.asarray(caps[:-1], jnp.int32)[None, :] < load[:, None], axis=1)
    with jax.named_scope("routed_experts"):
        y = _held_experts(caps, x.reshape(B * S, D), gate.reshape(B * S, held),
                          picked.reshape(B * S, held), rung,
                          p["e_gate"], p["e_up"], p["e_down"]).reshape(B, S, D)
    if mcfg.shared_expert:
        with jax.named_scope("shared_experts"):
            g = jnp.einsum("bsd,df->bsf", x, p["s_gate"])
            u = jnp.einsum("bsd,df->bsf", x, p["s_up"])
            h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
            y = y + jnp.einsum("bsf,fd->bsd", h, p["s_down"])
    rows = jnp.sum(jnp.asarray(caps, jnp.float32)[rung])
    dense = jnp.sum(rung == len(caps) - 1).astype(jnp.float32)
    return y, {"held_assignments": count, "expert_rows": rows, "dense_fallbacks": dense}
