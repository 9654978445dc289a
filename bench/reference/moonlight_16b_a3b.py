"""Plain reference of Moonlight-16B-A3B (DeepSeek-V3) with LoRA, in float32.

Written from the published configuration (``model_type`` ``deepseek_v3``),
not from the program: token embedding, ``first_k_dense_replace`` dense
layers and then expert layers, each [RMSNorm -> latent attention ->
residual -> RMSNorm -> feed-forward -> residual], a final RMSNorm and an
untied head.

- Latent attention, no q compression: ``q = x W_q`` with per head
  ``qk_nope_head_dim`` plain and ``qk_rope_head_dim`` rotary dims;
  ``[c, k_pe] = x W_kv_a``; ``c`` is RMS-normed and ``W_kv_b`` expands it to
  each head's plain key and its value; ``k_pe`` is rotated once and shared
  by every head; causal softmax at scale ``1/sqrt(qk_nope + qk_rope)``;
  ``W_o`` back to the model width.
- Expert layers: the router scores all ``deployment.n_routed_experts`` (64)
  experts, ``sigmoid(x W_r)``; a token picks the ``num_experts_per_tok`` with
  the largest ``score + e_score_correction_bias`` (one group: ``n_group`` and
  ``topk_group`` 1) and weights each by its unbiased score, normalised over
  the picks and times ``routed_scaling_factor``. Only the experts held here,
  0 to ``n_routed_experts - 1`` (the chip's share), are evaluated, each on
  the tokens that picked it; the absent experts add nothing. The
  ``n_shared_experts`` run as one SwiGLU of their summed width on every
  token. The dense layers run a SwiGLU of ``intermediate_size``.

Departure, noted: DeepSeek's code reorders the rotary dims from interleaved
pairs into two halves before rotating; with weights drawn at random that is
a fixed permutation of those weight columns, so the rotary dims are taken as
two halves from the start, as the program takes them.

LoRA adds ``(alpha / rank) * (x @ a) @ b`` to ``W_q``, ``W_kv_a``, ``W_kv_b``
and ``W_o`` of every layer. Every matrix product goes through the dense
reference's ``Precision.mm`` (float32 at "highest" precision, or float8 e4m3
operands for the control); norms, softmax, routing scores, the loss and the
optimizer stay in float32. The base weights stay as given (bfloat16, 6.7 GB
at published widths, 13.5 GB in float32): each layer's are cast to float32
inside the layer loop, which is exact, and the loop's body is recomputed in
the backward pass (``jax.checkpoint``), so one layer's float32 copy lives at
a time (kept for the backward pass, the 26 expert layers' copies alone would
take 12.7 GB: a described-chip compile).

The module also gives the harness the architecture's hooks (see
``bench/README.md``): ``sizes``, ``make_weights`` and the operation counts.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.lib.weights import jax_seed
from bench.reference.dense_decoder import (  # noqa: F401  (the contract's names)
    Precision,
    lora_mask,
    make_sample_sq_grads,
    make_sensitivity,
    make_train_step,
)

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


class Model:
    """Shapes and flags from the configuration file (published keys,
    ``deployment`` and ``run_as``)."""

    def __init__(self, config: Dict[str, Any]):
        run = config["run_as"]
        if config["q_lora_rank"] is not None:
            raise ValueError("the reference has no q compression")
        if (config["scoring_func"], config["topk_method"]) != ("sigmoid", "noaux_tc"):
            raise ValueError("the reference routes by sigmoid scores plus a bias")
        if (config["n_group"], config["topk_group"]) != (1, 1):
            raise ValueError("the reference has no expert groups")
        if config["tie_word_embeddings"]:
            raise ValueError("the reference has an untied head")
        self.L = config["num_hidden_layers"]
        self.n_dense = config["first_k_dense_replace"]
        self.D = config["hidden_size"]
        self.H = config["num_attention_heads"]
        self.dn, self.dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
        self.dv, self.r = config["v_head_dim"], config["kv_lora_rank"]
        self.E = config["deployment"]["n_routed_experts"]
        self.held = config["n_routed_experts"]
        self.K = config["num_experts_per_tok"]
        self.routed_scale = float(config["routed_scaling_factor"])
        self.norm_topk = bool(config["norm_topk_prob"])
        self.V = config["vocab_size"]
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.lora_scale = float(run["lora_alpha"]) / int(run["lora_rank"])

    # -- pieces ----------------------------------------------------------------

    def rms(self, x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * w

    def rope(self, x):
        """Rotary positions on (B, T, heads, d): the two halves rotate as
        pairs, frequency theta^(-2i/d)."""
        T, d = x.shape[1], x.shape[-1]
        inv = 1.0 / (self.theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
        ang = jnp.arange(T, dtype=F32)[:, None] * inv
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def proj(self, p: Precision, x, w, lo):
        y = p.mm("btd,de->bte", x, w)
        return y + self.lora_scale * p.mm("btr,re->bte", p.mm("btd,dr->btr", x, lo["a"]), lo["b"])

    def swiglu(self, p: Precision, x, w_gate, w_up, w_down):
        return p.mm("btf,fd->btd", jax.nn.silu(p.mm("btd,df->btf", x, w_gate))
                    * p.mm("btd,df->btf", x, w_up), w_down)

    def attention(self, p: Precision, h, w, lo):
        B, T, _ = h.shape
        H, dn, r = self.H, self.dn, self.r
        x = self.rms(h, w["attn_norm_w"])
        q = self.proj(p, x, w["wq"], lo["wq"]).reshape(B, T, H, dn + self.dr)
        kv_a = self.proj(p, x, w["wkv_a"], lo["wkv_a"])
        c = self.rms(kv_a[..., :r], w["kv_norm_w"])
        kv = self.proj(p, c, w["wkv_b"], lo["wkv_b"]).reshape(B, T, H, dn + self.dv)
        k_pe = self.rope(kv_a[:, :, None, r:])  # one rotary key for every head
        q = jnp.concatenate([q[..., :dn], self.rope(q[..., dn:])], axis=-1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (B, T, H, self.dr))], axis=-1)
        s = p.mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(dn + self.dr))
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = p.mm("bhqk,bkhd->bqhd", a, kv[..., dn:]).reshape(B, T, H * self.dv)
        return h + self.proj(p, o, w["wo"], lo["wo"])

    def route(self, p: Precision, x, w):
        """(picks (B, T, K), weights (B, T, K)) of each token."""
        scores = jax.nn.sigmoid(p.mm("btd,de->bte", x, w["router"]))
        _, picks = jax.lax.top_k(scores + w["router_bias"], self.K)
        weight = jnp.take_along_axis(scores, picks, axis=-1)
        if self.norm_topk:
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        return picks, weight * self.routed_scale

    def experts(self, p: Precision, h, w):
        """The expert layer's feed-forward; returns (h, held assignments per
        sample (B,))."""
        x = self.rms(h, w["mlp_norm_w"])
        picks, weight = self.route(p, x, w)
        y = self.swiglu(p, x, w["s_gate"], w["s_up"], w["s_down"])
        for e in range(self.held):  # each held expert, on the tokens that picked it
            gate = jnp.sum(jnp.where(picks == e, weight, 0.0), axis=-1)  # (B, T)
            y = y + gate[..., None] * self.swiglu(p, x, w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        held = jnp.sum((picks < self.held).astype(F32), axis=(1, 2))
        return h + y, held

    def dense(self, p: Precision, h, w):
        x = self.rms(h, w["mlp_norm_w"])
        return h + self.swiglu(p, x, w["w_gate"], w["w_up"], w["w_down"])

    # -- whole model -------------------------------------------------------------

    def forward(self, p: Precision, params, lora, tokens, noise=None):
        """Final hidden states (B, T, D), each layer's per-sample Frobenius
        norm of its output (L, B), and each sample's held assignments (B,)."""
        h = params["embed"][tokens].astype(F32)
        if noise is not None:
            h = h + noise
        lo = lora["layers"]
        n0 = self.n_dense

        @jax.checkpoint
        def dense_body(h, xs):
            w, lo_l = xs
            w = _f32(w)
            h = self.dense(p, self.attention(p, h, w, lo_l), w)
            return h, jnp.sqrt(jnp.sum(h * h, axis=(1, 2)))

        @jax.checkpoint
        def expert_body(carry, xs):
            h, held = carry
            w, lo_l = xs
            w = _f32(w)
            h, n = self.experts(p, self.attention(p, h, w, lo_l), w)
            return (h, held + n), jnp.sqrt(jnp.sum(h * h, axis=(1, 2)))

        h, norms0 = jax.lax.scan(dense_body, h, (params["dense_layers"],
                                                 jax.tree.map(lambda x: x[:n0], lo)))
        (h, held), norms1 = jax.lax.scan(
            expert_body, (h, jnp.zeros(h.shape[0], F32)),
            (params["layers"], jax.tree.map(lambda x: x[n0:], lo)))
        return h, jnp.concatenate([norms0, norms1]), held

    def hidden(self, p: Precision, params, lora, tokens, noise=None):
        h, norms, _ = self.forward(p, params, lora, tokens, noise)
        return h, norms

    def last_logits(self, p: Precision, params, lora, tokens, noise=None):
        h, _ = self.hidden(p, params, lora, tokens, noise)
        x = self.rms(h[:, -1], params["final_norm_w"].astype(F32))
        return p.mm("bd,dv->bv", x, params["lm_head"].astype(F32))

    def per_sample_loss(self, p: Precision, params, lora, batch, noise=None):
        logits = self.last_logits(p, params, lora, batch["tokens"], noise)
        gold = jnp.take_along_axis(logits, batch["label_token"][:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    def batch_loss(self, p: Precision, params, lora, batch, valid):
        per = self.per_sample_loss(p, params, lora, batch)
        return jnp.sum(per * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def to_f32(tree):
    """LoRA trees to float32. The base (the tree holding ``embed``) stays as
    given: :meth:`Model.forward` casts one layer at a time."""
    if "embed" in tree:
        return tree
    return jax.tree.map(lambda x: jnp.asarray(x, F32), tree)


# -- architecture hooks: sizes, weights, required operations ---------------------


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The shapes of this chip's share of the model."""
    run = config.get("run_as", {})
    return {
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "d_model": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "qk_nope": config["qk_nope_head_dim"],
        "qk_rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "kv_rank": config["kv_lora_rank"],
        "d_ff": config["intermediate_size"],
        "d_expert": config["moe_intermediate_size"],
        "d_shared": config["n_shared_experts"] * config["moe_intermediate_size"],
        "experts": config["deployment"]["n_routed_experts"],
        "held": config["n_routed_experts"],
        "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
        "rank": run.get("lora_rank", 8),
    }


def _attn_dims(s: Dict[str, int]) -> Dict[str, tuple]:
    d, H = s["d_model"], s["heads"]
    return {
        "wq": (d, H * (s["qk_nope"] + s["qk_rope"])),
        "wkv_a": (d, s["kv_rank"] + s["qk_rope"]),
        "wkv_b": (s["kv_rank"], H * (s["qk_nope"] + s["v_dim"])),
        "wo": (H * s["v_dim"], d),
    }


def _make(key, s: Dict[str, Any], dtype):
    L, n0, d, V, r = s["layers"], s["dense_layers"], s["d_model"], s["vocab"], s["rank"]
    keys = iter(jax.random.split(key, 64))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, F32) * scale

    def dense(*shape):  # (..., d_in, d_out)
        return normal(shape, 1.0 / math.sqrt(shape[-2]))

    def norm_w(*shape):
        return 1.0 + normal(shape, 0.1)

    def stack(n, ffn):
        out = {t: dense(n, *io) for t, io in _attn_dims(s).items()}
        out["kv_norm_w"] = norm_w(n, s["kv_rank"])
        out["attn_norm_w"] = norm_w(n, d)
        out["mlp_norm_w"] = norm_w(n, d)
        out.update(ffn(n))
        return out

    def dense_ffn(n):
        return {"w_gate": dense(n, d, s["d_ff"]), "w_up": dense(n, d, s["d_ff"]),
                "w_down": dense(n, s["d_ff"], d)}

    def expert_ffn(n):
        E, held, fe, fs = s["experts"], s["held"], s["d_expert"], s["d_shared"]
        return {"router": dense(n, d, E),
                "e_gate": dense(n, held, d, fe), "e_up": dense(n, held, d, fe),
                "e_down": dense(n, held, fe, d),
                "s_gate": dense(n, d, fs), "s_up": dense(n, d, fs), "s_down": dense(n, fs, d)}

    params = {"embed": normal((V, d), 0.02), "dense_layers": stack(n0, dense_ffn),
              "layers": stack(L - n0, expert_ffn), "final_norm_w": norm_w(d),
              "lm_head": dense(d, V)}
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    # the correction bias, float32 as published, nonzero so that it changes
    # which experts tokens pick
    params["layers"]["router_bias"] = normal((L - n0, s["experts"]), 0.1)
    # LoRA: a ~ N(0, 1/r^2), b = 0, float32 (the standard init)
    lora = {"layers": {
        t: {"a": normal((L, io[0], r), 1.0 / r), "b": jnp.zeros((L, r, io[1]), F32)}
        for t, io in sorted(_attn_dims(s).items())
    }}
    return params, lora


def make_weights(seed: int, s: Dict[str, Any], run_as: Dict[str, Any]):
    """``(params, lora)`` on the default device, from ``seed``, in one jitted
    call: the base in ``run_as["dtype"]`` (the correction bias in float32),
    LoRA in float32, laid out as the program's ``init_mla_decoder`` and
    ``init_lora`` lay them out."""
    dtype = jnp.dtype(run_as["dtype"])
    return jax.jit(lambda k: _make(k, s, dtype))(jax.random.PRNGKey(jax_seed(seed)))


# A multiply-add is two operations. Counted is what the result needs on this
# chip's share: the frozen base's forward pass and the backward pass to every
# layer's inputs (no weight gradients of the base); the LoRA factors' forward,
# input and weight gradients; causal attention's scores (over the
# qk_nope + qk_rope dims) and weighted values (v_dim); in an expert layer the
# router, the shared expert and the held experts at the share of tokens that
# picks them on average, top_k x held / experts evaluations per token (0.75 at
# 6 x 8 / 64); the head at the positions the loss reads.


def _layer_weights(s: Dict[str, int], expert_layer: bool) -> float:
    """Multiply-adds of one token through one layer's frozen weights."""
    attn = sum(i * o for i, o in _attn_dims(s).values())
    d = s["d_model"]
    if not expert_layer:
        return attn + 3 * d * s["d_ff"]
    routed = s["top_k"] * s["held"] / s["experts"] * 3 * d * s["d_expert"]
    return attn + d * s["experts"] + 3 * d * s["d_shared"] + routed


def _layer_lora(s: Dict[str, int]) -> int:
    return sum(s["rank"] * (i + o) for i, o in _attn_dims(s).values())


def _attention_fwd(s: Dict[str, int], seq_len: int) -> float:
    """Scores and weighted values of causal attention for one sequence: query
    ``i`` sees ``i + 1`` positions."""
    visible = seq_len * (seq_len + 1) / 2
    return 2 * s["heads"] * (s["qk_nope"] + s["qk_rope"] + s["v_dim"]) * visible


def _layers(s: Dict[str, int], seq_len: int, base_x: int, lora_x: int) -> float:
    """Every layer: ``base_x`` passes of the frozen weights, ``lora_x`` of
    the LoRA factors, three of attention."""
    total = 0.0
    for expert_layer, n in ((False, s["dense_layers"]), (True, s["layers"] - s["dense_layers"])):
        base = 2 * seq_len * _layer_weights(s, expert_layer)
        lora = 2 * seq_len * _layer_lora(s)
        total += n * (base_x * base + lora_x * lora + 3 * _attention_fwd(s, seq_len))
    return total


def forward_flops(s: Dict[str, int], seq_len: int, head_positions: int) -> float:
    """One sequence's forward pass, with the head at ``head_positions``."""
    total = 0.0
    for expert_layer, n in ((False, s["dense_layers"]), (True, s["layers"] - s["dense_layers"])):
        total += n * (2 * seq_len * (_layer_weights(s, expert_layer) + _layer_lora(s))
                      + _attention_fwd(s, seq_len))
    return total + 2 * head_positions * s["d_model"] * s["vocab"]


def lora_train_flops(s: Dict[str, int], seq_len: int, loss_positions: int = 1) -> float:
    """One sequence through forward and backward of LoRA training on the
    frozen base, the loss read at ``loss_positions`` positions."""
    head = 2 * loss_positions * s["d_model"] * s["vocab"]
    return _layers(s, seq_len, 2, 3) + 2 * head  # head: forward + input gradient


def input_grad_flops(s: Dict[str, int], seq_len: int, loss_positions: int = 1) -> float:
    """One sequence's forward pass and the gradient with respect to its
    inputs alone (no weight gradients), the loss at ``loss_positions``."""
    head = 2 * loss_positions * s["d_model"] * s["vocab"]
    return _layers(s, seq_len, 2, 2) + 2 * head
