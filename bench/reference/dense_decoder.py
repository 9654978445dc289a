"""Plain reference of a dense decoder with LoRA, in float32.

Written from the published architecture (Qwen2: q/k/v biases; Qwen3: an
RMSNorm over each head's q and k before RoPE), not from the program:
token embedding, ``layers`` x [RMSNorm -> grouped-query causal attention
with rotary positions -> residual -> RMSNorm -> SwiGLU MLP -> residual],
final RMSNorm, and the head tied to the embedding. LoRA adds
``(alpha / rank) * (x @ a) @ b`` to the q, k, v and o projections.

Every matrix product goes through ``Precision.mm``: float32 at "highest"
precision for the reference, or with both operands rounded to float8 (e4m3,
one scale per tensor) for the control, the precision below the
configuration's bfloat16. Norms, softmax, losses and the optimizer stay in
float32 either way.

The loss is the class-label objective the job trains: cross entropy of the
label token against the logits after the last position, averaged over the
valid samples of a batch. The head is applied at that position alone.

The module also gives the harness the architecture's hooks (see
``bench/README.md``): ``sizes``, ``make_weights`` and the operation counts
``lora_train_flops``, ``input_grad_flops`` and ``forward_flops``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bench.lib.weights import jax_seed

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return _einsum(spec, _fp8(a), _fp8(b))


def _fp8_einsum_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _fp8_einsum_bwd(spec, res, g):
    """The backward products in float8 too: the incoming gradient is
    rounded like an operand; the rounding itself passes gradients
    straight through."""
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), *res)
    return vjp(_fp8(g))


_fp8_einsum.defvjp(_fp8_einsum_fwd, _fp8_einsum_bwd)


class Precision:
    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        self.mode = mode

    def mm(self, spec: str, a, b):
        if self.mode == "fp8":
            return _fp8_einsum(spec, a, b)
        return _einsum(spec, a, b)


class Model:
    """Shapes and flags from a configuration file (published keys plus
    ``run_as``)."""

    def __init__(self, config: Dict[str, Any]):
        run = config["run_as"]
        self.L = config["num_hidden_layers"]
        self.D = config["hidden_size"]
        self.H = config["num_attention_heads"]
        self.KV = config["num_key_value_heads"]
        self.hd = run.get("head_dim") or config.get("head_dim") or self.D // self.H
        self.V = config["vocab_size"]
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.qkv_bias = bool(run["qkv_bias"])
        self.qk_norm = bool(run["qk_norm"])
        self.lora_scale = float(run["lora_alpha"]) / int(run["lora_rank"])
        if not config["tie_word_embeddings"]:
            raise ValueError("the reference ties the head to the embedding")

    # -- pieces ----------------------------------------------------------------

    def rms(self, x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * w

    def rope(self, x):
        """Rotary positions on (B, T, heads, hd): the two halves of each head
        rotate as pairs, frequency theta^(-2i/hd)."""
        T, hd = x.shape[1], x.shape[-1]
        inv = 1.0 / (self.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # (T, hd/2)
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def proj(self, p: Precision, x, w, lo, bias=None):
        y = p.mm("btd,de->bte", x, w)
        y = y + self.lora_scale * p.mm("btr,re->bte", p.mm("btd,dr->btr", x, lo["a"]), lo["b"])
        return y if bias is None else y + bias

    def layer(self, p: Precision, h, w, lo):
        B, T, _ = h.shape
        x = self.rms(h, w["attn_norm_w"])
        q = self.proj(p, x, w["wq"], lo["wq"], w.get("bq")).reshape(B, T, self.H, self.hd)
        k = self.proj(p, x, w["wk"], lo["wk"], w.get("bk")).reshape(B, T, self.KV, self.hd)
        v = self.proj(p, x, w["wv"], lo["wv"], w.get("bv")).reshape(B, T, self.KV, self.hd)
        if self.qk_norm:
            q, k = self.rms(q, w["q_norm_w"]), self.rms(k, w["k_norm_w"])
        q, k = self.rope(q), self.rope(k)
        group = self.H // self.KV
        k = jnp.repeat(k, group, axis=2)  # query head i reads kv head i // group
        v = jnp.repeat(v, group, axis=2)
        s = p.mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(self.hd))
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = p.mm("bhqk,bkhd->bqhd", a, v).reshape(B, T, self.H * self.hd)
        h = h + self.proj(p, o, w["wo"], lo["wo"])
        x = self.rms(h, w["mlp_norm_w"])
        g = p.mm("btd,df->btf", x, w["w_gate"])
        u = p.mm("btd,df->btf", x, w["w_up"])
        return h + p.mm("btf,fd->btd", jax.nn.silu(g) * u, w["w_down"])

    # -- whole model -------------------------------------------------------------

    def hidden(self, p: Precision, params, lora, tokens, noise=None):
        """Hidden states after every layer: final (B, T, D) and the
        per-layer, per-sample Frobenius norms (L, B)."""
        h = params["embed"][tokens]
        if noise is not None:
            h = h + noise

        def body(h, xs):
            w, lo = xs
            h = self.layer(p, h, w, lo)
            return h, jnp.sqrt(jnp.sum(h * h, axis=(1, 2)))

        return jax.lax.scan(body, h, (params["layers"], lora["layers"]))

    def last_logits(self, p: Precision, params, lora, tokens, noise=None):
        h, _ = self.hidden(p, params, lora, tokens, noise)
        x = self.rms(h[:, -1], params["final_norm_w"])
        return p.mm("bd,vd->bv", x, params["embed"])

    def per_sample_loss(self, p: Precision, params, lora, batch, noise=None):
        logits = self.last_logits(p, params, lora, batch["tokens"], noise)
        gold = jnp.take_along_axis(logits, batch["label_token"][:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    def batch_loss(self, p: Precision, params, lora, batch, valid):
        per = self.per_sample_loss(p, params, lora, batch)
        return jnp.sum(per * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def to_f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def adamw_step(lora, m, v, t, g, mask, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One AdamW step (no weight decay) in float32. Entries whose ``mask`` is
    0 keep their value and both moments; ``t`` counts the steps taken."""
    t = t + 1
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def one(x, mm, vv, gg, mk):
        keep = mk != 0
        mm2 = jnp.where(keep, b1 * mm + (1 - b1) * gg, mm)
        vv2 = jnp.where(keep, b2 * vv + (1 - b2) * gg * gg, vv)
        x2 = jnp.where(keep, x - lr * (mm2 / bc1) / (jnp.sqrt(vv2 / bc2) + eps), x)
        return x2, mm2, vv2

    out = jax.tree.map(one, lora, m, v, g, mask)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))  # noqa: E731
    return pick(0), pick(1), pick(2), t


def make_train_step(model: Model, prec: Precision):
    """Jitted ``(params, lora, m, v, t, mask, batch, valid, lr) ->
    (loss, grad, lora, m, v, t)``: one local step of a client."""

    def step(params, lora, m, v, t, mask, batch, valid, lr):
        loss, g = jax.value_and_grad(
            lambda lo: model.batch_loss(prec, params, lo, batch, valid))(lora)
        lora, m, v, t = adamw_step(lora, m, v, t, g, mask, lr)
        return loss, g, lora, m, v, t

    return jax.jit(step)


def lora_mask(lora, keep: Optional[Dict[str, Any]]):
    """Update mask of a LoRA tree from per-target neuron keep masks
    ``{target: (L, d_out)}``: ``a`` always trains, column ``j`` of ``b``
    trains where neuron ``j`` is kept."""
    out = {}
    for t, ab in lora["layers"].items():
        kb = jnp.ones_like(ab["b"]) if keep is None else jnp.broadcast_to(
            jnp.asarray(keep[t], jnp.float32)[:, None, :], ab["b"].shape)
        out[t] = {"a": jnp.ones_like(ab["a"]), "b": kb}
    return {"layers": out}


def make_sample_sq_grads(model: Model, prec: Precision):
    """Jitted ``(params, lora, batch) -> tree``: each sample's squared LoRA
    gradient of its own loss, leaves with a leading sample axis (the
    empirical Fisher diagonal of one sample)."""

    def one(params, lora, tokens, label):
        b = {"tokens": tokens[None], "label_token": label[None]}
        g = jax.grad(lambda lo: model.batch_loss(prec, params, lo, b, jnp.ones(1)))(lora)
        return jax.tree.map(jnp.square, g)

    return jax.jit(lambda params, lora, batch: jax.vmap(
        lambda t, lab: one(params, lora, t, lab))(batch["tokens"], batch["label_token"]))


def make_sensitivity(model: Model, prec: Precision, gamma: float):
    """Jitted ``(params, lora, batch) -> (layers,)``: each layer's mean
    relative change of its output norm when the embeddings take the
    worst-case perturbation of l2 norm ``gamma`` per sample (the gradient of
    the batch's mean loss with respect to the embeddings, scaled)."""

    def fn(params, lora, batch):
        tokens = batch["tokens"]
        B, T = tokens.shape

        def loss_of(noise):
            return jnp.mean(model.per_sample_loss(prec, params, lora, batch, noise))

        g = jax.grad(loss_of)(jnp.zeros((B, T, model.D), jnp.float32))
        norm = jnp.sqrt(jnp.sum(g * g, axis=(1, 2), keepdims=True))
        eps = gamma * g / jnp.maximum(norm, 1e-20)
        _, clean = model.hidden(prec, params, lora, tokens)
        _, pert = model.hidden(prec, params, lora, tokens, eps)
        return jnp.mean(jnp.abs(pert - clean) / jnp.maximum(clean, 1e-12), axis=-1)

    return jax.jit(fn)


# -- architecture hooks: sizes, weights, required operations ---------------------


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The shapes of a dense decoder from a configuration file's keys."""
    run = config.get("run_as", {})
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hd = run.get("head_dim") or config.get("head_dim") or d // h
    return {
        "layers": config["num_hidden_layers"],
        "d_model": d,
        "heads": h,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": hd,
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "rank": run.get("lora_rank", 8),
    }


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


def make_weights(seed: int, s: Dict[str, Any], run_as: Dict[str, Any]):
    """``(params, lora)`` on the default device, from ``seed``, in one jitted
    call: the base in ``run_as["dtype"]``, LoRA in float32, laid out as the
    program's ``init_decoder`` and ``init_lora`` lay them out."""
    qkv_bias, qk_norm = bool(run_as["qkv_bias"]), bool(run_as["qk_norm"])
    dtype = jnp.dtype(run_as["dtype"])
    fn = jax.jit(lambda k: _make(k, s, qkv_bias, qk_norm, dtype))
    return fn(jax.random.PRNGKey(jax_seed(seed)))


# A multiply-add is two operations. Only what the result needs is counted:
# the forward pass through the frozen base and the LoRA factors; the backward
# pass that carries the gradient back to every layer's inputs (the frozen base
# gets no weight gradient); the LoRA factors' input and weight gradients;
# causal attention (scores and weighted values over the positions each query
# sees); the LM head at only the positions the loss reads. Work the program
# does beyond this (logits no loss reads, padded steps, recomputation) lowers
# a utilization built on these counts.


def _layer_weights(s: Dict[str, int]) -> int:
    d, q, kv = s["d_model"], s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * s["d_ff"]  # gate, up, down
    return attn + mlp


def _layer_lora(s: Dict[str, int]) -> int:
    d, q, kv, r = s["d_model"], s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"], s["rank"]
    return sum(r * (i + o) for i, o in ((d, q), (d, kv), (d, kv), (q, d)))


def _attention_fwd(s: Dict[str, int], seq_len: int) -> float:
    """Scores and weighted values of causal attention for one sequence: query
    ``i`` sees ``i + 1`` positions."""
    visible = seq_len * (seq_len + 1) / 2
    return 2 * 2 * s["heads"] * s["head_dim"] * visible


def forward_flops(s: Dict[str, int], seq_len: int, head_positions: int) -> float:
    """One sequence's forward pass, with the head at ``head_positions``."""
    layer = 2 * seq_len * (_layer_weights(s) + _layer_lora(s)) + _attention_fwd(s, seq_len)
    return s["layers"] * layer + 2 * head_positions * s["d_model"] * s["vocab"]


def lora_train_flops(s: Dict[str, int], seq_len: int, loss_positions: int = 1) -> float:
    """One sequence through forward and backward of LoRA training on a frozen
    base, the loss read at ``loss_positions`` positions."""
    base = 2 * seq_len * _layer_weights(s)
    lora = 2 * seq_len * _layer_lora(s)
    attn = _attention_fwd(s, seq_len)
    # base: forward + input gradient; LoRA: forward + input + weight
    # gradients; attention: forward + the gradients of both of its products
    layer = 2 * base + 3 * lora + 3 * attn
    head = 2 * loss_positions * s["d_model"] * s["vocab"]
    return s["layers"] * layer + 2 * head  # head: forward + input gradient


def input_grad_flops(s: Dict[str, int], seq_len: int, loss_positions: int = 1) -> float:
    """One sequence's forward pass and the gradient with respect to its
    inputs alone (no weight gradients), the loss at ``loss_positions``."""
    base = 2 * seq_len * _layer_weights(s)
    lora = 2 * seq_len * _layer_lora(s)
    attn = _attention_fwd(s, seq_len)
    head = 2 * loss_positions * s["d_model"] * s["vocab"]
    return s["layers"] * (2 * base + 2 * lora + 3 * attn) + 2 * head
