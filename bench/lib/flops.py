"""Operations the algorithm requires, counted from a configuration's shapes.

A multiply-add is two operations. Only what the result needs is counted:

- the forward pass through the frozen base and the LoRA factors;
- the backward pass that carries the gradient back to the inputs of every
  layer (the frozen base gets no weight gradient);
- the LoRA factors' input and weight gradients;
- causal attention (scores and weighted values over the positions each
  query sees);
- the LM head at only the positions the loss reads.

So work the program does beyond this (logits at positions no loss reads,
padded steps, recomputation) lowers a utilization built on these counts.
"""
from __future__ import annotations

from typing import Any, Dict


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
