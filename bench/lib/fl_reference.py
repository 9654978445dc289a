"""Federated rounds recomputed by the plain reference.

Algorithm 1's tuning phase, written out step by step: each chosen client
takes the global LoRA on the GAL layers (line 15), runs one masked AdamW step
per curriculum batch in its order (lines 16-17), and the server averages the
chosen clients' LoRA over the GAL layers, weighted by shard size (line 18).
The discrete plan -- which clients each round drew, each client's batch
order, neuron masks and GAL layers -- is the program's; the init cell
checks how it is decided.

The init phase (lines 1-10), recomputed for the init cell: each batch's
Fisher difficulty (the sum over its samples of the squared per-sample LoRA
gradient); each client's layer sensitivity on the first batch of its
curriculum order, averaged over clients by shard size, and the GAL layers,
the most sensitive ``gal_fraction`` of them; the momentum Fisher diagonal
over the first ``fim_warmup_epochs`` batches of the order, and each layer's
kept neurons, the ``sparse_ratio`` of output neurons with the most Fisher
mass in LoRA ``b``. The order is the program's, as in the rounds: bf16 and
float32 scores order near-tied batches differently on their own, and a
different first batch would change every score computed from it. The init
cell checks the order against the program's scores by the ascending rule.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _batch(client: Dict[str, np.ndarray], b: int, B: int, half: bool):
    n = len(client["label_token"])
    ids = np.arange(b * B, min((b + 1) * B, n))
    pad = B - len(ids)
    tokens = np.concatenate([client["tokens"][ids], np.zeros((pad,) + client["tokens"].shape[1:], np.int32)])
    labels = np.concatenate([client["label_token"][ids], np.zeros(pad, np.int32)])
    valid = np.concatenate([np.ones(len(ids), np.float32), np.zeros(pad, np.float32)])
    if half:  # a planted fault: half of the batch left out, the mean over the rest
        valid[B - B // 2:] = 0.0
    return {"tokens": tokens, "label_token": labels}, valid


def run_rounds(ref, config: Dict[str, Any], params, lora0, clients: List[Dict[str, np.ndarray]],
               plan: Dict[str, Any], chosen_per_round: List[np.ndarray], *, batch_size: int,
               lr: float, mode: str = "f32", half_batch: bool = False) -> Dict[str, Any]:
    """Returns per-round mean losses, the chosen clients' first moments after
    the first round (stacked in cohort order), and the global LoRA after
    each round; all on the host."""
    model = ref.Model(config)
    prec = ref.Precision(mode)
    step = ref.make_train_step(model, prec)
    params = ref.to_f32(params)
    lora0 = ref.to_f32(lora0)
    zeros = jax.tree.map(jnp.zeros_like, lora0)
    gal = jnp.asarray(plan["gal"], jnp.float32)
    galm = jax.tree.map(lambda x: gal.reshape((-1,) + (1,) * (x.ndim - 1)), lora0)
    shards = np.asarray([len(c["label_token"]) for c in clients], np.float64)
    states: Dict[int, Any] = {}
    glob = lora0
    out = {"loss": [], "m1": None, "global": []}
    for r, chosen in enumerate(chosen_per_round):
        losses, trained = [], []
        for ci in (int(c) for c in chosen):
            lo, m, v, t = states.get(ci, (lora0, zeros, zeros, jnp.zeros((), jnp.float32)))
            lo = jax.tree.map(lambda g, l, k: k * g + (1.0 - k) * l, glob, lo, galm)
            mask = ref.lora_mask(lora0, plan["keep"][ci])
            for b in plan["order"][ci]:
                batch, valid = _batch(clients[ci], int(b), batch_size, half_batch)
                loss, _g, lo, m, v, t = step(params, lo, m, v, t, mask, batch, valid, lr)
                losses.append(float(loss))
            states[ci] = (lo, m, v, t)
            trained.append(lo)
        w = shards[np.asarray(chosen)]
        w = jnp.asarray(w / w.sum(), jnp.float32)
        avg = jax.tree.map(lambda *xs: jnp.tensordot(w, jnp.stack(xs), axes=1), *trained)
        glob = jax.tree.map(lambda k, a, g: k * a + (1.0 - k) * g, galm, avg, glob)
        out["loss"].append(float(np.mean(losses)))
        out["global"].append(jax.tree.map(np.asarray, glob))
        if r == 0:
            out["m1"] = jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *[states[int(ci)][1] for ci in chosen])
    return out


def decide_order(scores: np.ndarray) -> np.ndarray:
    """Curriculum order: batches by ascending difficulty."""
    return np.argsort(np.asarray(scores), kind="stable")


def decide_keep(fim: Dict[str, Any], sparse_ratio: float) -> Dict[str, np.ndarray]:
    """Per target, each layer's kept output neurons: the ``sparse_ratio``
    with the most Fisher mass in LoRA ``b`` (summed over the rank)."""
    keep = {}
    for t, ab in fim["layers"].items():
        imp = np.asarray(ab["b"], np.float32).sum(axis=-2)  # (layers, d_out)
        d_out = imp.shape[-1]
        k = max(1, int(round(sparse_ratio * d_out)))
        keep[t] = (imp >= np.sort(imp, axis=-1)[:, d_out - k][:, None]).astype(np.float32)
    return keep


def decide_gal(sensitivity: List[np.ndarray], shards: np.ndarray, gal_fraction: float) -> np.ndarray:
    """GAL layers: the ``gal_fraction`` most sensitive layers by the
    clients' scores averaged with shard-size weights."""
    shards = np.asarray(shards, np.float64)
    glob = (np.stack(sensitivity) * shards[:, None]).sum(0) / shards.sum()
    L = len(glob)
    n_star = int(np.clip(round(gal_fraction * L), 1, L))
    gal = np.zeros(L, bool)
    gal[np.argsort(-glob, kind="stable")[:n_star]] = True
    return gal


def run_init(ref, config: Dict[str, Any], params, lora0, clients: List[Dict[str, np.ndarray]],
             orders: List[np.ndarray], *, batch_size: int, fim_epochs: int, momentum: float,
             gamma: float, gal_fraction: float, sparse_ratio: float, mode: str = "f32") -> Dict[str, Any]:
    model = ref.Model(config)
    prec = ref.Precision(mode)
    sq_grads = ref.make_sample_sq_grads(model, prec)
    sensitivity = ref.make_sensitivity(model, prec, gamma)
    params = ref.to_f32(params)
    lora = ref.to_f32(lora0)
    out = {"difficulty": [], "order": [], "fim": [], "keep": [], "sensitivity": []}
    for c, order in zip(clients, orders):
        order = np.asarray(order)
        n = len(c["label_token"])
        nb = -(-n // batch_size)
        per_batch = []
        for b in range(nb):
            batch, valid = _batch(c, b, batch_size, False)
            per_batch.append((sq_grads(params, lora, batch), valid))
        scores = np.asarray([
            float(sum(np.sum(np.asarray(x, np.float64).reshape(len(v), -1).sum(1) * v)
                      for x in jax.tree.leaves(sq)))
            for sq, v in per_batch])
        fim = None
        for e in range(fim_epochs):
            sq, v = per_batch[int(order[min(e, nb - 1)])]
            w = jnp.asarray(v / max(v.sum(), 1.0))
            new = jax.tree.map(lambda x: jnp.tensordot(w, x, axes=1), sq)
            fim = new if fim is None else jax.tree.map(
                lambda a, b: momentum * a + (1.0 - momentum) * b, fim, new)
        fim = jax.tree.map(np.asarray, fim)
        ids = np.arange(int(order[0]) * batch_size, min((int(order[0]) + 1) * batch_size, n))
        sens = sensitivity(params, lora, {"tokens": c["tokens"][ids], "label_token": c["label_token"][ids]})
        out["difficulty"].append(scores)
        out["order"].append(order)
        out["fim"].append(fim)
        out["keep"].append(decide_keep(fim, sparse_ratio))
        out["sensitivity"].append(np.asarray(sens, np.float64))
    shards = np.asarray([len(c["label_token"]) for c in clients])
    out["gal"] = decide_gal(out["sensitivity"], shards, gal_fraction)
    return out
