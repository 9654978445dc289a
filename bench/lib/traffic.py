"""The federated job's data, generated from a traffic file and a seed.

A keyword-detection classification task in token space, as the paper's
prompt-style LLM runs use it: class ``c`` has a keyword token; each sequence
plants its class's keyword among distractor tokens (wrong-class keywords
among them, denser for harder samples), and the model must emit the class's
label token after the sequence.

The shard sizes and each client's class mix come from a Dirichlet(alpha)
label-skew partition drawn from the traffic file's ``partition_seed``, not
from the run's seed: every seed gets the same shards, so the same work, and
the seed changes only the tokens, which samples are harder, and the
weights. This mirrors ``repro.data.synthetic.make_keyword_task`` and
``repro.data.partition.dirichlet_partition`` without importing them.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

KEYWORD_BASE = 10
LABEL_BASE = 110
DISTRACTOR_BASE = 220
MAX_NOISE = 0.9


def class_counts(traffic: Dict[str, Any]) -> np.ndarray:
    """(population, n_classes) samples of each class on each client."""
    C, K = traffic["population"], traffic["n_classes"]
    rng = np.random.default_rng(traffic["partition_seed"])
    per_class = traffic["samples"] // K
    counts = np.zeros((C, K), np.int64)
    for c in range(K):
        props = rng.dirichlet(np.full(C, float(traffic["dirichlet_alpha"])))
        cuts = (np.cumsum(props) * per_class).astype(np.int64)[:-1]
        counts[:, c] = np.diff(np.concatenate([[0], cuts, [per_class]]))
    # every client holds at least one whole batch
    floor = traffic["batch_size"]
    for k in np.nonzero(counts.sum(1) < floor)[0]:
        counts[k, rng.integers(K)] += floor - counts[k].sum()
    return counts


def shard_sizes(traffic: Dict[str, Any]) -> np.ndarray:
    return class_counts(traffic).sum(1)


def make_clients(traffic: Dict[str, Any], vocab: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """One dict ``{"tokens": (n, T) int32, "label_token": (n,) int32}`` per
    client."""
    T = traffic["seq_len"]
    rng = np.random.default_rng([seed, 7])
    clients = []
    for row in class_counts(traffic):
        labels = rng.permutation(np.repeat(np.arange(len(row)), row))
        n = len(labels)
        noise = rng.uniform(0.0, MAX_NOISE, n)
        tokens = rng.integers(DISTRACTOR_BASE, vocab, (n, T))
        for i in range(n):
            n_distract = int(noise[i] * T * 0.15)
            if n_distract:
                pos = rng.choice(T, n_distract, replace=False)
                wrong = (labels[i] + 1 + rng.integers(0, len(row) - 1, n_distract)) % len(row)
                tokens[i, pos] = KEYWORD_BASE + wrong
            n_kw = max(1, int(round((1.0 - noise[i]) * T * 0.2)))
            tokens[i, rng.choice(T, min(n_kw, T), replace=False)] = KEYWORD_BASE + labels[i]
        clients.append({
            "tokens": tokens.astype(np.int32),
            "label_token": (LABEL_BASE + labels).astype(np.int32),
        })
    return clients


def batches_of(n: int, batch_size: int) -> int:
    return -(-n // batch_size)
