"""What every configuration's weights share: the key drawn from the seed,
and the check that they are laid out as the program lays out its own.

The benchmark makes the frozen base and the initial LoRA factors itself and
hands both to the program, so that the plain reference computes from weights
the program did not make. Each architecture draws them in its reference
module's ``make_weights`` (``bench/reference/<name>.py``) from
``jax_seed(seed)``; ``check_layout`` refuses a program whose layout differs.
"""
from __future__ import annotations

import jax
import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng([int(seed) & (2**64 - 1), 11]).integers(0, 2**31 - 1))


def check_layout(ours, theirs, what: str) -> None:
    """Refuses weights whose tree, shapes or types differ from the
    program's own initializer's (``jax.eval_shape`` of it)."""
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), ours)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), theirs)
    if a != b:
        raise SystemExit(f"the benchmark's {what} layout differs from the program's:\n{a}\n{b}")
