"""A test-only architecture: the dense decoder's hooks, wrapped so that a
test sees the harness take its sizes, weights and operation counts from the
configuration's reference module.

Each hook records its call in ``CALLS``; ``make_weights`` keeps what it made
in ``MADE``; ``sizes`` marks its dict with ``arch``; the operation counts read
``FLOPS_SCALE`` times the dense ones (a power of two, so the scaling is
exact), so that a job's ``required_flops`` shows whose counts it used.
"""
from __future__ import annotations

from pathlib import Path

from bench.lib import spec

dense = spec.load_reference(Path(__file__).resolve().parents[3], "dense_decoder")

CALLS = []
MADE = []
FLOPS_SCALE = 2.0


def sizes(config):
    CALLS.append("sizes")
    return dict(dense.sizes(config), arch="recording")


def make_weights(seed, s, run_as):
    CALLS.append("make_weights")
    MADE.append(dense.make_weights(seed, s, run_as))
    return MADE[-1]


def lora_train_flops(s, seq_len, loss_positions=1):
    CALLS.append("lora_train_flops")
    return FLOPS_SCALE * dense.lora_train_flops(s, seq_len, loss_positions)


def input_grad_flops(s, seq_len, loss_positions=1):
    CALLS.append("input_grad_flops")
    return FLOPS_SCALE * dense.input_grad_flops(s, seq_len, loss_positions)


def forward_flops(s, seq_len, head_positions):
    CALLS.append("forward_flops")
    return FLOPS_SCALE * dense.forward_flops(s, seq_len, head_positions)
