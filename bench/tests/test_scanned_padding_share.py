"""``scanned_padding_share`` on hand-made window counts: the share of the
window rounds' scanned lane-steps that train no batch, and nothing to read
from a program without the ``fl.round_scanned_steps`` histogram.

    PYTHONPATH=src python -m pytest -q bench/tests/test_scanned_padding_share.py
"""
from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.lib import spec  # noqa: E402


class _Registry:
    """A runtime registry whose ``fl.round_scanned_steps`` holds ``values``,
    or, with ``values`` None, keeps no recent observations (a program older
    than the histogram)."""

    def __init__(self, values):
        self.values = values

    def histogram(self, name):
        from repro.obs import Histogram

        assert name == "fl.round_scanned_steps"
        if self.values is None:
            return type("Old", (), {"count": 0})()
        h = Histogram()
        for v in self.values:
            h.observe(v)
        return h


def test_scanned_padding_share_reads_the_window_rounds(monkeypatch):
    import repro.obs

    read = spec.load_reader(REPO, "scanned_padding_share")
    # three window rounds of 7 lanes x 16 steps, after a set-up round of 160
    ctx = {"steps": [{"real_steps": r} for r in (84, 80, 88)]}
    monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry([160, 112, 112, 112]))
    assert abs(read(ctx) - 100.0 * (336 - 252) / 336) < 1e-9
    # an unpacked round scans the whole cohort grid, 10 clients x 16 steps
    monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry([160, 160, 160]))
    assert abs(read(ctx) - 100.0 * (480 - 252) / 480) < 1e-9


def test_scanned_padding_share_reads_nothing_without_the_histogram(monkeypatch):
    import repro.obs

    read = spec.load_reader(REPO, "scanned_padding_share")
    ctx = {"steps": [{"real_steps": 84}] * 3}
    for values in (None, [], [112, 112]):
        monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry(values))
        assert read(ctx) is None
    monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry([112] * 3))
    assert read({"steps": [{"loss": 1.0}]}) is None  # no window rounds
