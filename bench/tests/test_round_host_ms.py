"""``round_host_ms`` on a tiny rounds cell on the CPU: a traced run reads
the mean host time of exactly the window's rounds, and a program without
the round-phase histograms gives the reader nothing to read.

    PYTHONPATH=src python -m pytest -q bench/tests/test_round_host_ms.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import run  # noqa: E402
from bench.lib import spec  # noqa: E402
from bench.tests import tiny  # noqa: E402

PHASES = ("plan", "put", "dispatch", "account")


def test_round_host_ms_reads_the_window_rounds(tmp_path, capsys):
    from repro.obs import runtime_metrics

    root = tiny.make_root(tmp_path)
    hists = [runtime_metrics.histogram(f"fl.round_{p}_s") for p in PHASES]
    before = [h.count for h in hists]
    args = ["--workload", tiny.CELL, "--seed", "3000000019", "--seconds", "1", "--trace", "1"]
    assert run.main(args, root=root, require_tpu=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    n = res["attempted"]
    traffic = spec.load_traffic(root, "tiny")
    # set-up's checked rounds, then the window's; the reference runs none
    assert [h.count - b for h, b in zip(hists, before)] == [traffic["check_rounds"] + n] * 4
    want = 1e3 * sum(sum(list(h.recent)[-n:]) for h in hists) / n
    assert res["metrics"]["round_host_ms"]["value"] == want > 0


class _Registry:
    """A runtime registry whose round-phase histograms hold ``n`` entries,
    or, with ``n`` None, keep no recent observations (a program older than
    the round-phase histograms)."""

    def __init__(self, n):
        self.n = n

    def histogram(self, name):
        from repro.obs import Histogram

        h = Histogram()
        if self.n is None:
            return type("Old", (), {"count": 0})()
        for _ in range(self.n):
            h.observe(1e-3)
        return h


def test_round_host_ms_reads_nothing_without_the_window_rounds(monkeypatch):
    import repro.obs

    read = spec.load_reader(REPO, "round_host_ms")
    ctx = {"steps": [{"real_steps": 4}] * 3}
    for n in (None, 2):
        monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry(n))
        assert read(ctx) is None
    monkeypatch.setattr(repro.obs, "runtime_metrics", _Registry(3))
    assert abs(read(ctx) - 4.0) < 1e-9  # four phases of 1 ms per round
