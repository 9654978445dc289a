"""Paper Table 2 / Table 7 — fine-tuning time to target accuracy.

Paper claim: FibecFed reaches target accuracy up to 98.61% faster. The
curriculum uses fewer batches early, so wall-clock per round is smaller;
we measure time-to-target on the same budget.
"""
from __future__ import annotations

from benchmarks.common import csv_row, run_method

METHODS = ["fibecfed", "fedavg_lora", "random_select"]


def run() -> list:
    rows = []
    times = {}
    for m in METHODS:
        res = run_method(m, seed=1)
        ttt = res["time_to_target_s"]
        times[m] = ttt
        rows.append(csv_row(
            f"table2/{m}",
            (ttt or res["wall_s"]) * 1e6,
            f"time_to_45pct_s={'%.1f' % ttt if ttt else 'miss'};"
            f"tune_s={res['wall_s']:.1f};init_s={res['init_s']:.1f}",
        ))
    if times.get("fibecfed") and times.get("fedavg_lora"):
        speedup = 1.0 - times["fibecfed"] / times["fedavg_lora"]
        rows.append(csv_row("table2/speedup_vs_fedavg", 0.0, f"faster_by={speedup:+.2%}"))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r)
