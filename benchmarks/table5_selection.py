"""Paper Table 5/6 (App. G.2) — data-selection strategies.

Paper claim: Fisher-based selection beats ShortFormer/SLW/Voc/Random (up to
+8.51% accuracy, 92.49% faster to target). Same switch set here.
"""
from __future__ import annotations

from benchmarks.common import csv_row, run_method

STRATEGIES = {
    "fisher": "fibecfed",
    "length": "shortformer",
    "loss": "loss_curriculum",
    "random": "random_select",
}


def run() -> list:
    rows = []
    for label, method in STRATEGIES.items():
        res = run_method(method, seed=3)
        rows.append(csv_row(
            f"table5/{label}", res["wall_s"] * 1e6,
            f"acc={res['final_accuracy']:.3f};"
            f"ttt_s={res['time_to_target_s'] if res['time_to_target_s'] else 'miss'}",
        ))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r)
