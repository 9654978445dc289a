"""Paper §5.7 + Fig. 7 + Table 12 ablations:
(a) curriculum strategy linear/sqrt/exp (App. G.7 — paper picks linear),
(b) GAL selection order importance/ascending/random/full (§5.7),
(c) local sparse update on/off (§5.7),
(d) initial sample ratio β sweep (App. G.10 — paper best β≈0.6).
"""
from __future__ import annotations

import dataclasses

from benchmarks.common import csv_row, fl_config, run_method

# 11 full FL runs — capped at 8 rounds each so the whole suite stays within
# a CPU-core-hour; relative ablation ordering is stable at this budget.
_R = 8


def run() -> list:
    rows = []
    # (a) curriculum strategies (sqrt omitted: paper shows linear≈sqrt)
    for strat in ("linear", "exp", "none"):
        fl = fl_config(curriculum=strat, rounds=_R)
        res = run_method("fibecfed", seed=4, fl=fl)
        rows.append(csv_row(
            f"fig7c/curriculum_{strat}", res["wall_s"] * 1e6,
            f"acc={res['final_accuracy']:.3f}",
        ))
    # (b) GAL selection order (ascending ≈ random per paper; random kept)
    for mode in ("fibecfed", "gal_random", "gal_full"):
        res = run_method(mode, seed=4, fl=fl_config(rounds=_R))
        rows.append(csv_row(
            f"ablation_gal/{mode}", res["wall_s"] * 1e6,
            f"acc={res['final_accuracy']:.3f};bytes={res['comm_bytes_round0']}",
        ))
    # (c) sparse update on/off
    for mode in ("fibecfed", "no_sparse"):
        res = run_method(mode, seed=5, fl=fl_config(rounds=_R))
        rows.append(csv_row(
            f"ablation_sparse/{mode}", res["wall_s"] * 1e6,
            f"acc={res['final_accuracy']:.3f}",
        ))
    # (d) initial sample ratio beta
    for beta in (0.1, 0.6, 1.0):
        fl = fl_config(beta_initial_ratio=beta, rounds=_R)
        res = run_method("fibecfed", seed=6, fl=fl)
        rows.append(csv_row(
            f"table12/beta_{beta}", res["wall_s"] * 1e6,
            f"acc={res['final_accuracy']:.3f}",
        ))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r)
