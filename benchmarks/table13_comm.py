"""Paper Table 13 — communication overhead per round.

Paper claim: FibecFed transfers 25% less than full-LoRA aggregation (150 vs
200 units: only the GAL layers move) while prompt-tuning moves far less but
loses accuracy. We count actual bytes up+down per round.
"""
from __future__ import annotations

from benchmarks.common import csv_row, fl_config, run_method, world


def run() -> list:
    rows = []
    res_fib = run_method("fibecfed", seed=2)
    res_full = run_method("gal_full", seed=2)
    b_fib = res_fib["comm_bytes_round0"]
    b_full = res_full["comm_bytes_round0"]
    rows.append(csv_row("table13/fibecfed", 0.0, f"bytes_per_round={b_fib}"))
    rows.append(csv_row("table13/full_lora_agg", 0.0, f"bytes_per_round={b_full}"))
    rows.append(csv_row(
        "table13/reduction", 0.0,
        f"saved={1 - b_fib / max(b_full, 1):.2%};paper_claims=25%",
    ))
    # prompt tuning: far fewer bytes (paper: FibecFed is up to 3.51x FedPrompt)
    from repro.federated.prompt_tuning import FedPrompt

    model, task, client_data, test_data = world(2)
    fp = FedPrompt(model, fl_config(rounds=1), client_data, n_prompt=8)
    fp.run_round(0)
    rows.append(csv_row(
        "table13/fedprompt", 0.0, f"bytes_per_round={fp.comm_bytes_per_round[0]}"
    ))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r)
