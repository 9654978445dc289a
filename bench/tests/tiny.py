"""A checkout with one tiny cell, for running the harness on a CPU.

``make_root(dir)`` writes ``BENCHMARK.json`` and a tiny configuration,
traffic mix and limits into ``dir`` and links the benchmark's own code and
the program into it, so that ``run.main(root=dir, require_tpu=False)``
drives a whole run at a size a test can hold. ``reference=<module file>``
links that file's directory in place of the benchmark's reference modules
and names the module in the tiny configuration's ``reference``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny.rounds"
# the traffic each tiny cell borrows from a real cell, and that cell
KINDS = {"rounds": ("fl-k12-b3", "qwen2-0.5b.rounds"), "init": ("init-k12-b3", "qwen2-0.5b.init")}
# The tiny rounds cell's own limits. At this size on the CPU the program's
# bf16 gaps read higher than at full size on the chip, so the real cells'
# limits would fail sound runs here. Readings over 8 seeds (CPU): sound at
# most loss 2.0e-4, moment 5.6e-3, update 9.9e-3; the float8 control at
# least loss 5.1e-4, moment 2.1e-2, update 1.2e-2; half of each batch left
# out at least loss 1.9e-3, moment 0.12, update 6.2e-2.
TINY_ROUNDS_LIMITS = {"loss_gap": 4e-4, "moment_gap": 1.2e-2, "update_gap": 2e-2}
# The tiny init cell's: over 11 seeds (CPU) the sound difficulty, FIM and
# sensitivity gaps read at most 3.3e-2, 3.7e-2 and 4.2e-2, the float8
# control's at least 0.10, 0.23 and 0.14. The decisions are checked exactly
# against the program's scores.
TINY_INIT_LIMITS = {"difficulty_gap": 0.06, "fim_gap": 0.1, "sensitivity_gap": 0.08,
                    "order_mismatch": 0.0, "mask_mismatch": 1e-3, "gal_mismatch": 0.0}


def make_root(root: Path, *, kind: str = "rounds", base: str = "qwen2-0.5b", limits=None,
              reference: Optional[Path] = None) -> Path:
    root = Path(root)
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    for d in ("jobs", "lib", "metrics"):
        (root / "bench" / d).symlink_to(REPO / "bench" / d)
    refs = REPO / "bench" / "reference" if reference is None else Path(reference).parent
    (root / "bench" / "reference").symlink_to(refs)
    (root / "src").symlink_to(REPO / "src")
    cfg = json.loads((REPO / "bench" / "configs" / f"{base}.json").read_text())
    if reference is not None:
        cfg["reference"] = Path(reference).stem
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
    cfg["run_as"]["head_dim"] = 16
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    traffic, real_cell = KINDS[kind]
    cell = f"tiny.{kind}"
    tr = json.loads((REPO / "bench" / "traffic" / f"{traffic}.json").read_text())
    tr.update(population=4, cohort=3, batch_size=2, seq_len=16, samples=24)
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(tr))
    if limits is None:
        limits = TINY_ROUNDS_LIMITS if kind == "rounds" else TINY_INIT_LIMITS
    (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps({"limits": limits}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": cfg["source"], "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "a test's size"}]
    bench["workloads"] = [{"name": cell, "config": "tiny", "traffic": "tiny", "chips": 1,
                           "why": "a test's size"}]
    mine = lambda m: "workloads" not in m or real_cell in m["workloads"]  # noqa: E731
    bench["end_to_end"] = [dict(m, workloads=[cell]) if "workloads" in m else m
                           for m in bench["end_to_end"] if mine(m)]
    bench["per_layer"] = [dict(m, workloads=[cell]) for m in bench["per_layer"] if mine(m)]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
