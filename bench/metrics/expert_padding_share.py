"""Share of the expert rows the round program computed that no (token, held
expert) assignment needed, over the window's rounds: ``1 - sum assignments /
sum rows``, in %. Both come from the process-wide ``runtime_metrics``
histograms the runner fills per round of a held-expert MoE:
``fl.moe_held_assignments`` (assignments in valid samples of active steps,
from the program) and ``fl.moe_expert_rows`` (the rows its expert layers
computed, from the program's shapes); their last ``n`` observations, ``n``
the window's rounds. ``None`` where the program keeps no such histograms, or
fewer than ``n``."""

NAMES = ("fl.moe_held_assignments", "fl.moe_expert_rows")


def read(ctx):
    from repro.obs import runtime_metrics

    n = sum(1 for s in ctx["steps"] if "real_steps" in s)
    recent = [getattr(runtime_metrics.histogram(name), "recent", None) for name in NAMES]
    if not n or any(r is None or len(r) < n for r in recent):
        return None
    held, rows = (sum(list(r)[-n:]) for r in recent)
    return 100.0 * (1.0 - held / rows) if rows else None
