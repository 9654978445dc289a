"""Share of the lane-steps the round program scanned that train no batch,
over the window's rounds: ``(sum scanned - sum real_steps) / sum scanned``.
Scanned lane-steps come from the process-wide ``runtime_metrics`` histogram
``fl.round_scanned_steps`` (lanes x scan length, as the program ran them:
fewer lanes where the runner packs a cohort's ragged step runs), its last
``n`` observations, ``n`` the window's rounds (no round runs between the
window's end and the readers); real steps from the window's own counts.
``None`` where the program keeps no such histogram, or fewer than ``n``."""


def read(ctx):
    from repro.obs import runtime_metrics

    steps = [s for s in ctx["steps"] if "real_steps" in s]
    recent = getattr(runtime_metrics.histogram("fl.round_scanned_steps"), "recent", None)
    if not steps or recent is None or len(recent) < len(steps):
        return None
    scanned = sum(list(recent)[-len(steps):])
    if not scanned:
        return None
    return 100.0 * (scanned - sum(s["real_steps"] for s in steps)) / scanned
