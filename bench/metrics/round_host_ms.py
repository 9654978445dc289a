"""Host time per round outside the wait on the device: the runner's round
phases plan (cohort draw, curriculum step plan, weights), put (the plan's
uploads), dispatch (the round program's call until it returns) and account
(mean loss, round info, communication bytes), from the process-wide
``runtime_metrics`` histograms ``fl.round_<phase>_s``. The mean over the
window's rounds: the last ``n`` observations of each, ``n`` the window's
rounds (no round runs between the window's end and the readers). ``None``
where the program keeps no such histograms, or fewer than ``n``."""

PHASES = ("plan", "put", "dispatch", "account")


def read(ctx):
    from repro.obs import runtime_metrics

    n = sum(1 for s in ctx["steps"] if "real_steps" in s)
    recent = [getattr(runtime_metrics.histogram(f"fl.round_{p}_s"), "recent", None) for p in PHASES]
    if not n or any(r is None or len(r) < n for r in recent):
        return None
    return 1e3 * sum(sum(list(r)[-n:]) for r in recent) / n
