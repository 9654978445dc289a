"""Device time of the round program per round: the traced ``XLA Modules``
events of programs whose name holds ``round``, averaged over chips, over
the window's rounds."""


def read(ctx):
    trace, rounds = ctx.get("trace"), [s for s in ctx["steps"] if "real_steps" in s]
    if not trace or not rounds:
        return None
    t = sum(v for k, v in trace.get("modules_s", {}).items() if "round" in k)
    return 1e3 * t / len(rounds) if t > 0 else None
