"""Share of the traced window in which no operation ran on the device
(1 - busy / window, busy averaged over chips), in the init cell."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
