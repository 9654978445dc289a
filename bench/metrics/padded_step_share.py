"""Share of the round program's scanned client steps that train no real
batch (curriculum steps padded to the cohort's largest shard, then to a
power of two), summed over the window's rounds. From the runner's own
counts: ``padded_steps`` and ``last_round_info["client_steps"]``."""


def read(ctx):
    steps = [s for s in ctx["steps"] if "scanned_steps" in s]
    scanned = sum(s["scanned_steps"] for s in steps)
    if not scanned:
        return None
    return 100.0 * (scanned - sum(s["real_steps"] for s in steps)) / scanned
