"""The whole init phase's share of the chips' peak: the operations its
per-sample gradients require (``bench/jobs/init.Job.required_flops``: the
loss read at the last position, no padded samples), over window x chips x
peak bf16 FLOP/s."""


def read(ctx):
    calls = [s for s in ctx["steps"] if "spans" in s]
    if not calls or not ctx.get("peak_flops"):
        return None
    return 100.0 * ctx["required_flops"] / (ctx["window_s"] * ctx["chips"] * ctx["peak_flops"])
