"""The whole round's share of the chips' peak: the operations the window's
real client samples require (the configuration's reference module's
``lora_train_flops``: last position's head only, no padded steps, no weight
gradients of the frozen base), over window x chips x peak bf16 FLOP/s."""


def read(ctx):
    rounds = [s for s in ctx["steps"] if "real_steps" in s]
    if not rounds or not ctx.get("peak_flops"):
        return None
    return 100.0 * ctx["required_flops"] / (ctx["window_s"] * ctx["chips"] * ctx["peak_flops"])
