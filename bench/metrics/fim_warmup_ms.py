"""Host-clock time of the momentum-FIM warm-up and neuron-mask selection
(``_select_local_masks``): the program's ``fim_warmup`` span, averaged over
the window's ``init_phase`` calls."""


def read(ctx):
    spans = [s["spans"]["fim_warmup"] for s in ctx["steps"] if "fim_warmup" in s.get("spans", {})]
    return 1e3 * sum(spans) / len(spans) if spans else None
