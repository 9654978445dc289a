"""Host-clock time of the per-client layer-sensitivity probe
(``_probe_sensitivity``: one dispatch and one host read per client): the
program's ``sensitivity`` span, averaged over the window's ``init_phase``
calls."""


def read(ctx):
    spans = [s["spans"]["sensitivity"] for s in ctx["steps"] if "sensitivity" in s.get("spans", {})]
    return 1e3 * sum(spans) / len(spans) if spans else None
