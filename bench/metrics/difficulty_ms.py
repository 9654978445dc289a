"""Host-clock time of Fisher difficulty scoring (``_compute_difficulty``:
per-sample LoRA gradients of every client's batches): the program's
``difficulty`` span, averaged over the window's ``init_phase`` calls. The
span ends in a host read of the scores, so it covers the device work."""


def read(ctx):
    spans = [s["spans"]["difficulty"] for s in ctx["steps"] if "difficulty" in s.get("spans", {})]
    return 1e3 * sum(spans) / len(spans) if spans else None
