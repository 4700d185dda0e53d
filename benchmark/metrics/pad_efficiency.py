"""Batcher: real rows over rows dispatched (real plus padding) in the
window."""


def read(ctx):
    d = ctx["delta"]
    total = d["verdicts"] + d["padded"]
    return d["verdicts"] / total if total else None
