"""Device: share of the traced steady window in which no XLA op ran
on the chip (1 - busy / window, busy the union of op intervals)."""


def read(ctx):
    p = ctx["profile"]
    r = p and p["reduced"]
    if not r or not r["busy_ns"]:
        return None
    return 100.0 * (1.0 - r["busy_ns"] / r["window_ns"])
