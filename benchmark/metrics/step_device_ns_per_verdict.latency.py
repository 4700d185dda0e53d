"""Fused step: device time of the ``jit_serve*`` modules in the traced
window over the verdicts dispatched in it (latency cells)."""


def read(ctx):
    p = ctx["profile"]
    r = p and p["reduced"]
    if not r or not r["module_ns"] or not p["verdicts"]:
        return None
    return r["module_ns"] / p["verdicts"]
