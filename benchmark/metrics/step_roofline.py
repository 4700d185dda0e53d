"""Fused step: the least bytes the traced window's verdicts must move
(``roofline.verdict_bytes`` from its rows, conntrack inserts and
events) over the ``jit_serve*`` modules' device time, as a share of
the chip's peak HBM bandwidth (``peaks.json``)."""

from roofline import verdict_bytes


def read(ctx):
    p = ctx["profile"]
    r = p and p["reduced"]
    if not r or not r["module_ns"] or not p["verdicts"] \
            or "peaks" not in ctx:
        return None
    nbytes = verdict_bytes(p["verdicts"], p["inserts"], p["events"])
    rate = nbytes / (r["module_ns"] * 1e-9)
    return 100.0 * rate / ctx["peaks"]["hbm_bytes_per_s"]
