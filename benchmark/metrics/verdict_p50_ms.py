"""Median of the same samples as ``verdict_p99_ms``."""

import numpy as np


def read(ctx):
    s = ctx["spans"]
    if len(s) < 100:
        return None
    i, j = ctx["stages"].index("admit"), ctx["stages"].index("join")
    return float(np.quantile(s[:, j] - s[:, i], 0.5) * 1e3)
