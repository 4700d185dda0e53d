"""Event plane: 99th percentile of the ``device`` to ``join`` interval
(window fetched, to events decoded, joined and emitted) over the
window's spans."""

import numpy as np


def read(ctx):
    s = ctx["spans"]
    if len(s) < 100:
        return None
    i, j = ctx["stages"].index("device"), ctx["stages"].index("join")
    return float(np.quantile(s[:, j] - s[:, i], 0.99) * 1e3)
