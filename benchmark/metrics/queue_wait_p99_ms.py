"""Admission: 99th percentile of a sampled packet's wait in the
admission queue (``admit`` to ``dequeue`` stamps) over the window's
spans."""

import numpy as np


def read(ctx):
    s = ctx["spans"]
    if len(s) < 100:
        return None
    i, j = ctx["stages"].index("admit"), ctx["stages"].index("dequeue")
    return float(np.quantile(s[:, j] - s[:, i], 0.99) * 1e3)
