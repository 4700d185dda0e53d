"""99th percentile, over every sampled packet whose span joined inside
the window, of the time from its chunk's due time (the ``admit``
stamp, passed to ``submit`` as ``t``) to its ``join`` stamp (events
emitted to the monitor plane after the device finished)."""

import numpy as np


def read(ctx):
    s = ctx["spans"]
    if len(s) < 100:
        return None
    i, j = ctx["stages"].index("admit"), ctx["stages"].index("join")
    return float(np.quantile(s[:, j] - s[:, i], 0.99) * 1e3)
