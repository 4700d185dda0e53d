"""Load generator: 99th percentile of how late a chunk was offered
(submit time minus due time) over the chunks due in the window."""

import numpy as np


def read(ctx):
    lag = ctx["gen_lag_s"]
    if len(lag) < 100:
        return None
    return float(np.quantile(lag, 0.99) * 1e3)
