"""99th percentile of how late the load generator submitted each
request (send time minus due time), in ms, by the benchmark's clock."""

import numpy as np


def read(ctx):
    if ctx.kind != "open" or ctx.late_ms is None or not len(ctx.late_ms):
        return None
    return float(np.percentile(ctx.late_ms, 99))
