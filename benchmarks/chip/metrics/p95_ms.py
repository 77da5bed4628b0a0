"""95th percentile of the latencies of all ops of the window, in ms
(`encode_p95_ms`, `degraded_read_p95_ms`, ...: the cell's entry decides
which op it is)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.window.latencies, 95)) * 1e3
