"""Closed loop: one caller calls the op back to back for the window's
seconds, cycling over the payload pool; each op ends when its result is
on the host.  A reservoir drawn from the seed keeps `sample` of the
results for the comparison after the window."""
from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from harness import Window, keep


def run(session, seconds: float, op) -> Window:
    from jax.profiler import TraceAnnotation

    if session.cell.traffic["clients"] != 1:
        raise ValueError("the closed loop runs one client")
    payloads = session.payloads
    buffers = session.sample_buffers
    k = len(buffers)
    rng = np.random.default_rng([session.seed % (1 << 64), 1])
    span = f"bench.{session.entry_name}"
    w = Window()
    with TraceAnnotation("bench.window"):
        w.t0 = time.perf_counter()
        t_end, t_last, i = w.t0 + seconds, w.t0, 0
        while True:
            t_start = time.perf_counter()
            if t_start >= t_end:
                break
            try:
                with TraceAnnotation(span):
                    y = op(payloads[i % len(payloads)])
            except Exception:
                if not w.failed:
                    traceback.print_exc(file=sys.stderr)
                w.failed += 1
                y = None
            t_last = time.perf_counter()
            w.latencies.append(t_last - t_start)
            j = i if i < k else int(rng.integers(0, i + 1))
            if j < k:
                kept = (i, keep(y, buffers[j]))
                if i < k:
                    w.sample.append(kept)
                else:
                    w.sample[j] = kept
            # release the result as a client that has consumed it does:
            # held over the next op, it changes where the allocator puts
            # that op's temporaries (measured: every 4th degraded read at
            # 70 ms instead of 21 ms)
            y = None
            i += 1
    w.attempted = i
    w.seconds = t_last - w.t0
    return w
