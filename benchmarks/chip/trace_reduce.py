"""Reduce a JAX profiler trace (`.xplane.pb`) of one measured window to
the benchmark's device numbers.

The harness wraps its window in a host span `bench.window` and each call
in a span `bench.<op>`; both land in the trace through
`jax.profiler.TraceAnnotation`, on the host thread that drives the device.
From the trace this module takes:

- window_s: the length of the `bench.window` span;
- per device `/device:TPU:<i>` (the first `n_devices`): the union of the
  intervals in which an op of its "XLA Ops" line ran, clipped to the
  window (busy), and the time of its collective-permute ops;
- busy_s, the mean over those devices, and busy_any_s, the union over
  them (time in which at least one device ran an op);
- device_ops: the ops that took most device time, summed over devices;
- idle_gaps: the intervals in which no device ran an op, each attributed
  to the host spans open at its midpoint (`outer/inner`), summed by that
  path, longest first.

    python trace_reduce.py <file.xplane.pb> [n_devices]

prints the reduction as JSON.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def merge(intervals) -> list[tuple[float, float]]:
    """Union of half-open [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip_events(events, lo: float, hi: float):
    """(start, end, name) events cut to [lo, hi); those outside dropped."""
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi) that the sorted disjoint `busy` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_paths(events, points) -> list[str]:
    """For each time in `points` (ascending), the names of the host events
    of one thread open at that time, outermost first, joined by '/'.
    Events of one thread nest, so one sweep with a stack finds them."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    stack: list[tuple[float, float, str]] = []
    out, i = [], 0
    for t in points:
        while i < len(events) and events[i][0] <= t:
            s, e, name = events[i]
            while stack and stack[-1][1] <= s:
                stack.pop()
            stack.append((s, e, name))
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append("/".join(n for _, _, n in stack if n != WINDOW_SPAN)
                   or "_no_host_span_")
    return out


_HLO = re.compile(r"^%?(\S+) = (\w+\[[^\]]*\])\S* ([\w-]+)\(")


def op_name(hlo: str) -> str:
    """`name kind type[shape]` of a device op, whose trace name is the
    whole HLO instruction text."""
    m = _HLO.match(hlo)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else hlo[:96]


def _events(line, name=lambda n: n):
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
             name(ev.name)) for ev in line.events]


def reduce(path: str, n_devices: int, top: int = 10) -> dict | None:
    """The reduction of the trace at `path`, or None where it holds no
    window span or no device op inside the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host_line, window = None, None
    device_lines: dict[int, object] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines[int(m.group(1))] = line
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        host_line = line
                        window = (float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns))
    if window is None or len(device_lines) < n_devices:
        return None
    lo, hi = window

    per_device, union_in, collective = [], [], []
    op_time: dict[str, float] = defaultdict(float)
    for i in sorted(device_lines):
        evs = clip_events(_events(device_lines[i], op_name), lo, hi)
        busy = merge((s, e) for s, e, _ in evs)
        per_device.append(sum(e - s for s, e in busy))
        union_in.extend(busy)
        collective.append(sum(e - s for s, e, n in evs
                              if "collective-permute" in n))
        for s, e, n in evs:
            op_time[n] += e - s
    if not any(per_device):
        return None
    busy_any = merge(union_in)
    idle = gaps(busy_any, lo, hi)
    paths = span_paths(_events(host_line), [(s + e) / 2 for s, e in idle])
    gap_time: dict[str, float] = defaultdict(float)
    for (s, e), p in zip(idle, paths):
        gap_time[p] += e - s

    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(per_device) / len(per_device) * ns,
        "busy_any_s": sum(e - s for s, e in busy_any) * ns,
        "busy_s_per_device": [b * ns for b in per_device],
        "collective_s": sum(collective) / len(collective) * ns,
        "device_ops": [[n, t * ns] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[p, t * ns] for p, t in
                      sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]],
    }


def find_trace(log_dir) -> str:
    """The one `.xplane.pb` the profiler wrote under `log_dir`."""
    from pathlib import Path

    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return str(found[0])


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2
                            else 1), indent=1))
