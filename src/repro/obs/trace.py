"""Low-overhead span/event tracer with Chrome trace-event JSON export.

One `Tracer` collects timestamped events from every layer — simulator
rounds, stream pipeline stages, queue executions, service ops — onto
named (process, thread) tracks and exports the standard Chrome
trace-event format, loadable in perfetto (https://ui.perfetto.dev) or
chrome://tracing:

    from repro.obs import trace

    tracer = trace.install(trace.Tracer())
    ...                        # anything that runs emits onto it
    trace.uninstall(tracer)
    tracer.save("out.json")

Instrumented call sites key off the *installed* tracer (`get_tracer()`),
so tracing needs no parameter plumbing through cached plans or networks
constructed deep inside framework code — and when nothing is installed
every hook is a single `is None` check: tracing off costs nothing
measurable.

Track names are strings (`pid="simulator"`, `tid="proc 3"`); the trace
format wants integers, so the tracer interns them and emits the
`process_name` / `thread_name` metadata events perfetto uses for labels.
Timestamps are wall-clock microseconds from the tracer's own epoch (the
moment it was built), so every layer that emits onto one tracer lines up
on one timeline; two tracers do not share an epoch.

The API's host edge is timed by `stage`, always on and independent of any
installed tracer: each stage is a `jax.profiler.TraceAnnotation`
(`edge.<stage>`, on the profiler's clock, which the device ops share), an
observation of `edge_stage_seconds` in `obs.metrics.REGISTRY`, and, where
a tracer is installed, a complete event on it.  `count_reduce` counts
which way each payload reached the device format (`edge_reduce_total`),
`count_gather_fused` each conversion that took its rows out of a wider
array (`edge_gather_fused_total`), and `count_kernel` which kernel each
local device call ran (`local_kernel_total`).
"""
from __future__ import annotations

import json
import threading
from contextlib import contextmanager
from time import perf_counter_ns

from .metrics import REGISTRY, BoundCounter


class Tracer:
    """Thread-safe in-memory event collector (Chrome trace-event model).

    Events: `complete(...)` is a closed span ("X": ts + dur), `span(...)`
    a context manager measuring one, `instant(...)` a zero-duration mark
    ("i") — kills, aborts, state flips.  All take `pid`/`tid` track names
    (str or raw int) plus optional `cat` and an `args` dict shown in the
    viewer's detail pane.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._pids: dict[str, int] = {}
        self._tids: dict[tuple[int, str], int] = {}
        # this tracer's epoch: every layer emitting onto it aligns
        self._t0 = perf_counter_ns()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def now_us(self) -> float:
        """Microseconds since this tracer's epoch (wall clock)."""
        return self.at_us(perf_counter_ns())

    def at_us(self, t_ns: int) -> float:
        """A `perf_counter_ns()` reading on this tracer's clock (us)."""
        return (t_ns - self._t0) / 1e3

    # -- track interning -----------------------------------------------------
    def _pid(self, pid) -> int:
        if isinstance(pid, int):
            return pid
        n = self._pids.get(pid)
        if n is None:
            n = self._pids[pid] = len(self._pids) + 1
            self._events.append({
                "name": "process_name", "ph": "M", "pid": n, "tid": 0,
                "args": {"name": pid}})
        return n

    def _tid(self, pid: int, tid) -> int:
        if isinstance(tid, int):
            return tid
        key = (pid, tid)
        n = self._tids.get(key)
        if n is None:
            n = self._tids[key] = len(self._tids) + 1
            self._events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": n,
                "args": {"name": tid}})
        return n

    # -- emission ------------------------------------------------------------
    def complete(self, name: str, ts_us: float, dur_us: float, *,
                 pid="main", tid="main", cat: str = "",
                 args: dict | None = None) -> None:
        """A closed span: began at `ts_us`, lasted `dur_us` (both in
        microseconds on this tracer's clock — see `now_us`)."""
        ev = {"name": name, "ph": "X", "ts": ts_us,
              "dur": max(dur_us, 0.001)}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        with self._lock:
            p = self._pid(pid)
            ev["pid"], ev["tid"] = p, self._tid(p, tid)
            self._events.append(ev)

    def instant(self, name: str, *, ts_us: float | None = None,
                pid="main", tid="main", cat: str = "",
                args: dict | None = None) -> None:
        """A zero-duration mark (kill, abort, state flip)."""
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": self.now_us() if ts_us is None else ts_us}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        with self._lock:
            p = self._pid(pid)
            ev["pid"], ev["tid"] = p, self._tid(p, tid)
            self._events.append(ev)

    @contextmanager
    def span(self, name: str, *, pid="main", tid="main", cat: str = "",
             args: dict | None = None):
        """Measure the with-block as one complete event."""
        t0 = self.now_us()
        try:
            yield self
        finally:
            self.complete(name, t0, self.now_us() - t0, pid=pid, tid=tid,
                          cat=cat, args=args)

    # -- export --------------------------------------------------------------
    def events(self, *, cat: str | None = None,
               name: str | None = None) -> list[dict]:
        """A snapshot of collected events, optionally filtered (metadata
        events excluded) — the programmatic side of the export, used by
        trace-correctness tests."""
        with self._lock:
            evs = list(self._events)
        out = []
        for e in evs:
            if e["ph"] == "M":
                continue
            if cat is not None and e.get("cat") != cat:
                continue
            if name is not None and e.get("name") != name:
                continue
            out.append(e)
        return out

    def to_dict(self) -> dict:
        """The full trace as the Chrome trace-event JSON object."""
        with self._lock:
            return {"traceEvents": [dict(e) for e in self._events],
                    "displayTimeUnit": "ms"}

    def save(self, path) -> str:
        """Write the trace JSON to `path`; returns the path written."""
        with open(path, "w") as fh:
            json.dump(self.to_dict(), fh)
        return str(path)


# ---------------------------------------------------------------------------
# the installed-tracer stack (what instrumented call sites consult)
# ---------------------------------------------------------------------------

_INSTALLED: list[Tracer] = []


def install(tracer: Tracer) -> Tracer:
    """Make `tracer` the active tracer every instrumented call site emits
    to (a stack — nesting installs is fine); returns it for chaining."""
    _INSTALLED.append(tracer)
    return tracer


def uninstall(tracer: Tracer) -> None:
    """Remove `tracer` from the active stack (no-op if absent)."""
    for i in range(len(_INSTALLED) - 1, -1, -1):
        if _INSTALLED[i] is tracer:
            del _INSTALLED[i]
            return


def get_tracer() -> Tracer | None:
    """The currently installed tracer, or None (the common, free case)."""
    return _INSTALLED[-1] if _INSTALLED else None


def resolve(trace) -> tuple[Tracer | None, str | None]:
    """Normalize a user-facing `trace=` argument — the shape
    `CodedSystem(trace=...)` / `CodedService(trace=...)` accept:

        None/False     -> (None, None)         tracing off
        True           -> (new Tracer, None)   collect, caller exports
        a Tracer       -> (it, None)           caller-owned
        a path (str)   -> (new Tracer, path)   saved on close()
    """
    if trace is None or trace is False:
        return None, None
    if trace is True:
        return Tracer(), None
    if isinstance(trace, Tracer):
        return trace, None
    return Tracer(), str(trace)


@contextmanager
def installed(tracer: Tracer | None = None):
    """`with trace.installed() as t:` — install for the block's duration."""
    t = tracer or Tracer()
    install(t)
    try:
        yield t
    finally:
        uninstall(t)


# ---------------------------------------------------------------------------
# host-edge stages: profiler span + registry histogram (+ tracer event)
# ---------------------------------------------------------------------------

EDGE_SECONDS = REGISTRY.histogram(
    "edge_stage_seconds", "host-edge stage wall seconds per call, by "
    "stage (gather, prep, h2d, dispatch, d2h, widen; materialize in "
    "streams), op and backend")
EDGE_BYTES = REGISTRY.counter(
    "edge_bytes_total", "bytes of the device arrays placed (h2d) or read "
    "back (d2h) at the host edge, by direction, op and backend")
EDGE_REDUCE = REGISTRY.counter(
    "edge_reduce_total", "host payloads converted to the device format, by "
    "path (canonical: a range check and a cast; reduced: the % q pass), "
    "op and backend")
EDGE_GATHER_FUSED = REGISTRY.counter(
    "edge_gather_fused_total", "host payloads converted to the device "
    "format straight from the rows of a wider array (the survivors in a "
    "codeword), with no gather copy, by op and backend")
LOCAL_KERNEL = REGISTRY.counter(
    "local_kernel_total", "device calls of the local kernels, by kernel "
    "(ntt: the NTT encode; matmul: the dense field matmul), op and backend")

# the stages whose bytes cross the host-device link, and which way
_DIRECTION = {"h2d": "h2d", "d2h": "d2h", "materialize": "d2h"}


class _Site:
    """What one (stage, op, backend) resolves once: the span name, the
    bound registry handles and the tracer event's args."""

    __slots__ = ("span", "seconds", "nbytes", "args", "annotation")

    def __init__(self, name: str, op: str, backend: str):
        from jax.profiler import TraceAnnotation

        self.span = f"edge.{name}"
        self.seconds = EDGE_SECONDS.labels(stage=name, op=op,
                                           backend=backend)
        direction = _DIRECTION.get(name)
        self.nbytes = (None if direction is None else EDGE_BYTES.labels(
            direction=direction, op=op, backend=backend))
        self.args = {"op": op, "backend": backend}
        self.annotation = TraceAnnotation


_SITES: dict[tuple[str, str, str], _Site] = {}
_REDUCE: dict[tuple[str, str, str], BoundCounter] = {}
_FUSED: dict[tuple[str, str], BoundCounter] = {}
_KERNEL: dict[tuple[str, str, str], BoundCounter] = {}


def _count_one(counter, bound_of: dict, **labels: str) -> None:
    """Add one to `counter` at `labels`, resolved once per label set (in
    `bound_of`) as a stage's are."""
    key = tuple(labels.values())
    bound = bound_of.get(key)
    if bound is None:
        bound = bound_of.setdefault(key, counter.labels(**labels))
    bound.inc()


def count_reduce(path: str, *, op: str, backend: str) -> None:
    """Add one to `edge_reduce_total{path, op, backend}`."""
    _count_one(EDGE_REDUCE, _REDUCE, path=path, op=op, backend=backend)


def count_gather_fused(*, op: str, backend: str) -> None:
    """Add one to `edge_gather_fused_total{op, backend}`: one conversion
    that gathered its rows out of a wider array in its single pass."""
    _count_one(EDGE_GATHER_FUSED, _FUSED, op=op, backend=backend)


def count_kernel(kernel: str, *, op: str, backend: str) -> None:
    """Add one to `local_kernel_total{kernel, op, backend}`: one local
    device call, `kernel` being `ntt` or `matmul`."""
    _count_one(LOCAL_KERNEL, _KERNEL, kernel=kernel, op=op, backend=backend)


class stage:
    """Time one host-edge stage of an API call:

        with stage("h2d", op="encode", backend="local") as s:
            xd = jnp.asarray(x32)
            s.moved(xd.nbytes)

    The block runs inside `jax.profiler.TraceAnnotation("edge.<name>")`,
    a constant name, so a profiler trace names the host's share of every
    device idle gap; its `perf_counter_ns` duration is observed into
    `edge_stage_seconds{stage, op, backend}`; and where a tracer is
    installed it also becomes a complete event there (pid "edge").  The
    labels are resolved once per (name, op, backend), so a stage costs one
    annotation, two clock reads and one locked update.  `moved(nbytes)`
    adds to `edge_bytes_total{direction, op, backend}` on the stages that
    cross the link (h2d; d2h and a stream's materialize)."""

    __slots__ = ("_site", "_ann", "_t0")

    def __init__(self, name: str, *, op: str, backend: str):
        site = _SITES.get((name, op, backend))
        if site is None:
            site = _SITES.setdefault((name, op, backend),
                                     _Site(name, op, backend))
        self._site = site
        self._ann = site.annotation(site.span)

    def __enter__(self) -> "stage":
        self._ann.__enter__()
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = perf_counter_ns()
        site = self._site
        site.seconds.observe((t1 - self._t0) * 1e-9)
        tracer = get_tracer()
        if tracer is not None:
            tracer.complete(site.span, tracer.at_us(self._t0),
                            (t1 - self._t0) / 1e3, pid="edge", tid="host",
                            cat="edge", args=dict(site.args))
        # the annotation closes last, so a profile puts this bookkeeping
        # under the stage and not in the gap before the next one
        self._ann.__exit__(*exc)

    def moved(self, nbytes: int) -> None:
        """Count `nbytes` across the link for this h2d or d2h stage."""
        self._site.nbytes.inc(nbytes)
