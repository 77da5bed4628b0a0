"""Chip benchmark of the coded store: one cell of BENCHMARK.json per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up sets the runtime environment the cell's configuration states,
builds the cell's `CodedSystem`, makes its payload pool from the seed
and warms up the one shape the window uses; JAX's persistent
compilation cache keeps every program (no compile-time or size floor),
so a warm run compiles nothing.  The window then drives the public API
through the loop the cell's traffic names for `--seconds` (see
`harness.py`).  Afterwards a sample of the window's results, drawn from
the seed, is compared with the plain reference.

stdout ends with a line of set-up phases, compile-cache counts and the
window's ops in each fifth of its length, then the result: one JSON
object with `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics; with `--trace 1` its per-layer
metrics, read from a profiler trace of the window), `device`, with
`--trace 1` `breakdown`, and last `checks`, each number compared beside
its limit.  Those checks are also the last lines of stderr.  Without a
TPU, or with fewer chips than the cell asks for, it exits nonzero and
prints no result.
"""
from __future__ import annotations

import time

_T_TOP = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402


def _since_process_start() -> float:
    """Seconds since the kernel started this process (1/CLK_TCK steps)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


_INTERPRETER_S = _since_process_start()


def runtime_env(cell) -> dict:
    """Set the runtime environment the cell's configuration states for its
    deployment (libtpu's settings); before JAX loads libtpu.  Returns it."""
    env = {k: str(v) for k, v in cell.config.get("runtime_env", {}).items()}
    os.environ.update(env)
    return env


class NoChip(RuntimeError):
    pass


def chips(n: int) -> list:
    """The first n TPU devices; NoChip where JAX finds fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def persistent_cache() -> str:
    """Turn JAX's persistent compilation cache on for every program, with
    no floor on compile time or entry size, in the directory the program
    keeps it in (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`);
    returns that directory.  Call before the first compile."""
    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileEvents:
    """Counts of this process's compile requests and persistent-cache hits
    (`jax.monitoring` events), and the seconds spent tracing and lowering."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        self.trace_lower_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, duration_secs: float, **_):
        if event in ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_lower_s += duration_secs

    def summary(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "misses": self.requests - self.hits,
                "trace_lower_s": self.trace_lower_s}


def traced_window(session, seconds: float):
    """The window with JAX's profiler on (device ops and JAX's host spans;
    no Python tracer); returns it and the directory the trace is in,
    which the caller removes."""
    import jax

    from harness import run_window

    trace_dir = tempfile.mkdtemp(prefix="chip-bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window = run_window(session, seconds)
    finally:
        jax.profiler.stop_trace()
    return window, trace_dir


def _ops_per_fifth(window) -> list:
    """Ops completed in each fifth of the window: whether a slow run was
    slow all through or in one stretch."""
    import numpy as np

    ends = np.cumsum(window.latencies)
    return np.histogram(ends, bins=5, range=(0, max(window.seconds, 1e-9))
                        )[0].tolist()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    import harness

    cell = harness.load_cell(args.workload)
    env = runtime_env(cell)
    import peaks
    from harness import Session, compare, passed, run_window

    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    cache_dir = persistent_cache()
    events = CompileEvents()
    import repro.api  # noqa: F401

    phases = harness.Phases(imports=time.perf_counter() - t0)
    with phases("backend"):
        try:
            devs = chips(cell.chips)
        except NoChip as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 3
    peak = peaks.lookup(devs[0].device_kind)

    session = Session(cell, args.seed, phases)
    session.warm_up(phases)

    if args.trace:
        window, trace_dir = traced_window(session, args.seconds)
    else:
        window = run_window(session, args.seconds)
    setup_s = _INTERPRETER_S + (window.t0 - _T_TOP)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs)}
    checks = compare(session, window)

    result = {"correct": passed(checks), "attempted": window.attempted,
              "failed": window.failed, "metrics": {}, "device": device}
    summary = None
    if args.trace:
        import trace_reduce

        try:
            summary = trace_reduce.reduce(trace_reduce.find_trace(trace_dir),
                                          n_devices=cell.chips)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = SimpleNamespace(window=window, session=session, setup_s=setup_s,
                          trace=summary, ops=window.attempted,
                          config=cell.config, traffic=cell.traffic, peak=peak)
    result["metrics"] = harness.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, ctx)
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks

    print(json.dumps({"setup": {
        "setup_s": setup_s, "interpreter_s": _INTERPRETER_S,
        "phases": phases, "runtime_env": env,
        "compile_cache": events.summary(), "cache_dir": cache_dir},
        "window": {"ops_per_fifth": _ops_per_fifth(window)}}))
    for name, c in checks.items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['min']}")
        print(f"check {name} {c['value']} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
