"""Record a small chip trace of the kind `test_trace_reduce.py` reads.

    python3 benchmarks/chip/tests/record_trace.py --workload <cell> \
        --seconds 0.3 --out <dir>

Runs the cell's set-up and a short traced window exactly as `run.py
--trace 1` does, copies the profiler's `.xplane.pb` to `<dir>/<cell>.xplane.pb`,
and prints the window's op count, the trace's planes and lines with
their event counts, and the reduction.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    sys.path.insert(0, str(harness.ROOT / "src"))
    import run

    cell = harness.load_cell(args.workload)
    run.runtime_env(cell)
    import trace_reduce

    run.persistent_cache()
    run.chips(cell.chips)
    phases = harness.Phases()
    session = harness.Session(cell, args.seed, phases)
    session.warm_up(phases)
    w, tmp = run.traced_window(session, args.seconds)
    try:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        dest = out / f"{cell.name}.xplane.pb"
        shutil.copy(trace_reduce.find_trace(tmp), dest)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    print(json.dumps({"ops": w.attempted, "file": str(dest),
                      "bytes": dest.stat().st_size}))
    for plane in ProfileData.from_file(str(dest)).planes:
        for line in plane.lines:
            evs = list(line.events)
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": len(evs),
                              "first": [[e.name, e.start_ns, e.duration_ns]
                                        for e in evs[:3]]}))
    print(json.dumps(trace_reduce.reduce(str(dest), cell.chips)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
