"""The control of the comparison that decides `correct`, and the program's
readings beside it, for one cell on the chip.

The configurations state exact symbols of F_65537, which need 17 bits.
The control is the plain reference put in the program's place with every
symbol held in the nearest narrower type, 16 bits (uint16), the step that
would tempt a change to halve the bytes at the host edge: a symbol equal
to 65536 reads back as 0.  It must come out as not correct.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 3 \
        --seeds 11 12 13 ...

One process holds the chip: for each seed it builds the cell's session,
runs the program's window and then the control's window at the cell's
own size and load, and compares each with the reference.  Each seed
prints one JSON line; the last line gives the largest reading of the
program and the smallest of the control for each number compared.
"""
from __future__ import annotations

import argparse
import json
import sys

import harness


def readings(session: harness.Session, seconds: float, op=None) -> dict:
    w = harness.run_window(session, seconds, op=op)
    checks = harness.compare(session, w)
    return {"attempted": w.attempted, "correct": harness.passed(checks),
            **{k: c["value"] for k, c in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(harness.ROOT / "src"))
    import run

    cell = harness.load_cell(args.workload)
    run.runtime_env(cell)
    run.persistent_cache()
    try:
        run.chips(cell.chips)
    except run.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    program, control = [], []
    for seed in args.seeds:
        phases = harness.Phases()
        session = harness.Session(cell, seed, phases)
        session.warm_up(phases)
        p = readings(session, args.seconds)
        c = readings(session, args.seconds, op=session.control_op())
        program.append(p)
        control.append(c)
        print(json.dumps({"seed": seed, "program": p, "control": c}),
              flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(args.seeds),
        "program_correct_all": all(p["correct"] for p in program),
        "control_correct_none": not any(c["correct"] for c in control),
        "program_max_mismatched": max(p["mismatched_symbols"]
                                      for p in program),
        "control_min_mismatched": min(c["mismatched_symbols"]
                                      for c in control)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
