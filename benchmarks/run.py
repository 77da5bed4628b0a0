"""Benchmark driver — one section per paper table/claim.

Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes the machine-readable artifact, stamped with the producing commit
(``_meta.git_sha``) and a UTC timestamp so archived artifacts stay
traceable.  Keys starting with ``_`` are metadata, never gated rows.
Each JSON entry records the value
AND the benchmark's shape parameters (parsed from the ``K16``/``R4``/
``E2``/``W4096``/``p1`` tokens of the row name plus any ``backend=`` in
the derived column), so baselines stay comparable across edits::

    {"recover/decode_local_K16_R4_E4_W4096":
        {"us_per_call": 812.0,
         "params": {"K": 16, "R": 4, "E": 4, "W": 4096, "backend": "local"},
         "derived": "encode_us=..."}}

``--check BASELINE`` gates the run against a committed baseline
(``benchmarks/baselines/baseline.json``) and exits nonzero on regression:
for every baseline entry whose section was run, the shape params must
match exactly (shape drift without a baseline refresh is an error), and
the value must satisfy the entry's bound — absolute ``min``/``max`` when
present (e.g. the NTT speedup ratio's ``min: 1.5``), otherwise relative:
at most ``us_per_call * (1 + tolerance)`` with ``tolerance`` taken from
the entry or ``--tolerance`` (default 0.25).  Entries with
``"better": "higher"`` invert the relative direction.  Renames don't
silently escape the gate: a baseline entry with no measured row FAILS the
run (remove it from the baseline explicitly), and a measured row in a
gated section with no baseline entry warns loudly that it is running
ungated.  The JSON artifact is still written before the gate fires, so CI
uploads it for trend inspection even on a failing run.

Sections:

  table1/*       — Table I: universal / DFT / Vandermonde A2A costs vs theory
  multireduce/*  — Sec. II comparison vs Jeong et al. [21] + strawman
  framework/*    — Thm. 1/2/7/9 end-to-end decentralized encoding costs
  kernel/*       — Pallas gf_matmul / NTT micro-bench (interpreted on CPU)
  recover/*      — decode vs encode: DecodePlan kernel hot path + closed-form
                   network costs (the repair half of the pipeline)
  rebuild/*      — rebuild cost vs erasure count |E|: CodedSystem.rebuild
                   wall time + closed-form repair-schedule cost
  stream/*       — streamed vs single-shot plan execution + NTT fast path
                   vs dense local encode (benchmarks/stream_bench.py)
  serve/*        — multi-tenant serving: closed/open-loop load over Zipf
                   volumes, cross-session coalescing ratio, chaos-under-load
                   correctness (benchmarks/serve_bench.py)
  coded/*        — coded computation under failure: gradient-coded train
                   step time vs injected straggler count (gated ratio +
                   bitwise-recovery flag) and the Lagrange-coded matmul
                   dropout sweep (benchmarks/coded_train_bench.py)
  topo/*         — hierarchical topology: per-placement inter-tier traffic,
                   affinity-vs-flat crossover ratio, tier-model exactness
                   (benchmarks/topo_bench.py; deterministic, tightly gated)
  mesh_encode/*  — lowered-HLO collective bytes, universal vs RS (CPU-only
                   subprocess)
  mesh_a2a/*     — mesh A2A scaling (CPU-only subprocess)
  mesh/*         — the stable (HLO-census, no wall clock) rows of BOTH mesh
                   subprocess benches, folded into one gated section; the
                   section name "mesh" runs both scripts
  roofline/*     — coding-kernel fraction-of-roofline cells (NTT + dense
                   local encode vs the host's memcpy ceiling, fed by the
                   metrics registry) + dry-run cells if results/dryrun
                   exists

``--sections table1 recover ...`` restricts the run to the named sections.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
from datetime import datetime, timezone
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO / "src"))
sys.path.insert(0, str(_REPO))  # `benchmarks` namespace package, any cwd

_PARAM_RE = re.compile(r"(?:^|_)([KRWEp])(\d+)(?=_|$|,)")
_BACKEND_RE = re.compile(r"(?:^|;)backend=([a-zA-Z_]+)")


def _git_sha() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_REPO,
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — artifact metadata must never fail a run
        return "unknown"


def _params_from(name: str, derived: str) -> dict:
    """Shape parameters encoded in a row: K/R/E/W/p name tokens + backend."""
    tail = name.split("/", 1)[-1]
    params: dict = {k: int(v) for k, v in _PARAM_RE.findall(tail)}
    m = _BACKEND_RE.search(derived)
    if m:
        params["backend"] = m.group(1)
    return params


def _emit(row: str, acc: dict[str, dict]) -> None:
    print(row, flush=True)
    parts = row.split(",", 2)
    if len(parts) >= 2:
        try:
            us = float(parts[1])
        except ValueError:
            return
        derived = parts[2] if len(parts) > 2 else ""
        acc[parts[0]] = {"us_per_call": us,
                         "params": _params_from(parts[0], derived),
                         "derived": derived}


def _check_baseline(acc: dict[str, dict], base: dict[str, dict],
                    tolerance: float, ran_sections: set[str] | None
                    ) -> tuple[list[str], list[str]]:
    """Compare measured entries to the baseline.

    Returns (problems, warnings): `problems` fail the gate — including a
    baseline entry with no matching measured row (a renamed/dropped
    benchmark must not silently stop being gated); `warnings` flag the
    converse, measured rows in a gated section that have no baseline entry
    and therefore run UNGATED until the baseline is refreshed.
    """
    problems: list[str] = []
    gated_sections = {n.split("/", 1)[0] for n in base
                      if not n.startswith("_")}
    warnings = [
        f"{name}: measured but not in the baseline — NOT gated (add it to "
        "the baseline, or restore the old row name)"
        for name in sorted(acc)
        if name not in base and name.split("/", 1)[0] in gated_sections
    ]
    for name, b in sorted(base.items()):
        if name.startswith("_"):  # artifact metadata, not a gated row
            continue
        section = name.split("/", 1)[0]
        if ran_sections is not None and section not in ran_sections:
            continue
        cur = acc.get(name)
        if cur is None:
            problems.append(
                f"{name}: in baseline but not measured — a renamed or "
                "dropped benchmark must be removed from the baseline "
                "explicitly")
            continue
        bp, cp = b.get("params"), cur.get("params")
        if bp and cp and bp != cp:
            problems.append(
                f"{name}: shape params drifted (baseline {bp}, got {cp}) — "
                "regenerate the baseline if the change is intentional")
            continue
        val = cur["us_per_call"]
        if "min" in b and val < b["min"]:
            problems.append(f"{name}: {val:.2f} below required min {b['min']}")
        if "max" in b and val > b["max"]:
            problems.append(f"{name}: {val:.2f} above allowed max {b['max']}")
        if "min" in b or "max" in b or "us_per_call" not in b:
            continue
        tol = float(b.get("tolerance", tolerance))
        ref = float(b["us_per_call"])
        if b.get("better") == "higher":
            if val < ref * (1 - tol):
                problems.append(
                    f"{name}: {val:.2f} regressed below {ref:.2f} "
                    f"* (1 - {tol}) = {ref * (1 - tol):.2f}")
        elif val > ref * (1 + tol):
            problems.append(
                f"{name}: {val:.2f}us regressed above {ref:.2f}us "
                f"* (1 + {tol}) = {ref * (1 + tol):.2f}us")
    return problems, warnings


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the {name: {us_per_call, params}} artifact")
    ap.add_argument("--sections", nargs="+", default=None,
                    help="run only the named sections (default: all)")
    ap.add_argument("--check", metavar="BASELINE", default=None,
                    help="gate against a baseline JSON; nonzero exit on "
                         "regression")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="default relative tolerance for --check entries "
                         "without their own (default 0.25)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (coded_train_bench, framework_costs, kernel_bench,
                            multireduce_compare, rebuild_bench, recover_bench,
                            serve_bench, stream_bench, table1_costs,
                            topo_bench)

    inproc = {
        "table1": table1_costs,
        "multireduce": multireduce_compare,
        "framework": framework_costs,
        "kernel": kernel_bench,
        "recover": recover_bench,
        "rebuild": rebuild_bench,
        "stream": stream_bench,
        "serve": serve_bench,
        "coded": coded_train_bench,
        "topo": topo_bench,
    }
    # each script also prints stable mesh/* rows, gated as one "mesh" section
    subproc = {
        "mesh_encode": ("mesh_encode_bench.py", ("mesh_encode/", "mesh/")),
        "mesh_a2a": ("mesh_a2a_scale.py", ("mesh_a2a/", "mesh/")),
    }
    wanted = args.sections
    if wanted is not None:
        known = set(inproc) | set(subproc) | {"roofline", "mesh"}
        unknown = set(wanted) - known
        if unknown:
            raise SystemExit(f"unknown sections: {sorted(unknown)} "
                             f"(have {sorted(known)})")

    def on(name: str) -> bool:
        return wanted is None or name in wanted

    acc: dict[str, dict] = {}
    failed: list[str] = []
    print("name,us_per_call,derived")
    for name, mod in inproc.items():
        if on(name):
            for row in mod.rows():
                _emit(row, acc)

    # mesh benches need their own process (forced host devices): they are
    # CPU-only HLO censuses, and this process may already hold the chip
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    for name, (script, prefixes) in subproc.items():
        if not (on(name) or on("mesh")):
            continue
        proc = subprocess.run(
            [sys.executable, str(Path(__file__).resolve().parent / script)],
            capture_output=True, text=True, env=env, timeout=1200)
        for line in proc.stdout.splitlines():
            if line.startswith(prefixes):
                _emit(line, acc)
        if proc.returncode != 0:
            # failure is visible in the CSV and fails the run; it is NOT
            # recorded in the JSON artifact as a fake 0us measurement
            print(f"{prefixes[0]}FAILED,0,rc={proc.returncode}", flush=True)
            failed.append(name)

    if on("roofline"):
        from benchmarks import roofline

        # coding-kernel cells run anywhere (local backend, metrics-fed);
        # dry-run cells ride along only when their artifacts exist
        for row in roofline.coding_rows():
            _emit(row, acc)
        if (_REPO / "results" / "dryrun").exists():
            for row in roofline.rows():
                _emit(row, acc)

    if args.json:
        artifact = dict(acc)
        artifact["_meta"] = {
            "git_sha": _git_sha(),
            "timestamp": datetime.now(timezone.utc).isoformat(
                timespec="seconds"),
        }
        Path(args.json).write_text(
            json.dumps(artifact, indent=2, sort_keys=True))
        print(f"wrote {len(acc)} entries to {args.json}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark subprocesses failed: {failed}")
    if args.check:
        ran = None if wanted is None else set(wanted)
        base = json.loads(Path(args.check).read_text())
        problems, warnings = _check_baseline(acc, base, args.tolerance, ran)
        if warnings:
            print("PERF GATE WARNINGS (rows running UNGATED):",
                  file=sys.stderr)
            for w in warnings:
                print(f"  {w}", file=sys.stderr)
        if problems:
            print("PERF REGRESSION GATE FAILED:", file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            raise SystemExit(1)
        print(f"perf gate OK against {args.check} "
              f"({len(warnings)} ungated-row warnings)", file=sys.stderr)


if __name__ == "__main__":
    main()
