"""The cells of the chip benchmark: their files, their set-up and the
comparison that decides `correct`.

Everything that belongs to one cell is found by name under this
directory, so a new cell, configuration, traffic mix, entry, loop or
metric is new files and entries, with no edit to a file that is there:

    configs/<config>.json   sizes, code, guarantees and the runtime
                            environment of the deployment; checked by the
                            plain reference `references/<family>.py`
    traffic/<traffic>.json  the parameters of one mix, below
    entries/<entry>.py      the public call one op makes, the payload it
                            takes, the reference's answer and the control
    loops/<loop>.py         how ops are offered in the window
    metrics/<metric>.py     the reader of one metric (`read(ctx)`), or of
                            a family of them (see `reader_path`)

Traffic parameters:
    entry         the module under `entries/` that one op calls
    backend       the `CodedSystem` backend ("local" or "mesh")
    loop          the module under `loops/` that offers the ops
    clients       callers the loop runs
    pool          distinct payloads made from the seed, cycled in order
    warmup_calls  calls before the window, all with the window's shape
    sample        ops of the window kept for the comparison, drawn from
                  the seed over all ops; each result is copied into a
                  host buffer written in set-up
    (any other key is the entry's own, e.g. `lost` of a degraded read)
"""
from __future__ import annotations

import importlib.util
import json
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `root/BENCHMARK.json` with its configuration,
    traffic mix and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def reports(m):
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]),
                json.loads((root / cfg["file"]).read_text()), traffic,
                [m for m in bench["end_to_end"] if reports(m)],
                [m for m in bench["per_layer"] if reports(m)])


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chip_bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(name: str) -> Path:
    """`metrics/<name>.py`, else the reader of the name's family: the part
    before the first '.' (`host_ms_per_op.read` -> `host_ms_per_op.py`),
    or a tail of its '_'-separated words (`degraded_read_p95_ms` ->
    `p95_ms.py`), the longest that has a file."""
    words = name.split(".", 1)[0].split("_")
    for cand in [name] + ["_".join(words[i:]) for i in range(len(words))]:
        path = HERE / "metrics" / f"{cand}.py"
        if path.exists():
            return path
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{HERE / 'metrics'}")


def read_metrics(metrics: list, ctx) -> dict:
    """{name: {value, unit}} of each metric whose reader finds something
    to read in `ctx`; a reader that finds nothing returns None."""
    out = {}
    for m in metrics:
        value = load_module(reader_path(m["name"])).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def reference_for(config: dict):
    """The plain reference of the configuration's code family."""
    code = config["code"]
    return load_module(HERE / "references"
                       / f"{code['family']}.py").Reference(code)


class Phases(dict):
    """Wall seconds of named set-up phases: `with phases("plan"): ...`."""

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self[name] = self.get(name, 0.0) + time.perf_counter() - t0


class Session:
    """One cell's system under test, its entry and payload pool, built in
    set-up; the data are drawn from the seed, every shape from the cell."""

    def __init__(self, cell: Cell, seed: int, phases: Phases):
        from repro.api import CodedSystem, CodeSpec

        cfg, tr = cell.config, cell.traffic
        K, R, W = cfg["K"], cfg["R"], cfg["W"]
        self.cell, self.seed = cell, seed
        self.entry_name = tr["entry"]
        self.entry = load_module(HERE / "entries" / f"{tr['entry']}.py")
        with phases("plan"):
            self.system = CodedSystem(CodeSpec(kind=cfg["kind"], K=K, R=R),
                                      backend=tr["backend"])
            self.op = self.entry.build(self.system, tr)
        with phases("payloads"):
            self.reference = reference_for(cfg)
            rng = np.random.default_rng(seed % (1 << 64))
            self.data = [rng.integers(0, 1 << cfg["symbol_bits"], (K, W),
                                      dtype=np.int64)
                         for _ in range(tr["pool"])]
            self.payloads = [self.entry.payload(x, self.reference, tr)
                             for x in self.data]
        self.user_bytes_per_op = K * W * cfg["symbol_bits"] // 8
        self._expected: dict[int, np.ndarray] = {}

    def expected(self, i: int) -> np.ndarray:
        """The reference's answer for payload i of the pool."""
        if i not in self._expected:
            self._expected[i] = self.entry.expected(
                self.data[i], self.reference, self.cell.traffic)
        return self._expected[i]

    def control_op(self):
        """The reference in the program's place, in the nearest narrower
        type (see `control.py`)."""
        return self.entry.control(self.reference, self.cell.traffic)

    def warm_up(self, phases: Phases) -> None:
        """A fixed number of calls with the window's one shape; the first
        compiles or loads every program from the persistent cache.  Then
        the host buffers the window's sample is copied into are made and
        written once, so that no op of the window pays for fresh pages."""
        n = self.cell.traffic["warmup_calls"]
        with phases("first_call"):
            y = np.asarray(self.op(self.payloads[0]))
        with phases("warmup"):
            for i in range(1, n):
                self.op(self.payloads[i % len(self.payloads)])
            self.sample_buffers = [np.full_like(y, -1) for _ in
                                   range(self.cell.traffic["sample"])]


@dataclass
class Window:
    t0: float = 0.0                  # perf_counter at the first op
    seconds: float = 0.0             # first op's start to last op's end
    latencies: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    sample: list = field(default_factory=list)   # [(op index, result)]


def run_window(session: Session, seconds: float, op=None) -> Window:
    """The cell's loop (`loops/<loop>.py`) offers ops of `op`, the
    session's entry by default, for `seconds`."""
    loop = load_module(HERE / "loops" / f"{session.cell.traffic['loop']}.py")
    return loop.run(session, seconds, op or session.op)


def keep(y, buf: np.ndarray):
    """y copied into the pre-written `buf` where it fits; otherwise (only
    a broken op returns another shape) y itself."""
    if y is None:
        return None
    y = np.asarray(y)
    if y.shape != buf.shape:
        return y
    np.copyto(buf, y, casting="unsafe")
    return buf


def compare(session: Session, w: Window) -> dict:
    """Each number compared, with its limit: symbols of the sampled
    results that differ from the reference (exact: limit 0), ops that
    raised (limit 0), and how many results were compared (at least 1)."""
    pool = len(session.payloads)
    mismatched, compared = 0, 0
    for i, y in w.sample:
        if y is None:
            continue
        want = session.expected(i % pool)
        y = np.asarray(y)
        mismatched += (int(np.count_nonzero(y != want))
                       if y.shape == want.shape else want.size)
        compared += 1
    return {"mismatched_symbols": {"value": mismatched, "limit": 0},
            "failed_ops": {"value": w.failed, "limit": 0},
            "compared_ops": {"value": compared, "min": 1}}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if "limit" in c
               else c["value"] >= c["min"] for c in checks.values())
