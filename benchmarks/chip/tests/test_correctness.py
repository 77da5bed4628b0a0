"""A whole run of each cell on the CPU, at a width a test can hold, and
the comparison that decides `correct`: sound runs pass, and the control
and each fault a cell can have come out as not correct.

The chip look is skipped (`run.chips` hands over CPU devices) and the
width is cut to 2^16 symbols (from 2^19 and 2^17), where the control's 16-bit
symbols still go wrong in a few dozen places per sampled op.
"""
import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

import control
import harness
import peaks
import run

MESH = "minio-ec4-8.mesh-encode"
CELLS = ["minio-ec4-8.encode", "hdfs-rs-6-3.encode",
         "hdfs-rs-6-3.degraded-read", MESH]
W = 1 << 16
Q = 65537
_load_cell = harness.load_cell


def small_cell(name):
    cell = _load_cell(name)
    cell.config["W"] = W
    return cell


@pytest.fixture
def cpu_run(monkeypatch):
    """run.main on the CPU at width W; returns the result line's object."""
    import jax

    monkeypatch.setattr(run, "chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(peaks, "lookup", lambda kind: {
        "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(harness, "load_cell", small_cell)

    def go(name, seed=2**31 + 7, trace=0):
        out = io.StringIO()
        with redirect_stdout(out):
            rc = run.main(["--workload", name, "--seed", str(seed),
                           "--seconds", "0.5", "--trace", str(trace)])
        assert rc == 0
        lines = out.getvalue().strip().splitlines()
        setup = json.loads(lines[-2])["setup"]
        assert set(setup["phases"]) == {"imports", "backend", "plan",
                                        "payloads", "first_call", "warmup"}
        return json.loads(lines[-1])
    return go


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(cpu_run, name):
    res = cpu_run(name)
    cell = small_cell(name)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["compared_ops"]["value"] == min(
        res["attempted"], cell.traffic["sample"])


def test_traced_run_without_device_planes_reads_nothing(cpu_run):
    """A CPU trace has no TPU plane: every reader returns nothing, so the
    line carries no per-layer metric and no breakdown, and `correct`
    still comes from the comparison."""
    res = cpu_run(CELLS[0], trace=1)
    assert res["correct"] is True
    assert res["metrics"] == {} and "breakdown" not in res


def test_no_chip_prints_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    import jax

    cell = small_cell(name)
    if cell.chips > len(jax.devices()):
        pytest.skip("needs the conftest's four virtual devices")
    phases = harness.Phases()
    session = harness.Session(cell, 5, phases)
    session.warm_up(phases)
    r = control.readings(session, 0.5, op=session.control_op())
    assert r["correct"] is False
    assert r["mismatched_symbols"] > 0


def _bump_first(y):
    """The same array with symbol [0, 0] changed."""
    return y.at[0, 0].set((y[0, 0] + 1) % Q)


def altered_at_kernel(monkeypatch, name):
    """A symbol altered where the kernel produces it."""
    import repro.api.backends as enc
    import repro.api.planner as planner
    import repro.kernels.ops as ops

    if name.endswith("degraded-read"):
        orig = ops.decode_blocks
        monkeypatch.setattr(ops, "decode_blocks",
                            lambda v, d: _bump_first(orig(v, d)))
    elif name.endswith("mesh-encode"):
        orig_m = planner.EncodePlan.mesh_callable

        def mesh_callable(plan):
            fn = orig_m(plan)
            return lambda x: _bump_first(fn(x))
        monkeypatch.setattr(planner.EncodePlan, "mesh_callable",
                            mesh_callable)
    else:
        orig_l = enc.local_encode_callable

        def local(plan):
            fn = orig_l(plan)
            return lambda x: _bump_first(fn(x))
        monkeypatch.setattr(enc, "local_encode_callable", local)


def stale_answer(monkeypatch, name):
    """Each call returns what the previous call returned."""
    from repro.api.system import CodedSystem

    attr = "read" if name.endswith("degraded-read") else "encode"
    orig = getattr(CodedSystem, attr)
    last = {}

    def stale(self, x):
        y = orig(self, x)
        prev = last.get("y", y)
        last["y"] = y
        return prev
    monkeypatch.setattr(CodedSystem, attr, stale)


def exchange_left_out(monkeypatch, name):
    """Every ppermute of the mesh rounds leaves each shard where it is."""
    import jax

    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)


FAULTS = ([(n, altered_at_kernel) for n in CELLS]
          + [(n, stale_answer) for n in CELLS]
          + [("minio-ec4-8.mesh-encode", exchange_left_out)])


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__}" for n, f in FAULTS])
def test_fault_is_not_correct(cpu_run, monkeypatch, name, fault):
    from repro.api import cache_clear

    cache_clear()                       # no plan built before the fault
    fault(monkeypatch, name)
    try:
        res = cpu_run(name)
    finally:
        cache_clear()
    assert res["correct"] is False
    assert res["checks"]["mismatched_symbols"]["value"] > 0


def test_reference_matches_the_program_at_small_width():
    """The reference, built from the configuration's stated points alone,
    agrees with the program on one payload of each configuration."""
    from repro.api import CodedSystem, CodeSpec

    for name in ("hdfs-rs-6-3-1024k", "minio-ec4-8drive"):
        cfg = json.loads((harness.HERE / "configs" / f"{name}.json")
                         .read_text())
        ref = harness.reference_for(cfg)
        x = np.random.default_rng(3).integers(0, 1 << 16, (cfg["K"], 256))
        system = CodedSystem(CodeSpec(kind=cfg["kind"], K=cfg["K"],
                                      R=cfg["R"]), backend="local")
        parity = system.encode(x)
        assert np.array_equal(ref.encode(x), parity)
        cw = np.concatenate([x, parity])
        cw[[1, cfg["K"]]] = 0
        assert np.array_equal(ref.read(cw, [1, cfg["K"]]), x)
