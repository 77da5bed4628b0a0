"""The HDFS RS-10-4 cell on the CPU: a sound run is correct, the control is
not, the plain reference built from the configuration's stated points
agrees with the program, and `ntt_calls_pct` reads the program's kernel
counter at the cell's labels."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

import control
import harness
from repro.obs import metrics
from test_correctness import cpu_run, small_cell  # noqa: F401 (fixture)

CELL = "hdfs-rs-10-4.encode"
CONFIG = "hdfs-rs-10-4-1024k"


def test_sound_run_is_correct(cpu_run):  # noqa: F811
    res = cpu_run(CELL)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in
                                   small_cell(CELL).end_to_end}


def test_control_is_not_correct():
    phases = harness.Phases()
    session = harness.Session(small_cell(CELL), 2**40 + 11, phases)
    session.warm_up(phases)
    r = control.readings(session, 0.5, op=session.control_op())
    assert r["correct"] is False
    assert r["mismatched_symbols"] > 0


def test_reference_agrees_with_the_program():
    """The stated points are the program's, and the plain GRS over them is
    what `CodedSystem` computes: encode and a read with one data and one
    parity row lost."""
    from repro.api import CodedSystem, CodeSpec

    cfg = json.loads((harness.HERE / "configs" / f"{CONFIG}.json")
                     .read_text())
    ref = harness.reference_for(cfg)
    system = CodedSystem(CodeSpec(kind=cfg["kind"], K=cfg["K"], R=cfg["R"]),
                         backend="local")
    grs = system.encode_plan.sgrs.grs
    assert grs.alphas.tolist() == cfg["code"]["alphas"]
    assert grs.betas.tolist() == cfg["code"]["betas"]
    x = np.random.default_rng(5).integers(0, 1 << 16, (cfg["K"], 512))
    parity = system.encode(x)
    assert np.array_equal(ref.encode(x), parity)
    lost = [2, cfg["K"] + 1]
    cw = np.concatenate([x, parity])
    cw[lost] = 0
    assert np.array_equal(ref.read(cw, lost), x)
    system.fail(lost)
    assert np.array_equal(system.read(cw), x)


@pytest.mark.parametrize("matmul,expect", [(0, 100.0), (6, 50.0)])
def test_ntt_calls_pct(monkeypatch, matmul, expect):
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    c = harness.load_cell(CELL)
    reader = harness.load_module(harness.reader_path("ntt_calls_pct.encode"))
    ctx = SimpleNamespace(traffic=c.traffic, trace={"busy_s": 1.0})
    assert reader.read(ctx) is None                      # no counter
    calls = reg.counter("local_kernel_total")
    calls.inc(6, kernel="ntt", op="encode", backend="local")
    calls.inc(9, kernel="matmul", op="read", backend="local")  # other op
    if matmul:
        calls.inc(matmul, kernel="matmul", op="encode", backend="local")
    assert reader.read(ctx) == expect
    assert reader.read(SimpleNamespace(traffic=c.traffic, trace=None)) is None
