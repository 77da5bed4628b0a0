"""The readers of the program's host-edge counters (`edge_stages.py`):
on a registry with known values, each picks its cell's labels, divides by
the dispatch count, and reads nothing where the labels, the family or
the traced run's device are missing."""
import json
from types import SimpleNamespace

import pytest

import harness
from repro.obs import metrics

NEW = ("host_numpy_ms_per_op", "transfer_ms_per_op", "link_bytes_per_op")


def _read(name, cell, trace=True):
    c = harness.load_cell(cell)
    # 4 ops with 2 ms of device work: 0.5 ms an op
    ctx = SimpleNamespace(traffic=c.traffic, ops=4, trace={
        "window_s": 1.0, "busy_any_s": 0.002} if trace else None)
    return harness.load_module(harness.reader_path(name)).read(ctx)


@pytest.fixture
def registry(monkeypatch):
    """A fresh REGISTRY with 4 calls of (read, local), 2 of (encode,
    mesh) and one of (encode, local) at other values."""
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    sec = reg.histogram("edge_stage_seconds")
    nbytes = reg.counter("edge_bytes_total")
    for op, backend, calls, scale in (("read", "local", 4, 1.0),
                                      ("encode", "mesh", 2, 10.0),
                                      ("encode", "local", 1, 100.0)):
        for _ in range(calls):
            for i, st in enumerate(("gather", "prep", "h2d", "dispatch",
                                    "d2h", "widen")):
                if st == "gather" and op != "read":
                    continue
                sec.observe(scale * (i + 1) * 1e-3, stage=st, op=op,
                            backend=backend)
            nbytes.inc(scale * 1000, direction="h2d", op=op,
                       backend=backend)
            nbytes.inc(scale * 24, direction="d2h", op=op, backend=backend)
    return reg


def test_readers_pick_their_cells_labels(registry):
    # read: gather 1 + prep 2 + widen 6 ms; h2d 3 + d2h 5 ms less 0.5
    # ms of device work; 1024 B
    assert _read("host_numpy_ms_per_op.read",
                 "hdfs-rs-6-3.degraded-read") == pytest.approx(9.0)
    assert _read("transfer_ms_per_op.read",
                 "hdfs-rs-6-3.degraded-read") == pytest.approx(7.5)
    assert _read("link_bytes_per_op.read",
                 "hdfs-rs-6-3.degraded-read") == 1024
    # mesh: no gather; scale 10
    assert _read("host_numpy_ms_per_op.mesh",
                 "minio-ec4-8.mesh-encode") == pytest.approx(80.0)
    assert _read("transfer_ms_per_op.mesh",
                 "minio-ec4-8.mesh-encode") == pytest.approx(79.5)
    assert _read("link_bytes_per_op.mesh",
                 "minio-ec4-8.mesh-encode") == 10240
    for cell in ("minio-ec4-8.encode", "hdfs-rs-6-3.encode"):
        assert _read("host_numpy_ms_per_op.encode", cell) == \
            pytest.approx(800.0)
        assert _read("link_bytes_per_op.encode", cell) == 102400


@pytest.mark.parametrize("name", NEW)
def test_no_labels_no_family_no_device_read_nothing(monkeypatch, name):
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    assert _read(name, "minio-ec4-8.encode") is None      # no family
    reg.histogram("edge_stage_seconds").observe(
        1e-3, stage="dispatch", op="read", backend="local")
    reg.counter("edge_bytes_total").inc(8, direction="h2d", op="read",
                                        backend="local")
    assert _read(name, "minio-ec4-8.encode") is None      # other labels
    assert _read(name, "hdfs-rs-6-3.degraded-read",
                 trace=False) is None                     # no device
    reg.histogram("edge_stage_seconds").observe(
        1e-3, stage="dispatch", op="encode", backend="local")
    assert _read(name, "minio-ec4-8.encode") is None      # no such stage


def test_every_cell_reports_its_family():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for cell in (w["name"] for w in bench["workloads"]):
        names = {m["name"].split(".")[0] for m in harness.load_cell(
            cell).per_layer}
        assert set(NEW) <= names, cell
    for m in bench["per_layer"]:
        if m["name"].split(".")[0] in NEW:
            assert m["layer"] == "api host edge"
            assert m["source"] == "host_clock"
            assert harness.reader_path(m["name"]).stem == \
                m["name"].split(".")[0]
