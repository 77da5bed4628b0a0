"""Every name in BENCHMARK.json finds its files: each cell its
configuration and traffic mix, each mix its entry and loop, and each
metric its reader."""
import json

import pytest

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_files(name):
    cell = harness.load_cell(name)
    tr = cell.traffic
    assert (harness.HERE / "entries" / f"{tr['entry']}.py").is_file()
    assert (harness.HERE / "loops" / f"{tr['loop']}.py").is_file()
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("name", METRICS)
def test_metric_finds_its_reader(name):
    assert callable(harness.load_module(harness.reader_path(name)).read)


def test_reader_families():
    m = harness.HERE / "metrics"
    assert harness.reader_path("host_ms_per_op.read") == m / "host_ms_per_op.py"
    assert harness.reader_path("degraded_read_p95_ms") == m / "p95_ms.py"
    assert harness.reader_path("encode_roofline") == m / "encode_roofline.py"
    with pytest.raises(FileNotFoundError):
        harness.reader_path("no_such_metric")
