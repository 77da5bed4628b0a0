"""The trace reduction, on made-up intervals and on traces recorded on
TPU v5 lite chips by `record_trace.py`: `data/minio-ec4-8.encode.xplane.pb`
(one chip, a 0.3 s window of 12 encodes at W = 2^19) and
`data/minio-ec4-8.mesh-encode.xplane.pb` (four chips, a 0.3 s window of
63 mesh encodes at W = 2^17 per chip)."""
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "minio-ec4-8.encode.xplane.pb"
MESH_TRACE = (Path(__file__).parent / "data"
              / "minio-ec4-8.mesh-encode.xplane.pb")


def test_merge_and_gaps():
    busy = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9), (10, 12)])
    assert busy == [(0, 3), (5, 8), (10, 12)]
    assert tr.gaps(busy, -1, 11) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.gaps(busy, 0, 12) == [(3, 5), (8, 10)]
    assert tr.clip_events([(0, 4, "a"), (5, 6, "b"), (9, 20, "c")], 1, 10) \
        == [(1, 4, "a"), (5, 6, "b"), (9, 10, "c")]


def test_span_paths_follow_nesting():
    events = [(0, 100, tr.WINDOW_SPAN), (10, 40, "op"), (12, 20, "h2d"),
              (30, 39, "d2h"), (50, 90, "op"), (60, 61, "tiny")]
    points = [5, 11, 15, 25, 35, 45, 55, 60.5, 95]
    assert tr.span_paths(events, points) == [
        "_no_host_span_", "op", "op/h2d", "op", "op/d2h", "_no_host_span_",
        "op", "op/tiny", "_no_host_span_"]


def test_op_name_is_short():
    hlo = ('%copy.1 = u32[4,524288]{1,0:T(4,128)} copy(u32[4,524288]'
           '{1,0:T(4,128)} %args_0_.1)')
    assert tr.op_name(hlo) == "copy.1 copy u32[4,524288]"
    assert tr.op_name("something else") == "something else"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(str(TRACE), n_devices=1)


def test_recorded_trace_reduces(reduced):
    r = reduced
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == r["busy_any_s"] == r["busy_s_per_device"][0]
    assert r["collective_s"] == 0.0              # one chip, no exchange
    assert 1 <= len(r["device_ops"]) <= 10
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True)
    # every idle gap lies inside the window and outside the busy time
    idle = sum(t for _, t in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
    assert r["idle_gaps"][0][0] == "bench.encode"
    # pinned: a change to the reduction that moves these is a change of
    # what every later PR measures
    assert r["window_s"] == pytest.approx(0.327230664, rel=1e-12)
    assert r["busy_s"] == pytest.approx(0.001898993, rel=1e-12)


def test_recorded_mesh_trace_reduces():
    """Four chips: busy time on each, the union over them, and the
    collective-permute ops of the mesh rounds on the "XLA Ops" line."""
    r = tr.reduce(str(MESH_TRACE), n_devices=4)
    assert len(r["busy_s_per_device"]) == 4
    assert all(b > 0 for b in r["busy_s_per_device"])
    assert max(r["busy_s_per_device"]) <= r["busy_any_s"] <= r["window_s"]
    assert 0 < r["collective_s"] < r["busy_s"]
    assert any("collective-permute" in n for n, _ in r["device_ops"])
    # pinned, as for the one-chip trace
    assert r["window_s"] == pytest.approx(0.304694377, rel=1e-12)
    assert r["busy_any_s"] == pytest.approx(0.003327131, rel=1e-12)
    assert r["collective_s"] == pytest.approx(0.00164255525, rel=1e-12)


def test_busy_time_matches_a_raster_of_the_trace(reduced):
    """Busy time counted independently: mark every 100 ns step of the
    window in which an op of the device's XLA Ops line runs."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(TRACE))
    host = [ev for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name == tr.WINDOW_SPAN][0]
    lo, hi = host.start_ns, host.start_ns + host.duration_ns
    step = 100.0
    mark = np.zeros(int((hi - lo) / step) + 1, bool)
    ops = [ev for p in data.planes if p.name == "/device:TPU:0"
           for line in p.lines if line.name == tr.OPS_LINE
           for ev in line.events]
    assert ops
    for ev in ops:
        s = max(ev.start_ns, lo)
        e = min(ev.start_ns + ev.duration_ns, hi)
        if e > s:
            mark[int((s - lo) // step):int(np.ceil((e - lo) / step))] = True
    raster_s = mark.sum() * step * 1e-9
    # each op can gain at most two partial steps from the raster
    assert raster_s == pytest.approx(reduced["busy_s"],
                                     abs=2 * len(ops) * step * 1e-9)
