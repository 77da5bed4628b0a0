"""Unified observability layer: round-level tracing (Chrome trace-event
export), the one metrics registry, and the cost-model drift ledger —
trace correctness under chaos, snapshot consistency under concurrency,
and the zero-drift acceptance criterion across all four code kinds."""
import json
import threading

import numpy as np
import pytest

from repro.api import CodeSpec, CodedSystem, Encoder
from repro.core.field import FERMAT
from repro.core.simulator import PartialRunError, RoundNetwork
from repro.obs import drift, metrics, trace
from repro.recover import Decoder

RNG = np.random.default_rng(41)


def _spec(kind, K, R, **kw):
    if kind == "universal":
        kw.setdefault("seed", 5)
    return CodeSpec(kind=kind, K=K, R=R, **kw)


def _codeword(spec, x):
    plan = Encoder.plan(spec, backend="simulator")
    return np.concatenate([x % spec.q, plan.run(x)], axis=0)


# ---------------------------------------------------------------------------
# tracer: export shape + chaos correctness
# ---------------------------------------------------------------------------

def test_tracer_export_is_valid_chrome_trace(tmp_path):
    t = trace.Tracer()
    with t.span("work", pid="p", tid="t", args={"k": 1}):
        t.instant("mark", pid="p", tid="t")
    path = tmp_path / "out.json"
    t.save(path)
    d = json.loads(path.read_text())
    assert d["displayTimeUnit"] == "ms"
    evs = d["traceEvents"]
    # metadata names the string tracks; pid/tid in events are interned ints
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"p", "t"} <= names
    phs = [e["ph"] for e in evs if e["ph"] != "M"]
    assert sorted(phs) == ["X", "i"]
    assert all(isinstance(e["pid"], int) and isinstance(e["tid"], int)
               for e in evs)


def test_trace_rounds_bitwise_match_network_counters():
    spec = _spec("rs", 16, 4)
    x = FERMAT.rand((16, 3), RNG)
    with trace.installed() as t:
        plan = Encoder.plan(spec, backend="simulator")
        plan.run(x)
        net = plan.sim_net
        rounds = t.events(cat="sim.round")
    assert len(rounds) == net.C1
    assert sum(e["args"]["m_t"] for e in rounds) == net.C2
    # per-processor tracks tell the same story: per round, the max over
    # procs of sent elems IS that round's m_t contribution upper bound
    per_proc = t.events(cat="sim.proc")
    assert {e["args"]["round"] for e in per_proc} == \
        {e["args"]["round"] for e in rounds}


def test_chaos_kill_instant_lands_in_the_right_round():
    spec = _spec("rs", 8, 4)
    cw = _codeword(spec, FERMAT.rand((8, 3), RNG))
    tracer = trace.Tracer()
    net = RoundNetwork(spec.N, spec.p, tracer=tracer)
    net.fail_at(1, (3,))
    plan = Decoder.plan(spec, erased=(0, 9), backend="simulator")
    from repro.recover import decentralized_decode

    net.fail((0, 9))
    with pytest.raises(PartialRunError):
        decentralized_decode(FERMAT, plan.tables.D,
                             FERMAT.arr(cw[list(plan.kept)]),
                             list(plan.kept), spec.p, net)
    kills = tracer.events(cat="sim.fail", name="kill")
    assert [e["args"] for e in kills] == [{"round": 1, "proc": 3}]
    aborts = tracer.events(cat="sim.fail", name="abort")
    assert len(aborts) == 1 and aborts[0]["args"]["proc"] == 3
    # static fails got their own instants, on per-processor tracks
    fails = tracer.events(cat="sim.fail", name="fail")
    assert {e["args"]["proc"] for e in fails} == {0, 9}
    # the completed prefix is fully traced: one round event per accounted
    # round, C2 preserved bitwise
    rounds = tracer.events(cat="sim.round")
    assert len(rounds) == net.C1 == 1
    assert sum(e["args"]["m_t"] for e in rounds) == net.C2


def test_round_log_events_keep_legacy_tuple_contract():
    net = RoundNetwork(8, 1, keep_log=True, tracer=False)
    from repro.core.prepare_shoot import prepare_shoot

    out = {}
    vals = {k: FERMAT.rand((2,), RNG) for k in range(8)}
    net.run(prepare_shoot(FERMAT, FERMAT.rand((8, 8), RNG), vals,
                          list(range(8)), 1, out))
    assert len(net.round_log) > 0
    # legacy consumers unpack (n_msgs, m_t) 2-tuples
    assert net.C2 == sum(m for _, m in net.round_log)
    ev = net.round_log[0]
    assert len(ev) == 2 and ev[0] == ev.n_msgs and ev[1] == ev.m_t
    # the structured upgrade rides along: per-proc send/recv breakdowns
    # that sum to the round's traffic
    assert sum(n for _, n in ev.sent) == sum(n for _, n in ev.recv)


def test_tracing_off_means_no_tracer_consulted():
    assert trace.get_tracer() is None
    net = RoundNetwork(4, 1)
    assert net.tracer is None  # resolved once, hot path is one None check


# ---------------------------------------------------------------------------
# host-edge stages: profiler spans + registry histograms (+ tracer events)
# ---------------------------------------------------------------------------

EDGE = ("prep", "h2d", "dispatch", "d2h", "widen")
W_EDGE = 128


def _edge_delta(before, after, family, op, backend):
    """{stage or direction: count or bytes} added between two snapshots
    at (op, backend)."""
    out = {}
    for key, v in after.get(family, {}).get("values", {}).items():
        labels = dict(p.split("=", 1) for p in key.split(","))
        if labels.pop("op") != op or labels.pop("backend") != backend:
            continue
        old = before.get(family, {}).get("values", {}).get(key)
        if isinstance(v, dict):
            d = v["count"] - (old["count"] if old else 0)
        else:
            d = v - (old or 0)
        if d:
            # None keys a family with no label beyond op and backend
            out[labels.popitem()[1] if labels else None] = d
    return out


def _edge_system(op):
    """A local 6+3 session, failed at data row 2 unless encoding, and the
    call of `op` with its payload (the full (N, W) codeword)."""
    system = CodedSystem(_spec("rs", 6, 3), backend="local")
    x = RNG.integers(0, 1 << 16, (6, W_EDGE))
    cw = system.codeword(x)
    if op == "encode":
        return system, system.encode, x
    system.fail([2])
    system.read(cw)              # plans and compiles outside the count
    system.decode(cw)
    return system, getattr(system, op), cw


# stages in order, and bytes h2d / d2h, of one call at K=6, R=3, |E|=1:
# the read uploads the 6x6 data matrix (144 B) beside the survivors; the
# read and decode of a codeword gather its survivors in `prep`
EDGE_CASES = {
    "encode": (EDGE, 6 * W_EDGE * 4, 3 * W_EDGE * 4),
    "read": (EDGE, 6 * W_EDGE * 4 + 144, 6 * W_EDGE * 4),
    "decode": (EDGE, 6 * W_EDGE * 4, 1 * W_EDGE * 4),
}


@pytest.mark.parametrize("op", sorted(EDGE_CASES))
def test_edge_stages_counted_in_order(op):
    stages, h2d, d2h = EDGE_CASES[op]
    system, call, payload = _edge_system(op)
    before = metrics.REGISTRY.snapshot()
    with trace.installed() as t:
        call(payload)
        call(payload)
    after = metrics.REGISTRY.snapshot()
    system.close()
    # exact counts: every stage once per call, and nothing else
    assert _edge_delta(before, after, "edge_stage_seconds", op,
                       "local") == {s: 2 for s in stages}
    assert _edge_delta(before, after, "edge_bytes_total", op,
                       "local") == {"h2d": 2 * h2d, "d2h": 2 * d2h}
    # a codeword's survivors are gathered by the conversion, once a call
    assert _edge_delta(before, after, "edge_gather_fused_total", op,
                       "local") == ({} if op == "encode" else {None: 2})
    # the installed tracer received the same stages, in order
    evs = t.events(cat="edge")
    assert [e["name"] for e in evs] == [f"edge.{s}" for s in stages] * 2
    assert all(e["args"] == {"op": op, "backend": "local"} for e in evs)
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3
               for a, b in zip(evs, evs[1:]))


def test_read_stages_labelled_with_the_backend_that_runs():
    """`DecodePlan.data` runs on one device whatever the plan's backend,
    so a simulator session's degraded read records its stages (the
    gather fused into `prep`) under backend "local"."""
    system = CodedSystem(_spec("rs", 6, 3), backend="simulator")
    cw = system.codeword(RNG.integers(0, 1 << 16, (6, W_EDGE)))
    system.fail([2])
    system.read(cw)
    before = metrics.REGISTRY.snapshot()
    assert np.array_equal(system.read(cw), cw[:6])
    after = metrics.REGISTRY.snapshot()
    system.close()
    assert _edge_delta(before, after, "edge_stage_seconds", "read",
                       "local") == {s: 1 for s in EDGE_CASES["read"][0]}
    assert _edge_delta(before, after, "edge_stage_seconds", "read",
                       "simulator") == {}
    assert _edge_delta(before, after, "edge_gather_fused_total", "read",
                       "local") == {None: 1}


def test_edge_stages_reach_the_profiler_without_a_tracer(tmp_path):
    """A `jax.profiler` trace on the CPU holds the `edge.*` annotations on
    a host plane; no repro tracer is installed."""
    import jax
    from jax.profiler import ProfileData

    system, call, payload = _edge_system("read")
    assert trace.get_tracer() is None
    with jax.profiler.trace(str(tmp_path)):
        call(payload)
    system.close()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name.startswith("edge.")]
    assert names == [f"edge.{s}" for s in EDGE_CASES["read"][0]]


def test_stream_pipeline_stages_go_through_the_edge_helper():
    """`run_stream`'s h2d / dispatch / materialize are edge stages: one
    each per chunk, with the chunks' bytes."""
    plan = Encoder.plan(_spec("rs", 4, 4), backend="local")
    x = RNG.integers(0, 1 << 16, (4, 3 * W_EDGE))
    before = metrics.REGISTRY.snapshot()
    with trace.installed() as t:
        out = np.concatenate(list(plan.run_stream(x, chunk_w=W_EDGE)),
                             axis=1)
    after = metrics.REGISTRY.snapshot()
    assert np.array_equal(out, plan.run(x))
    assert _edge_delta(before, after, "edge_stage_seconds", "encode",
                       "local") == {"h2d": 3, "dispatch": 3,
                                    "materialize": 3}
    assert _edge_delta(before, after, "edge_bytes_total", "encode",
                       "local") == {"h2d": 3 * 4 * W_EDGE * 4,
                                    "d2h": 3 * 4 * W_EDGE * 4}
    assert [e["name"] for e in t.events(cat="edge")] == [
        "edge.h2d", "edge.dispatch", "edge.h2d", "edge.materialize",
        "edge.dispatch", "edge.h2d", "edge.materialize", "edge.dispatch",
        "edge.materialize"]


# ---------------------------------------------------------------------------
# host-edge conversion to the device format (`api.backends.to_field_u32`)
# ---------------------------------------------------------------------------

Q = FERMAT.q
I64 = np.iinfo(np.int64)
# q - 1 is 2**16, the largest parity symbol and one above 16-bit data
REDUCE_VALUES = (0, Q - 1, Q, 1 << 32, (1 << 32) + 1, -1, -Q,
                 int(I64.min), int(I64.max), 1 << 56)
# ">i8": big-endian int64, whose bytes of 2**56 read natively are 1
REDUCE_DTYPES = ("uint8", "uint16", "int32", "int64", "uint64", "float64",
                 ">i8")


def _fits(dtype: str, v: int) -> bool:
    if np.dtype(dtype).kind == "f":
        return True
    info = np.iinfo(dtype)
    return info.min <= v <= info.max


def _reduce_oracle(x):
    """`(np.asarray(x) % q).astype(np.uint32)`; the 8- and 16-bit dtypes
    are widened first, since numpy refuses `% q` where q does not fit."""
    if x.dtype.kind in "iu" and x.dtype.itemsize < 4:
        x = x.astype(np.int64)
    return (x % Q).astype(np.uint32)


def _reduce_payload(dtype: str, value, layout: str):
    """A small payload of `dtype` holding canonical symbols and `value`
    (None: canonical symbols only), laid out as `layout`."""
    hi = min(Q, np.iinfo(dtype).max + 1) if np.dtype(dtype).kind in "iu" \
        else Q
    x = RNG.integers(0, hi, (6, 8)).astype(dtype)
    if value is not None:
        x[3, 5] = value
    if layout == "empty":
        return x[:0]
    return x[1::2] if layout == "row_slice" else x


REDUCE_CASES = [(dt, v, layout) for dt in REDUCE_DTYPES
                for v in (None,) + REDUCE_VALUES if v is None or _fits(dt, v)
                for layout in ("contiguous", "row_slice")] + [
    (dt, None, "empty") for dt in REDUCE_DTYPES]


@pytest.mark.parametrize("dtype,value,layout", REDUCE_CASES)
def test_to_field_u32_matches_the_reduction(dtype, value, layout):
    """Bit for bit `% q` and a cast, C-contiguous uint32, for every dtype,
    value and layout; `edge_reduce_total` counts the call once, under
    `canonical` exactly where the dtype or one exact check proves every
    symbol in [0, q)."""
    from repro.api.backends import to_field_u32

    x = _reduce_payload(dtype, value, layout)
    edge = {"op": "convert", "backend": "test"}
    before = metrics.REGISTRY.snapshot()
    got = to_field_u32(x, Q, edge)
    after = metrics.REGISTRY.snapshot()
    assert got.dtype == np.uint32 and got.flags.c_contiguous
    assert got.shape == x.shape
    assert np.array_equal(got, _reduce_oracle(x))
    proven = np.dtype(dtype).kind == "u" and np.dtype(dtype).itemsize <= 2
    in_range = np.dtype(dtype).kind in "iu" and (
        proven or (x.size > 0 and x.min() >= 0 and x.max() < Q))
    path = "canonical" if in_range else "reduced"
    assert _edge_delta(before, after, "edge_reduce_total", "convert",
                       "test") == {path: 1}


def _convert_and_count(x, rows):
    """`to_field_u32(x, Q, ..., rows)` checked against `% q` and a cast of
    `x[rows]`: bit-equal, C-contiguous uint32; returns the call's counts
    ({path: 1} of `edge_reduce_total`, `edge_gather_fused_total`)."""
    from repro.api.backends import to_field_u32

    edge = {"op": "convert", "backend": "test"}
    before = metrics.REGISTRY.snapshot()
    got = to_field_u32(x, Q, edge, rows)
    after = metrics.REGISTRY.snapshot()
    want = _reduce_oracle(x if rows is None else x[list(rows)])
    assert got.dtype == np.uint32 and got.flags.c_contiguous
    assert got.shape == want.shape and np.array_equal(got, want)
    return (_edge_delta(before, after, "edge_reduce_total", "convert",
                        "test"),
            _edge_delta(before, after, "edge_gather_fused_total", "convert",
                        "test").get(None, 0))


def _tiled_payload(dtype):
    """Four rows of canonical symbols, each a little over three tiles of
    the conversion's pass."""
    from repro.api.backends import _TILE

    return RNG.integers(0, Q, (4, 3 * _TILE + 5)).astype(dtype)


# where in a row one symbol leaves [0, q): in tiles of `_TILE` symbols
TILE_SPOTS = {"first": lambda t: 3, "middle": lambda t: t + 7,
              "last": lambda t: 3 * t + 2,
              "before_a_boundary": lambda t: 2 * t - 1}


@pytest.mark.parametrize("value", (-1, Q))
@pytest.mark.parametrize("dtype", ("int64", "int32"))
@pytest.mark.parametrize("spot", sorted(TILE_SPOTS))
def test_to_field_u32_finds_an_off_range_symbol_in_any_tile(spot, dtype,
                                                             value):
    """A payload wider than three tiles with one symbol off [0, q) in the
    first, a middle or the last tile, or just before a tile boundary,
    takes the `% q` pass: bit-equal, counted once as `reduced`, whether
    the pass reads every row or a selection holding that row; a selection
    without that row is canonical."""
    from repro.api.backends import _TILE

    x = _tiled_payload(dtype)
    x[2, TILE_SPOTS[spot](_TILE)] = value
    assert _convert_and_count(x, None) == ({"reduced": 1}, 0)
    assert _convert_and_count(x, (3, 2)) == ({"reduced": 1}, 1)
    assert _convert_and_count(x, (0, 3, 1)) == ({"canonical": 1}, 1)


ROW_LISTS = {"subset": lambda n: [0, n - 1],
             "reordered_subset": lambda n: [n - 1, 0],
             "full_set": lambda n: list(range(n))}


@pytest.mark.parametrize("value", (None, -1))
@pytest.mark.parametrize("layout", ("contiguous", "row_slice", "tiled"))
@pytest.mark.parametrize("rows", sorted(ROW_LISTS))
def test_to_field_u32_gathers_the_rows_asked_for(rows, layout, value):
    """`rows` picks rows of the payload in the conversion's one pass:
    bit-equal to `x[rows] % q` and a cast, counted once in
    `edge_reduce_total`, and once in `edge_gather_fused_total` exactly
    where `rows` selects from a wider array."""
    if layout == "tiled":
        x = _tiled_payload("int64")
        if value is not None:
            x[-1, -1] = value
    else:
        x = _reduce_payload("int64", value, layout)
    picked = ROW_LISTS[rows](len(x))
    off = value is not None and (layout == "row_slice" and 1 in picked
                                 or layout == "contiguous" and 3 in picked
                                 or layout == "tiled"
                                 and len(x) - 1 in picked)
    assert _convert_and_count(x, picked) == (
        {"reduced" if off else "canonical": 1}, int(len(picked) < len(x)))


# one R-loss pattern per stripe beside every single loss: data and parity
FUSED_LOSSES = {(6, 3): (0, 4, 7), (10, 4): (1, 5, 10, 13)}
FUSED_CASES = [(K, R, lost) for (K, R), many in FUSED_LOSSES.items()
               for lost in [(e,) for e in range(K + R)] + [many]]


@pytest.mark.parametrize("K,R,lost", FUSED_CASES)
def test_local_read_and_decode_of_a_codeword_match_the_oracle(K, R, lost):
    """On the local backend the degraded read and the decode of an (N, W)
    codeword, canonical or shifted by a multiple of q, return the data and
    the lost rows of the numpy field oracle's codeword (and, where the
    code is not shortened, the simulator's decode); each call converts
    the survivors straight out of the codeword, once."""
    spec = _spec("rs", K, R)
    x = RNG.integers(0, 1 << 16, (K, W_EDGE))
    A = Encoder.plan(spec, backend="local").A
    cw = np.concatenate([x, FERMAT.matmul(A.T, x)])
    if K % R == 0:              # the schedule backends run no shortened code
        oracle = CodedSystem(spec, backend="simulator").fail(lost)
        assert np.array_equal(oracle.decode(cw), cw[list(lost)])
    damaged = cw.copy()
    damaged[list(lost)] = 0
    system = CodedSystem(spec, backend="local").fail(lost)
    for shift in (0, -2):
        payload = damaged + shift * Q
        before = metrics.REGISTRY.snapshot()
        read, repaired = system.read(payload), system.decode(payload)
        after = metrics.REGISTRY.snapshot()
        assert np.array_equal(read, x), shift
        assert np.array_equal(repaired, cw[list(lost)]), shift
        for op in ("read", "decode"):
            assert _edge_delta(before, after, "edge_gather_fused_total", op,
                               "local") == {None: 1}
            assert _edge_delta(before, after, "edge_reduce_total", op,
                               "local") == {
                "reduced" if shift else "canonical": 1}
            assert "gather" not in _edge_delta(
                before, after, "edge_stage_seconds", op, "local")
    system.close()


SHIFTS = (0, 1, -1, -3, 1 << 40)


@pytest.mark.parametrize("op", ("encode", "read"))
@pytest.mark.parametrize("shift", SHIFTS)
def test_shifted_payload_same_bits_as_canonical(op, shift):
    """A payload shifted by `shift` * q, negative ones included, encodes
    and degraded-reads to the same bits as the canonical payload on the
    local backend, and both match the simulator oracle; only the shifted
    payload takes the `% q` pass."""
    spec = _spec("rs", 6, 3)
    x = RNG.integers(0, 1 << 16, (6, W_EDGE))
    cw = _codeword(spec, x)
    system = CodedSystem(spec, backend="local")
    if op == "read":
        system.fail([2])
    call, payload, want = ((system.encode, x, cw[6:]) if op == "encode"
                           else (system.read, cw, x))
    canonical = call(payload)
    before = metrics.REGISTRY.snapshot()
    shifted = call(payload + shift * Q)
    after = metrics.REGISTRY.snapshot()
    system.close()
    assert np.array_equal(canonical, want)
    assert np.array_equal(shifted, want)
    assert _edge_delta(before, after, "edge_reduce_total", op, "local") == {
        "reduced" if shift else "canonical": 1}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_roundtrip():
    reg = metrics.MetricsRegistry()
    reg.counter("ops_total", "ops").inc(2, op="encode")
    reg.gauge("depth").set(7, q="a")
    h = reg.histogram("lat_us")
    for v in (1.0, 3.0, 2.0):
        h.observe(v, op="encode")
    snap = reg.snapshot()
    assert snap["ops_total"]["values"]["op=encode"] == 2
    assert snap["depth"]["values"]["q=a"] == 7
    hv = snap["lat_us"]["values"]["op=encode"]
    assert hv == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0,
                  "mean": 2.0}
    text = reg.render_text()
    assert 'repro_ops_total{op="encode"} 2' in text
    assert "repro_lat_us_count" in text
    with pytest.raises(ValueError):
        reg.gauge("ops_total")  # name already registered as a counter


def test_bound_handles_update_their_labelset():
    reg = metrics.MetricsRegistry()
    c, h = reg.counter("bytes_total"), reg.histogram("lat_s")
    bc = c.labels(direction="h2d", op="encode")
    bh = h.labels(op="encode", stage="prep")
    bc.inc(5)
    c.inc(2, op="encode", direction="h2d")      # same labelset, any order
    for v in (3.0, 1.0, 2.0):
        bh.observe(v)
    h.observe(4.0, stage="prep", op="encode")
    snap = reg.snapshot()
    assert snap["bytes_total"]["values"] == {"direction=h2d,op=encode": 7}
    assert snap["lat_s"]["values"]["op=encode,stage=prep"] == {
        "count": 4, "sum": 10.0, "min": 1.0, "max": 4.0, "mean": 2.5}
    reg.reset()                                  # a handle outlives reset
    bc.inc(1)
    assert reg.snapshot()["bytes_total"]["values"] == {
        "direction=h2d,op=encode": 1}


def test_bound_handles_lose_no_update_under_threads():
    import sys

    reg = metrics.MetricsRegistry()
    bc = reg.counter("n_total").labels(t="x")
    bh = reg.histogram("v").labels(t="x")

    def writer():
        for _ in range(2000):
            bc.inc(1)
            bh.observe(1.0)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    snap = reg.snapshot()
    assert snap["n_total"]["values"]["t=x"] == 16000
    assert snap["v"]["values"]["t=x"]["count"] == 16000
    assert snap["v"]["values"]["t=x"]["sum"] == 16000.0


def test_registry_snapshot_consistent_under_concurrency():
    reg = metrics.MetricsRegistry()
    a = reg.counter("a_total")
    b = reg.counter("b_total")
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            # invariant: a is ALWAYS incremented before b
            a.inc(1, t="x")
            b.inc(1, t="x")

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            snap = reg.snapshot()
            av = snap["a_total"]["values"].get("t=x", 0)
            bv = snap["b_total"]["values"].get("t=x", 0)
            # one lock guards all families: no snapshot may catch b ahead
            # of a (each writer orders a before b under that lock)
            assert av >= bv
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_plan_run_publishes_into_the_registry():
    before = metrics.REGISTRY.snapshot().get(
        "coded_runs_total", {}).get("values", {}).get(
        "backend=simulator,kind=rs,op=encode", 0)
    spec = _spec("rs", 8, 4)
    Encoder.plan(spec, backend="simulator").run(FERMAT.rand((8, 2), RNG))
    after = metrics.REGISTRY.snapshot()["coded_runs_total"]["values"][
        "backend=simulator,kind=rs,op=encode"]
    assert after == before + 1


# ---------------------------------------------------------------------------
# drift ledger: measured C1/C2 vs the closed-form model
# ---------------------------------------------------------------------------

KINDS = [("universal", 16, 4, (2, 17)), ("rs", 16, 4, (1, 18)),
         ("lagrange", 16, 4, (0, 19)), ("dft", 8, 8, (5, 9, 13))]


def test_zero_drift_across_all_kinds_on_simulator():
    drift.LEDGER.reset()
    for kind, K, R, erased in KINDS:
        spec = _spec(kind, K, R)
        x = FERMAT.rand((K, 3), RNG)
        sys1 = CodedSystem(spec, backend="simulator")
        cw = sys1.codeword(x)
        sys1.fail(erased)
        assert np.array_equal(sys1.decode(cw), cw[list(erased)])
        sys1.close()
    entries = drift.LEDGER.entries()
    # every kind contributed an encode AND a decode cell, all exact
    assert {(e.spec.kind, e.op) for e in entries} == \
        {(k, op) for k, _, _, _ in KINDS for op in ("encode", "decode")}
    assert all(e.runs == e.exact for e in entries)
    assert drift.LEDGER.drifted() == []
    assert "ZERO drift" in drift.LEDGER.describe()


def test_streamed_runs_keep_zero_drift():
    drift.LEDGER.reset()
    spec = _spec("rs", 16, 4)
    plan = Encoder.plan(spec, backend="simulator")
    for _ in plan.run_stream(FERMAT.rand((16, 400), RNG), chunk_w=128):
        pass
    entries = drift.LEDGER.entries()
    assert entries and drift.LEDGER.drifted() == []
    assert sum(e.runs for e in entries) == 4  # ceil(400/128) chunks


def test_drift_fails_loudly_on_model_mismatch():
    drift.LEDGER.reset()
    spec = _spec("rs", 8, 4)
    plan = Encoder.plan(spec, backend="simulator")
    net = RoundNetwork(spec.N, spec.p, tracer=False)
    net.C1, net.C2 = 999, 999  # a cooked measurement cannot match
    drift.record_run(plan, net, "encode", 1)
    bad = drift.LEDGER.drifted()
    assert len(bad) == 1 and bad[0].last_mismatch is not None
    assert "DRIFTED" in drift.LEDGER.describe()
    drift.LEDGER.reset()


def test_system_stats_surface_metrics_and_drift():
    drift.LEDGER.reset()
    spec = _spec("rs", 8, 4)
    with CodedSystem(spec, backend="simulator") as sys1:
        sys1.codeword(FERMAT.rand((8, 2), RNG))
        st = sys1.stats()
    assert "coded_runs_total" in st["metrics"]
    assert st["drift"]["drifted"] == 0
    assert st["drift"]["runs"] == st["drift"]["exact"] > 0
    with CodedSystem(spec, backend="local") as sys2:
        assert "drift" not in sys2.stats()  # nothing measured to compare


# ---------------------------------------------------------------------------
# ServiceStats latency reservoir (deque(maxlen=...) + dropped accounting)
# ---------------------------------------------------------------------------

def test_service_stats_reservoir_bounds_and_counts_drops():
    from repro.launch.tenancy import ServiceStats

    st = ServiceStats("t", reservoir=16)
    for i in range(40):
        st.record_submitted(8)
        st.record_done(float(i), 8, True)
    snap = st.snapshot()
    assert snap["lat_samples"] == 16
    assert snap["lat_dropped"] == 24
    # the reservoir keeps the NEWEST samples (deque maxlen semantics)
    assert st.latencies_us() == [float(i) for i in range(24, 40)]


# ---------------------------------------------------------------------------
# PlanStats thread-local contract (pinned by the PlanStats docstring)
# ---------------------------------------------------------------------------

def test_plan_stats_cross_thread():
    spec = _spec("rs", 8, 4)
    plan = Encoder.plan(spec, backend="simulator")
    plan.run(FERMAT.rand((8, 2), RNG))
    assert plan.last_stats is not None

    seen = {}

    def reader():
        # a thread that never ran the plan reads None — never another
        # thread's stats
        seen["last"] = plan.last_stats
        seen["stream"] = plan.stream_stats

    th = threading.Thread(target=reader)
    th.start()
    th.join()
    assert seen == {"last": None, "stream": None}
    assert plan.last_stats is not None  # the owner's view is untouched


# ---------------------------------------------------------------------------
# CodedSystem trace= user surface
# ---------------------------------------------------------------------------

def test_coded_system_trace_path_saved_on_close(tmp_path):
    path = tmp_path / "sys.json"
    spec = _spec("rs", 8, 4)
    sys1 = CodedSystem(spec, backend="simulator", trace=str(path))
    cw = sys1.codeword(FERMAT.rand((8, 2), RNG))
    sys1.fail([1])
    sys1.decode(cw)
    assert trace.get_tracer() is sys1.tracer
    sys1.close()
    assert trace.get_tracer() is None  # uninstalled, not leaked
    d = json.loads(path.read_text())
    cats = {e.get("cat") for e in d["traceEvents"]}
    assert "sim.round" in cats and "sim.proc" in cats
