"""Decode & repair subsystem: erasure injection, all-to-all decode with
exact closed-form network costs, Decoder/DecodePlan backend parity, the
GF solve kernel, and degraded checkpoint reads (the mesh backend is
exercised in `recover_mesh_checks.py` on 8 forced host devices)."""
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

from conftest_hypothesis import given, settings, st

from repro.api import CodeSpec, Encoder
from repro.core.field import FERMAT
from repro.core.simulator import FailedProcessorError, Msg, RoundNetwork
from repro.recover import Decoder, UndecodableError, decode_cost
from repro.recover.engine import decode_batches

REPO = Path(__file__).resolve().parent.parent
RNG = np.random.default_rng(23)


def _spec(kind, K, R, **kw):
    if kind == "universal":
        kw.setdefault("seed", 5)
    return CodeSpec(kind=kind, K=K, R=R, **kw)


def _codeword(spec, x):
    y = Encoder.plan(spec, backend="simulator").run(x)
    return np.concatenate([x % spec.q, y])


# ---------------------------------------------------------------------------
# simulator layer: erasure injection + opt-in round log
# ---------------------------------------------------------------------------

def test_fail_blocks_sends_and_receives():
    net = RoundNetwork(4, 1)
    net.fail([2])
    with pytest.raises(FailedProcessorError):
        net._account([Msg(2, 0, 1)])  # failed sender
    with pytest.raises(FailedProcessorError):
        net._account([Msg(0, 2, 1)])  # failed receiver
    net._account([Msg(0, 1, 1)])      # survivors talk freely
    assert net.C1 == 1


def test_fail_rejects_out_of_range():
    net = RoundNetwork(4)
    with pytest.raises(ValueError, match=r"outside \[0, 4\)"):
        net.fail([4])
    with pytest.raises(ValueError):
        net.fail_at(2, [4])
    with pytest.raises(ValueError):
        net.fail_at(-1, [1])


def test_encode_schedule_raises_on_failed_sink():
    """The *encode* framework routes through sink processors — once one is
    failed, running the schedule must raise, not silently miscount."""
    from repro.core.framework import decentralized_encode

    spec = _spec("rs", 8, 4)
    A = Encoder.plan(spec, backend="simulator").A
    net = RoundNetwork(12, 1)
    net.fail([9])  # sink T_1
    with pytest.raises(FailedProcessorError):
        decentralized_encode(FERMAT, A, FERMAT.rand((8, 1), RNG), net=net)


def test_round_log_is_opt_in():
    spec = _spec("rs", 16, 4)
    x = FERMAT.rand((16, 2), RNG)
    plan = Encoder.plan(spec, backend="simulator")
    plan.run(x)
    assert plan.sim_net.C1 > 0 and plan.sim_net.round_log == []

    net = RoundNetwork(8, 1, keep_log=True)
    from repro.core.prepare_shoot import prepare_shoot

    out = {}
    vals = {k: FERMAT.rand((2,), RNG) for k in range(8)}
    net.run(prepare_shoot(FERMAT, FERMAT.rand((8, 8), RNG), vals,
                          list(range(8)), 1, out))
    assert len(net.round_log) == net.C1 > 0
    assert net.C2 == sum(m for _, m in net.round_log)


# ---------------------------------------------------------------------------
# all-to-all decode: exactness + closed-form C1/C2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,K,R", [
    ("universal", 16, 4), ("universal", 4, 16), ("rs", 16, 4),
    ("rs", 8, 8), ("lagrange", 16, 4), ("dft", 8, 8),
])
def test_decode_inverts_encode_sim_and_local(kind, K, R):
    spec = _spec(kind, K, R)
    W = 3
    x = FERMAT.rand((K, W), RNG)
    cw = _codeword(spec, x)
    rng = np.random.default_rng(K * 31 + R)
    patterns = [tuple(sorted(rng.choice(K + R, size=n, replace=False).tolist()))
                for n in range(R + 1)]
    for erased in patterns:
        ds = Decoder.plan(spec, erased=erased, backend="simulator")
        dl = Decoder.plan(spec, erased=erased, backend="local")
        v = cw[list(ds.kept)]
        rep = ds.run(v)
        assert np.array_equal(rep, cw[list(erased)]), (kind, erased)
        assert np.array_equal(dl.run(v), rep), (kind, erased)
        assert np.array_equal(ds.data(v), cw[:K]), (kind, erased)
        if erased:
            # measured network counts == closed form, exactly
            c = decode_cost(K, len(erased), spec.p)
            assert ds.sim_net.C1 == c.C1, (kind, erased)
            assert ds.sim_net.C2 == c.C2 * W, (kind, erased)
            assert ds.cost().C1 == c.C1  # spec.W == 1 here


@pytest.mark.parametrize("p", [1, 2, 3])
def test_decode_cost_closed_form_many_shapes(p):
    """decode_cost is *exact* for every (K, |E|) shape, not an upper bound."""
    rng = np.random.default_rng(p)
    for K in (2, 3, 5, 8, 12, 16):
        for E in (1, 2, K - 1, K, min(2 * K, 20)):
            W = 2
            D = FERMAT.rand((K, E), rng)
            v = FERMAT.rand((K, W), rng)
            from repro.recover import decentralized_decode

            net = RoundNetwork(K + 1, p)
            y, net = decentralized_decode(FERMAT, D, v, list(range(K)), p, net)
            assert np.array_equal(y, FERMAT.matmul(D.T, v))
            c = decode_cost(K, E, p)
            assert (net.C1, net.C2) == (c.C1, c.C2 * W), (K, E, p)


def test_decode_more_erasures_than_survivor_slots_batches():
    """K < R specs can lose more shards than there are survivors; the
    schedule processes repair targets in batches of K columns."""
    spec = _spec("universal", 4, 16)
    x = FERMAT.rand((4, 2), RNG)
    cw = _codeword(spec, x)
    erased = tuple(range(1, 11))  # 10 erasures > K = 4
    assert decode_batches(4, 10) == [(4, 4), (4, 4), (2, 2)]
    plan = Decoder.plan(spec, erased=erased, backend="simulator")
    v = cw[list(plan.kept)]
    assert np.array_equal(plan.run(v), cw[list(erased)])
    assert plan.sim_net.C1 == decode_cost(4, 10, 1).C1


def test_decode_simulator_fails_erased_processors():
    """The decode network has the erased processors failed — the schedule
    provably never touches them (it would raise otherwise)."""
    spec = _spec("rs", 16, 4)
    x = FERMAT.rand((16, 1), RNG)
    cw = _codeword(spec, x)
    erased = (0, 5, 17, 19)
    plan = Decoder.plan(spec, erased=erased, backend="simulator")
    plan.run(cw[list(plan.kept)])
    assert plan.sim_net.failed == set(erased)
    with pytest.raises(FailedProcessorError):
        plan.sim_net._account([Msg(0, 1, 1)])


def test_decoder_validation_and_cache():
    spec = _spec("rs", 16, 4)
    with pytest.raises(ValueError):
        Decoder.plan(spec, erased=(0, 1, 2, 3, 4))  # > R
    with pytest.raises(ValueError):
        Decoder.plan(spec, erased=(20,))            # out of range
    with pytest.raises(ValueError):
        Decoder.plan(spec, erased=(0,), backend="warp-drive")
    with pytest.raises(ValueError):                 # kernels are Fermat-only
        Decoder.plan(CodeSpec(kind="rs", K=8, R=4, q=7681), erased=(0,),
                     backend="local")
    p1 = Decoder.plan(spec, erased=(17, 0))
    p2 = Decoder.plan(spec, erased=(0, 17))         # order-normalized key
    assert p2 is p1
    p3 = Decoder.plan(spec, erased=(0, 17), backend="local")
    assert p3.tables is p1.tables                   # backends share tables


def test_decode_zero_erasures_is_noop():
    spec = _spec("rs", 8, 4)
    plan = Decoder.plan(spec, erased=())
    v = FERMAT.rand((8, 3), RNG)
    assert plan.run(v).shape == (0, 3)
    assert np.array_equal(plan.data(v), v % FERMAT.q)  # kept == data shards


def test_dft_undecodable_pattern_raises():
    """[I | A_dft] is not MDS: a full-R erasure whose survivors are rank
    deficient must raise UndecodableError (found by scanning patterns)."""
    import itertools

    spec = CodeSpec(kind="dft", K=8, R=8)
    hit = None
    for erased in itertools.combinations(range(16), 8):
        try:
            Decoder.plan(spec, erased=erased)
        except UndecodableError:
            hit = erased
            break
    assert hit is not None, "expected at least one undecodable DFT pattern"


def test_decoder_skips_dependent_survivor_columns():
    """With < R erasures there are spare survivors; the greedy kept-set
    selection must skip dependent columns instead of failing."""
    import itertools

    spec = CodeSpec(kind="dft", K=8, R=8)
    x = FERMAT.rand((8, 2), RNG)
    cw = _codeword(spec, x)
    checked = 0
    for erased in itertools.combinations(range(16), 6):
        plan = Decoder.plan(spec, erased=erased)  # must always succeed...
        if plan.kept != tuple(sorted(set(range(16)) - set(erased)))[:8]:
            # ...and this pattern actually exercised the skip logic
            v = cw[list(plan.kept)]
            assert np.array_equal(plan.run(v), cw[list(erased)])
            checked += 1
            if checked >= 3:
                break
    assert checked, "no dependent-column pattern found at |E| = 6"


def test_explicit_matrix_decode():
    K, R = 6, 3
    A = FERMAT.rand((K, R), RNG)
    spec = CodeSpec(kind="universal", K=K, R=R)
    x = FERMAT.rand((K, 2), RNG)
    cw = np.concatenate([x % FERMAT.q,
                         Encoder.plan(spec, backend="simulator", A=A).run(x)])
    plan = Decoder.plan(spec, erased=(2, 7), A=A)
    v = cw[list(plan.kept)]
    assert np.array_equal(plan.run(v), cw[[2, 7]])


def test_describe_mentions_pattern():
    plan = Decoder.plan(_spec("rs", 16, 4), erased=(1, 18))
    text = plan.describe()
    assert "erased" in text and "[1, 18]" in text and "C1=" in text


# ---------------------------------------------------------------------------
# hypothesis property: random erasure patterns, all four kinds
# ---------------------------------------------------------------------------

@given(kind=st.sampled_from(["universal", "rs", "lagrange", "dft"]),
       data=st.data())
@settings(max_examples=30, deadline=None)
def test_decode_roundtrip_property(kind, data):
    """encode ∘ decode identity for random |E| <= R patterns, every kind."""
    K, R = {"universal": (8, 4), "rs": (8, 4),
            "lagrange": (8, 4), "dft": (8, 8)}[kind]
    spec = _spec(kind, K, R)
    N = K + R
    n = data.draw(st.integers(0, R), label="n_erased")
    erased = tuple(sorted(data.draw(
        st.lists(st.integers(0, N - 1), min_size=n, max_size=n, unique=True),
        label="erased")))
    seed = data.draw(st.integers(0, 2**31), label="seed")
    x = FERMAT.rand((K, 2), np.random.default_rng(seed))
    cw = _codeword(spec, x)
    try:
        plan = Decoder.plan(spec, erased=erased, backend="simulator")
    except UndecodableError:
        assert kind == "dft", "only the non-MDS DFT kind may be undecodable"
        return
    v = cw[list(plan.kept)]
    assert np.array_equal(plan.run(v), cw[list(erased)])
    assert np.array_equal(plan.data(v), cw[:K])
    if erased:
        c = decode_cost(K, len(erased), spec.p)
        assert (plan.sim_net.C1, plan.sim_net.C2) == (c.C1, c.C2 * 2)


@given(K=st.integers(1, 12), R=st.integers(1, 12), seed=st.integers(0, 2**31))
@settings(max_examples=30, deadline=None)
def test_reconstruct_property(K, R, seed):
    """core.parity.reconstruct (kernel solve path) recovers the data from
    any K-of-N sample of the codeword, for random structured codes
    (shortened ones where neither of K, R divides the other)."""
    from repro.core.cauchy import StructuredGRS
    from repro.core.parity import reconstruct

    rng = np.random.default_rng(seed)
    sgrs = StructuredGRS.build(FERMAT, K, R)
    x = FERMAT.rand((K, 3), rng)
    A = sgrs.grs.A_direct()
    full = np.concatenate([x, FERMAT.matmul(A.T, x)])
    kept = np.sort(rng.choice(K + R, size=K, replace=False))
    assert np.array_equal(reconstruct(FERMAT, sgrs, kept, full[kept]), x)


# ---------------------------------------------------------------------------
# kernel layer: GF solve
# ---------------------------------------------------------------------------

def test_gf_gauss_inverse_matches_numpy_oracle():
    from repro.core.matrices import gauss_inverse
    from repro.kernels.gf_solve import gf_gauss_inverse, gf_solve

    rng = np.random.default_rng(2)
    for n in (1, 3, 16, 40):
        a = FERMAT.rand((n, n), rng)
        ref = gauss_inverse(FERMAT, a)
        assert np.array_equal(np.asarray(gf_gauss_inverse(a), np.int64), ref)
        b = FERMAT.rand((n, 5), rng)
        assert np.array_equal(np.asarray(gf_solve(a, b), np.int64),
                              FERMAT.matmul(ref, b))


def test_gf_gauss_inverse_singular_raises():
    from repro.kernels.gf_solve import gf_gauss_inverse

    a = np.array([[1, 2, 3], [2, 4, 6], [0, 0, 5]], np.int64)  # row2 = 2*row1
    with pytest.raises(ValueError, match="singular"):
        gf_gauss_inverse(a)


def test_decode_blocks_is_encode_dual():
    from repro.kernels.ops import decode_blocks, encode_blocks

    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    v = jnp.asarray(FERMAT.rand((16, 9), rng), jnp.uint32)
    D = jnp.asarray(FERMAT.rand((16, 5), rng), jnp.uint32)
    assert np.array_equal(np.asarray(decode_blocks(v, D)),
                          np.asarray(encode_blocks(v, D)))
    assert np.array_equal(np.asarray(decode_blocks(v, D), np.int64),
                          FERMAT.matmul(np.asarray(D, np.int64).T,
                                        np.asarray(v, np.int64)))


# ---------------------------------------------------------------------------
# application layer: degraded checkpoint reads
# ---------------------------------------------------------------------------

def _tree_equal(a, b):
    import jax

    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(flat_a, flat_b))


def test_checkpoint_degraded_read_missing_files():
    from repro.ckpt import CodedCheckpointer

    state = {"w": np.arange(2048, dtype=np.float32).reshape(32, 64),
             "b": np.linspace(-2, 2, 517, dtype=np.float32)}
    with tempfile.TemporaryDirectory() as td:
        ck = CodedCheckpointer(td, n_shards=8, n_parity=4)
        ck.save(1, state)
        d = Path(td) / "step_000001"
        # R files vanish from disk: 3 data shards + 1 parity shard
        for f in ("shard_000.npy", "shard_003.npy", "shard_006.npy",
                  "parity_001.npy"):
            os.remove(d / f)
        assert _tree_equal(state, ck.restore(1, state))
        # one more simulated failure pushes past R
        with pytest.raises(AssertionError):
            ck.restore(1, state, failed_shards={1})


def test_checkpoint_degraded_plus_simulated_failures():
    from repro.ckpt import CodedCheckpointer

    state = {"w": np.arange(100, dtype=np.int32)}
    with tempfile.TemporaryDirectory() as td:
        ck = CodedCheckpointer(td, n_shards=8, n_parity=4)
        ck.save(2, state)
        os.remove(Path(td) / "step_000002" / "shard_005.npy")
        assert _tree_equal(state, ck.restore(2, state, failed_shards={0, 7}))


@pytest.mark.slow
def test_recover_backend_parity_subprocess_8_devices():
    """simulator == local == mesh decode bitwise + degraded ckpt restore,
    on 8 forced host devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "recover_mesh_checks.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RECOVER_MESH_CHECKS_OK" in proc.stdout
