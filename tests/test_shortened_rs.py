"""Shortened structured Reed-Solomon codes: stripes where neither of K and R
divides the other (HDFS RS-10-4, RS-3-2, Backblaze 17+3, ...) run through
`CodedSystem` on the local backend, bitwise-equal to a plain Lagrange
reference; the schedule backends refuse them; and the shapes that need no
shortening keep the exact code and NTT constants they had before."""
import hashlib

import jax
import numpy as np
import pytest

from repro.api import BackendCapabilityError, CodedSystem, CodeSpec, Encoder
from repro.core.cauchy import StructuredGRS
from repro.core.field import FERMAT
from repro.kernels.ntt_encode import NTTEncodeParams, ntt_encode
from repro.recover import Decoder

Q = 65537
SHAPES = [(10, 4), (3, 2), (17, 3), (3, 8), (5, 4)]
IMPL = {(10, 4): "ntt", (3, 2): "ntt", (17, 3): "dense", (3, 8): "ntt",
        (5, 4): "ntt"}


def lagrange_block(src, dst):
    """M[k, j] = L_k(dst[j]), L_k the Lagrange basis of the points src."""
    out = np.zeros((len(src), len(dst)), np.int64)
    for k, ak in enumerate(src):
        for j, b in enumerate(dst):
            num = den = 1
            for i, ai in enumerate(src):
                if i != k:
                    num = num * (int(b) - int(ai)) % Q
                    den = den * (int(ak) - int(ai)) % Q
            out[k, j] = num * pow(den, Q - 2, Q) % Q
    return out


def reference_parity(sgrs, x):
    A = lagrange_block(sgrs.grs.alphas, sgrs.grs.betas)
    return (A.T.astype(object) @ np.asarray(x).astype(object) % Q).astype(
        np.int64)


def _data(K, W, seed=0):
    return np.random.default_rng(seed).integers(0, 1 << 16, (K, W))


@pytest.mark.parametrize("K,R", SHAPES)
def test_A_is_the_plain_lagrange_block(K, R):
    sgrs = StructuredGRS.build(FERMAT, K, R)
    assert sgrs.parent is not None and sgrs.K == K and sgrs.R == R
    assert sgrs.parent.K == StructuredGRS.parent_sources(K, R) > K
    A = lagrange_block(sgrs.grs.alphas, sgrs.grs.betas)
    assert np.array_equal(sgrs.grs.A_direct(), A)
    # the parent shortened at the dropped points, with diagonal scalings
    ha, hb_inv = sgrs.shorten_scales()
    Ap = sgrs.parent.grs.A_direct()[:K]
    assert np.array_equal(A, ha[:, None] * Ap % Q * hb_inv[None, :] % Q)
    assert np.array_equal(Encoder.plan(CodeSpec("rs", K, R),
                                       backend="local").A, A)


@pytest.mark.parametrize("K,R", SHAPES)
def test_local_encode_and_stream_match_reference(K, R):
    system = CodedSystem(CodeSpec(kind="rs", K=K, R=R), backend="local")
    x = _data(K, 300)
    ref = reference_parity(system.encode_plan.sgrs, x)
    assert np.array_equal(system.encode(x), ref)
    assert np.array_equal(system.codeword(x), np.concatenate([x, ref]))
    y = np.concatenate(list(system.encode_stream(x, chunk_w=128)), axis=1)
    assert np.array_equal(y, ref)


@pytest.mark.parametrize("K,R", SHAPES)
def test_read_after_every_single_loss_and_R_losses(K, R):
    system = CodedSystem(CodeSpec(kind="rs", K=K, R=R), backend="local")
    x = _data(K, 40, seed=1)
    cw = system.codeword(x)
    N = K + R
    patterns = [(e,) for e in range(N)]
    # R losses: data first (all of it when K <= R), then parity
    patterns.append(tuple(range(min(R, K))) + tuple(range(K, K + R - min(R, K))))
    for lost in patterns:
        damaged = cw.copy()
        damaged[list(lost)] = 0
        system.heal().fail(lost)
        assert np.array_equal(system.read(damaged), x), lost
    system.heal()


@pytest.mark.parametrize("K,R", SHAPES)
def test_local_impl(K, R):
    plan = Encoder.plan(CodeSpec(kind="rs", K=K, R=R), backend="local")
    assert plan.local_impl == IMPL[(K, R)]
    assert "rs" not in plan.costs  # no structured schedule for a shortened code
    assert plan.describe()


@pytest.mark.parametrize("K,R", SHAPES)
def test_schedule_backends_refuse(K, R):
    spec = CodeSpec(kind="rs", K=K, R=R)
    for backend, missing in (("simulator", "zero sources"),
                             ("mesh", "several processors per device")):
        with pytest.raises(BackendCapabilityError, match=missing):
            Encoder.plan(spec, backend=backend)
        with pytest.raises(BackendCapabilityError, match="shortened"):
            Decoder.plan(spec, erased=(0,), backend=backend)
        with pytest.raises(BackendCapabilityError, match="shortened"):
            CodedSystem(spec, backend=backend)


def test_shortened_ntt_uploads_K_rows_and_pads_on_device():
    """HDFS 10+4: the NTT runs as 3 blocks of 4, the two zero rows are
    appended inside the program (its `ntt_encode.shorten` scope), and only
    the K data rows cross the link."""
    from repro.obs.metrics import REGISTRY

    system = CodedSystem(CodeSpec(kind="rs", K=10, R=4), backend="local")
    p = system.encode_plan.tables.ntt_params()
    assert (p.K, p.Z, p.M, p.rows) == (10, 4, 3, 12)
    x32 = jax.ShapeDtypeStruct((10, 256), np.uint32)
    text = jax.jit(lambda x: ntt_encode(x, p)).lower(x32).as_text(
        debug_info=True)
    assert "ntt_encode.shorten" in text

    def h2d():
        v = REGISTRY.snapshot()["edge_bytes_total"]["values"]
        return v.get("backend=local,direction=h2d,op=encode", 0)

    before = h2d()
    system.encode(_data(10, 256))
    assert h2d() - before == 10 * 256 * 4


def test_local_kernel_counter_counts_each_local_call():
    from repro.obs.metrics import REGISTRY

    def counts():
        return dict(REGISTRY.snapshot().get("local_kernel_total", {})
                    .get("values", {}))

    ntt = CodedSystem(CodeSpec(kind="rs", K=10, R=4), backend="local")
    dense = CodedSystem(CodeSpec(kind="rs", K=17, R=3), backend="local")
    before = counts()
    x = _data(10, 256)
    cw = ntt.codeword(x)                                  # 1 ntt encode
    list(ntt.encode_stream(x, chunk_w=128))               # 2 ntt chunks
    dense.encode(_data(17, 64))                           # 1 matmul encode
    ntt.fail(3)
    ntt.decode(cw)                                        # 1 matmul decode
    ntt.read(cw)                                          # 1 matmul read
    ntt.heal()
    after = counts()

    def added(key):
        return after.get(key, 0) - before.get(key, 0)

    assert added("backend=local,kernel=ntt,op=encode") == 3
    assert added("backend=local,kernel=matmul,op=encode") == 1
    assert added("backend=local,kernel=matmul,op=decode") == 1
    assert added("backend=local,kernel=matmul,op=read") == 1


def _digest_A(A):
    A = np.ascontiguousarray(np.asarray(A, np.int64))
    return hashlib.sha256(repr(A.shape).encode() + A.tobytes()).hexdigest()[:16]


def _digest_params(p):
    if p is None:
        return None
    h = hashlib.sha256(repr((p.kind, p.K, p.R, p.Z, p.M, p.case_kge)).encode())
    for a in (p.phi_inv, p.psi, p.twist):
        h.update(np.ascontiguousarray(np.asarray(a, np.int64)).tobytes())
    return h.hexdigest()[:16]


# computed before shortening existed: these shapes must not move a bit
PINNED = {(4, 4): ("7c778a1c12e5ef31", "ba16fec3a18cf3cc"),
          (6, 3): ("b6a20ed930aa96a9", None),
          (8, 4): ("2d31d2cf6d0d5df9", "56bbe6804122da70"),
          (16, 4): ("7d3d67b43f71be0a", "6025750cd74fa535"),
          (4, 8): ("6fc310d0f92d6548", "42ba0d507cdb141d")}


@pytest.mark.parametrize("K,R", sorted(PINNED))
def test_unshortened_codes_are_unchanged(K, R):
    sgrs = StructuredGRS.build(FERMAT, K, R)
    assert sgrs.parent is None and sgrs.dropped.size == 0
    params = NTTEncodeParams.build(CodeSpec(kind="rs", K=K, R=R), sgrs)
    assert (_digest_A(sgrs.grs.A_direct()), _digest_params(params)) \
        == PINNED[(K, R)]
    if params is not None:
        assert params.rows == params.K == K


def test_coded_checkpointer_10_4_restores_without_a_shard_file(tmp_path):
    from repro.ckpt.checkpoint import CodedCheckpointer

    rng = np.random.default_rng(4)
    state = {"w": jax.numpy.asarray(rng.standard_normal((33, 17)),
                                    jax.numpy.float32),
             "step": jax.numpy.asarray(7, jax.numpy.int32)}
    ck = CodedCheckpointer(str(tmp_path), n_shards=10, n_parity=4)
    ck.save(3, state)
    (tmp_path / "step_000003" / "shard_004.npy").unlink()
    rest = ck.restore(3, state)
    for k in state:
        assert np.array_equal(np.asarray(rest[k]), np.asarray(state[k]))


@pytest.mark.parametrize("degraded", [False, True])
def test_serve_coded_selfcheck_10_shards_4_parity(capsys, degraded):
    from repro.launch.serve import _coded_selfcheck

    params = {"emb": np.arange(1000, dtype=np.float32).reshape(40, 25)}
    _coded_selfcheck(params, 10, 4, degraded=degraded)
    assert "10 param shards + 4 parity" in capsys.readouterr().out
