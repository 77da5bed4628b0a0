"""Pallas gf_matmul kernel: shape sweep + adversarial values vs oracles."""
import jax.numpy as jnp
import numpy as np
import pytest
from conftest_hypothesis import given, settings, st

from repro.core.field import FERMAT, FERMAT_Q
from repro.kernels.ops import encode_blocks, gf_matmul
from repro.kernels.ref import gf_matmul_ref

RNG = np.random.default_rng(11)


def _oracle(a, b):
    return FERMAT.matmul(a.astype(np.int64), b.astype(np.int64)).astype(np.uint32)


@pytest.mark.parametrize(
    "M,K,N",
    [(1, 1, 1), (128, 128, 128), (7, 300, 65), (130, 257, 96),
     (200, 130, 250), (128, 1, 128), (1, 1024, 1)],
)
def test_gf_matmul_shape_sweep(M, K, N):
    a = RNG.integers(0, FERMAT_Q, (M, K)).astype(np.uint32)
    b = RNG.integers(0, FERMAT_Q, (K, N)).astype(np.uint32)
    exp = _oracle(a, b)
    assert np.array_equal(np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b))), exp)
    assert np.array_equal(np.asarray(gf_matmul_ref(jnp.asarray(a), jnp.asarray(b))), exp)


@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.uint16])
def test_gf_matmul_dtypes(dtype):
    hi = min(FERMAT_Q - 1, np.iinfo(dtype).max)
    a = RNG.integers(0, hi, (64, 96)).astype(dtype)
    b = RNG.integers(0, hi, (96, 32)).astype(dtype)
    got = np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, _oracle(a.astype(np.uint32), b.astype(np.uint32)))


@pytest.mark.parametrize("blocks", [(32, 32, 32), (128, 128, 16), (64, 128, 128)])
def test_gf_matmul_block_shapes(blocks):
    bm, bn, bk = blocks
    a = RNG.integers(0, FERMAT_Q, (200, 170)).astype(np.uint32)
    b = RNG.integers(0, FERMAT_Q, (170, 90)).astype(np.uint32)
    got = np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b), bm=bm, bn=bn, bk=bk))
    assert np.array_equal(got, _oracle(a, b))


def test_gf_matmul_adversarial_65536():
    """65536 == -1 (mod q) is the only uint32-overflow corner."""
    for shape in [(64, 64), (130, 64)]:
        a = np.full(shape, 65536, np.uint32)
        b = np.full((shape[1], 32), 65536, np.uint32)
        assert np.array_equal(np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b))),
                              _oracle(a, b))


def test_gf_matmul_worst_case_accumulation():
    """All-max values at a large bk: overflow-proof check (bk_inner slices of
    8 bound the per-sum addend count; 4096 exercises many slices)."""
    a = np.full((8, 4096), FERMAT_Q - 1, np.uint32)
    b = np.full((4096, 8), FERMAT_Q - 1, np.uint32)
    got = np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b), bk=4096))
    assert np.array_equal(got, _oracle(a, b))


@given(
    m=st.integers(1, 40), k=st.integers(1, 60), n=st.integers(1, 40),
    seed=st.integers(0, 2**31),
)
@settings(max_examples=10, deadline=None)
def test_gf_matmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, FERMAT_Q, (m, k)).astype(np.uint32)
    b = rng.integers(0, FERMAT_Q, (k, n)).astype(np.uint32)
    assert np.array_equal(
        np.asarray(gf_matmul(jnp.asarray(a), jnp.asarray(b), bm=32, bn=32, bk=32)),
        _oracle(a, b),
    )


def test_encode_blocks_dispatch():
    x = RNG.integers(0, FERMAT_Q, (160, 200)).astype(np.uint32)
    coeffs = RNG.integers(0, FERMAT_Q, (160, 130)).astype(np.uint32)
    got = np.asarray(encode_blocks(jnp.asarray(x), jnp.asarray(coeffs)))
    assert np.array_equal(got, _oracle(coeffs.T, x))
    small = np.asarray(encode_blocks(jnp.asarray(x[:4]), jnp.asarray(coeffs[:4, :3])))
    assert np.array_equal(small, _oracle(coeffs[:4, :3].T, x[:4]))


# ---------------- NTT kernel (the paper's DFT layer on-chip) -----------------

@pytest.mark.parametrize("K", [4, 16, 64, 256, 1024])
def test_ntt_kernel_vs_permuted_dft(K):
    from repro.kernels.ntt import ntt_ref
    from repro.kernels.ops import ntt

    x = RNG.integers(0, FERMAT_Q, (K, 6)).astype(np.uint32)
    got = np.asarray(ntt(jnp.asarray(x)))
    assert np.array_equal(got, ntt_ref(jnp.asarray(x)))


@pytest.mark.parametrize("K", [16, 128])
def test_ntt_roundtrip_and_padding(K):
    from repro.kernels.ops import ntt

    x = RNG.integers(0, FERMAT_Q, (K, 131)).astype(np.uint32)  # W % bw != 0
    y = ntt(jnp.asarray(x))
    back = np.asarray(ntt(y, inverse=True))
    assert np.array_equal(back, x)


def test_ntt_adversarial_values():
    from repro.kernels.ntt import ntt_ref
    from repro.kernels.ops import ntt

    x = np.full((64, 4), FERMAT_Q - 1, np.uint32)
    assert np.array_equal(np.asarray(ntt(jnp.asarray(x))), ntt_ref(jnp.asarray(x)))
