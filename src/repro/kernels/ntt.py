"""Pallas TPU kernel: batched radix-2 NTT over F_65537 (the paper's DFT
layer, Sec. V-A, as an on-chip kernel).

Computes, for each of W independent columns, the K-point NTT in
decimation-in-frequency order — the output at position k is X[rev(k)],
which is exactly the paper's *permuted* DFT matrix D_K·Pi (the algorithm of
Sec. V-A produces the same permutation; validated in tests against
`permuted_dft_matrix`).  Used as the local fast-encode path: a W-symbol
payload column is one lane, so a (K, W) tile is transformed in
O(K log K · W) field ops instead of the O(K^2 · W) matmul.

Kernel layout: one (rows, bw) tile stays in VMEM across all log2(K)
stages.  Each stage pairs row i with row i XOR half through two sublane
rolls and a row-parity select, so no stage slices, concatenates or
reshapes across a sublane tile, and each stage's twiddles are one column
of a host-built (rows, H) table (1 on the rows that take no twiddle).  For
K < 8 the wrapper stacks 8/K column blocks into the sublanes, so every
tile holds at least one full (8, 128) register.  On a v5e the compiled
kernel fits VMEM up to K = 2048.  All arithmetic is the
uint32 Fermat-prime path — no 64-bit, TPU-native.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.field import FERMAT, FERMAT_Q
from .gf_matmul import _fermat_add_u32, _fermat_mul_u32

_SUBLANES = 8           # rows of one uint32 register tile
_LANES = 128
_BLOCK_ELEMS = 1 << 16  # elements of one (rows, bw) tile: 256 KiB uint32


def ntt_twiddles(K: int, inverse: bool = False) -> np.ndarray:
    """(H, K/2) twiddle table for DIF stage h: w_h[j] = root^(j * 2^h)."""
    H = int(math.log2(K))
    assert 2**H == K and (FERMAT_Q - 1) % K == 0
    root = FERMAT.root_of_unity(K)
    if inverse:
        root = pow(root, FERMAT_Q - 2, FERMAT_Q)
    tw = np.zeros((H, K // 2), np.uint32)
    for h in range(H):
        stride = 2**h
        for j in range(K // 2):
            tw[h, j] = pow(root, (j % (K // (2 * stride))) * stride, FERMAT_Q)
    return tw


def _fermat_sub_u32(a, b):
    return jnp.where(a >= b, a - b, a + jnp.uint32(FERMAT_Q) - b)


def _ntt_stages(x, tw, *, K: int, inverse: bool):
    """DIF butterflies forward; stage-wise inverse (DIT form, inverse
    twiddles, reversed stage order) for the inverse transform.

    The fused-XLA body (`ntt_xla`).  The Pallas kernel runs the same
    butterflies on its own layout (`_ntt_kernel`); all arithmetic is exact
    uint32 mod-q, so the two are bitwise-equal.
    x: (K, bw) uint32 values; tw: (H, K/2) uint32 twiddles.
    """
    H = int(math.log2(K))
    stages = range(H - 1, -1, -1) if inverse else range(H)
    for h in stages:
        half = K >> (h + 1)
        groups = K // (2 * half)
        xr = x.reshape(groups, 2 * half, -1)
        u = xr[:, :half]
        v = xr[:, half:]
        twr = tw[h, :].reshape(groups, half)[:, :, None]
        if inverse:
            # inverse of the DIF stage: u' = a + b*w^-1, v' = a - b*w^-1
            # (the 1/2-per-stage factors fold into the final K^-1 scale)
            bw_ = _fermat_mul_u32(v, twr)
            s = _fermat_add_u32(u, bw_)
            d = _fermat_sub_u32(u, bw_)
        else:
            # DIF: u' = u + v, v' = (u - v) * w
            s = _fermat_add_u32(u, v)
            d = _fermat_mul_u32(_fermat_sub_u32(u, v), twr)
        x = jnp.concatenate([s, d], axis=1).reshape(K, -1)
    return x


def _row_twiddles(K: int, rows: int, inverse: bool) -> np.ndarray:
    """(rows, H) twiddle table for the kernel's row layout: column h is
    stage h's per-row twiddle.

    Row i holds point i % K of its transform.  At stage h (half = K >>
    (h+1)) the row takes `ntt_twiddles(K)[h]` at its butterfly index when
    bit `half` of i is set, and 1 otherwise.  The inverse transform's
    first stage also carries the 1/K scale: every row passes through that
    multiply, and the transform is linear.
    """
    tw = ntt_twiddles(K, inverse=inverse)
    H = tw.shape[0]
    i = np.arange(rows) % K
    out = np.ones((rows, H), np.int64)
    for h in range(H):
        half = K >> (h + 1)
        take = (i & half) != 0
        out[take, h] = tw[h, (i[take] // (2 * half)) * half + i[take] % half]
    if inverse:
        out[:, H - 1] = out[:, H - 1] * pow(K, FERMAT_Q - 2, FERMAT_Q) % FERMAT_Q
    return out.astype(np.uint32)


def _ntt_kernel(x_ref, tw_ref, o_ref, *, K: int, inverse: bool):
    """One (rows, bw) tile through every stage.  Row i's butterfly partner
    is row i XOR half: `up[i] = x[i + half]` serves the rows with that bit
    clear (u), `dn[i] = x[i - half]` the rows with it set (v)."""
    x = x_ref[...]
    rows = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    H = int(math.log2(K))
    for h in (range(H - 1, -1, -1) if inverse else range(H)):
        half = K >> (h + 1)
        is_v = (row & half) != 0
        w = tw_ref[:, h:h + 1]                            # (rows, 1)
        if inverse:
            # u' = u + v*w, v' = u - v*w: scale the v rows, then combine
            x = _fermat_mul_u32(x, w)
        up = jnp.roll(x, rows - half, axis=0)
        dn = jnp.roll(x, half, axis=0)
        x = jnp.where(is_v, _fermat_sub_u32(dn, x), _fermat_add_u32(x, up))
        if not inverse:
            # u' = u + v, v' = (u - v)*w: combine, then scale the v rows
            x = _fermat_mul_u32(x, w)
    o_ref[...] = x


def ntt_pallas(x: jnp.ndarray, *, inverse: bool, interpret: bool
               ) -> jnp.ndarray:
    """Batched NTT along axis 0: x (K, W) uint32 in [0, q).

    Forward: out[k] = sum_j x[j] * beta^(j * rev(k))   (== x @ D_K Pi).
    Inverse: exact inverse of forward (includes the 1/K scaling).
    Callers go through `kernels.ops.ntt`, which sets `interpret` from the
    platform the program is lowered for.
    """
    x = x.astype(jnp.uint32)
    K, W = x.shape
    H = int(math.log2(K))
    assert K >= 2 and 2**H == K, "K must be a power of two >= 2"
    g = max(1, _SUBLANES // K)          # column blocks stacked per tile
    rows = g * K
    wg = -(-W // g)
    bw = max(_LANES, min(_BLOCK_ELEMS // rows, -(-wg // _LANES) * _LANES)
             // _LANES * _LANES)
    wp = -(-wg // bw) * bw
    xp = jnp.pad(x, ((0, 0), (0, g * wp - W)))
    if g > 1:
        xp = xp.reshape(K, g, wp).transpose(1, 0, 2).reshape(rows, wp)
    out = pl.pallas_call(
        functools.partial(_ntt_kernel, K=K, inverse=inverse),
        grid=(wp // bw,),
        in_specs=[
            pl.BlockSpec((rows, bw), lambda w: (0, w)),
            pl.BlockSpec((rows, H), lambda w: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, bw), lambda w: (0, w)),
        out_shape=jax.ShapeDtypeStruct((rows, wp), jnp.uint32),
        interpret=interpret,
    )(xp, jnp.asarray(_row_twiddles(K, rows, inverse)))
    if g > 1:
        out = out.reshape(g, K, wp).transpose(1, 0, 2).reshape(K, g * wp)
    return out[:, :W]


@functools.partial(jax.jit, static_argnames=("inverse",))
def ntt_xla(x: jnp.ndarray, *, inverse: bool = False) -> jnp.ndarray:
    """`ntt` as one fused XLA computation (no pallas_call, no grid).

    Bitwise-identical to the Pallas kernel (exact integer arithmetic) but
    without the per-grid-step interpreter overhead, so it is the CPU path
    of `kernels.ops.ntt_auto`.
    """
    x = x.astype(jnp.uint32)
    K = x.shape[0]
    assert 2 ** int(math.log2(K)) == K, "K must be a power of two"
    tw = jnp.asarray(ntt_twiddles(K, inverse=inverse))
    out = _ntt_stages(x, tw, K=K, inverse=inverse)
    if inverse:
        kinv = jnp.uint32(pow(K, FERMAT_Q - 2, FERMAT_Q))
        out = _fermat_mul_u32(out, kinv)
    return out


def ntt_ref(x: jnp.ndarray, inverse: bool = False) -> np.ndarray:
    """Oracle: direct matmul against the (permuted) DFT matrix."""
    from repro.core.matrices import gauss_inverse, permuted_dft_matrix

    K = x.shape[0]
    D = permuted_dft_matrix(FERMAT, K, 2)
    if inverse:
        D = gauss_inverse(FERMAT, D)
    return (FERMAT.matmul(D.T, np.asarray(x, np.int64))).astype(np.uint32)
