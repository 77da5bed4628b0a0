"""Pallas TPU kernel: blocked matmul over F_65537 — the encode hot-spot.

The local-encode step of every all-to-all encode algorithm (initializing the
shoot-phase packets w_{k,s}, eq. before Remark 6, and the direct fallback
x @ A) is a matrix product over the field. This kernel tiles it for VMEM:

  grid = (M/bm, N/bn, K/bk), K innermost so each (i, j) output tile stays
  resident in VMEM across the K-reduction (revisiting semantics).

Overflow proof (all uint32, no 64-bit — TPU-native):
  * inputs are in [0, q) with q = 2^16 + 1
  * each product is Fermat-reduced *before* accumulation (fermat_mul), so
    every addend is < q <= 2^16 + 1
  * the per-slice partial sum accumulates bk_inner <= 2^14 addends:
    2^14 * (2^16 + 1) < 2^31, so it is summed exactly in int32 (Mosaic has
    no unsigned reductions) and cast back to uint32 for one fermat_reduce
  * the running output tile is kept reduced (< q) via modular add.

dtype note: TPU Pallas prefers >=2D int32/uint32 tiles with last dim 128; we
use (bm, bk) x (bk, bn) tiles with bm = bn = 128 by default and bk <= 16384
(VMEM: the (bm, bk, bn) broadcast product is materialized per k-slice of 8,
see inner loop — working set ~ (128*8*128)*4B = 512 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.field import FERMAT_Q


def _fermat_reduce_u32(x):
    lo = x & jnp.uint32(0xFFFF)
    hi = x >> jnp.uint32(16)
    r = lo + jnp.uint32(FERMAT_Q) - hi
    return jnp.where(r >= jnp.uint32(FERMAT_Q), r - jnp.uint32(FERMAT_Q), r)


def _fermat_mul_u32(a, b):
    safe_a = jnp.where(a == jnp.uint32(65536), jnp.uint32(0), a)
    prod = _fermat_reduce_u32(safe_a * b)
    neg_b = jnp.where(b == jnp.uint32(0), jnp.uint32(0), jnp.uint32(FERMAT_Q) - b)
    return jnp.where(a == jnp.uint32(65536), neg_b, prod)


def _fermat_add_u32(a, b):
    s = a + b
    return jnp.where(s >= jnp.uint32(FERMAT_Q), s - jnp.uint32(FERMAT_Q), s)


def _gf_matmul_kernel(a_ref, b_ref, o_ref, *, bk_inner: int):
    """One (bm, bn) output tile; grid axis 2 sweeps the K reduction."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]  # (bm, bk) uint32
    b = b_ref[...]  # (bk, bn) uint32
    bk = a.shape[1]
    acc = o_ref[...]
    # inner loop over bk in slices of bk_inner to bound the 3D broadcast
    for s in range(0, bk, bk_inner):
        a_sl = a[:, s : s + bk_inner]           # (bm, ki)
        b_sl = b[s : s + bk_inner, :]           # (ki, bn)
        prods = _fermat_mul_u32(a_sl[:, :, None], b_sl[None, :, :])
        # every addend < q <= 2^16+1; ki <= 2^14 => sum < 2^31: exact in int32
        part = jnp.sum(prods.astype(jnp.int32), axis=1).astype(jnp.uint32)
        acc = _fermat_add_u32(acc, _fermat_reduce_u32(part))
    o_ref[...] = acc


def _pad_to(x, mult0, mult1):
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def gf_matmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    interpret: bool,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    bk_inner: int = 8,
) -> jnp.ndarray:
    """(a @ b) mod 65537 with explicit VMEM tiling.

    a: (M, K), b: (K, N), any uint32-compatible dtype with values in [0, q).
    Callers go through `kernels.ops.gf_matmul`, which sets `interpret` from
    the platform the program is lowered for.
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert bk <= 16384, "accumulation overflow guard (see module docstring)"
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    Mp, Kp = ap.shape
    _, Np = bp.shape
    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_gf_matmul_kernel, bk_inner=bk_inner),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.uint32),
        interpret=interpret,
    )(ap, bp)
    return out[:M, :N]
