"""Batched GF(65537) solve — the decode/repair hot-spot.

Erasure decode of a systematic code [I | A] is a two-step computation:

  1. invert the K x K survivor submatrix  S = G[:, kept]   (once per
     erasure pattern — Cauchy/Vandermonde-structured for RS/Lagrange codes,
     arbitrary for universal ones), and
  2. apply it to the (K, W) survivor payloads, W up to millions of symbols:
     x = (S^T)^-1 v, and lost symbols y_E = (S^-1 G[:, E])^T v.

Step 2 is a field matmul and runs through the same `ops.field_matmul`
dispatch as the encode path (the VMEM-tiled Pallas kernel for large
operands, uint32-only — see `gf_matmul.py` for the overflow proof);
step 1 is an exact Gauss-Jordan elimination over F_65537 implemented
here directly on the jnp uint32 path (no int64 anywhere, so the same code
lowers on TPU), with the numpy `core.matrices.gauss_inverse` as
its host oracle.  The inverse of a nonsingular matrix is unique, so both
paths are bitwise identical.

Sequentiality note: Gauss-Jordan is O(K) dependent pivot steps of O(K^2)
vectorized work — it stays on the eager jnp path (each step is one fused
VPU sweep) rather than a Pallas grid, because the K x K inverse is built
once per erasure pattern and cached by the decode planner; only the (K, W)
application is the per-payload hot path.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.field import FERMAT_Q, fermat_mul, fermat_sub

from .ops import field_matmul


def _as_field_u32(x) -> jnp.ndarray:
    """Reduce to [0, q) exactly, then cast uint32.

    The mod runs in numpy int64 *before* the uint32 cast — casting first
    would wrap negatives/large values (uint32(-1) % q == 0, but
    -1 mod q == q - 1), silently diverging from the numpy oracle.
    """
    return jnp.asarray(np.asarray(x, np.int64) % FERMAT_Q, jnp.uint32)


def _fermat_pow(x, e: int):
    """Scalar x**e mod 65537 by square-and-multiply (e a python int)."""
    acc = jnp.uint32(1)
    base = x.astype(jnp.uint32)
    while e:
        if e & 1:
            acc = fermat_mul(acc, base)
        base = fermat_mul(base, base)
        e >>= 1
    return acc


def gf_gauss_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """Exact inverse of a (n, n) matrix over F_65537, pure uint32 jnp.

    Partial pivoting by first nonzero entry (same pivot order as the numpy
    oracle; the result is the unique inverse either way).  Raises
    ``ValueError`` on a singular input — for MDS codes every survivor
    submatrix is nonsingular, but e.g. the DFT transform's [I | A] codeword
    admits singular patterns (see `repro.recover.UndecodableError`).
    """
    a = _as_field_u32(a)
    n = a.shape[0]
    assert a.shape == (n, n), a.shape
    inv = jnp.eye(n, dtype=jnp.uint32)
    for col in range(n):
        nz = a[col:, col] != 0
        if not bool(jnp.any(nz)):
            raise ValueError(f"singular matrix over F_{FERMAT_Q} (column {col})")
        piv = col + int(jnp.argmax(nz))
        if piv != col:
            a = a.at[(col, piv), :].set(a[(piv, col), :])
            inv = inv.at[(col, piv), :].set(inv[(piv, col), :])
        s = _fermat_pow(a[col, col], FERMAT_Q - 2)
        a = a.at[col].set(fermat_mul(a[col], s))
        inv = inv.at[col].set(fermat_mul(inv[col], s))
        f = a[:, col].at[col].set(jnp.uint32(0))  # eliminate every other row
        a = fermat_sub(a, fermat_mul(f[:, None], a[col][None, :]))
        inv = fermat_sub(inv, fermat_mul(f[:, None], inv[col][None, :]))
    return inv


def gf_solve(a, b) -> jnp.ndarray:
    """Solve a @ x = b over F_65537: x = a^-1 b, exact.

    a: (n, n), b: (n, W) — the decode use is a = S^T (survivor submatrix,
    transposed) and b the survivor payloads, giving the original data x.
    """
    return field_matmul(gf_gauss_inverse(a), _as_field_u32(b))
