"""The public entry points of the field kernels, and the one place that
decides how a Pallas kernel runs.

Every Pallas kernel is compiled for the platform its program is lowered
for, and runs in interpret mode only where that platform is the CPU.  The
choice is `lax.platform_dependent`, resolved when the program is lowered,
so a program compiled for a TPU always carries the compiled kernel
(`tpu_custom_call`) and can never fall back to the interpreter in silence.
No other module passes `interpret`.

`field_matmul` (and `encode_blocks` / `decode_blocks` over it) picks the
Pallas kernel for large operands and the pure-jnp reference for small ones
(kernel launch overhead dominates below ~128x128), keeping one call site
for the encode, decode and solve hot-spot.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .gf_matmul import gf_matmul_pallas
from .ntt import ntt_pallas, ntt_xla
from .ref import gf_matmul_ref

_PALLAS_MIN_DIM = 128


def _by_platform(kernel, *args, cpu=None, **static):
    """`kernel(*args, interpret=..., **static)`, compiled unless the
    program is lowered for the CPU.  There it runs `cpu(*args, **static)`
    instead when given, else the kernel in interpret mode."""
    cpu_fn = (partial(cpu, **static) if cpu is not None
              else partial(kernel, interpret=True, **static))
    return lax.platform_dependent(
        *args, cpu=cpu_fn, default=partial(kernel, interpret=False, **static))


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "bk_inner"))
def gf_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
              bn: int = 128, bk: int = 128, bk_inner: int = 8) -> jnp.ndarray:
    """(a @ b) mod 65537 on the Pallas kernel (`gf_matmul.py`)."""
    return _by_platform(gf_matmul_pallas, a, b, bm=bm, bn=bn, bk=bk,
                        bk_inner=bk_inner)


@partial(jax.jit, static_argnames=("inverse",))
def ntt(x: jnp.ndarray, *, inverse: bool = False) -> jnp.ndarray:
    """Batched NTT along axis 0 on the Pallas kernel (`ntt.py`)."""
    return _by_platform(ntt_pallas, x, inverse=inverse)


def ntt_auto(x: jnp.ndarray, *, inverse: bool = False) -> jnp.ndarray:
    """The NTT the encode path runs: the compiled Pallas kernel, or on the
    CPU the bitwise-equal fused-XLA transform (the interpreter would only
    be slower there).  Traceable under jit."""
    return _by_platform(ntt_pallas, x, cpu=ntt_xla, inverse=inverse)


@jax.jit
@jax.named_scope("field_matmul")
def field_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a @ b) mod 65537: the Pallas kernel when every dimension is at
    least `_PALLAS_MIN_DIM`, the jnp reference otherwise."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    if min(a.shape + b.shape) >= _PALLAS_MIN_DIM:
        return gf_matmul(a, b)
    return gf_matmul_ref(a, b)


@jax.named_scope("encode_blocks")
def encode_blocks(x: jnp.ndarray, coeffs: jnp.ndarray) -> jnp.ndarray:
    """y = x^T-style field encode: (S, W) data against (S, T) coefficients.

    Returns (T, W) = coeffs.T @ x over F_65537.
    """
    return field_matmul(coeffs.T, x)


@jax.named_scope("decode_blocks")
def decode_blocks(v: jnp.ndarray, dmat: jnp.ndarray) -> jnp.ndarray:
    """Apply a precomputed decode matrix to survivor payloads.

    v: (K, W) survivor symbols, dmat: (K, E) — returns (E, W) = dmat.T @ v
    over F_65537.  The exact dual of `encode_blocks`: decode of an erasure
    pattern is an encode with the repair matrix D = S^-1 G[:, E] (S the
    survivor submatrix), so the same Pallas/jnp kernel serves both hot
    paths; `kernels.gf_solve` builds D's ingredients.
    """
    return encode_blocks(v, dmat)
