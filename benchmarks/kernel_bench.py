"""Pallas gf_matmul / NTT kernel micro-bench.  On the CPU the kernels run
interpreted, so the numbers there are correctness-path timings, not a
measure of the chip (`interp_mode=1` marks them)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.field import FERMAT_Q
from repro.kernels.ops import gf_matmul, ntt
from repro.kernels.ref import gf_matmul_ref


def _time(fn, reps=3):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn().block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def rows() -> list[str]:
    rng = np.random.default_rng(3)
    interp = int(jax.default_backend() == "cpu")
    out = []
    for (M, K, N) in [(128, 128, 128), (256, 256, 128)]:
        a = jnp.asarray(rng.integers(0, FERMAT_Q, (M, K)).astype(np.uint32))
        b = jnp.asarray(rng.integers(0, FERMAT_Q, (K, N)).astype(np.uint32))
        us_k = _time(lambda: gf_matmul(a, b))
        us_r = _time(lambda: gf_matmul_ref(a, b))
        gf_ops = 2 * M * K * N
        out.append(f"kernel/gf_matmul_{M}x{K}x{N},{us_k:.0f},"
                   f"gf_ops={gf_ops};interp_mode={interp};ref_us={us_r:.0f}")

    for K in (256, 1024):
        W = 128
        x = jnp.asarray(rng.integers(0, FERMAT_Q, (K, W)).astype(np.uint32))
        us_n = _time(lambda: ntt(x))
        # O(K log K * W) vs the O(K^2 * W) matmul encode
        import math
        ops_ntt = K * int(math.log2(K)) * W
        ops_mm = K * K * W
        out.append(f"kernel/ntt_{K}x{W},{us_n:.0f},"
                   f"field_ops={ops_ntt};matmul_equiv_ops={ops_mm};"
                   f"algorithmic_gain={ops_mm / ops_ntt:.1f}x")
    return out
