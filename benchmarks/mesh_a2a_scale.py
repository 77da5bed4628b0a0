"""Paper-technique perf cell: all-to-all encode ON THE MESH at N=64 —
universal (prepare-and-shoot) vs specific (radix-2 DFT) scheduling for the
same DFT coding matrix, measured as lowered ppermute traffic.

Table I at K=64, p=1 predicts C2: universal 14 vs DFT-specific 6 (2.33x).
Runs in its own process (64 forced host devices).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=64 "
    + os.environ.get("XLA_FLAGS", ""))

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.field import FERMAT
from repro.core.matrices import permuted_dft_matrix
from repro.core.shardmap_exec import (
    build_dft_tables, build_universal_tables, mesh_dft, mesh_universal_a2a)
from repro.launch.hlo_cost import analyze


def main():
    f = FERMAT
    N, W = 64, 8192
    mesh = Mesh(np.array(jax.devices()), ("d",))
    x = jnp.asarray(f.rand((N, W), np.random.default_rng(0)).astype(np.uint32))
    D = permuted_dft_matrix(f, N, 2)

    # --- universal scheduling on the DFT matrix ---------------------------
    tu = build_universal_tables(f, [D], N, p=1)

    @partial(shard_map, mesh=mesh, in_specs=(P("d"),) * 3, out_specs=P("d"))
    def step_u(xb, coef, corr):
        return mesh_universal_a2a(xb[0], coef[0], corr[0], tu, "d")[None]

    # --- specific (radix-2 DFT) scheduling --------------------------------
    td = build_dft_tables(f, N, 64)

    @partial(shard_map, mesh=mesh, in_specs=(P("d"),) * 3, out_specs=P("d"))
    def step_d(xb, ca, cb):
        return mesh_dft(xb[0], ca[0], cb[0], td, "d")[None]

    exp = f.matmul(D.T, np.asarray(x, np.int64))
    bytes_of, all_ok = {}, 1
    for name, fn, args in [
        ("universal", step_u, (jnp.asarray(tu.coef), jnp.asarray(tu.corr))),
        ("dft_specific", step_d, (jnp.asarray(td.ca.T), jnp.asarray(td.cb.T))),
    ]:
        t0 = time.perf_counter()
        compiled = jax.jit(lambda xg: fn(xg, *args)).lower(x).compile()
        census = analyze(compiled.as_text())
        us = (time.perf_counter() - t0) * 1e6
        ok = np.array_equal(np.asarray(fn(x, *args)), exp)
        bytes_of[name] = census["collective_bytes"]
        all_ok &= int(ok)
        print(f"mesh_a2a/{name}_N64_W{W},{us:.0f},"
              f"ppermute_bytes={census['collective_bytes']:.0f};correct={int(ok)}")
    # stable (HLO-census, no wall clock) rows for the gated mesh/* section
    print(f"mesh/a2a_bytes_gain_W{W},"
          f"{bytes_of['universal'] / bytes_of['dft_specific']:.3f},"
          f"universal_bytes={bytes_of['universal']:.0f};"
          f"dft_bytes={bytes_of['dft_specific']:.0f};backend=mesh")
    print(f"mesh/a2a_ok_W{W},{all_ok},both schedules bitwise vs the dense "
          f"matmul;backend=mesh")


if __name__ == "__main__":
    main()
