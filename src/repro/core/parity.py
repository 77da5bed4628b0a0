"""Mesh parity encode: Sec. III-A framework across a device axis.

N devices each hold one state shard x_k (k = device index); R parity symbols
of the systematic [N+R, N] GRS code must land on devices 0..R-1 (which also
keep their own data shards — rotating-parity style double duty; any f <= R/2
device failures erase at most 2f codeword symbols and remain decodable;
with parity *offloaded to a checkpoint store* any R erasures are decodable).

Phase 1 — column-wise all-to-all encode: devices form an R x M grid
(column m = devices [mR, (m+1)R), M = N/R); each column computes its R x R
block A_m of A.  Implemented either with the universal prepare-and-shoot
tables ('universal') or the Thm. 7 Cauchy-like pipeline ('rs':
scale phi^-1 -> inverse draw-and-loose on V_{alpha,m} -> forward
draw-and-loose on V_beta -> scale psi).

Phase 2 — row-wise (p+1)-nomial reduce onto the column-0 device of each row.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .cauchy import StructuredGRS
from .field import FERMAT_Q, Field, fermat_add, fermat_mul
from .matrices import StructuredPoints, gauss_inverse
from .shardmap_exec import (
    DFTTables,
    UniversalTables,
    _group_perm,
    _ppermute,
    _v_m_matrix,
    build_dft_tables,
    build_universal_tables,
    mesh_dft,
    mesh_universal_a2a,
)


@dataclass(frozen=True)
class ParityTables:
    """Everything the jitted parity-encode step needs, precomputed host-side.

    `sgrs` is None when the tables were built from an arbitrary (non-GRS)
    generator block via `build_encode_tables(..., method="universal")`.
    """

    N: int
    R: int
    M: int
    p: int
    method: str
    sgrs: StructuredGRS | None
    # universal path
    univ: UniversalTables | None
    # rs path: inverse DL on alpha blocks + forward DL on beta
    dl_scale_pre: np.ndarray | None    # (N,) phi^-1
    dl_inv_univ: UniversalTables | None
    dl_inv_dft: DFTTables | None
    dl_inv_scale: np.ndarray | None
    dl_fwd_univ: UniversalTables | None
    dl_fwd_dft: DFTTables | None
    dl_fwd_scale: np.ndarray | None
    dl_scale_post: np.ndarray | None   # (N,) psi
    reduce_mask: np.ndarray            # (T_red, p, N) uint32

    def device_arrays(self) -> dict[str, np.ndarray]:
        """Arrays to pass as sharded (axis-partitioned) step inputs."""
        out = {"reduce_mask": np.moveaxis(self.reduce_mask, -1, 0)}  # (N, T, p)
        if self.method == "universal":
            out["u_coef"] = self.univ.coef
            out["u_corr"] = self.univ.corr
        else:
            out["pre"] = self.dl_scale_pre
            out["post"] = self.dl_scale_post
            out["i_scale"] = self.dl_inv_scale
            out["f_scale"] = self.dl_fwd_scale
            if self.dl_inv_univ is not None:
                out["i_coef"] = self.dl_inv_univ.coef
                out["i_corr"] = self.dl_inv_univ.corr
            if self.dl_inv_dft is not None:
                out["i_ca"] = self.dl_inv_dft.ca.T  # (N, H)
                out["i_cb"] = self.dl_inv_dft.cb.T
            if self.dl_fwd_univ is not None:
                out["f_coef"] = self.dl_fwd_univ.coef
                out["f_corr"] = self.dl_fwd_univ.corr
            if self.dl_fwd_dft is not None:
                out["f_ca"] = self.dl_fwd_dft.ca.T
                out["f_cb"] = self.dl_fwd_dft.cb.T
        return out


def _build_grid_draw_loose(
    field: Field,
    sps: list[StructuredPoints],
    p: int,
    inverse: bool,
) -> tuple[UniversalTables | None, DFTTables | None, np.ndarray]:
    """Draw-and-loose tables for several grids along the axis, one
    StructuredPoints per grid (they must share M, Z, P)."""
    sp0 = sps[0]
    M, Z = sp0.M, sp0.Z
    K = M * Z
    N = len(sps) * K
    univ = None
    if M > 1:
        mats = []
        # group id for (grid g, column j) = g*Z + j
        for g in range(len(sps)):
            vm = _v_m_matrix(field, sps[g])
            if inverse:
                vm = gauss_inverse(field, vm)
            mats.extend([vm] * Z)
        univ = build_universal_tables(field, mats, N, p, group_stride=Z)
    dft = None
    if Z > 1:
        dft = build_dft_tables(field, N, Z, group_stride=1, inverse=inverse)
    scale = np.zeros(N, np.uint32)
    for dev in range(N):
        g, k = dev // K, dev % K
        i, j = k // Z, k % Z
        s = pow(sps[g].alpha(i), j, field.q)
        if inverse:
            s = pow(s, field.q - 2, field.q)
        scale[dev] = s
    return univ, dft, scale


def build_parity_tables(
    field: Field, N: int, R: int, p: int = 1, method: str = "rs"
) -> ParityTables:
    """Systematic [N+R, N] GRS parity across an N-device axis, R | N."""
    sgrs = StructuredGRS.build(field, N, R, P=2)
    return build_encode_tables(field, sgrs.grs.A_direct(), p=p, method=method,
                               sgrs=sgrs)


def build_encode_tables(
    field: Field,
    A: np.ndarray,
    p: int = 1,
    method: str = "universal",
    sgrs: StructuredGRS | None = None,
) -> ParityTables:
    """Mesh-encode tables for an arbitrary (K, R) generator block A, R | K.

    The K devices of the axis hold the sources; sink r overlays device r
    (Sec. III-A with borrowed sinks).  method="universal" works for ANY A;
    method="rs" additionally needs the StructuredGRS code A came from
    (Thm. 7 factorization).  This is the single table builder behind both
    `build_parity_tables` and the unified `repro.api` mesh backend.
    """
    A = field.arr(A)
    N, R = A.shape
    assert N % R == 0, "R must divide the axis size"
    M = N // R

    univ = None
    pre = post = i_scale = f_scale = None
    i_univ = i_dft = f_univ = f_dft = None
    if method == "universal":
        mats = [A[m * R : (m + 1) * R, :] for m in range(M)]
        univ = build_universal_tables(field, mats, N, p, group_stride=1)
    elif method == "rs":
        assert sgrs is not None and sgrs.K == N and sgrs.R == R, \
            "method='rs' needs the StructuredGRS code A was built from"
        pre = np.zeros(N, np.uint32)
        post = np.zeros(N, np.uint32)
        for m in range(M):
            phi, psi = sgrs.scaling_factors(m)
            for s in range(R):
                pre[m * R + s] = pow(int(phi[s]), field.q - 2, field.q)
                post[m * R + s] = int(psi[s])
        i_univ, i_dft, i_scale = _build_grid_draw_loose(
            field, list(sgrs.alpha_blocks), p, inverse=True
        )
        f_univ, f_dft, f_scale = _build_grid_draw_loose(
            field, [sgrs.beta_blocks[0]] * M, p, inverse=False
        )
    else:
        raise ValueError(method)

    # phase-2 reduce masks: rows = {r, r+R, ...}, reduce onto position 0
    T_red = max(1, math.ceil(math.log(M, p + 1))) if M > 1 else 0
    mask = np.zeros((T_red, p, N), np.uint32)
    for t in range(1, T_red + 1):
        blk = (p + 1) ** t
        sub = (p + 1) ** (t - 1)
        for dev in range(N):
            j = dev // R  # position within the row group (stride R)
            for rho in range(1, p + 1):
                if j % blk == 0 and (j + rho * sub) < M:
                    mask[t - 1, rho - 1, dev] = 1
    return ParityTables(
        N, R, M, p, method, sgrs, univ,
        pre, i_univ, i_dft, i_scale, f_univ, f_dft, f_scale, post, mask,
    )


def mesh_parity_encode(x, rows: dict, t: ParityTables, axis_name: str):
    """shard_map body: x (W,) uint32 -> (W,) where devices 0..R-1 end up
    holding parity symbols 0..R-1 (other devices return partial garbage that
    callers mask out)."""
    v = x.astype(jnp.uint32)

    # ---- phase 1: column-wise A2A on A_m ---------------------------------
    # each transform and each reduce round is a named scope, so device ops
    # in a profile name the round they belong to
    if t.method == "universal":
        with jax.named_scope("parity.universal"):
            v = mesh_universal_a2a(v, rows["u_coef"], rows["u_corr"], t.univ, axis_name)
    else:
        v = fermat_mul(rows["pre"], v)
        # inverse draw-and-loose on V_{alpha,m}
        if t.dl_inv_dft is not None:
            with jax.named_scope("parity.inv_dft"):
                v = mesh_dft(v, rows["i_ca"], rows["i_cb"], t.dl_inv_dft, axis_name, inverse=True)
        v = fermat_mul(rows["i_scale"], v)
        if t.dl_inv_univ is not None:
            with jax.named_scope("parity.inv_universal"):
                v = mesh_universal_a2a(v, rows["i_coef"], rows["i_corr"], t.dl_inv_univ, axis_name)
        # forward draw-and-loose on V_beta
        if t.dl_fwd_univ is not None:
            with jax.named_scope("parity.fwd_universal"):
                v = mesh_universal_a2a(v, rows["f_coef"], rows["f_corr"], t.dl_fwd_univ, axis_name)
        v = fermat_mul(rows["f_scale"], v)
        if t.dl_fwd_dft is not None:
            with jax.named_scope("parity.fwd_dft"):
                v = mesh_dft(v, rows["f_ca"], rows["f_cb"], t.dl_fwd_dft, axis_name, inverse=False)
        v = fermat_mul(rows["post"], v)

    # ---- phase 2: row-wise reduce onto column 0 ---------------------------
    N, R, M, p = t.N, t.R, t.M, t.p
    T_red = t.reduce_mask.shape[0]
    for tt in range(1, T_red + 1):
        sub = (p + 1) ** (tt - 1)
        for rho in range(1, p + 1):
            with jax.named_scope(f"parity.reduce_{tt}_{rho}"):
                perm = _group_perm(N, R, M, -rho * sub)
                recv = _ppermute(v, axis_name, perm)
                m_row = rows["reduce_mask"][tt - 1, rho - 1]
                v = fermat_add(v, fermat_mul(m_row, recv))
    return v


def reconstruct(field: Field, sgrs: StructuredGRS, kept: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Any-K-of-N decode: kept (K,) codeword indices, vals (K, W) symbols.

    For the Fermat field the solve runs on the `kernels.gf_solve` path
    (uint32 Gauss-Jordan inverse + Pallas/jnp matmul application); other
    fields keep the exact numpy host path.  Both are exact mod q, so the
    result is bitwise identical either way.
    """
    K = sgrs.K
    A = sgrs.grs.A_direct()
    G = np.concatenate([np.eye(K, dtype=np.int64), A], axis=1)
    sub = G[:, kept]  # K x K
    if field.q == FERMAT_Q:
        from ..kernels.gf_solve import gf_solve

        return np.asarray(gf_solve(sub.T % FERMAT_Q, field.arr(vals)), np.int64)
    return field.matmul(gauss_inverse(field, sub.T), field.arr(vals))
