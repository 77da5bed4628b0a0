"""Mesh execution of the paper's schedules: devices as processors.

Each device along one mesh axis plays one of the paper's processors; each
communication round becomes one `jax.lax.ppermute` (the p-port model maps to
p concurrent ICI links; we emit p ppermutes per round which XLA can overlap).
All payloads are uint32 field elements (F_65537) and all per-device
coefficients are *sharded table inputs* — the schedule itself is
data-independent (Remark 1), so tables are precomputed host-side with the
exact same numpy code paths that the simulator validates.

Functions named `mesh_*` are shard_map *bodies*; `build_*_tables` are their
host-side companions.  `coded_*` wrappers in `repro.coding` wire them into
jitted train/checkpoint steps.

Slot layout (prepare phase): Bruck-style contiguous growth — slot l holds
x_{k - idx(l)} where idx maps digit-string l (base p+1, LSD first) to the
paper's offset sum_s b_s (p+1)^(T_p - s).  This keeps every round's message a
*static contiguous slice*, so lowered collective bytes match the paper's C2
accounting (up to the power-of-(p+1) padding of the shoot slots, documented
below).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ref import gf_matmul_ref
from .field import fermat_add, fermat_mul, fermat_sub
from .matrices import StructuredPoints, gauss_inverse, vandermonde
from .prepare_shoot import phase_split


# ---------------------------------------------------------------------------
# grouped ppermute helper
# ---------------------------------------------------------------------------

def _group_perm(N: int, stride: int, size: int, shift: int) -> list[tuple[int, int]]:
    """Cyclic shift by `shift` within groups of `size` members spaced
    `stride` apart (group of device k: same k % stride ... k // (stride*size)).

    Covers columns (stride=Z), rows (stride=1) and the full axis
    (stride=1, size=N).
    """
    perm = []
    for k in range(N):
        base = (k // (stride * size)) * (stride * size) + (k % stride)
        pos = (k % (stride * size)) // stride
        dst = base + ((pos + shift) % size) * stride
        perm.append((k, dst))
    return perm


@dataclass(frozen=True)
class TieredAxis:
    """A (hosts x dph) hierarchical mesh axis, used AS an `axis_name`.

    The mesh bodies below are written against one flat axis of K devices
    in host-major order (device k = host k // dph, position k % dph).
    Passing a `TieredAxis` instead of the flat axis string routes every
    collective through `_tiered_ppermute`, which lowers each round onto
    the tier it actually uses — a dev-axis leg (intra-host ICI), a
    host-axis leg (inter-host DCN), or a joint permute over both axes
    when a round genuinely mixes tiers.  The permutation applied is
    identical either way, so outputs are bitwise-equal to the flat mesh.
    """

    hosts: int
    dph: int
    host_axis: str = "host"
    dev_axis: str = "dev"

    @property
    def axes(self) -> tuple[str, str]:
        return (self.host_axis, self.dev_axis)


def _tiered_ppermute(x, axis: TieredAxis, perm):
    dph = axis.dph
    if all(s // dph == d // dph for s, d in perm):
        # host-local round: one dev-axis ppermute, IF every host sees the
        # same local pair set (otherwise hosts would need distinct perms)
        by_host: dict[int, set] = {}
        for s, d in perm:
            by_host.setdefault(s // dph, set()).add((s % dph, d % dph))
        legs = set(map(frozenset, by_host.values()))
        if len(by_host) == axis.hosts and len(legs) == 1:
            return jax.lax.ppermute(x, axis.dev_axis, sorted(legs.pop()))
    if all(s % dph == d % dph for s, d in perm):
        # cross-host round at fixed device position: one host-axis ppermute
        by_pos: dict[int, set] = {}
        for s, d in perm:
            by_pos.setdefault(s % dph, set()).add((s // dph, d // dph))
        legs = set(map(frozenset, by_pos.values()))
        if len(by_pos) == dph and len(legs) == 1:
            return jax.lax.ppermute(x, axis.host_axis, sorted(legs.pop()))
    # mixed round: joint permute over the flattened (host, dev) index space
    return jax.lax.ppermute(x, axis.axes, perm)


def _ppermute(x, axis_name, perm):
    if isinstance(axis_name, TieredAxis):
        return _tiered_ppermute(x, axis_name, perm)
    return jax.lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# universal prepare-and-shoot on a mesh axis (or sub-groups of it)
# ---------------------------------------------------------------------------

def _slot_index_map(p: int, T_p: int) -> list[int]:
    """idx(l): slot l (digits LSD-first base p+1) -> paper offset delta."""
    m = (p + 1) ** T_p
    idx = []
    for l in range(m):
        digs = []
        ll = l
        for _ in range(T_p):
            digs.append(ll % (p + 1))
            ll //= p + 1
        # digit b_s (s = 1..T_p) contributes b_s * (p+1)^(T_p - s)
        delta = sum(b * (p + 1) ** (T_p - s - 1) for s, b in enumerate(digs))
        idx.append(delta)
    return idx


@dataclass(frozen=True)
class UniversalTables:
    """Per-device constants for mesh prepare-and-shoot of one matrix set."""

    K: int          # group size (paper's K)
    p: int
    T_p: int
    T_s: int
    m: int
    n: int          # ceil(K/m)
    n_pad: int      # (p+1)^T_s slot padding
    coef: np.ndarray  # (N, n_pad, m) uint32 — shoot-packet init coefficients
    corr: np.ndarray  # (N, m) uint32 — eq. (4) overlap correction
    group_stride: int
    group_size: int


def build_universal_tables(
    field, mats: list[np.ndarray], N: int, p: int, group_stride: int = 1
) -> UniversalTables:
    """Tables for parallel prepare-and-shoot instances on groups of size K.

    `mats[g]` is the K x K matrix of group g; groups partition the N devices
    with members spaced `group_stride` apart (see _group_perm). Requires
    m <= K (true whenever K >= p+1 ... asserted).
    """
    K = mats[0].shape[0]
    n_groups = N // K
    assert len(mats) == n_groups
    L, T_p, T_s, m = phase_split(K, p)
    assert m <= K, f"tiny-group corner (m={m} > K={K}) unsupported on mesh"
    n = math.ceil(K / m)
    n_pad = (p + 1) ** T_s
    idx = _slot_index_map(p, T_p)
    coef = np.zeros((N, n_pad, m), np.uint32)
    corr = np.zeros((N, m), np.uint32)
    for dev in range(N):
        pos = (dev % (group_stride * K)) // group_stride  # local index k
        # group id: enumerate groups in the same order as mats
        g = (dev // (group_stride * K)) * group_stride + (dev % group_stride)
        C = np.asarray(mats[g], np.int64) % field.q
        k = pos
        for l_t in range(n):
            s = (k + l_t * m) % K
            for l in range(m):
                coef[dev, l_t, l] = C[(k - idx[l]) % K, s]
        # eq. (4): offsets delta in [0, m*n - K) duplicated once
        dup = m * n - K
        for l in range(m):
            if idx[l] < dup:
                corr[dev, l] = C[(k - idx[l]) % K, k]
    return UniversalTables(K, p, T_p, T_s, m, n, n_pad, coef, corr,
                           group_stride, K)


def mesh_universal_a2a(x, coef, corr, tables: UniversalTables, axis_name: str):
    """shard_map body: x (W,) uint32 per device -> encoded (W,) per device.

    coef (n_pad, m) / corr (m,) are this device's sharded table rows.
    """
    K, p, T_p, T_s, m = tables.K, tables.p, tables.T_p, tables.T_s, tables.m
    N = tables.coef.shape[0]
    W = x.shape[-1] if x.ndim else 1
    x = x.reshape(1, -1).astype(jnp.uint32)

    # ---- prepare: Bruck-contiguous growth --------------------------------
    buf = jnp.zeros((m, x.shape[-1]), jnp.uint32).at[0].set(x[0])
    size = 1
    for t in range(1, T_p + 1):
        stride = (p + 1) ** (T_p - t)
        pieces = [buf[:size]]
        for rho in range(1, p + 1):
            perm = _group_perm(N, tables.group_stride, K, rho * stride)
            pieces.append(_ppermute(buf[:size], axis_name, perm))
        size *= p + 1
        buf = jnp.concatenate(pieces + [buf[size:]], axis=0) if size < m else jnp.concatenate(pieces, axis=0)
        buf = buf[:m]

    # ---- local encode (the gf_matmul hot-spot) ----------------------------
    w = gf_matmul_ref(coef.astype(jnp.uint32), buf)  # (n_pad, W)

    # ---- shoot: (p+1)-nomial reduce of the w slots ------------------------
    for t in range(1, T_s + 1):
        blk = (p + 1) ** t
        sub = (p + 1) ** (t - 1)
        w_r = w.reshape(tables.n_pad // blk, blk, -1)
        acc = w_r[:, 0]
        for rho in range(1, p + 1):
            sel = w_r[:, rho * sub]  # slots this device must send
            perm = _group_perm(N, tables.group_stride, K, rho * sub * m)
            recv = _ppermute(sel, axis_name, perm)
            acc = fermat_add(acc, recv)
        # survivor slots are ltarget multiples of blk: repack contiguously
        keep = jnp.zeros((tables.n_pad // blk, blk, w.shape[-1]), jnp.uint32)
        keep = keep.at[:, 0].set(acc)
        # retain not-yet-consumed lower-digit slots for later rounds
        for r_keep in range(1, blk):
            if r_keep % sub == 0 and r_keep // sub in range(1, p + 1):
                continue  # consumed this round
            keep = keep.at[:, r_keep].set(w_r[:, r_keep])
        w = keep.reshape(tables.n_pad, -1)

    y = w[0]
    # ---- eq. (4) overlap correction ---------------------------------------
    dup_term = gf_matmul_ref(corr.astype(jnp.uint32)[None, :], buf)[0]
    return fermat_sub(y, dup_term)


# ---------------------------------------------------------------------------
# radix-2 DFT stages on a mesh axis (Sec. V-A, P = 2)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DFTTables:
    Z: int          # group size = 2^H
    H: int
    ca: np.ndarray  # (H, N) uint32: own coefficient per stage
    cb: np.ndarray  # (H, N) uint32: partner coefficient per stage
    group_stride: int


def build_dft_tables(
    field, N: int, Z: int, group_stride: int = 1, inverse: bool = False
) -> DFTTables:
    """Radix-2 permuted-DFT stage coefficients for groups of size Z."""
    from .dft_a2a import _stage_matrix

    H = int(round(math.log2(Z)))
    assert 2**H == Z and (field.q - 1) % Z == 0
    ca = np.zeros((H, N), np.uint32)
    cb = np.zeros((H, N), np.uint32)
    stages = range(H)
    for h in stages:
        pos = 2 ** (H - h - 1)
        for dev in range(N):
            j = (dev % (group_stride * Z)) // group_stride  # index in group
            member0 = j & ~pos  # group member with bit cleared
            mat = _stage_matrix(field, Z, 2, H, h, member0)
            if inverse:
                mat = gauss_inverse(field, mat)
            d = (j >> int(math.log2(pos))) & 1
            ca[h, dev] = mat[d, d]
            cb[h, dev] = mat[1 - d, d]
    if inverse:
        ca = ca[::-1].copy()
        cb = cb[::-1].copy()
    return DFTTables(Z, H, ca, cb, group_stride)


def mesh_dft(x, ca, cb, tables: DFTTables, axis_name: str, inverse: bool = False):
    """shard_map body: per-device (W,) -> (W,). ca/cb are (H,) table rows.

    Stage order is baked into the tables (build with inverse=True for the
    inverse transform). Each stage: one pairwise exchange + butterfly.
    """
    N = tables.ca.shape[1]
    Z, H = tables.Z, tables.H
    v = x.astype(jnp.uint32)
    for h in range(H):
        pos = 2 ** (H - h - 1) if not inverse else 2 ** h
        perm = []
        for k in range(N):
            j = (k % (tables.group_stride * Z)) // tables.group_stride
            jp = j ^ pos
            dst = k + (jp - j) * tables.group_stride
            perm.append((k, dst))
        recv = _ppermute(v, axis_name, perm)
        v = fermat_add(fermat_mul(ca[h], v), fermat_mul(cb[h], recv))
    return v


# ---------------------------------------------------------------------------
# draw-and-loose on a mesh axis (Sec. V-B)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DrawLooseTables:
    sp: StructuredPoints
    univ: UniversalTables | None  # draw phase (columns, size M), None if M=1
    dft: DFTTables | None         # loose phase (rows, size Z), None if Z=1
    scale: np.ndarray             # (N,) uint32 alpha_i^j (or inverse)
    inverse: bool


def build_draw_loose_tables(
    field, sp: StructuredPoints, N_devices: int, p: int, inverse: bool = False
) -> DrawLooseTables:
    M, Z = sp.M, sp.Z
    K = M * Z
    n_rep = N_devices // K  # multiple independent grids along the axis
    univ = None
    if M > 1:
        vm = _v_m_matrix(field, sp)
        if inverse:
            vm = gauss_inverse(field, vm)
        univ = build_universal_tables(field, [vm] * (Z * n_rep), N_devices, p,
                                      group_stride=Z)
    dft = None
    if Z > 1:
        dft = build_dft_tables(field, N_devices, Z, group_stride=1,
                               inverse=inverse)
    scale = np.zeros(N_devices, np.uint32)
    for dev in range(N_devices):
        k = dev % K
        i, j = k // Z, k % Z
        s = pow(sp.alpha(i), j, field.q)
        if inverse:
            s = pow(s, field.q - 2, field.q)
        scale[dev] = s
    return DrawLooseTables(sp, univ, dft, scale, inverse)


def _v_m_matrix(field, sp: StructuredPoints) -> np.ndarray:
    alphas_z = np.array([pow(sp.alpha(i), sp.Z, field.q) for i in range(sp.M)],
                        np.int64)
    return vandermonde(field, alphas_z)


def mesh_draw_loose(x, t: DrawLooseTables, table_rows: dict, axis_name: str):
    """shard_map body. table_rows carries this device's sharded rows:
    {'coef','corr','ca','cb','scale'} as applicable."""
    v = x.astype(jnp.uint32)
    if not t.inverse:
        if t.univ is not None:
            v = mesh_universal_a2a(v, table_rows["coef"], table_rows["corr"],
                                   t.univ, axis_name)
        v = fermat_mul(table_rows["scale"], v)
        if t.dft is not None:
            v = mesh_dft(v, table_rows["ca"], table_rows["cb"], t.dft,
                         axis_name, inverse=False)
    else:
        if t.dft is not None:
            v = mesh_dft(v, table_rows["ca"], table_rows["cb"], t.dft,
                         axis_name, inverse=True)
        v = fermat_mul(table_rows["scale"], v)
        if t.univ is not None:
            v = mesh_universal_a2a(v, table_rows["coef"], table_rows["corr"],
                                   t.univ, axis_name)
    return v


# ---------------------------------------------------------------------------
# generic schedule-IR lowering: compile ANY `core.schedule.RoundIR` (in
# particular a `tier_commute`-rewritten one, whose rounds no longer match
# the hand-built table paths above) into per-device slot tables + ppermute
# legs.  The hand-specialized mesh_* bodies above stay the fast path for
# canonical schedules; this is the general one.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class IRLeg:
    """One partial-permutation step of a round: every device sends/receives
    at most once; messages are `width`-lane packet bundles (short bundles
    pad with trash-slot lanes that receivers scatter back to trash)."""

    perm: tuple                 # ((src_dev, dst_dev), ...)
    gather: np.ndarray          # (n_dev, width) int32 slots to read
    scatter: np.ndarray         # (n_dev, width) int32 slots to write


@dataclass(frozen=True)
class IRCombineLayer:
    """One dependency layer of a round's combines (terms only reference
    slots written by earlier rounds/legs/layers), as padded per-device
    tables: out <- sum_t coeff[., t] * buf[term[., t]]."""

    out_idx: np.ndarray         # (n_dev, n_comb) int32 (pad -> trash)
    coeff: np.ndarray           # (n_dev, n_comb, n_term) uint32 (pad -> 0)
    term: np.ndarray            # (n_dev, n_comb, n_term) int32


@dataclass(frozen=True)
class IRMeshProgram:
    """A `RoundIR` compiled for devices-as-processors execution: per-device
    packet slots (slot 0 is the trash slot all padding routes through),
    and per round a list of ppermute legs plus combine layers."""

    n_dev: int
    n_slots: int
    init_slot: np.ndarray       # (n_dev,) int32 slot of the local input row
    out_slot: np.ndarray        # (n_dev,) int32 slot of the local output row
    rounds: tuple               # ((legs, layers), ...) per IR round

    def device_arrays(self) -> dict[str, np.ndarray]:
        """All (n_dev, ...) tables keyed for sharded shard_map args."""
        arrs = {"init": self.init_slot[:, None], "out": self.out_slot[:, None]}
        for r, (legs, layers) in enumerate(self.rounds):
            for i, leg in enumerate(legs):
                arrs[f"g{r}_{i}"] = leg.gather
                arrs[f"s{r}_{i}"] = leg.scatter
            for i, lay in enumerate(layers):
                arrs[f"o{r}_{i}"] = lay.out_idx
                arrs[f"c{r}_{i}"] = lay.coeff
                arrs[f"t{r}_{i}"] = lay.term
        return arrs


def build_ir_mesh_program(ir, dev_of: list[int]) -> IRMeshProgram:
    """Compile `ir` (a `core.schedule.RoundIR`) against the processor ->
    device overlay `dev_of` (encode: source k -> device k, sink K+r ->
    device r, the Sec. III-A grid).  Sends between processors that share a
    device are free (one per-device buffer); cross-device sends decompose
    into partial-permutation legs with at most one send and one receive
    per device; combines split into intra-round dependency layers."""
    n_dev = max(dev_of) + 1
    TRASH = 0
    next_slot = [1] * n_dev                       # slot 0 = trash
    slot_of: dict[tuple[int, int], int] = {}      # (dev, packet) -> slot

    def alloc(dev: int, pid: int) -> int:
        key = (dev, pid)
        if key not in slot_of:
            slot_of[key] = next_slot[dev]
            next_slot[dev] += 1
        return slot_of[key]

    init_slot = np.zeros(n_dev, np.int32)
    for proc, pid in ir.inputs:
        init_slot[dev_of[proc]] = alloc(dev_of[proc], pid)

    rounds = []
    for rnd in ir.rounds:
        # ---- sends -> partial-permutation legs --------------------------
        cross = [s for s in rnd.sends
                 if dev_of[s.src] != dev_of[s.dst]]
        leg_sends: list[list] = []
        for s in cross:
            placed = False
            for leg in leg_sends:
                if all(dev_of[s.src] != dev_of[o.src]
                       and dev_of[s.dst] != dev_of[o.dst] for o in leg):
                    leg.append(s)
                    placed = True
                    break
            if not placed:
                leg_sends.append([s])
        legs = []
        for sends in leg_sends:
            width = max(len(s.packets) for s in sends)
            gather = np.full((n_dev, width), TRASH, np.int32)
            scatter = np.full((n_dev, width), TRASH, np.int32)
            perm = []
            for s in sends:
                sd, dd = dev_of[s.src], dev_of[s.dst]
                perm.append((sd, dd))
                for i, pid in enumerate(s.packets):
                    gather[sd, i] = slot_of[(sd, pid)]
                    scatter[dd, i] = alloc(dd, pid)
            legs.append(IRLeg(tuple(sorted(perm)), gather, scatter))
        for s in rnd.sends:                       # same-device: already held
            if dev_of[s.src] == dev_of[s.dst]:
                for pid in s.packets:
                    slot_of[(dev_of[s.dst], pid)] = slot_of[
                        (dev_of[s.src], pid)]

        # ---- combines -> dependency layers ------------------------------
        layer_of: dict[int, int] = {}             # out pid -> layer index
        grouped: list[list] = []
        for c in rnd.combines:
            lvl = 0
            for _, pid in c.terms:
                if pid in layer_of:
                    lvl = max(lvl, layer_of[pid] + 1)
            layer_of[c.out] = lvl
            while len(grouped) <= lvl:
                grouped.append([])
            grouped[lvl].append(c)
        layers = []
        for combs in grouped:
            per_dev: dict[int, list] = {}
            for c in combs:
                per_dev.setdefault(dev_of[c.proc], []).append(c)
            n_comb = max(len(v) for v in per_dev.values())
            n_term = max((len(c.terms) for c in combs), default=0) or 1
            out_idx = np.full((n_dev, n_comb), TRASH, np.int32)
            coeff = np.zeros((n_dev, n_comb, n_term), np.uint32)
            term = np.full((n_dev, n_comb, n_term), TRASH, np.int32)
            for dev, cs in per_dev.items():
                for i, c in enumerate(cs):
                    out_idx[dev, i] = alloc(dev, c.out)
                    for t, (cref, pid) in enumerate(c.terms):
                        coeff[dev, i, t] = ir.coeffs[cref] % ir.q
                        term[dev, i, t] = slot_of[(dev, pid)]
            layers.append(IRCombineLayer(out_idx, coeff, term))
        rounds.append((tuple(legs), tuple(layers)))

    out_slot = np.zeros(n_dev, np.int32)
    for proc, pid in ir.outputs:
        out_slot[dev_of[proc]] = slot_of[(dev_of[proc], pid)]
    return IRMeshProgram(n_dev, max(next_slot), init_slot, out_slot,
                         tuple(rounds))


def mesh_ir_encode(x, rows: dict, prog: IRMeshProgram, axis_name):
    """shard_map body: per-device (W,) uint32 -> (W,) uint32 running the
    compiled IR program.  `rows` carries this device's rows of
    `prog.device_arrays()` (leading n_dev axis already sharded away)."""
    W = x.shape[-1]
    buf = jnp.zeros((prog.n_slots, W), jnp.uint32)
    buf = buf.at[rows["init"][0]].set(x.astype(jnp.uint32))
    for r, (legs, layers) in enumerate(prog.rounds):
        for i, leg in enumerate(legs):
            sel = buf[rows[f"g{r}_{i}"]]              # (width, W)
            recv = _ppermute(sel, axis_name, list(leg.perm))
            buf = buf.at[rows[f"s{r}_{i}"]].set(recv)
            buf = buf.at[0].set(jnp.zeros((W,), jnp.uint32))  # re-arm trash
        for i, _lay in enumerate(layers):
            coeff = rows[f"c{r}_{i}"]                 # (n_comb, n_term)
            vals = buf[rows[f"t{r}_{i}"]]             # (n_comb, n_term, W)
            acc = jnp.zeros(vals.shape[:1] + vals.shape[2:], jnp.uint32)
            for t in range(coeff.shape[1]):
                acc = fermat_add(acc, fermat_mul(coeff[:, t, None],
                                                 vals[:, t]))
            buf = buf.at[rows[f"o{r}_{i}"]].set(acc)
            buf = buf.at[0].set(jnp.zeros((W,), jnp.uint32))
    return buf[rows["out"][0]]
