"""The three built-in executors behind `EncodePlan.run`, registered on the
`api.registry` Backend protocol.

    simulator — the round-based `RoundNetwork` lockstep engine (exact numpy
                oracle; measured C1/C2 recorded thread-locally on
                `plan.last_stats` / `plan.sim_net`)
    mesh      — devices-as-processors `shard_map`/`ppermute` execution (one
                device per source, sinks overlaid on devices 0..R-1)
    local     — single-device `kernels.ops.encode_blocks` (Pallas/jnp field
                matmul; no communication schedule at all)

All three return the same sink values bitwise: sink r holds x^T A[:, r] over
F_q.  Inputs/outputs are normalized to numpy int64 (K, W) -> (R, W).  The
decode halves of the same three backends live in `recover.backends`; the
`Backend` objects below bind both, so one registry serves both planners.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from ..core import schedule
from ..core.field import FERMAT_Q
from ..core.simulator import RoundNetwork
from ..obs.trace import (count_gather_fused, count_kernel, count_reduce,
                         stage)
from .registry import Backend, BackendCapabilityError, register_backend


def run_simulator(plan, x: np.ndarray) -> tuple[np.ndarray, RoundNetwork]:
    """Execute the plan on the paper's p-port round network; returns
    (sink values, the network with its measured C1/C2).

    All four kinds run through one path: the plan's schedule IR
    (`plan.schedule_ir()` — the canonical builder output, or the
    `tier_commute`-rewritten program for `commute=True` plans) executed
    generically by `core.schedule.execute`, which emits the exact same
    rounds the retired per-kind generator dispatch produced."""
    spec, f = plan.spec, plan.field
    x = f.arr(x)
    pl = getattr(plan, "placement", None)
    ir = plan.schedule_ir()
    net = RoundNetwork(ir.n_procs, spec.p, placement=pl)
    y = schedule.execute(ir, f, x, net)
    return np.asarray(y, np.int64), net


def local_encode_callable(plan):
    """The plan's single jitted local-encode executable (K, w) uint32 ->
    (R, w) uint32, cached on the plan for its lifetime.

    The planner auto-selects the O(K log K) NTT fast path
    (`kernels.ntt_encode`) for dft and structured rs/lagrange specs when
    their point sets are radix-2 single cosets (in particular, K a power
    of two); otherwise this is the dense `encode_blocks` field matmul.
    Both are exact mod-q arithmetic, so the choice is bitwise-invisible.
    jit's shape cache makes one executable per chunk width.
    """
    if plan._local_fn is None:
        import jax.numpy as jnp

        from .stream import maybe_donate_jit

        params = plan.tables.ntt_params()
        if params is not None:
            from ..kernels.ntt_encode import ntt_encode

            fn = maybe_donate_jit(lambda x: ntt_encode(x, params),
                                  donate=plan.spec.K == plan.spec.R)
        else:
            from ..kernels.ops import encode_blocks

            A = jnp.asarray(plan.A, jnp.uint32)
            fn = maybe_donate_jit(lambda x: encode_blocks(x, A),
                                  donate=False)
        plan._local_fn = fn
    return plan._local_fn


def run_local(plan, x: np.ndarray) -> np.ndarray:
    """Single-device encode on the kernel path (no network): the cached
    jitted NTT fast path or dense field matmul, per the planner.  Each
    host-edge stage is an `obs.trace.stage`."""
    import jax.numpy as jnp

    edge = {"op": "encode", "backend": "local"}
    with stage("prep", **edge):
        x32 = to_field_u32(x, plan.field.q, edge)
    with stage("h2d", **edge) as s:
        xd = jnp.asarray(x32)
        s.moved(xd.nbytes)
        del x32  # released once the upload returns (see `_finish`)
    return _finish(counted_kernel(local_encode_callable(plan),
                                  local_kernel(plan), edge), xd, edge)


def local_kernel(plan) -> str:
    """The `local_kernel_total` label of an encode plan's local kernel:
    `ntt` (the NTT fast path) or `matmul` (`encode_blocks`)."""
    return "ntt" if plan.local_impl == "ntt" else "matmul"


def counted_kernel(fn, kernel: str, edge: dict):
    """`fn`, each call of which adds one to `local_kernel_total{kernel,
    op, backend}` at `edge`'s labels: one per local device call (encode,
    decode, read and each stream chunk)."""
    def call(x):
        count_kernel(kernel, **edge)
        return fn(x)
    return call


# Symbols per tile of `to_field_u32`'s one pass: 32 Ki int64 are 256 KiB,
# which stays in a core's L2 cache from the tile's range check to its
# cast, so the caller's payload is read from DRAM once.
_TILE = 1 << 15


def to_field_u32(x, q: int, edge: dict, rows=None) -> np.ndarray:
    """A host payload in the device format: a C-contiguous uint32 array
    equal bit for bit to `(np.asarray(x)[rows] % q).astype(np.uint32)`
    (all of `x` where `rows` is None), for every input.  Runs inside the
    caller's `prep` stage.

    A payload a storage user writes is canonical already, in [0, q):
    16-bit data, and parity or survivors out of the field.  There `% q`,
    a division per element on int64, returns its input, so one exact
    check of the range and a cast replace it.  The pass walks each row
    asked for in tiles of `_TILE` symbols, and checks and casts each
    tile while it is in cache: the payload is read once, and the rows
    `rows` of a wider array (the survivors in a codeword) are gathered
    by the same pass, with no copy.  An unsigned dtype whose every value
    lies below q is its own proof; int64 and uint64 take the max of the
    bits read as uint64 (in the array's byte order), where a negative
    value wraps far above q; other integers take min and max.  An empty
    payload, a failed check and any other dtype take the `% q` pass
    over the rows, whose int64 temporary is freed on return, inside the
    caller's stage.  Each call adds one to `edge_reduce_total{path, op,
    backend}`, `path` being `canonical` or `reduced`, and one to
    `edge_gather_fused_total{op, backend}` where `rows` selects from a
    wider array, at the caller's `edge` labels."""
    x = np.asarray(x)
    picked = range(len(x)) if rows is None else rows
    if rows is not None and len(rows) < len(x):
        count_gather_fused(**edge)
    out = np.empty((len(picked),) + x.shape[1:], np.uint32)
    canonical = x.dtype.kind in "iu"
    if canonical:
        check = _range_check(x.dtype, q)
        canonical = (check is None or out.size > 0) and _cast_in_tiles(
            x, picked, out, check)
    count_reduce("canonical" if canonical else "reduced", **edge)
    if canonical:
        return out
    del out
    return ((x if rows is None else x[list(rows)]) % q).astype(
        np.uint32, order="C")


def _range_check(dtype: np.dtype, q: int):
    """The exact test that a tile of integer `dtype` lies in [0, q), or
    None where the dtype proves it."""
    info = np.iinfo(dtype)
    if info.min >= 0 and info.max < q:
        return None
    if dtype.itemsize == 8:
        u64 = np.dtype(np.uint64).newbyteorder(dtype.byteorder)
        return lambda t: t.view(u64).max() < q
    return lambda t: t.min() >= 0 and t.max() < q


def _cast_in_tiles(x, picked, out, check) -> bool:
    """Cast rows `picked` of `x` into the rows of `out`, `_TILE` symbols
    at a time, each tile checked (unless `check` is None) just before its
    cast; False at the first tile that fails."""
    for j, i in enumerate(picked):
        src, dst = x[i].reshape(-1), out[j].reshape(-1)
        for a in range(0, src.size, _TILE):
            tile = src[a:a + _TILE]
            if check is not None and not check(tile):
                return False
            np.copyto(dst[a:a + _TILE], tile, casting="unsafe")
    return True


def _finish(fn, xd, edge: dict, rows: int | None = None) -> np.ndarray:
    """The device half of a call and its way back: dispatch `fn(xd)`,
    read the result to the host and widen it to int64 (its first `rows`
    rows), each an `obs.trace.stage` labelled `edge`.

    `d2h` is the one wait of the call: an upload returns once its bytes
    are staged, and `np.asarray` issues every shard's read-back and then
    blocks, so `d2h` holds the rest of the H2D transfer and the
    computation too.  A `block_until_ready` before it wakes the host once
    more per shard (with one, the four-chip mesh encode on TPU v5e read
    1.6-3.6% slower than without the stages).  Each host array is
    released inside the stage that consumed it, so the stages cover the
    frees too.  Callers release their upload's host temporaries in their
    h2d stage: a multi-MiB array held over the call changes where the
    allocator puts the int64 result, which then page-faults afresh on
    every call (measured on a 6 MiB stripe: a degraded read at 70 ms,
    not 21)."""
    with stage("dispatch", **edge):
        y = fn(xd)
    with stage("d2h", **edge) as s:
        yh = np.asarray(y)
        s.moved(y.nbytes)
    with stage("widen", **edge):
        y64 = yh.astype(np.int64)
        del y, yh
        return y64 if rows is None else y64[:rows]


def _require_devices(n: int):
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh backend needs >= {n} devices, found {len(devs)} "
            "(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return devs[:n]


def _mesh_axes(plan, devs):
    """(Mesh, axis_name, PartitionSpec) for the plan: the flat K-device
    "enc" axis, or — when the plan carries a multi-host topology whose
    host count divides K — a (hosts x K/hosts) grid in host-major device
    order with a `TieredAxis` axis name, so every schedule round lowers
    onto its own tier's ppermute leg (see `core.shardmap_exec`).  Shard
    layout is identical either way (device k still holds source k), so
    outputs are bitwise-equal to the flat mesh."""
    from jax.sharding import Mesh, PartitionSpec as P

    from ..core.shardmap_exec import TieredAxis

    topo = getattr(plan, "topology", None)
    K = plan.spec.K
    if topo is not None and 1 < topo.hosts <= K and K % topo.hosts == 0:
        axis = TieredAxis(topo.hosts, K // topo.hosts)
        mesh = Mesh(np.array(devs).reshape(axis.hosts, axis.dph), axis.axes)
        return mesh, axis, P(axis.axes)
    return Mesh(np.array(devs), ("enc",)), "enc", P("enc")


def mesh_sharding(plan):
    """The layout the mesh callable consumes: row k (source k) on device
    k, so each source is placed on its own device straight from the host
    instead of staging the whole payload on device 0."""
    from jax.sharding import NamedSharding

    mesh, _, pspec = _mesh_axes(plan, _require_devices(plan.spec.K))
    return NamedSharding(mesh, pspec)


def build_mesh_callable(plan):
    """Jitted global-array function (K, W) uint32 -> (K, W) uint32 running
    the plan's schedule under shard_map on the first K devices.  Device k
    holds source k; after the call devices 0..R-1 hold the sink values."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map

    from ..core.parity import mesh_parity_encode
    from ..core.shardmap_exec import mesh_dft

    spec = plan.spec
    devs = _require_devices(spec.K)
    mesh, axis, pspec = _mesh_axes(plan, devs)

    if spec.kind == "dft":
        t = plan.tables.dft_mesh_tables()

        @partial(shard_map, mesh=mesh,
                 in_specs=(pspec, pspec, pspec), out_specs=pspec)
        def step(xb, ca, cb):
            return mesh_dft(xb[0], ca[0], cb[0], t, axis)[None]

        args = (jnp.asarray(t.ca.T), jnp.asarray(t.cb.T))
        return jax.jit(lambda xg: step(xg, *args))

    if spec.K % spec.R != 0:
        raise NotImplementedError(
            f"mesh backend covers the R | K grid (Sec. III-A); got "
            f"K={spec.K}, R={spec.R}")

    if getattr(plan, "commute", False):
        # a tier_commute-rewritten schedule no longer matches the
        # hand-built table fast path: lower its IR generically (per-round
        # ppermute legs + combine layers, see core.shardmap_exec)
        from ..core.shardmap_exec import (build_ir_mesh_program,
                                          mesh_ir_encode)

        ir = plan.schedule_ir()
        dev_of = list(range(spec.K)) + list(range(spec.R))  # sink K+r -> r
        prog = build_ir_mesh_program(ir, dev_of)
        arrs = prog.device_arrays()
        keys = list(arrs)

        @partial(shard_map, mesh=mesh,
                 in_specs=(pspec,) + tuple(pspec for _ in keys),
                 out_specs=pspec)
        def ir_step(xb, *tb):
            rows = {k: v[0] for k, v in zip(keys, tb)}
            return mesh_ir_encode(xb[0], rows, prog, axis)[None]

        ir_args = tuple(jnp.asarray(arrs[k]) for k in keys)
        return jax.jit(lambda xg: ir_step(xg, *ir_args))

    t = plan.tables.mesh_tables(plan.method)
    arrs = t.device_arrays()
    keys = list(arrs)

    @partial(shard_map, mesh=mesh,
             in_specs=(pspec,) + tuple(pspec for _ in keys),
             out_specs=pspec)
    def step(xb, *tb):
        rows = {k: v[0] for k, v in zip(keys, tb)}
        return mesh_parity_encode(xb[0], rows, t, axis)[None]

    args = tuple(jnp.asarray(arrs[k]) for k in keys)
    return jax.jit(lambda xg: step(xg, *args))


def run_mesh(plan, x: np.ndarray) -> np.ndarray:
    import jax

    spec = plan.spec
    fn = plan.mesh_callable()
    edge = {"op": "encode", "backend": "mesh"}
    with stage("prep", **edge):
        x32 = to_field_u32(x, plan.field.q, edge)
    with stage("h2d", **edge) as s:
        xd = jax.device_put(x32, mesh_sharding(plan))
        s.moved(xd.nbytes)
        del x32
    return _finish(fn, xd, edge, None if spec.kind == "dft" else spec.R)


# ---------------------------------------------------------------------------
# the built-in Backend registrations (encode halves above, decode halves in
# recover.backends — imported lazily to keep the api <-> recover import DAG
# acyclic)
# ---------------------------------------------------------------------------


def _refuse_shortened(backend: str, spec, missing: str) -> None:
    """Refuse a shortened structured spec on a schedule backend."""
    if spec.shortened():
        raise BackendCapabilityError(
            f"kind={spec.kind!r} K={spec.K}, R={spec.R} is a shortened "
            f"structured code (neither of K, R divides the other): backend "
            f"{backend!r} needs {missing}; backend='local' runs it")


@register_backend("simulator")
class SimulatorBackend(Backend):
    """Exact lockstep oracle on the paper's p-port round network.  Runs any
    prime modulus; the only backend that measures network cost (exact C1/C2
    recorded thread-locally on `plan.last_stats`/`plan.sim_net`)."""

    measures_network = True

    def validate(self, spec, op: str = "encode") -> None:
        _refuse_shortened(self.name, spec, "the schedule that drops the "
                          "zero sources' sends, not built yet")
        super().validate(spec, op)

    def encode(self, plan, x):
        y, net = run_simulator(plan, x)
        plan._record_net(net, op="encode", width=x.shape[1])
        return y

    def decode(self, plan, v):
        from ..recover.backends import run_simulator as run_dec

        y, net = run_dec(plan, v)
        plan._record_net(net, op="decode", width=v.shape[1])
        return y


@register_backend("local")
class LocalBackend(Backend):
    """Single-device kernel path (NTT fast path / dense Pallas/jnp field
    matmul).  No communication schedule; uint32 Fermat arithmetic only."""

    supports_stream = True
    takes_codeword = True
    field_note = f"the uint32 kernels are Fermat-only, q={FERMAT_Q}"

    def supports_field(self, q: int) -> bool:
        return q == FERMAT_Q

    def encode(self, plan, x):
        return run_local(plan, x)

    def decode(self, plan, v):
        from ..recover.backends import run_local as run_dec

        return run_dec(plan, v)


@register_backend("mesh")
class MeshBackend(Backend):
    """Devices-as-processors shard_map/ppermute execution: one jax device
    per source/survivor.  Fermat-only; encode additionally needs the
    R | K framework grid (Sec. III-A) for non-dft kinds."""

    supports_stream = True
    takes_codeword = True
    field_note = f"the uint32 kernels are Fermat-only, q={FERMAT_Q}"

    def supports_field(self, q: int) -> bool:
        return q == FERMAT_Q

    def device_requirement(self, spec) -> int:
        return spec.K

    def validate(self, spec, op: str = "encode") -> None:
        # structural mismatch first: it holds on any device count
        _refuse_shortened(self.name, spec, "the schedule that drops the zero "
                          "sources' sends and several processors per device, "
                          "neither built yet")
        if op == "encode" and spec.kind != "dft" and spec.K % spec.R != 0:
            raise BackendCapabilityError(
                f"mesh encode covers the R | K framework grid (Sec. III-A); "
                f"got K={spec.K}, R={spec.R} — use backend='simulator' or "
                "'local' for this spec")
        super().validate(spec, op)

    def encode(self, plan, x):
        return run_mesh(plan, x)

    def decode(self, plan, v):
        from ..recover.backends import run_mesh as run_dec

        return run_dec(plan, v)
