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
from ..obs.trace import kernel_span
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
    jitted NTT fast path or dense field matmul, per the planner."""
    import jax.numpy as jnp

    x32 = jnp.asarray(np.asarray(x) % plan.field.q, jnp.uint32)
    with kernel_span(f"local_encode.{plan.local_impl}",
                     kind=plan.spec.kind, K=plan.spec.K,
                     w=int(x32.shape[1])):
        y = local_encode_callable(plan)(x32)
    return np.asarray(y, np.int64)


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
    xd = jax.device_put((np.asarray(x) % plan.field.q).astype(np.uint32),
                        mesh_sharding(plan))
    with kernel_span("mesh_encode", kind=spec.kind, K=spec.K,
                     w=int(xd.shape[1])):
        y = np.asarray(fn(xd), np.int64)
    return y if spec.kind == "dft" else y[: spec.R]


# ---------------------------------------------------------------------------
# the built-in Backend registrations (encode halves above, decode halves in
# recover.backends — imported lazily to keep the api <-> recover import DAG
# acyclic)
# ---------------------------------------------------------------------------


@register_backend("simulator")
class SimulatorBackend(Backend):
    """Exact lockstep oracle on the paper's p-port round network.  Runs any
    prime modulus; the only backend that measures network cost (exact C1/C2
    recorded thread-locally on `plan.last_stats`/`plan.sim_net`)."""

    measures_network = True

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
    field_note = f"the uint32 kernels are Fermat-only, q={FERMAT_Q}"

    def supports_field(self, q: int) -> bool:
        return q == FERMAT_Q

    def device_requirement(self, spec) -> int:
        return spec.K

    def validate(self, spec, op: str = "encode") -> None:
        # structural mismatch first: it holds on any device count
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
