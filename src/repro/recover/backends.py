"""The decode halves of the three built-in backends (the `Backend`
objects binding these to the registry live in `api.backends`).

    simulator — all-to-all decode among the K kept survivors on the
                round network, with the erased processors fail()-ed
                (exact numpy oracle; measured C1/C2 recorded
                thread-locally on `plan.last_stats` / `plan.sim_net`)
    mesh      — devices-as-survivors shard_map execution: device i holds
                the symbol of survivor `plan.kept[i]`; each batch of
                repair columns runs the same universal mesh A2A as the
                encode path, with the repaired symbols landing on devices
                0..E'-1
    local     — single-device `kernels.ops.decode_blocks` (Pallas/jnp)

All three return the erased symbols bitwise-equal: row j holds
v^T D[:, j] over F_q for erased position `plan.erased[j]`.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from ..core import schedule
from ..core.simulator import RoundNetwork
from ..obs.trace import kernel_span


def run_simulator(plan, v: np.ndarray) -> tuple[np.ndarray, RoundNetwork]:
    """Decode on the paper's p-port round network: the erased processors
    are failed (any schedule touching them would raise); returns the
    repaired symbols and the network with its measured C1/C2.  Executes
    the plan's decode `RoundIR` (`plan.schedule_ir()`) generically — the
    same rounds the retired `decentralized_decode` generators produced."""
    spec, f = plan.spec, plan.field
    net = RoundNetwork(spec.N, spec.p)
    net.fail(plan.erased)
    y = schedule.execute(plan.schedule_ir(), f, f.arr(v), net)
    return np.asarray(y, np.int64), net


def local_decode_callable(plan):
    """The plan's single jitted local-decode executable (K, w) uint32 ->
    (|E|, w) uint32, cached for the plan's lifetime (jit's shape cache
    gives one compiled variant per chunk width — see api/stream.py)."""
    if plan._local_fn is None:
        import jax.numpy as jnp

        from ..api.stream import maybe_donate_jit
        from ..kernels.ops import decode_blocks

        D = jnp.asarray(plan.tables.D % plan.field.q, jnp.uint32)
        plan._local_fn = maybe_donate_jit(lambda v: decode_blocks(v, D),
                                          donate=False)
    return plan._local_fn


def run_local(plan, v: np.ndarray) -> np.ndarray:
    """Single-device decode on the Pallas/jnp kernel path (no network)."""
    import jax.numpy as jnp

    q = plan.field.q
    v32 = jnp.asarray(np.asarray(v) % q, jnp.uint32)
    with kernel_span("local_decode", kind=plan.spec.kind, K=plan.spec.K,
                     E=len(plan.erased), w=int(v32.shape[1])):
        y = local_decode_callable(plan)(v32)
    return np.asarray(y, np.int64)


def mesh_sharding(plan):
    """Survivor i's row on device i: the layout the mesh callables
    consume, placed straight from the host."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..api.backends import _require_devices

    return NamedSharding(Mesh(np.array(_require_devices(plan.spec.K)),
                              ("dec",)), P("dec"))


def _mesh_callables(plan) -> list:
    """One jitted shard_map executable per repair batch, kept for the
    plan's lifetime (same caching contract as `EncodePlan.mesh_callable`).

    Each executable maps the global (K, W) uint32 survivor array (device i
    <-> survivor `plan.kept[i]`) to a (K, W) array whose rows 0..E'-1 hold
    the batch's repaired symbols.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..core.parity import mesh_parity_encode

    if plan._mesh_fns is not None:
        return plan._mesh_fns

    mesh = mesh_sharding(plan).mesh

    def _batch_fn(t):
        arrs = t.device_arrays()
        keys = list(arrs)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("dec"),) + tuple(P("dec") for _ in keys),
                 out_specs=P("dec"))
        def step(xb, *tb):
            rows = {k: a[0] for k, a in zip(keys, tb)}
            return mesh_parity_encode(xb[0], rows, t, "dec")[None]

        args = tuple(jnp.asarray(arrs[k]) for k in keys)
        return jax.jit(lambda xg: step(xg, *args))

    fns = [_batch_fn(plan.tables.mesh_tables(b))
           for b in range(len(plan.tables.batches()))]
    plan._mesh_fns = fns
    return fns


def run_mesh(plan, v: np.ndarray) -> np.ndarray:
    import jax

    q = plan.field.q
    vg = jax.device_put((np.asarray(v) % q).astype(np.uint32),
                        mesh_sharding(plan))
    out = []
    with kernel_span("mesh_decode", kind=plan.spec.kind, K=plan.spec.K,
                     E=len(plan.erased), w=int(vg.shape[1])):
        for fn, (eb, _) in zip(_mesh_callables(plan), plan.tables.batches()):
            y = np.asarray(fn(vg), np.int64)
            out.append(y[:eb])
    return np.concatenate(out, axis=0)
