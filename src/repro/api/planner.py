"""Encoder: one planner over the simulator, mesh, and kernel backends.

    spec = CodeSpec(kind="rs", K=16, R=4)
    plan = Encoder.plan(spec, backend="simulator")   # method="auto"
    y = plan.run(x)                                  # (R, W) sink values

`plan()` does all host-side work once — generator matrix / StructuredGRS
construction, cost-model algorithm selection, mesh table precompute — and
caches it keyed by the spec, so the hot path (`plan.run`) never rebuilds
tables.  Two cache levels:

  * table cache: `CodeSpec.table_key()` (spec minus payload width W) ->
    `HostTables`.  Shared across backends and W variants; this is what used
    to be rebuilt on every `shardmap_exec.build_*_tables` /
    `framework.decentralized_encode` call.
  * plan cache: (spec, backend, method, A-digest) -> `EncodePlan`, so mesh
    plans also keep their compiled shard_map executable across calls.

`method="auto"` picks the argmin of the Table-I linear cost
C = alpha*C1 + beta_bits*C2 (C2 already scaled by the spec's payload width
W) over the schedules available for the spec (universal prepare-and-shoot
always; the RS/Lagrange-specific draw-and-loose factorization when the code
is structured).
"""
from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field as dc_field
from typing import Any, Callable

import numpy as np

from ..core import cost_model
from ..core.cauchy import StructuredGRS, cost_cauchy
from ..core.cost_model import LinearCost
from ..core.dft_a2a import cost_dft
from ..core.field import Field
from ..topo import (Placement, TieredCost, TieredLinkModel, Topology,
                    n_procs as topo_n_procs, place, tiered_encode_cost)
from .backends import build_mesh_callable
from .registry import PlanStats, get_backend
from .spec import CodeSpec

# default link model used for auto selection and describe(): ~10us latency,
# 17 bits/ns-class links (the constants the demos/benchmarks report with)
ALPHA_DEFAULT = 1e-5
BETA_BITS_DEFAULT = 17e-9


# ---------------------------------------------------------------------------
# host-side tables (cached per spec, W-independent)
# ---------------------------------------------------------------------------

@dataclass
class HostTables:
    """Everything host-side a plan needs: the generator block, the structured
    code (when any), and lazily-built mesh schedules per method."""

    spec: CodeSpec
    field: Field
    A: np.ndarray                      # (K, R) generator block
    sgrs: StructuredGRS | None
    _mesh: dict[str, Any] = dc_field(default_factory=dict)
    _ntt: Any = "unset"                # lazy NTTEncodeParams | None
    _ir: dict = dc_field(default_factory=dict)  # method -> RoundIR

    def encode_ir(self, method: str):
        """The canonical (placement-free) `core.schedule.RoundIR` of the
        full framework encode for `method`, built and `validate()`d once
        per table set — every backend lowers from this one program."""
        if method not in self._ir:
            from ..core.schedule import build_encode_ir

            self._ir[method] = build_encode_ir(
                self.spec, method=method, A=self.A,
                sgrs=self.sgrs).validate()
        return self._ir[method]

    def ntt_params(self):
        """NTT fast-path constants for the local backend (None when the
        spec has no radix-2 single-coset structure), built once."""
        if self._ntt == "unset":
            from ..kernels.ntt_encode import NTTEncodeParams

            self._ntt = NTTEncodeParams.build(self.spec, self.sgrs)
        return self._ntt

    def mesh_tables(self, method: str):
        """ParityTables for the framework grid, built once per method."""
        if method not in self._mesh:
            from ..core.parity import build_encode_tables

            self._mesh[method] = build_encode_tables(
                self.field, self.A, p=self.spec.p, method=method,
                sgrs=self.sgrs)
        return self._mesh[method]

    def dft_mesh_tables(self):
        if "dft" not in self._mesh:
            from ..core.shardmap_exec import build_dft_tables

            self._mesh["dft"] = build_dft_tables(self.field, self.spec.K,
                                                 self.spec.K)
        return self._mesh["dft"]


_TABLES: dict[tuple, HostTables] = {}
_PLANS: dict[tuple, "EncodePlan"] = {}
_STATS = {"table_hits": 0, "table_misses": 0,
          "plan_hits": 0, "plan_misses": 0}


def _digest(A: np.ndarray | None) -> str | None:
    if A is None:
        return None
    A = np.ascontiguousarray(np.asarray(A, np.int64))
    return hashlib.sha1(repr(A.shape).encode() + A.tobytes()).hexdigest()


def _host_tables(spec: CodeSpec, A: np.ndarray | None, digest: str | None) -> HostTables:
    key = spec.table_key() + (digest,)
    hit = _TABLES.get(key)
    if hit is not None:
        _STATS["table_hits"] += 1
        return hit
    _STATS["table_misses"] += 1
    f = spec.field
    sgrs = None
    if A is not None:
        A = f.arr(A)
        if A.shape != (spec.K, spec.R):
            raise ValueError(f"A must be ({spec.K}, {spec.R}), got {A.shape}")
        if spec.kind in ("dft", "rs"):
            raise ValueError(
                f"kind={spec.kind!r} derives its matrix from the spec; drop "
                "A (use kind='universal' or 'lagrange' for explicit matrices)")
    else:
        if spec.structured():
            sgrs = StructuredGRS.build(f, spec.K, spec.R, P=spec.P,
                                       lagrange=spec.kind == "lagrange")
            A = sgrs.grs.A_direct()
        else:
            A = spec.default_matrix(f)
    tables = HostTables(spec, f, A, sgrs)
    _TABLES[key] = tables
    return tables


# ---------------------------------------------------------------------------
# method selection (Table I cost model)
# ---------------------------------------------------------------------------

def method_costs(spec: CodeSpec, sgrs: StructuredGRS | None) -> dict[str, LinearCost]:
    """Analytic (C1, C2) of the full framework encode per available method.

    C2 is already scaled by the spec's payload width W (matching the
    measured `RoundNetwork.C2` of a W-wide run) — evaluate totals with
    `cost.total(alpha, beta_bits)` at W=1, not with W again."""
    if spec.kind == "dft":
        c1, c2 = cost_dft(spec.K, spec.P, spec.p)
        return {"dft": LinearCost(c1, c2 * spec.W)}
    out = {
        "universal": cost_model.framework(
            spec.K, spec.R, spec.p,
            cost_model.universal(min(spec.K, spec.R), spec.p), spec.W)
    }
    if sgrs is not None and sgrs.parent is None:  # a shortened code has no rs schedule
        a2a = LinearCost(*cost_cauchy(sgrs, 0, spec.p))
        out["rs"] = cost_model.framework(spec.K, spec.R, spec.p, a2a, spec.W)
    return out


def _ir_tiered_cost(tables: "HostTables", method: str,
                    placement: Placement) -> TieredCost | None:
    """Per-tier cost derived from the canonical schedule IR — the fallback
    pricing for placement profiles with no closed form (e.g. the K < R
    broadcast phase on a host boundary)."""
    try:
        a = tables.encode_ir(method).attribute(placement)
    except Exception:  # noqa: BLE001 — pricing fallback must never raise
        return None
    W = tables.spec.W
    return TieredCost(LinearCost(a["intra"][0], a["intra"][1] * W),
                      LinearCost(a["inter"][0], a["inter"][1] * W))


def _resolve_method(spec: CodeSpec, tables: "HostTables | None", method: str,
                    placement: Placement | None = None, link=None
                    ) -> tuple[str, dict[str, LinearCost]]:
    sgrs = tables.sgrs if tables is not None else None
    costs = method_costs(spec, sgrs)
    if method == "auto":
        # argmin of the linear cost (W already folded into each C2);
        # specific schedule wins exact ties.  Under a placement and a
        # tiered link model, each method is priced by its per-tier split
        # (IR-derived when the closed form doesn't apply, flat as a last
        # resort) — topology can flip the choice when one schedule keeps
        # more traffic intra.
        if placement is not None and isinstance(link, TieredLinkModel):
            def _score(m: str) -> float:
                tc = tiered_encode_cost(spec, m, placement, sgrs=sgrs)
                if tc is None and tables is not None:
                    tc = _ir_tiered_cost(tables, m, placement)
                return link.us(tc if tc is not None else costs[m])
        elif link is not None:
            def _score(m: str) -> float:
                return link.us(costs[m])
        else:
            def _score(m: str) -> float:
                return costs[m].total(ALPHA_DEFAULT, BETA_BITS_DEFAULT)
        chosen = min(costs, key=lambda m: (_score(m), m == "universal"))
        return chosen, costs
    if method not in costs:
        raise ValueError(
            f"method {method!r} unavailable for {spec.kind!r} spec "
            f"(have {tuple(costs)})")
    return method, costs


# ---------------------------------------------------------------------------
# EncodePlan
# ---------------------------------------------------------------------------

@dataclass
class EncodePlan(PlanStats):
    """An executable encode: spec + resolved method + backend + host tables.

    Obtained from `Encoder.plan`; cached, so hold on to it (or re-call
    `Encoder.plan` — both hit the cache) and call `.run` per payload.

    Plans are shared across callers AND threads; per-run measurements
    (`last_stats`, `sim_net`, `stream_stats` — see `registry.PlanStats`)
    are thread-local, so every thread reads the stats of its own last run.
    """

    op = "encode"  # stream/backend dispatch discriminator (not a field)

    spec: CodeSpec
    backend: str
    method: str
    tables: HostTables
    costs: dict[str, LinearCost]
    # hierarchical-topology context (see repro.topo): placement drives the
    # simulator's per-tier accounting, topology the hierarchical mesh grid,
    # link the tiered pricing in describe()/auto selection
    placement: Placement | None = None
    topology: Topology | None = None
    link: Any = None
    # run the tier_commute rewrite pass over the schedule IR (requires a
    # placement; simulator backend executes the rewritten program)
    commute: bool = False
    _mesh_fn: Callable | None = None
    _local_fn: Callable | None = None
    _ir: Any = None                    # lazily-resolved plan-level RoundIR
    # thread-local per-run stats storage (PlanStats reads/writes this)
    _tls: Any = dc_field(default_factory=threading.local, repr=False)

    @property
    def field(self) -> Field:
        return self.tables.field

    @property
    def A(self) -> np.ndarray:
        """The (K, R) generator block (x^T A are the sink values)."""
        return self.tables.A

    @property
    def sgrs(self) -> StructuredGRS | None:
        return self.tables.sgrs

    def run(self, x) -> np.ndarray:
        """Encode payloads x (K,) or (K, W) -> sink values (R,)/(R, W)."""
        x = np.asarray(x)
        if x.shape[0] != self.spec.K:
            raise ValueError(f"x must have leading dim K={self.spec.K}, "
                             f"got {x.shape}")
        squeeze = x.ndim == 1
        y = get_backend(self.backend).encode(self, x[:, None] if squeeze
                                             else x)
        return y[:, 0] if squeeze else y

    def run_stream(self, payload, *, chunk_w: int | None = None):
        """Streamed encode: generator of (R, w) sink blocks.

        `payload` is a (K, W) array (split into VMEM-sized chunks of width
        `chunk_w`, default `stream.default_chunk_w`) or an iterable of
        (K, w_i) chunks (streamed as given, re-split only above chunk_w).
        Concatenating the yielded blocks is bitwise-equal to `run` on the
        concatenated payload.  On the simulator backend,
        `plan.stream_stats` carries exact per-chunk C1/C2.
        """
        from . import stream

        return stream.run_stream(self, payload, chunk_w=chunk_w)

    def run_batched(self, xs, *, chunk_w: int | None = None) -> list[np.ndarray]:
        """Encode a batch of payloads (each (K,) or (K, W_i)) in one
        coalesced streamed execution; returns per-payload sink values."""
        from . import stream

        return stream.run_batched(self, xs, chunk_w=chunk_w)

    @property
    def local_impl(self) -> str:
        """Which kernel the local backend runs: "ntt" (O(K log K) fast
        path) or "dense" (field-matmul `encode_blocks`)."""
        return "ntt" if self.tables.ntt_params() is not None else "dense"

    # -- streaming adapter (see api/stream.py) ------------------------------
    def _stream_sim_chunk(self, x: np.ndarray):
        from .backends import run_simulator

        return run_simulator(self, x)  # (y, RoundNetwork) pair

    def _stream_device_fn(self):
        import jax

        from .backends import mesh_sharding, to_field_u32

        q = self.field.q
        spec = self.spec
        sharding = mesh_sharding(self) if self.backend == "mesh" else None
        edge = {"op": self.op, "backend": self.backend}

        def to_device(c):
            return jax.device_put(to_field_u32(c, q, edge), sharding)

        if self.backend == "mesh":
            fn = self.mesh_callable()
            if spec.kind == "dft":
                return to_device, fn, lambda y: np.asarray(y, np.int64)
            return to_device, fn, lambda y: np.asarray(
                y, np.int64)[: spec.R]
        from .backends import (counted_kernel, local_encode_callable,
                               local_kernel)

        fn = counted_kernel(local_encode_callable(self), local_kernel(self),
                            edge)
        return to_device, fn, lambda y: np.asarray(y, np.int64)

    def schedule_ir(self):
        """The plan's `core.schedule.RoundIR`: the canonical per-method
        program from the host tables, with `tier_commute(placement)`
        applied when the plan was built with `commute=True`.  Cached for
        the plan's lifetime (tables cache the canonical IR per method)."""
        if self._ir is None:
            ir = self.tables.encode_ir(self.method)
            if self.commute and self.placement is not None:
                ir = ir.tier_commute(self.placement)
            self._ir = ir
        return self._ir

    def cost(self) -> LinearCost:
        """(C1, C2) of the chosen schedule per the Table-I cost model
        (the canonical schedule — a commuted plan's exact counts come from
        `schedule_ir().cost()`, see `obs.drift`)."""
        return self.costs[self.method]

    def tiered_cost(self) -> TieredCost | None:
        """Exact per-tier (intra, inter) split of `cost()` under the plan's
        placement; None without a placement or when the placement has no
        closed form (the simulator's measured `sim_net.by_tier()` still
        applies).  A `commute=True` plan's split comes from its rewritten
        schedule IR — that is the program its runs execute."""
        if self.placement is None:
            return None
        if self.commute:
            a = self.schedule_ir().attribute(self.placement)
            W = self.spec.W
            return TieredCost(
                LinearCost(a["intra"][0], a["intra"][1] * W),
                LinearCost(a["inter"][0], a["inter"][1] * W))
        return tiered_encode_cost(self.spec, self.method, self.placement,
                                  sgrs=self.sgrs)

    def mesh_callable(self):
        """The jitted shard_map executable (mesh backend only): global
        (K, W) uint32 -> (K, W) uint32; kept for the plan's lifetime."""
        if self.backend != "mesh":
            raise ValueError("mesh_callable() is for backend='mesh' plans")
        if self._mesh_fn is None:
            self._mesh_fn = build_mesh_callable(self)
        return self._mesh_fn

    def describe(self) -> str:
        s = self.spec
        c = self.cost()
        model_us = c.total(ALPHA_DEFAULT, BETA_BITS_DEFAULT) * 1e6
        lines = [
            f"EncodePlan[{s.kind}] K={s.K} R={s.R} p={s.p} W={s.W} q={s.q}",
            f"  backend : {self.backend}",
            f"  method  : {self.method} "
            f"(available: {', '.join(sorted(self.costs))})",
            f"  cost    : C1={c.C1} rounds, C2={c.C2} elems/port "
            f"(model C ~ {model_us:.1f} us)",
            f"  tables  : cached, key={s.table_key()}",
            f"  schedule: {self.schedule_ir().summary(self.placement)}",
        ]
        if self.topology is not None:
            t = self.topology
            pol = self.placement.policy if self.placement else "none"
            lines.append(f"  topo    : {t.hosts} hosts x "
                         f"{t.devices_per_host} devices, placement={pol}")
            tc = self.tiered_cost()
            if tc is not None:
                us = (self.link.us(tc)
                      if isinstance(self.link, TieredLinkModel) else None)
                lines.append(
                    f"  tiers   : intra C1={tc.intra.C1} C2={tc.intra.C2} | "
                    f"inter C1={tc.inter.C1} C2={tc.inter.C2}"
                    + (f" (model C ~ {us:.1f} us)" if us is not None else ""))
        if self.backend == "local":
            impl = ("O(K log K) NTT fast path" if self.local_impl == "ntt"
                    else "Pallas/jnp field-matmul kernel")
            lines.append(f"  note    : local backend runs the {impl}; "
                         "no schedule is executed")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

class Encoder:
    """Namespace for the plan-then-execute API (all classmethods)."""

    ALPHA = ALPHA_DEFAULT
    BETA_BITS = BETA_BITS_DEFAULT

    @classmethod
    def plan(cls, spec: CodeSpec, backend: str = "simulator",
             method: str = "auto", A: np.ndarray | None = None, *,
             topology: Topology | Placement | None = None,
             link=None, commute: bool = False) -> EncodePlan:
        """Plan an encode: resolve the algorithm, build-or-reuse host tables,
        and return the cached executable plan.

        backend : a registered backend name — "simulator" | "mesh" |
                  "local" built in, plus anything added via
                  `api.register_backend` (capability-checked here, at plan
                  time, via `Backend.validate`)
        method  : "auto" (cost-model argmin) | "universal" | "rs" | "dft"
        A       : explicit (K, R) generator block — required for
                  kind="universal" specs without a seed; allowed for
                  kind="lagrange" with arbitrary (unstructured) points, in
                  which case only the universal schedule applies.
        topology: a `repro.topo.Topology` (placed with the affinity policy
                  when it has enough slots) or an explicit `Placement`.
                  The simulator then reports exact per-tier C1/C2
                  (`plan.sim_net.by_tier()`, asserted in the drift
                  ledger); the mesh backend runs a (hosts x K/hosts)
                  hierarchical grid when hosts divides K.
        link    : `LinkModel` or `repro.topo.TieredLinkModel` — prices
                  `method="auto"`; with a placement and a tiered link the
                  argmin runs over the per-tier split.
        commute : apply the `RoundIR.tier_commute` rewrite pass under the
                  resolved placement (required): the commuting reduce
                  rounds are re-synthesized host-aware so inter-host
                  rounds strictly shrink (or the schedule is unchanged).
                  Simulator runs execute the rewritten program; the drift
                  ledger checks it against `schedule_ir().cost()`.
        """
        get_backend(backend).validate(spec, op="encode")
        placement = None
        topo = None
        if topology is not None:
            if isinstance(topology, Placement):
                placement, topo = topology, topology.topology
            elif isinstance(topology, Topology):
                topo = topology
                if topology.n_slots >= topo_n_procs(spec):
                    placement = place(spec, topology, "affinity")
                elif get_backend(backend).measures_network:
                    raise ValueError(
                        f"topology has {topology.n_slots} slots < "
                        f"{topo_n_procs(spec)} processors — pass a larger "
                        "topology (or an explicit Placement) for the "
                        "simulator backend")
            else:
                raise TypeError(
                    f"topology must be a Topology or Placement, "
                    f"got {type(topology).__name__}")
        if commute and placement is None:
            raise ValueError(
                "commute=True requires a placement — pass topology= (a "
                "Topology with enough slots, or an explicit Placement)")
        digest = _digest(A)
        plan_key = (spec, backend, method, digest, placement, topo, link,
                    commute)
        hit = _PLANS.get(plan_key)
        if hit is not None:
            _STATS["plan_hits"] += 1
            return hit
        _STATS["plan_misses"] += 1
        tables = _host_tables(spec, A, digest)
        resolved, costs = _resolve_method(spec, tables, method,
                                          placement, link)
        plan = EncodePlan(spec, backend, resolved, tables, costs,
                          placement=placement, topology=topo, link=link,
                          commute=commute)
        _PLANS[plan_key] = plan
        return plan

    @classmethod
    def auto_method(cls, spec: CodeSpec) -> str:
        """The method `method="auto"` resolves to for this spec."""
        tables = None
        if spec.structured():
            tables = _host_tables(spec, None, None)
        return _resolve_method(spec, tables, "auto")[0]

    @classmethod
    def cache_info(cls) -> dict[str, int]:
        return dict(_STATS, plans=len(_PLANS), tables=len(_TABLES))

    @classmethod
    def cache_clear(cls) -> None:
        """Coordinated clear of ALL plan/table caches — encode plans, the
        shared host-table cache, AND the decode caches (decode tables hold
        references into the encoder's host tables, so clearing only the
        encode side would leave decode plans serving stale tables).  Same
        entry point as `repro.api.cache_clear()`."""
        import sys

        _clear_encoder_state()
        # decode caches exist only once the recover stack was imported;
        # an encode-only process has nothing stale and skips the import
        _rplanner = sys.modules.get(
            __package__.rsplit(".", 1)[0] + ".recover.planner")
        if _rplanner is not None:
            _rplanner._clear_decoder_state()


def _clear_encoder_state() -> None:
    """Drop the encode-side caches only (see `Encoder.cache_clear` for the
    coordinated clear applications should use)."""
    _PLANS.clear()
    _TABLES.clear()
    for k in _STATS:
        _STATS[k] = 0
