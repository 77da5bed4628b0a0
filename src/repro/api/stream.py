"""Streaming execution under `EncodePlan.run` / `DecodePlan.run`.

The cost model charges every all-to-all encode per symbol of payload width
W, so the throughput regime is *streaming*: large payloads arrive (or are
produced) in pieces, and the executor should amortize planning, jit
dispatch, and host<->device transfers across them instead of re-paying
them per whole-W call.  This module is the engine behind
`plan.run_stream(chunks)` and `plan.run_batched(xs)` on both planners:

* the W (payload) axis is split into VMEM-sized chunks
  (`default_chunk_w`: the (K, w) uint32 tile fits a fixed byte budget,
  rounded to full 128-lane registers);
* each (spec, backend, chunk-shape) gets ONE cached jitted callable —
  the plan holds a single traced function and jit's shape cache keys the
  per-width executables, so a long stream never re-traces (a ragged last
  chunk costs exactly one extra compile);
* on the local and mesh backends the pipeline is double-buffered: chunk
  k+1's host->device transfer is enqueued while chunk k's compute is in
  flight, and chunk k's result is only materialized afterwards;
* the simulator backend keeps lockstep semantics per chunk and records
  EXACT per-chunk C1/C2 on `plan.stream_stats` (a fresh `RoundNetwork`
  per chunk — C1 is per-chunk rounds, C2 scales with the chunk width).

Buffer donation: on accelerator backends the chunk input buffer is donated
to the jitted callable when the output aliases its shape (square
transforms, mesh schedules); on CPU donation is unsupported and skipped.

Bitwise contract (tested across all backends and both planners):

    np.concatenate(list(plan.run_stream(chunks)), axis=1)
        == plan.run(np.concatenate(chunks, axis=1))
"""
from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Callable, Iterable, Iterator

import numpy as np

from ..obs.metrics import REGISTRY as _METRICS
from ..obs.trace import stage

DEFAULT_VMEM_BUDGET_BYTES = 4 << 20  # (K, w) uint32 payload tile budget
_LANES = 128                         # TPU register lane width

_CHUNKS = _METRICS.counter("stream_chunks_total",
                           "chunks executed through run_stream")
_CHUNK_ELEMS = _METRICS.counter(
    "stream_elems_total", "payload field elements streamed (K * w summed)")


def default_chunk_w(K: int, *, itemsize: int = 4,
                    budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES) -> int:
    """Largest multiple of 128 lanes such that a (K, w) tile fits the
    budget (at least one full lane group)."""
    return max(_LANES, budget_bytes // (K * itemsize) // _LANES * _LANES)


@dataclass
class StreamStats:
    """Per-chunk accounting of one `run_stream` pass (simulator backend
    additionally fills the exact C1/C2 of each chunk's lockstep run)."""

    widths: list[int] = dc_field(default_factory=list)
    C1: list[int] = dc_field(default_factory=list)
    C2: list[int] = dc_field(default_factory=list)

    @property
    def chunks(self) -> int:
        return len(self.widths)

    @property
    def W(self) -> int:
        return sum(self.widths)

    def totals(self) -> tuple[int, int]:
        """(sum C1, sum C2) across chunks — the cost of the streamed run
        as the round network actually measured it."""
        return sum(self.C1), sum(self.C2)


def iter_chunks(payload, K: int, chunk_w: int | None) -> Iterator[np.ndarray]:
    """Normalize a payload into (K, w) chunks.

    A single (K, W) array is split into `chunk_w`-wide pieces; an iterable
    of arrays is streamed as given, each piece re-split only if it exceeds
    `chunk_w`.  Chunks must all carry the plan's K rows.  Zero-width
    pieces yield nothing (a stream of no data has no chunks).
    """
    if isinstance(payload, np.ndarray) or hasattr(payload, "shape"):
        pieces: Iterable = (payload,)
    else:
        pieces = payload
    cw = chunk_w or default_chunk_w(K)
    for piece in pieces:
        piece = np.asarray(piece)
        if piece.ndim != 2 or piece.shape[0] != K:
            raise ValueError(
                f"stream chunks must be (K={K}, w) arrays, got {piece.shape}")
        for c0 in range(0, piece.shape[1], cw):
            yield piece[:, c0 : c0 + cw]


def split_chunks(payload, chunk_w: int) -> Iterator[np.ndarray]:
    """Split a (rows, W) array or an iterable of (rows, w_i) pieces into
    chunks of width <= `chunk_w`, preserving whatever leading dim the
    pieces carry (the caller validates it — unlike `iter_chunks` this is
    row-count-agnostic, for streams that carry full codeword rows).
    Zero-width pieces yield nothing."""
    pieces: Iterable = ((payload,) if hasattr(payload, "shape") else payload)
    for piece in pieces:
        piece = np.asarray(piece)
        if piece.ndim != 2:
            raise ValueError(
                f"stream chunks must be 2-D (rows, w) arrays, got "
                f"{piece.shape}")
        for c0 in range(0, piece.shape[1], chunk_w):
            yield piece[:, c0 : c0 + chunk_w]


def run_paired_stream(plan, chunks: Iterator[np.ndarray], slice_fn: Callable,
                      *, chunk_w: int) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """Drive `plan.run_stream` over `slice_fn(chunk)` while pairing every
    output block 1:1 with the chunk it came from — the passthrough side of
    a rebuild rides along with the repaired rows, still through the
    double-buffered device pipeline.

    `chunks` must already be split to width <= `chunk_w` (use
    `split_chunks` with the same value) so `run_stream` never re-splits a
    piece and the pairing stays aligned; the pipeline's one-chunk
    read-ahead means at most two chunks are held at once.
    """
    from collections import deque

    pending: deque = deque()

    def _feed():
        for c in chunks:
            pending.append(c)
            yield slice_fn(c)

    for y in plan.run_stream(_feed(), chunk_w=chunk_w):
        yield pending.popleft(), y


def _nbytes(y) -> int:
    """Bytes of a device result: one array, or a mesh decode's list."""
    return sum(a.nbytes for a in y) if isinstance(y, list) else y.nbytes


def _pipelined(chunks: Iterator[np.ndarray], to_device: Callable,
               dev_fn: Callable, finalize: Callable, *, op: str,
               backend: str) -> Iterator[np.ndarray]:
    """Double-buffered device pipeline.

    For each chunk: dispatch compute on the resident buffer, enqueue the
    NEXT chunk's host->device transfer, and only then materialize the
    in-flight result — so on an async backend the k+1 transfer overlaps
    the k compute, and the jitted callable's buffers turn over without a
    host sync between chunks.

    The three pipeline stages of every chunk (h2d / dispatch /
    materialize) are `obs.trace.stage`s labelled `op` and `backend`.
    """
    def _h2d(c):
        with stage("h2d", op=op, backend=backend) as s:
            d = to_device(c)
            s.moved(d.nbytes)
        return d

    def _run(d):
        with stage("dispatch", op=op, backend=backend):
            return dev_fn(d)

    def _materialize(y):
        with stage("materialize", op=op, backend=backend) as s:
            out = finalize(y)
            s.moved(_nbytes(y))
        return out

    cur = None
    for c in chunks:
        if cur is None:
            cur = _h2d(c)
            continue
        y = _run(cur)            # async dispatch of chunk k
        cur = _h2d(c)            # H2D of chunk k+1 overlaps the compute
        yield _materialize(y)    # block on chunk k only now
    if cur is not None:
        yield _materialize(_run(cur))


def run_stream(plan, payload, *, chunk_w: int | None = None
               ) -> Iterator[np.ndarray]:
    """Generator of per-chunk outputs for `plan` (encode or decode).

    Dispatch follows the plan's registered backend capabilities: a
    network-measuring backend (simulator) runs lockstep per chunk and
    records exact per-chunk C1/C2 on `plan.stream_stats`; a
    `supports_stream` backend (local/mesh) supplies the double-buffered
    device pipeline via the plan's `_stream_device_fn()` adapter; any
    other registered backend streams by plain per-chunk `encode`/`decode`
    calls — no pipelining, but the bitwise contract still holds.
    """
    from .registry import get_backend

    K = plan.spec.K

    def _counted(cs):
        for c in cs:
            _CHUNKS.inc(1, op=plan.op, backend=plan.backend)
            _CHUNK_ELEMS.inc(K * c.shape[1], op=plan.op,
                             backend=plan.backend)
            yield c

    chunks = _counted(iter_chunks(payload, K, chunk_w))
    backend = get_backend(plan.backend)
    if backend.measures_network:
        stats = StreamStats()
        plan.stream_stats = stats
        for c in chunks:
            y, net = plan._stream_sim_chunk(c)
            stats.widths.append(c.shape[1])
            stats.C1.append(net.C1)
            stats.C2.append(net.C2)
            plan._record_net(net, op=plan.op, width=c.shape[1])
            yield y
        return
    if backend.supports_stream:
        to_device, dev_fn, finalize = plan._stream_device_fn()
        yield from _pipelined(chunks, to_device, dev_fn, finalize,
                              op=plan.op, backend=plan.backend)
        return
    run_chunk = backend.encode if plan.op == "encode" else backend.decode
    for c in chunks:
        yield run_chunk(plan, c)


def run_batched(plan, xs, *, chunk_w: int | None = None) -> list[np.ndarray]:
    """Coalesce a batch of payloads into one streamed execution.

    xs: list of (K,) or (K, W_i) arrays (W_i may differ per request).
    The payloads are concatenated on the W axis, run through `run_stream`
    (so concurrent requests share chunk callables and the transfer/compute
    pipeline), and the outputs are split back per request.
    """
    K = plan.spec.K
    norm: list[np.ndarray] = []
    squeeze: list[bool] = []
    for x in xs:
        x = np.asarray(x)
        if x.shape[0] != K:
            raise ValueError(f"payload leading dim must be K={K}, got {x.shape}")
        squeeze.append(x.ndim == 1)
        norm.append(x[:, None] if x.ndim == 1 else x)
    if not norm:
        return []
    widths = [x.shape[1] for x in norm]
    big = np.concatenate(norm, axis=1)
    if big.shape[1] == 0:
        y = plan.run(big)  # zero-width batch: keep run()'s (rows, 0) shape
    else:
        y = np.concatenate(list(run_stream(plan, big, chunk_w=chunk_w)),
                           axis=1)
    out: list[np.ndarray] = []
    col = 0
    for w, sq in zip(widths, squeeze):
        piece = y[:, col : col + w]
        out.append(piece[:, 0] if sq else piece)
        col += w
    return out


def maybe_donate_jit(fn: Callable, *, donate: bool) -> Callable:
    """jit(fn), donating the payload buffer when the backend supports it
    (donation is a no-op with a warning on CPU, so it is gated off there)."""
    import jax

    if donate and jax.default_backend() != "cpu":
        return jax.jit(fn, donate_argnums=(0,))
    return jax.jit(fn)
