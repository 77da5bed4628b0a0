"""Fault-tolerant checkpointing with Reed-Solomon coded parity.

Layout (one directory per step, atomic rename on completion):

    ckpt_dir/step_000123/
        meta.json            — pytree structure, shapes, dtypes, N, R, q
        shard_000.npy ...    — N data shards (equal-size 16-bit symbol chunks
                               of the concatenated flat state)
        parity_000.npy ...   — R parity shards (systematic GRS over F_65537)

The parity is exactly the paper's decentralized-encoding output: on a real
cluster each of the N hosts writes its own shard and the R parity shards are
produced *in-network* by `core.parity.mesh_parity_encode` along the data
axis (no central encoder); here the host-side `encode_parity` reuses the
same StructuredGRS code so restore logic is identical.

Restore tolerates up to R missing shards (any-N-of-(N+R) MDS property,
validated in tests): shard/parity files missing from disk are detected,
`fail()`-ed on a restore-scoped `repro.api.CodedSystem` session, and
decoded around automatically (degraded read — the same `DecodePlan` the
survivors would execute in-network).  Elastic
resharding is supported: a checkpoint written with N shards restores onto
any N' (the flat symbol stream is re-split).

Integrity: `save` records a sha256 of every shard/parity payload in
meta.json, and `scrub()` is the background-repair pass a coded store runs
continuously — verify every file on disk against its checksum, then
rebuild missing/corrupt ones *in place* via the streamed decentralized
rebuild (`CodedSystem.rebuild_stream` off the survivor memmaps), restoring
full redundancy without ever materializing the whole codeword.

Async: `save(..., background=True)` hands the write to a daemon thread —
training continues; `wait()` joins before the next save (single-writer).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..api import CodedSystem, CodeSpec
from ..core.field import FERMAT, bytes_to_symbols, symbols_to_bytes


# ---------------------------------------------------------------------------
# pytree <-> flat symbol stream
# ---------------------------------------------------------------------------

def _leaf_meta(x) -> dict:
    return {"shape": list(x.shape), "dtype": str(x.dtype)}


def tree_to_bytes(tree: Any) -> tuple[np.ndarray, dict]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    metas = []
    bufs = []
    for leaf in leaves:
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            arr = arr.view(np.uint16)
            metas.append({"shape": list(leaf.shape), "dtype": "bfloat16"})
        else:
            metas.append(_leaf_meta(arr))
        bufs.append(arr.tobytes())
    raw = np.frombuffer(b"".join(bufs), np.uint8)
    meta = {"leaves": metas, "treedef": str(treedef), "nbytes": int(raw.size)}
    return raw, meta


def bytes_to_tree(raw: np.ndarray, meta: dict, treedef_example: Any) -> Any:
    leaves_ex, treedef = jax.tree_util.tree_flatten(treedef_example)
    out = []
    off = 0
    for m, ex in zip(meta["leaves"], leaves_ex):
        if m["dtype"] == "bfloat16":
            nb = int(np.prod(m["shape"])) * 2
            arr = np.frombuffer(raw[off:off + nb].tobytes(), np.uint16)
            arr = jnp.asarray(arr.reshape(m["shape"]).view(jnp.bfloat16))
        else:
            dt = np.dtype(m["dtype"])
            nb = int(np.prod(m["shape"])) * dt.itemsize
            arr = np.frombuffer(raw[off:off + nb].tobytes(), dt).reshape(m["shape"])
        out.append(arr)
        off += nb
    assert off == meta["nbytes"]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# coded checkpoint manager
# ---------------------------------------------------------------------------

@dataclass
class CodedCheckpointer:
    directory: str
    n_shards: int = 16
    n_parity: int = 4
    field: Any = None
    # streaming chunk width (payload columns) for the coded save/restore
    # paths; None = api.stream.default_chunk_w for the shard count
    chunk_w: int | None = None
    _thread: threading.Thread | None = None

    def __post_init__(self):
        self.field = self.field or FERMAT
        # one CodedSystem session owns both coding directions: the encode
        # plan carries the StructuredGRS code and its generator block, and
        # degraded restores replan the decode side per erasure pattern.
        # The shared plan caches mean repeated checkpointer instances
        # (reshard, restarts) never rebuild the code tables.  The uint32
        # kernel backend is Fermat-only; other fields fall back to the
        # exact host matmul for parity (same generator block either way).
        spec = CodeSpec(kind="rs", K=self.n_shards, R=self.n_parity,
                        q=self.field.q)
        self._fermat = self.field.q == FERMAT.q
        self._system = CodedSystem(
            spec, backend="local" if self._fermat else "simulator",
            chunk_w=self.chunk_w)
        self.sgrs = self._system.encode_plan.sgrs
        self._A = self._system.encode_plan.A
        Path(self.directory).mkdir(parents=True, exist_ok=True)

    # -- encode -------------------------------------------------------------
    def shard_symbols(self, raw: np.ndarray) -> np.ndarray:
        """(N, L) int64 symbols: 16-bit chunks, zero-padded to N*L."""
        sym = bytes_to_symbols(raw)
        L = -(-sym.size // self.n_shards)
        pad = np.zeros(self.n_shards * L - sym.size, np.int64)
        return np.concatenate([sym, pad]).reshape(self.n_shards, L)

    def encode_parity(self, shards: np.ndarray) -> np.ndarray:
        """(R, L) parity — same code the in-network mesh encode computes.

        Runs through `CodedSystem.encode`, i.e. the kernels.ops encode
        path (previously a host-side field.matmul); non-Fermat fields keep
        the exact host matmul."""
        if not self._fermat:
            return self.field.matmul(self._A.T, shards)
        return self._system.encode(shards)

    def _parity_stream(self, shards: np.ndarray):
        """Generator of (R, w) parity blocks — `CodedSystem.encode_stream`
        on the kernel path (cached chunk callables, NTT fast path when the
        shard counts allow it), exact chunked host matmul otherwise."""
        if self._fermat:
            yield from self._system.encode_stream(shards)
            return
        from ..api.stream import iter_chunks

        for c in iter_chunks(shards, self.n_shards, self.chunk_w):
            yield self.field.matmul(self._A.T, c)

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: Any, background: bool = False) -> str:
        raw, meta = tree_to_bytes(state)
        shards = self.shard_symbols(raw)

        def _write():
            final = Path(self.directory) / f"step_{step:06d}"
            tmp = Path(self.directory) / f".tmp_step_{step:06d}"
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            # per-file sha256 of the symbol payload (the uint32 array
            # bytes, not the .npy container) — scrub() verifies against
            # these to localize silent corruption to a file
            sums: dict[str, str] = {}
            for k in range(self.n_shards):
                arr = shards[k].astype(np.uint32)
                np.save(tmp / f"shard_{k:03d}.npy", arr)
                sums[f"shard_{k:03d}"] = hashlib.sha256(
                    arr.tobytes()).hexdigest()
            # parity is STREAMED into preallocated .npy memmaps: the encode
            # runs chunk-by-chunk (double-buffered on the kernel path) and
            # the full (R, L) parity matrix is never materialized; the
            # checksums accumulate over exactly the bytes written
            L = shards.shape[1]
            if L == 0:  # empty state: mmap cannot map zero bytes
                for r in range(self.n_parity):
                    np.save(tmp / f"parity_{r:03d}.npy",
                            np.zeros(0, np.uint32))
                    sums[f"parity_{r:03d}"] = hashlib.sha256(b"").hexdigest()
            else:
                mms = [np.lib.format.open_memmap(
                           tmp / f"parity_{r:03d}.npy", mode="w+",
                           dtype=np.uint32, shape=(L,))
                       for r in range(self.n_parity)]
                hs = [hashlib.sha256() for _ in range(self.n_parity)]
                col = 0
                for blk in self._parity_stream(shards):
                    w = blk.shape[1]
                    for r in range(self.n_parity):
                        row = blk[r].astype(np.uint32)
                        mms[r][col : col + w] = row
                        hs[r].update(row.tobytes())
                    col += w
                assert col == L
                for mm in mms:
                    mm.flush()
                del mms
                for r in range(self.n_parity):
                    sums[f"parity_{r:03d}"] = hs[r].hexdigest()
            meta2 = dict(meta, N=self.n_shards, R=self.n_parity,
                         q=self.field.q, step=step, sha256=sums)
            (tmp / "meta.json").write_text(json.dumps(meta2))
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)

        self.wait()  # single-writer: join any in-flight background save
        if background:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()
        else:
            _write()
        return str(Path(self.directory) / f"step_{step:06d}")

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    # -- restore ------------------------------------------------------------
    def latest_step(self) -> int | None:
        steps = sorted(int(p.name.split("_")[1])
                       for p in Path(self.directory).glob("step_*"))
        return steps[-1] if steps else None

    def restore(self, step: int, example_state: Any,
                failed_shards: set[int] = frozenset()) -> Any:
        """Restore, reconstructing up to R erased shards via the decode
        subsystem (`repro.recover.Decoder`).

        Degraded reads are automatic: shard/parity files missing from disk
        count as erasures, in addition to the explicitly `failed_shards`
        (simulated node failures, indices into [0, N)).  The restore
        succeeds as long as data + parity erasures total at most R."""
        d = Path(self.directory) / f"step_{step:06d}"
        meta = json.loads((d / "meta.json").read_text())
        N, R = meta["N"], meta["R"]
        erased = {int(k) for k in failed_shards}
        for k in range(N):
            if k not in erased and not (d / f"shard_{k:03d}.npy").exists():
                erased.add(k)
        for r in range(R):
            if not (d / f"parity_{r:03d}.npy").exists():
                erased.add(N + r)

        loaded: dict[int, np.ndarray] = {}

        def _load(idx: int) -> np.ndarray:
            # memory-mapped: survivor files are read chunk-by-chunk by the
            # streamed repair and row-by-row by the final assembly, never
            # duplicated wholesale on the heap
            if idx not in loaded:
                name = (f"shard_{idx:03d}.npy" if idx < N
                        else f"parity_{idx - N:03d}.npy")
                loaded[idx] = np.load(d / name, mmap_mode="r")
            return loaded[idx]

        if any(e < N for e in erased):
            assert len(erased) <= R, "more failures than parity can cover"
            spec = CodeSpec(kind="rs", K=N, R=R,
                            q=int(meta.get("q", self.field.q)))
            # a restore-scoped CodedSystem session for the file's (N, R)
            # layout (may differ from self under elastic reshard): fail
            # the missing positions, then stream the degraded read
            rsys = CodedSystem(
                spec, backend="local" if spec.q == FERMAT.q else "simulator",
                chunk_w=self.chunk_w)
            rsys.fail(sorted(erased))
            plan = rsys.decode_plan
            # repair only the |E| lost columns (K x |E| work) instead of
            # re-deriving all K data shards through the full K x K solve;
            # repaired rows for missing *parity* files ride along unused
            # (they must be in `erased` so plan.kept avoids them — at most
            # R-1 extra columns, still far below the K-column full solve).
            # The repair itself is STREAMED: survivor chunks are sliced
            # straight off the memmaps and decoded through the plan's
            # cached chunk callables, so no full-width survivor stack or
            # repaired matrix is ever materialized at once.
            L = int(_load(plan.kept[0]).shape[0])
            rep = {e: np.empty(L, np.int64) for e in plan.erased}
            from ..api.stream import default_chunk_w

            cw = self.chunk_w or default_chunk_w(N)

            def survivor_chunks():
                for c0 in range(0, L, cw):
                    yield np.stack([np.asarray(_load(i)[c0 : c0 + cw],
                                               np.int64)
                                    for i in plan.kept])

            col = 0
            for blk in rsys.decode_stream(survivor_chunks()):
                for j, e in enumerate(plan.erased):
                    rep[e][col : col + blk.shape[1]] = blk[j]
                col += blk.shape[1]
            assert col == L
            shards = np.stack([rep[k] if k in rep
                               else np.asarray(_load(k), np.int64)
                               for k in range(N)])
        else:
            shards = np.stack([np.asarray(_load(k), np.int64)
                               for k in range(N)])
        sym = shards.reshape(-1)[: -(-meta["nbytes"] // 2)]
        raw = symbols_to_bytes(sym, meta["nbytes"])
        return bytes_to_tree(raw, meta, example_state)

    # -- scrub: verify on-disk shards, rebuild the bad ones in place --------
    def scrub(self, step: int | None = None) -> dict:
        """Verify a checkpoint's shard/parity files and rebuild the
        missing/corrupt ones in place (the coded store's background
        integrity pass: fail -> rebuild -> healed, on disk).

        Every file must exist, parse as the expected (L,) uint32 array and
        match the sha256 recorded at save time (checkpoints written before
        checksums fall back to a shape + symbol-range check).  Files
        failing any check count as erasures; as long as they total at most
        R, the survivors rebuild them bitwise via the streamed
        decentralized rebuild (`CodedSystem.rebuild_stream` driven off the
        survivor memmaps — no full-width stack is ever materialized), each
        rebuilt file is re-verified against its recorded checksum, and the
        replacement is atomic per file.  Returns a report dict:

            {"step", "checked", "missing", "corrupt", "rebuilt",
             "verified"}
        """
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {self.directory}")
        d = Path(self.directory) / f"step_{step:06d}"
        meta = json.loads((d / "meta.json").read_text())
        N, R, q = meta["N"], meta["R"], int(meta.get("q", self.field.q))
        sums: dict = meta.get("sha256", {})
        sym = -(-meta["nbytes"] // 2)
        L = -(-sym // N) if sym else 0

        def _name(i: int) -> str:
            return (f"shard_{i:03d}" if i < N else f"parity_{i - N:03d}")

        missing: list[int] = []
        corrupt: list[int] = []
        for i in range(N + R):
            path = d / (_name(i) + ".npy")
            if not path.exists():
                missing.append(i)
                continue
            try:
                mm = np.load(path, mmap_mode="r")
            except Exception:  # noqa: BLE001 — unparseable container
                corrupt.append(i)
                continue
            if mm.shape != (L,) or mm.dtype != np.uint32:
                corrupt.append(i)
                continue
            expected = sums.get(_name(i))
            if expected is not None:
                h = hashlib.sha256()
                for c0 in range(0, L, 1 << 20):
                    h.update(np.ascontiguousarray(
                        mm[c0 : c0 + (1 << 20)]).tobytes())
                if h.hexdigest() != expected:
                    corrupt.append(i)
            elif L and int(np.max(mm)) >= q:
                corrupt.append(i)  # pre-checksum checkpoint: range check
        erased = sorted(missing + corrupt)
        report = {"step": step, "checked": N + R, "missing": missing,
                  "corrupt": corrupt, "rebuilt": erased, "verified": True}
        if not erased:
            return report
        if len(erased) > R:
            raise RuntimeError(
                f"scrub: {len(erased)} missing/corrupt files exceed the "
                f"code's R={R} — the checkpoint is unrecoverable "
                f"(missing={missing}, corrupt={corrupt})")

        if L == 0:
            for e in erased:
                np.save(d / (_name(e) + ".npy"), np.zeros(0, np.uint32))
            return report

        spec = CodeSpec(kind="rs", K=N, R=R, q=q)
        rsys = CodedSystem(
            spec, backend="local" if q == FERMAT.q else "simulator",
            chunk_w=self.chunk_w)
        rsys.fail(erased)
        kept = rsys.decode_plan.kept
        srcs = {i: np.load(d / (_name(i) + ".npy"), mmap_mode="r")
                for i in kept}
        from ..api.stream import default_chunk_w

        cw = self.chunk_w or default_chunk_w(N)
        hs = {e: hashlib.sha256() for e in erased}
        try:
            tmps = {e: np.lib.format.open_memmap(
                        d / f".scrub_{_name(e)}.npy", mode="w+",
                        dtype=np.uint32, shape=(L,))
                    for e in erased}

            def survivor_chunks():
                for c0 in range(0, L, cw):
                    yield np.stack([np.asarray(srcs[i][c0 : c0 + cw],
                                               np.int64)
                                    for i in kept])

            col = 0
            for healed in rsys.rebuild_stream(survivor_chunks()):
                w = healed.shape[1]
                for e in erased:
                    row = healed[e].astype(np.uint32)
                    tmps[e][col : col + w] = row
                    hs[e].update(row.tobytes())
                col += w
            assert col == L
            for e in erased:
                tmps[e].flush()
            del tmps
            # verify EVERY rebuilt payload before replacing ANY file: a
            # checksum mismatch must leave the checkpoint untouched
            for e in erased:
                expected = sums.get(_name(e))
                if expected is not None and hs[e].hexdigest() != expected:
                    report["verified"] = False
                    raise RuntimeError(
                        f"scrub: rebuilt {_name(e)} does not match its "
                        "recorded checksum — survivors are inconsistent "
                        "(more corruption than the parity can localize?)")
            for e in erased:
                os.replace(d / f".scrub_{_name(e)}.npy",
                           d / (_name(e) + ".npy"))
        finally:
            # never strand .scrub_* temps on a failed rebuild/verify
            for e in erased:
                (d / f".scrub_{_name(e)}.npy").unlink(missing_ok=True)
        return report

    def reshard(self, step: int, new_n: int, new_r: int) -> "CodedCheckpointer":
        """Elastic rescale: rewrite step with a different (N, R) layout."""
        d = Path(self.directory) / f"step_{step:06d}"
        meta = json.loads((d / "meta.json").read_text())
        shards = np.stack([np.load(d / f"shard_{k:03d}.npy").astype(np.int64)
                           for k in range(meta["N"])])
        sym = shards.reshape(-1)[: -(-meta["nbytes"] // 2)]
        raw = symbols_to_bytes(sym, meta["nbytes"])
        new = CodedCheckpointer(self.directory + f"_n{new_n}", new_n, new_r,
                                self.field)
        nshards = new.shard_symbols(raw)
        parity = new.encode_parity(nshards)
        final = Path(new.directory) / f"step_{meta['step']:06d}"
        final.mkdir(parents=True, exist_ok=True)
        meta2 = dict(meta, N=new_n, R=new_r)
        (final / "meta.json").write_text(json.dumps(meta2))
        for k in range(new_n):
            np.save(final / f"shard_{k:03d}.npy", nshards[k].astype(np.uint32))
        for r in range(new_r):
            np.save(final / f"parity_{r:03d}.npy", parity[r].astype(np.uint32))
        return new
