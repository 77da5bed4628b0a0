"""The API host edge's own counters, read in-process after the window.

`repro.obs.trace.stage` times every host-edge stage of an API call into
`edge_stage_seconds{stage, op, backend}` and counts the bytes its h2d
and d2h stages move into `edge_bytes_total{direction, op, backend}`, in
`repro.obs.metrics.REGISTRY`.  A cell's labels come from its traffic:
`entry` is the op, `backend` the backend.  A per-op value divides by the
count of the `dispatch` stage, one per device call.  The registry holds
the whole process, so the set-up's warm-up calls are counted too.
"""
from __future__ import annotations


def _at(snapshot: dict, family: str, labels: dict) -> dict:
    """{value of the family's remaining label: value} of `family` at
    `labels` (e.g. {stage: histogram} at op, backend)."""
    out = {}
    for key, value in snapshot.get(family, {}).get("values", {}).items():
        parts = dict(p.split("=", 1) for p in key.split(",") if p)
        if all(parts.pop(k, None) == v for k, v in labels.items()) \
                and len(parts) == 1:
            out[next(iter(parts.values()))] = value
    return out


def per_op(ctx, family: str, keys: tuple) -> float | None:
    """The sum over `keys` (stages or directions) of `family` at the
    cell's labels (a histogram's sum), over the dispatch count.  None
    where the traced run found no device (a run off the chip measures no
    chip's host edge), or the registry holds no dispatch or none of
    `keys` at those labels."""
    if ctx.trace is None:
        return None
    from repro.obs.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    labels = {"op": ctx.traffic["entry"], "backend": ctx.traffic["backend"]}
    calls = _at(snap, "edge_stage_seconds", labels).get("dispatch")
    found = _at(snap, family, labels)
    if not calls or not any(k in found for k in keys):
        return None
    total = sum(v["sum"] if isinstance(v, dict) else v
                for k, v in found.items() if k in keys)
    return total / calls["count"]
