"""Share of the cell's local device calls that ran the NTT encode, in
percent (kernels): the program's `local_kernel_total{kernel}` at the
cell's labels, `ntt` over all kernels (see `edge_stages.py`).  None
where the traced run found no device, or the registry holds no local
call at those labels (a program without the counter)."""
from edge_stages import _at


def read(ctx):
    if ctx.trace is None:
        return None
    from repro.obs.metrics import REGISTRY

    calls = _at(REGISTRY.snapshot(), "local_kernel_total",
                {"op": ctx.traffic["entry"], "backend": ctx.traffic["backend"]})
    total = sum(calls.values())
    if not total:
        return None
    return 100.0 * calls.get("ntt", 0) / total
