"""Transfer milliseconds per op (api host edge): the program's own
`edge_stage_seconds` of the h2d and d2h stages over its dispatch count
(see `edge_stages.py`), less the traced window's device-busy time per
op.  An upload returns once its bytes are staged and its transfer
completes behind the call, inside `d2h`, the call's one wait, which
also holds the device's work; taking the busy time out leaves the
link's share."""
from edge_stages import per_op


def read(ctx):
    s = per_op(ctx, "edge_stage_seconds", ("h2d", "d2h"))
    if s is None:
        return None
    return (s - ctx.trace["busy_any_s"] / ctx.ops) * 1e3
