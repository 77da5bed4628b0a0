"""Host numpy milliseconds per op (api host edge): the program's own
`edge_stage_seconds` of the gather, prep (`% q`, casts) and widen (int64)
stages, over its dispatch count (see `edge_stages.py`)."""
from edge_stages import per_op


def read(ctx):
    s = per_op(ctx, "edge_stage_seconds", ("gather", "prep", "widen"))
    return None if s is None else s * 1e3
