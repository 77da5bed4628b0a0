"""Bytes across the host-device link per op (api host edge): the
program's own `edge_bytes_total`, h2d and d2h, over its dispatch count
(see `edge_stages.py`)."""
from edge_stages import per_op


def read(ctx):
    return per_op(ctx, "edge_bytes_total", ("h2d", "d2h"))
