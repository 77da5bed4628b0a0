"""User bytes (K·W·symbol_bits/8 per op) of every op completed in the
window, over the window's seconds, in GB/s (1 GB = 1e9 B)."""


def read(ctx):
    w = ctx.window
    return ((w.attempted - w.failed) * ctx.session.user_bytes_per_op
            / w.seconds / 1e9)
