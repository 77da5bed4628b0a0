"""Encode's share of its HBM roofline, in percent: the least time in which
the chip can read the K data rows and write the R parity rows as uint32
at its peak HBM bandwidth, over the device-busy time per op.  Every op
on the device counts, so the share reads the same work whatever kernel
implements it."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    cfg = ctx.config
    least_s = (cfg["K"] + cfg["R"]) * cfg["W"] * 4 / ctx.peak["hbm_bytes_per_s"]
    return least_s / (t["busy_s"] / ctx.ops) * 100.0
