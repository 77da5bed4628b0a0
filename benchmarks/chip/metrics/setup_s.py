"""Seconds from the start of the process to the first op of the window:
interpreter, imports, backend init, plan, payloads, compile or cache
load, warm-up."""


def read(ctx):
    return ctx.setup_s
