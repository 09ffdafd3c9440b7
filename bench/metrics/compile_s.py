"""Seconds of the host span around lower + compile of the cell's jitted
forward (a persistent-cache load once the cache holds it)."""


def read(ctx):
    return ctx.compile_s
