"""Bytes the simulated NVM devices were written (all shards) per byte of
snapshot the page store took, both over the window.  A count: it repeats
exactly for the same code."""


def read(ctx):
    snap = ctx.counters.get("snapshot_bytes", 0)
    return ctx.counters["nvm_bytes_written"] / snap if snap else None
