"""Bytes of rewritten decode state (an SSM or RWKV state, a conv window)
a page-store snapshot writes, per snapshot over the window, from the page
store's ``snapshot_state_bytes`` counter (1 MB = 1e6 B).  None where the
page store keeps no such counter."""


def read(ctx):
    n = ctx.counters.get("snapshots", 0)
    b = ctx.counters.get("snapshot_state_bytes")
    return b / n / 1e6 if n and b is not None else None
