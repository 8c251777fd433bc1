"""Share of the append-only K/V blocks that page-store snapshots kept
(skipped because their bytes had not changed since the session's last
snapshot) among those kept and written, over the window, from the page
store's ``snapshot_kv_blocks_kept`` and ``snapshot_kv_blocks_written``
counters.  None where the page store keeps no such counters, or stored no
K/V block."""


def read(ctx):
    kept = ctx.counters.get("snapshot_kv_blocks_kept")
    written = ctx.counters.get("snapshot_kv_blocks_written")
    if kept is None or written is None or not kept + written:
        return None
    return kept / (kept + written)
