"""Page bytes handed to the store's ``multi_write`` over the time spent in
it, in the traced part of the window (``Context.layer_window``; 1 MB =
1e6 B)."""


def read(ctx):
    lo, hi = ctx.layer_window
    n, s = ctx.rec.total("multi_write", lo, hi)
    written = sum(b for t, b in ctx.rec.written if lo <= t < hi)
    return written / s / 1e6 if n and s > 0 else None
