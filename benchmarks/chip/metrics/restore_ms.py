"""Mean time of a page-store restore (``restore_cache``) in the traced part
of the window (``Context.layer_window``)."""


def read(ctx):
    n, s = ctx.rec.total("restore", *ctx.layer_window)
    return s / n * 1e3 if n else None
