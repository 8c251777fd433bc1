"""Tokens delivered to the host in the window over the window's seconds.

The window is whole sessions (snapshots, restores, prefills and re-decodes
inside it).  A position re-decoded after a resume counts once."""


def read(ctx):
    lo, hi = ctx.window
    tokens = sum(len({pos for pos, _ in s.deliveries}) for s in ctx.sessions)
    return ctx.batch * tokens / (hi - lo)
