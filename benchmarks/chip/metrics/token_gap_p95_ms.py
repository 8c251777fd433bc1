"""95th percentile of the gaps between successive tokens of a session, over
every gap in the window, stalls included (nearest rank)."""
from stats import percentile


def read(ctx):
    gaps = []
    for s in ctx.sessions:
        times = sorted(t for _, t in s.deliveries)
        gaps += [b - a for a, b in zip(times, times[1:])]
    return percentile(sorted(gaps), 95.0) * 1e3 if gaps else None
