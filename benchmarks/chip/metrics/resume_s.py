"""Mean, over the window's preemptions, of the time from the preemption (the
restore's start) to the first token at a position not delivered before it."""


def read(ctx):
    waits = []
    for s in ctx.sessions:
        for t_pre, _ in s.restores:
            seen = max((p for p, t in s.deliveries if t <= t_pre), default=-1)
            new = [t for p, t in s.deliveries if t > t_pre and p > seen]
            if new:
                waits.append(min(new) - t_pre)
    return sum(waits) / len(waits) if waits else None
