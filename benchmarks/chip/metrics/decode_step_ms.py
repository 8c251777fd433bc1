"""Device time of the programs a decode step launches (the step, and the
token pick the engine runs after it), from the trace, per decode step in the
traced part of the window."""


def read(ctx):
    if ctx.trace is None:
        return None
    steps = len(ctx.decode_calls(*ctx.trace_window))
    dev = ctx.trace["program_s"].get("decode", 0.0)
    return dev / steps * 1e3 if steps and dev > 0 else None
