"""Process start to the first timed step: imports, device, weights, page
store, compile (or compile-cache loads) and the warm-up session."""


def read(ctx):
    return ctx.setup_s
