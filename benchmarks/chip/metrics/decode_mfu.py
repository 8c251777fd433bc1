"""Model FLOPs of the decode steps in the traced window (computed from the
configuration's shapes) over the window's seconds times the chip's bf16
peak, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    ctxs = ctx.decode_calls(*ctx.trace_window)
    if not ctxs:
        return None
    model = ctx.cell.config["model"]
    flops = sum(ctx.family.decode_cost(model, ctx.batch, c)[0] for c in ctxs)
    return 100 * flops / (ctx.trace["window_s"] * ctx.peaks["bf16_flops"])
