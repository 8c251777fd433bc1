"""Share of the HBM roofline the decode steps reach: the bytes the steps in
the traced window must move (every weight, and the cache or state, from
shapes) over the HBM peak times their device time, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    ctxs = ctx.decode_calls(*ctx.trace_window)
    dev = ctx.trace["program_s"].get("decode", 0.0)
    if not ctxs or dev <= 0:
        return None
    model = ctx.cell.config["model"]
    moved = sum(ctx.family.decode_cost(model, ctx.batch, c)[1] for c in ctxs)
    return 100 * moved / (ctx.peaks["hbm_bytes_s"] * dev)
