"""exchange_roofline: the exchange programs' share of the HBM roofline, %.

The least bytes one exchange needs on a chip are its staged records read
once and its delivered records written once (yardstick.exchange_min_bytes,
from the cell's data and the reference's delivered rows), over the chips.
At the published HBM rate that is the least time; its share of the
measured device time of the exchange programs per shuffle (as
exchange.device_ms reads it) is the metric."""


def read(ctx):
    n = sum(1 for s in ctx.shuffles if not s.error)
    if ctx.trace is None or ctx.trace.module_s <= 0 or not n:
        return None
    import yardstick
    least = yardstick.exchange_min_bytes(
        ctx.inputs.rows, int(ctx.expected.sizes.sum()), ctx.config) \
        / ctx.chips
    return 100.0 * least / ctx.peak("hbm_bytes_per_s") \
        / (ctx.trace.module_s / n)
