"""a2a_roofline: the all-to-all's share of the ICI roofline, %.

The bytes that must leave the busiest chip in one exchange come from the
data and the range bounds (yardstick.ici_bytes_out: records whose
partition another chip owns). At the published ICI rate that is the least
time; its share of the measured device time of the collective ops per
shuffle (mean over chips) is the metric. Silent on one chip."""


def read(ctx):
    if ctx.chips < 2 or ctx.trace is None or ctx.trace.collective_s <= 0:
        return None
    n = sum(1 for s in ctx.shuffles if not s.error)
    if not n:
        return None
    import yardstick
    out = int(yardstick.ici_bytes_out(ctx.inputs, ctx.config,
                                      ctx.chips).max())
    least_s = out * 8 / ctx.peak("ici_bits_per_s")
    return 100.0 * least_s / (ctx.trace.collective_s / n)
