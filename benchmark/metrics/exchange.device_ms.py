"""exchange.device_ms: device milliseconds of the programs the window's
reads launched, per shuffle, from the profiler trace.

The benchmark launches no program of its own in the window, so every
program on the device there is a read's exchange (its steps, waves and
merges). Program time is the union of the program intervals of a chip,
the mean over chips."""


def read(ctx):
    n = sum(1 for s in ctx.shuffles if not s.error)
    if ctx.trace is None or ctx.trace.module_s <= 0 or not n:
        return None
    return ctx.trace.module_s * 1e3 / n
