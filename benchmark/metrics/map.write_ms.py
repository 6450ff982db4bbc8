"""map.write_ms: host-clock milliseconds of one shuffle's map side (every
map's write and commit), the median over the window's shuffles."""

import statistics


def read(ctx):
    xs = [s.write_s * 1e3 for s in ctx.shuffles if not s.error]
    return statistics.median(xs) if xs else None
