"""drain.ms: host-clock milliseconds from read() returning to the last
partition on the host (partitions_ready), the median over the window's
shuffles."""

import statistics


def read(ctx):
    xs = [s.drain_s * 1e3 for s in ctx.shuffles if not s.error]
    return statistics.median(xs) if xs else None
