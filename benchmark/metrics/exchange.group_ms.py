"""exchange.group_ms: milliseconds from the exchange's dispatch to its
completion, H2D included, as the shuffle manager times them
(ExchangeReport group_ms), the median over the window's shuffles."""

import statistics


def read(ctx):
    xs = [s.report["group_ms"] for s in ctx.shuffles if s.report]
    return statistics.median(xs) if xs else None
