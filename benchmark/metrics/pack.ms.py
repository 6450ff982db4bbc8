"""pack.ms: the read's plan and pack milliseconds as the shuffle manager
times them (ExchangeReport plan_ms + pack_ms), the median over the
window's shuffles."""

import statistics


def read(ctx):
    xs = [s.report["plan_ms"] + s.report["pack_ms"]
          for s in ctx.shuffles if s.report]
    return statistics.median(xs) if xs else None
