"""exchange.idle_ms: device idle milliseconds under the read's
``shuffle.dispatch`` and ``shuffle.exchange.wait`` spans, per shuffle, from
the profiler trace: the chip waiting on the exchange's rows in flight to
it, their host relayout and the launch (mean over chips)."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    return progtrace.per_shuffle_ms(ctx, pt.idle_seconds(
        ["shuffle.dispatch", "shuffle.exchange.wait"]))
