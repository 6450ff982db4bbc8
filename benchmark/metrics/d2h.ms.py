"""d2h.ms: milliseconds of the window under ``shuffle.d2h`` spans (each a
device-to-host copy of a read's output, in ``read()`` and in the drain),
per shuffle."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    return progtrace.per_shuffle_ms(ctx, pt.seconds(["shuffle.d2h"]))
