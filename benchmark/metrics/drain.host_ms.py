"""drain.host_ms: milliseconds of the window under ``shuffle.fetch`` spans
and under no ``shuffle.d2h`` span, per shuffle: the drain's host work
(slicing runs, unpacking rows, merging waves) less its copies from the
device."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    return progtrace.per_shuffle_ms(
        ctx, pt.seconds_less(["shuffle.fetch"], ["shuffle.d2h"]))
