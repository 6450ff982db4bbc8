"""merge.host_ms: milliseconds of the window under host ``shuffle.merge``
spans (``impl`` ``host``: the cross-wave merge or combine of a partition),
per shuffle; 0 where no read has waves."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    return progtrace.per_shuffle_ms(ctx, pt.seconds(
        ["shuffle.merge"], where=lambda s: s.stats.get("impl") == "host"))
