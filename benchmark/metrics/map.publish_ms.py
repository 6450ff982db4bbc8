"""map.publish_ms: milliseconds of the window under ``shuffle.publish``
spans, per shuffle: each map's commit (materialize its staged rows,
partition ids, sizes, publish)."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    return progtrace.per_shuffle_ms(ctx, pt.seconds(["shuffle.publish"]))
