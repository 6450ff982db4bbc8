"""compile.window_count: ``compile.step`` spans (a step program's first,
compiling call) inside the measured window, from the profiler trace. The
warm-up shuffle compiles every program the window uses, so 0 is
expected."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    return pt.count("compile.step")
