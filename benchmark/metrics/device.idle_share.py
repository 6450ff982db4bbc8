"""device.idle_share: the share of the measured window in which no
operation ran on the device, 1 - busy / window, from the profiler trace
(busy is the union of op intervals, the mean over chips)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
