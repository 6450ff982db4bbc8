"""segreduce_roofline: the receive-side Pallas segment-reduce
(``ops/pallas/segmented.py``, the ``pallas_call`` named ``KERNEL``), as a
share of the HBM roofline, %.

The least bytes on a chip are the rows that must reach it under full
map-side combining (``combine_a2a_roofline.distinct_words``: map m staged
on chip ``m % chips``, partitions owned in contiguous blocks), read once,
plus the rows it delivers (the reference's, ``ctx.expected``), written
once, at 12 B a row; the mean over chips. At the published HBM rate that
is the least time; its share of the kernel's device time per shuffle
(mean over chips) is the metric.

The trace reduction names a custom call by its HLO instruction, so the
kernel's ops are found in the raw device planes by the kernel's name in
their HLO text. Silent where no op carries it: one chip (no receive-side
reduce) or a read whose ``read.mergeImpl`` resolved ``jnp``."""

import os
from typing import Optional

import numpy as np

KERNEL = "segreduce_blocked"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def kernel_seconds(profile, needle: str = KERNEL) -> Optional[float]:
    """Device seconds inside ``bench.window`` (the whole trace where it
    is absent) of the XLA ops whose HLO text holds ``needle``, mean over
    chips; None where no chip ran one."""
    import devtrace
    window, chips = None, {}
    for plane in profile.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == devtrace.OPS_LINE:
                iv = chips.setdefault(int(m.group(1)), [])
                iv.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if needle in ev.name)
            elif plane.name.startswith("/host:") and window is None:
                window = next(((ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events
                               if ev.name == devtrace.WINDOW), None)
    if not any(chips.values()):
        return None
    lo, hi = window or (-np.inf, np.inf)
    return sum(devtrace.total(devtrace.clip(devtrace.union(iv), lo, hi))
               for iv in chips.values()) / len(chips) / 1e9


def least_bytes(inputs, config, chips: int, delivered: np.ndarray) -> float:
    """Mean over chips of (rows reaching the chip under full map-side
    combining + rows it delivers) x record bytes; ``delivered`` the
    reference's [R] partition sizes."""
    import run
    import yardstick
    counts = run.load_metric(ROOT, "combine_a2a_roofline")
    recv = counts.distinct_words(inputs, config, chips).sum(axis=0)
    out = np.bincount(counts.owners(delivered.shape[0], chips),
                      weights=delivered, minlength=chips)
    return float((recv + out).mean()) * yardstick.record_bytes(config)


def read(ctx):
    if ctx.chips < 2 or ctx.trace is None:
        return None
    n = sum(1 for s in ctx.shuffles if not s.error)
    if not n:
        return None
    import devtrace
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(devtrace.find_xplane(
        os.path.join(ctx.root, ".bench_trace", ctx.cell["name"])))
    kernel_s = kernel_seconds(profile)
    if not kernel_s:
        return None
    least = least_bytes(ctx.inputs, ctx.config, ctx.chips,
                        ctx.expected.sizes)
    return 100.0 * least / ctx.peak("hbm_bytes_per_s") / (kernel_s / n)
