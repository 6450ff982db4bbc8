"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

A device plane is one chip (``/device:TPU:<n>``). Its ``XLA Ops`` line
holds one event per operation run on the chip, ``Async XLA Ops`` the
spans of asynchronous ones (copies, collectives: start to done) and
``XLA Modules`` one event per program launch. The benchmark marks its own phases on the host
with ``jax.profiler.TraceAnnotation`` named ``bench.<phase>``; the
``bench.window`` annotation spans the measured window.

From these the reduction gives, clipped to the window: busy seconds per
chip (the union of the op intervals), program and collective seconds,
seconds per op, and the device's idle time by what the host was doing:
the benchmark phase over it and, after a colon, the innermost runtime
event of the same host thread (``bench.drain:np.asarray(jax.Array)``).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PHASE_PREFIX = "bench."
WINDOW = "bench.window"
# "%fusion.4 = s32[3340960,25]{0,1:T(8,128)} fusion(...)" -> "fusion.4
# s32[3340960,25]": the op and its result shape, without the layout
OP_TEXT = re.compile(r"^%?([^\s=]+) = (\(?[a-z0-9]+\[[0-9,]*\])?")
# HLO names the ragged all-to-all "ragged_all_to_all", the others with "-"
COLLECTIVE = re.compile(
    r"(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|reduce[-_]scatter|"
    r"collective[-_]permute)")

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping intervals covering the input."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


@dataclass
class Chip:
    """One device plane, in ns on the trace's clock."""

    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    async_ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Summary:
    """The trace reduced to the window. Times in seconds."""

    window_s: float
    chips: int
    busy_s: float                      # mean over chips
    module_s: float                    # program time, mean over chips
    collective_s: float                # collective spans, mean over chips
    op_s: Dict[str, float]             # per op name, mean over chips
    idle_gaps: List[Tuple[str, float]]  # first chip, by host activity

    def top_ops(self, n: int = 10) -> List[List]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s] for name, s in ops]


def op_name(text: str) -> str:
    m = OP_TEXT.match(text)
    if not m:
        return text[:80]
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m.group(2) \
        else m.group(1)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def read_planes(profile) -> Tuple[Dict[int, Chip], List[Tuple[str, float,
                                                                float]]]:
    """(chips by device id, host events) from a ProfileData. The host
    events are those of the threads that carry ``bench.`` phases."""
    chips: Dict[int, Chip] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), Chip())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = chip.ops
                elif line.name == ASYNC_LINE:
                    dest = chip.async_ops
                elif line.name == MODULES_LINE:
                    dest = chip.modules
                else:
                    continue
                for ev in line.events:
                    name = ev.name if dest is chip.modules \
                        else op_name(ev.name)
                    dest.append((name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if any(n.startswith(HOST_PHASE_PREFIX) for n, _, _ in evs):
                    host.extend(evs)
    return chips, host


def summarize(chips: Dict[int, Chip],
              host: List[Tuple[str, float, float]],
              n_gaps: int = 10) -> Summary:
    """Reduce device and host events to the ``bench.window`` span (the
    whole trace when it is absent)."""
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        ends = [e for c in chips.values() for _, _, e in c.ops]
        starts = [s for c in chips.values() for _, s, _ in c.ops]
        lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    n = max(1, len(chips))
    busy = module = coll = 0.0
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    phases = [(name, s, e) for name, s, e in host
              if name.startswith(HOST_PHASE_PREFIX) and name != WINDOW]
    inner = [(name, s, e) for name, s, e in host
             if not name.startswith(HOST_PHASE_PREFIX)]
    for dev in sorted(chips):
        chip = chips[dev]
        busy_iv = clip(union([(s, e) for _, s, e in chip.ops]), lo, hi)
        busy += total(busy_iv)
        module += total(clip(union([(s, e) for _, s, e in chip.modules]),
                             lo, hi))
        coll += total(clip(union([(s, e) for name, s, e
                                  in chip.ops + chip.async_ops
                                  if COLLECTIVE.search(name)]), lo, hi))
        for name, s, e in chip.ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d
        if dev == min(chips):
            gaps = _named_gaps(busy_iv, lo, hi, phases, inner)
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) / 1e9, chips=len(chips),
        busy_s=busy / n / 1e9, module_s=module / n / 1e9,
        collective_s=coll / n / 1e9,
        op_s={k: v / n / 1e9 for k, v in op_s.items()},
        idle_gaps=[(name, d / 1e9) for name, d in gaps[:n_gaps]])


def _innermost(t: float, events) -> str:
    """Name of the shortest event that covers time t, or ""."""
    best, best_d = "", None
    for name, es, ee in events:
        if es <= t < ee and (best_d is None or ee - es < best_d):
            best, best_d = name, ee - es
    return best


def _named_gaps(busy: List[Interval], lo: float, hi: float,
                phases, inner) -> List[Tuple[str, float]]:
    """Idle time of one chip inside [lo, hi] by what the host was doing:
    every idle stretch is cut where a host event starts or ends, and each
    piece is charged to the benchmark phase over it ("host.unmarked"
    where none is) and, after a colon, the innermost runtime event of the
    benchmark's thread over it. Totals per name."""
    events = sorted(phases + inner, key=lambda ev: ev[1])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out: Dict[str, float] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        over = [ev for ev in events if ev[1] < e and ev[2] > s]
        cuts = sorted({s, e} | {x for _, es, ee in over for x in (es, ee)
                                if s < x < e})
        for a, b in zip(cuts[:-1], cuts[1:]):
            name = _innermost(a, [ev for ev in over if ev in phases]) \
                or "host.unmarked"
            detail = _innermost(a, [ev for ev in over if ev not in phases])
            key = f"{name}:{detail}" if detail else name
            out[key] = out.get(key, 0.0) + (b - a)
    return list(out.items())


def load_summary(log_dir: str) -> Summary:
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(find_xplane(log_dir))
    chips, host = read_planes(profile)
    if not chips:
        raise ValueError(f"trace under {log_dir} holds no TPU device plane")
    return summarize(chips, host)


def profile_options():
    """Host annotations and device activity. The runtime's own host events
    (level 2: millions of transpose chunks a window), Python call tracing
    and HLO protos would swell the trace and slow the host."""
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def breakdown(summary: Summary, n: int = 10) -> Dict:
    return {"device_ops": summary.top_ops(n),
            "idle_gaps": [[name, s] for name, s in summary.idle_gaps[:n]]}
