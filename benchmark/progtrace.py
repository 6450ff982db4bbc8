"""The program's own spans in a profiler trace (``.xplane.pb``).

The program marks its layer boundaries with ``jax.profiler.TraceAnnotation``
host events named ``shuffle.<...>`` and ``compile.<...>`` (the names
``PROGRAM_PREFIXES`` keeps): ``shuffle.write`` and ``shuffle.publish`` on
the map side, ``shuffle.dispatch``, ``shuffle.exchange.wait`` and
``compile.step`` around the exchange, ``shuffle.d2h`` around each
device-to-host copy of a read's output, ``shuffle.fetch`` and
``shuffle.merge`` in the drain. Their attributes are the events' stats.
This module reads them from every host line, beside ``devtrace``'s
reduction, and reduces them to the ``bench.window`` span (the whole trace
when it is absent):

* per span name: count, seconds (the union of its intervals), self
  seconds (less the program spans nested in it on the same thread) and
  the device's idle seconds under it (mean over chips);
* the first chip's idle time by what the host was doing, named
  ``<bench phase>:<innermost program span>:<runtime event>``. The middle
  part is left out where no program span covers the gap, so on a trace
  without program spans the names and seconds are ``devtrace``'s.

    python3 benchmark/progtrace.py <trace dir>

prints that reduction as one JSON object.
"""

from __future__ import annotations

import functools
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import devtrace  # noqa: E402
from devtrace import Interval, clip, total, union  # noqa: E402

PROGRAM_PREFIXES = ("shuffle.", "compile.")
# the read's own envelope: a gap it names is not yet named by a layer
SUBMIT = "shuffle.submit"
UNMARKED = "host.unmarked"


@dataclass
class Span:
    """One program span, in ns on the trace's clock."""

    name: str
    start: float
    end: float
    line: int                       # index of its host line
    stats: Dict = field(default_factory=dict)


@dataclass
class SpanStat:
    count: int
    seconds: float
    self_seconds: float
    idle_seconds: float


def _intersection(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _complement(busy: List[Interval], lo: float, hi: float
                ) -> List[Interval]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


class ProgramTrace:
    """Program spans and device idle time of one trace, in the window.

    ``spans`` are the program spans that overlap the window (unclipped);
    ``idle`` each chip's idle intervals there; ``gaps`` the first chip's
    idle seconds by (bench phase, innermost program span, innermost
    runtime event), "" where none covers the gap."""

    def __init__(self, lo: float, hi: float, spans: List[Span],
                 idle: Dict[int, List[Interval]],
                 gaps: Dict[Tuple[str, str, str], float]):
        self.lo, self.hi = lo, hi
        self.spans = spans
        self.idle = idle
        self.gaps = gaps

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def present(self) -> bool:
        """Whether the program put any span in the window; a program
        without annotations gives no program metric, not zeros."""
        return bool(self.spans)

    def _union(self, names: Iterable[str],
               where: Optional[Callable[[Span], bool]] = None
               ) -> List[Interval]:
        names = set(names)
        return clip(union([(s.start, s.end) for s in self.spans
                           if s.name in names
                           and (where is None or where(s))]),
                    self.lo, self.hi)

    def count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def seconds(self, names: Iterable[str],
                where: Optional[Callable[[Span], bool]] = None) -> float:
        """Seconds of the window covered by spans of ``names``."""
        return total(self._union(names, where)) / 1e9

    def seconds_less(self, names: Iterable[str],
                     inner: Iterable[str]) -> float:
        """Seconds covered by ``names`` and by no span of ``inner``."""
        outer = self._union(names)
        return (total(outer)
                - total(_intersection(outer, self._union(inner)))) / 1e9

    def idle_seconds(self, names: Iterable[str]) -> float:
        """Device idle seconds under spans of ``names``, mean over chips."""
        cover = self._union(names)
        if not self.idle:
            return 0.0
        return sum(total(_intersection(cover, iv))
                   for iv in self.idle.values()) / len(self.idle) / 1e9

    def stats(self) -> Dict[str, SpanStat]:
        """Per span name: count, seconds, self seconds, idle seconds."""
        children: Dict[str, List[Interval]] = {}
        by_line: Dict[int, List[Span]] = {}
        for s in self.spans:
            by_line.setdefault(s.line, []).append(s)
        for line in by_line.values():
            stack: List[Span] = []
            for s in sorted(line, key=lambda x: (x.start, -x.end)):
                while stack and stack[-1].end <= s.start:
                    stack.pop()
                if stack and s.end <= stack[-1].end:
                    children.setdefault(stack[-1].name, []).append(
                        (s.start, s.end))
                stack.append(s)
        out = {}
        for name in sorted({s.name for s in self.spans}):
            own = self._union([name])
            inner = clip(union(children.get(name, [])), self.lo, self.hi)
            out[name] = SpanStat(
                count=self.count(name), seconds=total(own) / 1e9,
                self_seconds=(total(own)
                              - total(_intersection(own, inner))) / 1e9,
                idle_seconds=self.idle_seconds([name]))
        return out

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """The first chip's idle seconds by three-part name, largest
        first."""
        out: Dict[str, float] = {}
        for (phase, prog, rt), d in self.gaps.items():
            key = ":".join(p for p in (phase, prog, rt) if p)
            out[key] = out.get(key, 0.0) + d
        return sorted(((k, d / 1e9) for k, d in out.items()),
                      key=lambda kv: -kv[1])

    def named_share(self, phases: Iterable[str],
                    exclude: Iterable[str] = (SUBMIT,)) -> Optional[float]:
        """Share of the first chip's idle time inside ``phases`` that a
        program span other than ``exclude`` names."""
        phases, exclude = set(phases), set(exclude)
        idle = named = 0.0
        for (phase, prog, _rt), d in self.gaps.items():
            if phase in phases:
                idle += d
                if prog and prog not in exclude:
                    named += d
        return named / idle if idle else None


def _innermost(t: float, events) -> str:
    """Name of the shortest event that covers time t (the first of
    equals), or ""."""
    best, best_d = "", None
    for name, es, ee, _kind in events:
        if es <= t < ee and (best_d is None or ee - es < best_d):
            best, best_d = name, ee - es
    return best


def _named_gaps(idle: List[Interval], phases, programs, runtime
                ) -> Dict[Tuple[str, str, str], float]:
    """Idle time cut where a host event starts or ends, each piece
    charged to the innermost bench phase, program span and runtime
    event over it — ``devtrace._named_gaps`` with the program span as a
    middle part, in one sweep over the events sorted by start."""
    events = sorted([ev + (0,) for ev in phases]
                    + [ev + (2,) for ev in runtime]
                    + [ev + (1,) for ev in programs], key=lambda ev: ev[1])
    out: Dict[Tuple[str, str, str], float] = {}
    active: list = []
    k = 0
    for s, e in idle:
        while k < len(events) and events[k][1] < e:
            active.append(events[k])
            k += 1
        active = [ev for ev in active if ev[2] > s]
        cuts = sorted({s, e} | {x for ev in active for x in ev[1:3]
                                if s < x < e})
        by_kind = [[ev for ev in active if ev[3] == i] for i in range(3)]
        for a, b in zip(cuts[:-1], cuts[1:]):
            key = (_innermost(a, by_kind[0]) or UNMARKED,
                   _innermost(a, by_kind[1]), _innermost(a, by_kind[2]))
            out[key] = out.get(key, 0.0) + (b - a)
    return out


def reduce_profile(profile) -> ProgramTrace:
    """The ProgramTrace of a ``jax.profiler.ProfileData``."""
    ops: Dict[int, List[Interval]] = {}
    host_lines = []
    for plane in profile.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            chip = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    chip.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                                for ev in line.events)
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    window = None
    spans: List[Span] = []
    phases, runtime = [], []
    for i, line in enumerate(host_lines):
        evs, bench = [], False
        for ev in line.events:
            name = ev.name
            start, end = ev.start_ns, ev.start_ns + ev.duration_ns
            if name.startswith(PROGRAM_PREFIXES):
                spans.append(Span(name, start, end, i, dict(ev.stats)))
            evs.append((name, start, end))
            bench = bench or name.startswith(devtrace.HOST_PHASE_PREFIX)
        if not bench:
            continue
        for ev in evs:
            if ev[0] == devtrace.WINDOW:
                window = window or (ev[1], ev[2])
            elif ev[0].startswith(devtrace.HOST_PHASE_PREFIX):
                phases.append(ev)
            elif not ev[0].startswith(PROGRAM_PREFIXES):
                runtime.append(ev)
    if window is None:
        starts = [s for iv in ops.values() for s, _ in iv]
        ends = [e for iv in ops.values() for _, e in iv]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    lo, hi = window
    idle = {dev: _complement(clip(union(iv), lo, hi), lo, hi)
            for dev, iv in ops.items()}
    spans = [s for s in spans if s.end > lo and s.start < hi]
    programs = [(s.name, s.start, s.end) for s in spans]
    gaps = _named_gaps(idle[min(idle)], phases, programs, runtime) \
        if idle else {}
    return ProgramTrace(lo, hi, spans, idle, gaps)


@functools.lru_cache(maxsize=1)
def _load_file(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def load(log_dir: str) -> ProgramTrace:
    return _load_file(devtrace.find_xplane(log_dir))


def for_run(ctx) -> Optional[ProgramTrace]:
    """The program spans of a ``--trace 1`` run (``run.Context``), read
    once for all the metric readers of the run; None without a trace or
    without a program span in it."""
    if ctx.trace is None:
        return None
    pt = load(os.path.join(ctx.root, ".bench_trace", ctx.cell["name"]))
    return pt if pt.present else None


def per_shuffle_ms(ctx, seconds: float) -> Optional[float]:
    """Window seconds as milliseconds per shuffle without error."""
    n = sum(1 for s in ctx.shuffles if not s.error)
    return seconds * 1e3 / n if n else None


def report(pt: ProgramTrace, n_gaps: int = 40) -> Dict:
    phases = ("bench.write", "bench.read", "bench.drain")
    return {
        "window_s": pt.window_s, "chips": len(pt.idle),
        "spans": {k: vars(v) for k, v in pt.stats().items()},
        "idle_gaps": pt.idle_gaps()[:n_gaps],
        "named_share": {p: pt.named_share([p]) for p in phases}
        | {"all": pt.named_share(phases)},
    }


if __name__ == "__main__":
    print(json.dumps(report(load(sys.argv[1])), indent=1))
