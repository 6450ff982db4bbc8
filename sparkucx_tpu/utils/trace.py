"""Structured tracing — spans, Chrome-trace export, profiler annotations.

The reference has no tracer; its observability is targeted latency logging
(map-publish overhead per mapId, ref: CommonUcxShuffleBlockResolver.scala:105-106;
per-request completion ms, ref: UcxWorkerWrapper.scala:101-103; per-endpoint
fetch bytes+ms, ref: OnBlocksFetchCallback.java:55-56). SURVEY.md §5 calls for
"the same spirit via structured timers + jax.profiler traces" — this module is
that: nested wall-clock spans on the host side, each also a
``jax.profiler.TraceAnnotation`` so it lands on the profiler's clock beside
the device's ops whenever a profiler capture runs, and a Chrome
``chrome://tracing`` / Perfetto export so a shuffle's publish → plan →
exchange → group timeline is inspectable without one.

Design constraints:

* **Profiler annotation always, ring on demand.** Every ``span()`` enters a
  ``TraceAnnotation`` named after the span, with its attrs as the event's
  stats, and ``set()`` on an open span adds stats to it; with no capture
  running that costs under a microsecond. The span
  ring (timings, attrs, the anatomy fold, the Chrome export) records only
  when the tracer is enabled: conf key ``spark.shuffle.tpu.trace.enabled``
  (env ``SPARKUCX_TPU_TRACE_ENABLED=1``) or ``Tracer(enabled=True)``. A
  disabled span is the annotation itself — no other allocation, no clock
  read.
* **Thread-safe, nesting-aware.** Spans nest per-thread (a reduce task's
  ``exchange`` span sits under its ``read`` span); cross-thread events land
  on their own track, like the reference's per-task-thread workers
  (ref: UcxNode.java:85-95).
* **Bounded memory.** A ring buffer of ``capacity`` finished spans; drops are
  counted, never silent (the same no-silent-truncation policy as the data
  plane's overflow flag).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from sparkucx_tpu.utils.logging import get_logger

log = get_logger("trace")


def format_trace_id(shuffle_id: int, epoch: int, seq: int) -> str:
    """The cluster-correlation key ``(shuffle_id, epoch, exchange_seq)``
    as one grep-able token, ``s<sid>.e<epoch>.x<seq>``. Reads are
    collective and execute in the same order on every process (the SPMD
    discipline), so the per-process exchange sequence number agrees
    cluster-wide — the same trace id names the same exchange in every
    process's spans, reports and flight events."""
    return f"s{shuffle_id}.e{epoch}.x{seq}"


@dataclass
class Span:
    """One finished span (Chrome trace 'X' event)."""

    name: str
    start_us: float
    dur_us: float
    tid: int
    depth: int
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def dur_ms(self) -> float:
        return self.dur_us / 1e3


_ANNOTATION = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` with the span surface's no-op
    ``set()`` — what a disabled tracer's ``span()`` returns. Built on
    first use, so importing this module does not import JAX."""
    global _ANNOTATION
    if _ANNOTATION is None:
        import jax.profiler

        class _Annotation(jax.profiler.TraceAnnotation):
            __slots__ = ()

            def set(self, **attrs):  # parity with _LiveSpan
                # more stats on the event (a no-op with no capture on)
                self.set_metadata(**attrs)
                return self

        _ANNOTATION = _Annotation
    return _ANNOTATION


class _LiveSpan:
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_annot")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._annot = None

    def set(self, **attrs) -> "_LiveSpan":
        self.attrs.update(attrs)
        if self._annot is not None:
            self._annot.set_metadata(**attrs)
        return self

    def __enter__(self) -> "_LiveSpan":
        tls = self._tracer._tls
        tls.depth = getattr(tls, "depth", 0) + 1
        # the annotation's own cost falls inside the timed span, so the
        # anatomy fold does not see it as dark time between spans
        self._t0 = time.perf_counter()
        self._annot = _annotation_class()(self.name, **self.attrs)
        self._annot.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._annot.__exit__(*exc)
        t1 = time.perf_counter()
        tls = self._tracer._tls
        depth = getattr(tls, "depth", 1)
        tls.depth = depth - 1
        self._tracer._record(Span(
            name=self.name,
            start_us=(self._t0 - self._tracer._epoch) * 1e6,
            dur_us=(t1 - self._t0) * 1e6,
            tid=threading.get_ident(),
            depth=depth - 1,
            attrs=self.attrs,
        ))
        return False


class Tracer:
    """Span collector with Chrome-trace export; every span is also a
    profiler annotation (module doc)."""

    def __init__(self, enabled: bool = False, capacity: int = 65536):
        self.enabled = enabled
        self._spans: deque = deque(maxlen=capacity)
        self._dropped = 0
        self._published_dropped = 0
        self._capacity = capacity
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._epoch = time.perf_counter()

    # -- recording --------------------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager marking one region: a profiler annotation
        always, and a timed span in the ring when enabled."""
        if not self.enabled:
            return (_ANNOTATION or _annotation_class())(name, **attrs)
        return _LiveSpan(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker event."""
        if not self.enabled:
            return
        self._record(Span(name, (time.perf_counter() - self._epoch) * 1e6,
                          0.0, threading.get_ident(), 0, attrs))

    def record_span(self, name: str, t0: float, t1: Optional[float] = None,
                    **attrs) -> None:
        """Record a span from explicit ``perf_counter`` endpoints — for
        regions whose start predates knowing whether (or under what
        name/attrs) they'd be recorded. The exchange WALL span is the
        canonical user: ``ExchangeReport`` stamps its start inside
        ``submit()`` and the settlement callback closes it with the
        trace id only once the read is fully notified. Ring only — its
        endpoints are in the past, so no profiler annotation can mark
        it. No-op when disabled — ONE branch, so the disabled read path
        pays a single attribute check per exchange."""
        if not self.enabled:
            return
        if t1 is None:
            t1 = time.perf_counter()
        self._record(Span(name, (t0 - self._epoch) * 1e6,
                          (t1 - t0) * 1e6, threading.get_ident(), 0,
                          attrs))

    def _record(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) == self._capacity:
                self._dropped += 1
            self._spans.append(s)

    # -- clock anchoring ---------------------------------------------------
    def anchor(self) -> Dict[str, float]:
        """The wall↔perf anchor pair that makes this process's span
        timestamps comparable across processes. Span ``start_us`` is
        ``perf_counter`` relative to the tracer's private epoch — a
        monotonic clock with an arbitrary per-process zero — so two
        processes' spans cannot be merged without knowing where each
        epoch sits on the (NTP-shared) wall clock. ``wall_epoch`` is
        exactly that: the wall time at span ts=0, sampled as an adjacent
        (time.time, perf_counter) pair so the conversion error is one
        scheduler quantum, not the process's lifetime drift. Embedded in
        every snapshot/dump (export.collect_snapshot) and allgathered at
        connect (runtime/node.py) so offline timeline merging is exact."""
        perf = time.perf_counter()
        wall = time.time()
        return {
            "wall": wall,                       # the sample pair itself
            "perf": perf,
            "perf_epoch": self._epoch,          # span ts=0 in perf time
            "wall_epoch": wall - (perf - self._epoch),  # span ts=0, wall
            "pid": float(os.getpid()),
        }

    # -- inspection -------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        return [s for s in out if s.name == name] if name else out

    def spans_ending_after(self, t_us: float) -> List[Span]:
        """Spans whose END falls at/after ``t_us`` (tracer-epoch µs),
        oldest-first. The ring appends in END order (a span records at
        __exit__), so a reversed walk can stop at the first span that
        ended earlier — the anatomy fold's per-exchange cost is bounded
        by the spans of THAT exchange, not the ring's full capacity."""
        out: List[Span] = []
        with self._lock:
            for s in reversed(self._spans):
                if s.start_us + s.dur_us < t_us:
                    break
                out.append(s)
        out.reverse()
        return out

    def publish_dropped(self, metrics) -> int:
        """Publish ring drops into ``metrics`` as the
        ``trace.spans.dropped`` counter, watermark-delta style: each
        call adds only the drops since the last publish, so periodic
        callers (the exchange settlement hook) keep counter semantics
        over a monotonically growing internal total. Returns the delta."""
        with self._lock:
            delta = self._dropped - self._published_dropped
            self._published_dropped = self._dropped
        if delta > 0:
            from sparkucx_tpu.utils.metrics import C_TRACE_DROPPED
            metrics.inc(C_TRACE_DROPPED, delta)
        return delta

    @property
    def dropped(self) -> int:
        # under the lock: an unsynchronized read can observe a torn
        # update relative to the span append it pairs with (_record holds
        # the lock for both), so exporters could report a drop count that
        # disagrees with the buffer they just copied
        with self._lock:
            return self._dropped

    def resize(self, capacity: int) -> None:
        """Resize the span ring, preserving buffered spans (newest-first
        within the new capacity) and the drop count — spans discarded by
        a shrink are counted as dropped, same no-silent-truncation policy
        as the ring itself. One atomic mutation under the lock: a
        concurrent _record must never see capacity and deque disagree."""
        with self._lock:
            if capacity == self._capacity:
                return
            discarded = max(0, len(self._spans) - capacity)
            self._dropped += discarded
            self._capacity = capacity
            self._spans = deque(self._spans, maxlen=capacity)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name {count, total_ms, mean_ms, p50_ms, p99_ms, max_ms}
        aggregate — the MemoryPool-stats-at-close analog
        (ref: MemoryPool.java:30-39). p50/p99 mirror the reference's
        per-fetch latency log (ref: OnBlocksFetchCallback.java:55-56),
        which BASELINE.md adopts as half its metric."""
        groups: Dict[str, List[float]] = defaultdict(list)
        for s in self.spans():
            groups[s.name].append(s.dur_ms)
        out = {}
        for name, ds in groups.items():
            ds.sort()
            out[name] = {
                "count": float(len(ds)),
                "total_ms": sum(ds),
                "mean_ms": sum(ds) / len(ds),
                "p50_ms": ds[len(ds) // 2],
                "p99_ms": ds[min(len(ds) - 1, (len(ds) * 99) // 100)],
                "max_ms": ds[-1],
            }
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0
            self._published_dropped = 0

    # -- export -----------------------------------------------------------
    def chrome_events(self) -> List[Dict[str, Any]]:
        """The span buffer as Chrome trace-event dicts (the 'X' events of
        a ``traceEvents`` list) — shared by the file export, snapshot
        embedding, and the flight recorder's postmortem. Runs per
        snapshot/doctor pass over the full ring, so the conversion skips
        the per-attr sanitizer pass when every attr is already a
        primitive (the overwhelmingly common case)."""
        out: List[Dict[str, Any]] = []
        prim = (str, int, float, bool)
        for s in self.spans():
            attrs = s.attrs
            if attrs and any(type(v) not in prim and v is not None
                             for v in attrs.values()):
                attrs = {k: _jsonable(v) for k, v in attrs.items()}
            else:
                attrs = dict(attrs)    # events must not alias the span
            out.append({
                "name": s.name, "ph": "X", "ts": s.start_us,
                "dur": s.dur_us, "pid": 0, "tid": s.tid, "args": attrs})
        return out

    def export_chrome_trace(self, path: str) -> int:
        """Write the span buffer as a Chrome trace-event JSON file, loadable
        in Perfetto / chrome://tracing. Returns the number of events."""
        events = self.chrome_events()
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        from sparkucx_tpu.utils.atomicio import atomic_write_json
        atomic_write_json(path, doc, indent=None)
        dropped = self.dropped
        if dropped:
            log.warning("trace export dropped %d spans (capacity %d)",
                        dropped, self._capacity)
        return len(events)


def _jsonable(v):
    # fast path: span attrs are overwhelmingly primitives, and a doctor/
    # snapshot pass renders every buffered span — a json.dumps probe per
    # attr dominated chrome_events()
    if v is None or type(v) in (str, int, float, bool):
        return v
    try:
        json.dumps(v)
        return v
    except TypeError:
        return repr(v)


GLOBAL_TRACER = Tracer(enabled=False)


def configure_from_conf(conf) -> Tracer:
    """Wire the global tracer from conf keys:

    ``spark.shuffle.tpu.trace.enabled``   span ring on (default off)
    ``spark.shuffle.tpu.trace.capacity``  span ring size (default 65536)
    """
    GLOBAL_TRACER.enabled = conf.get_bool("trace.enabled", False)
    GLOBAL_TRACER.resize(conf.get_int("trace.capacity", 65536))
    return GLOBAL_TRACER
