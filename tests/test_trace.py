"""Tracing subsystem tests (utils/trace.py).

The reference has no tracer (SURVEY.md §5) — these tests cover the
do-better subsystem: span recording, nesting, thread tracks, ring-buffer
bounds, Chrome export, conf wiring, and end-to-end spans from a real
shuffle read."""

import json
import threading

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.utils.trace import (GLOBAL_TRACER, Tracer,
                                      configure_from_conf)


def test_disabled_tracer_records_nothing():
    t = Tracer(enabled=False)
    with t.span("x"):
        pass
    t.instant("marker")
    assert t.spans() == []


def test_disabled_span_is_shared_noop():
    """A disabled span is the profiler annotation itself: no ring span,
    no clock read, no other allocation."""
    import jax.profiler
    from sparkucx_tpu.utils.trace import _LiveSpan
    t = Tracer(enabled=False)
    sp = t.span("a", rows=3)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert not isinstance(sp, _LiveSpan)
    assert type(sp) is type(t.span("b"))


def test_span_timing_and_attrs():
    t = Tracer(enabled=True)
    with t.span("work", shuffle_id=7) as s:
        s.set(rows=123)
    (span,) = t.spans()
    assert span.name == "work"
    assert span.attrs == {"shuffle_id": 7, "rows": 123}
    assert span.dur_us >= 0
    assert span.depth == 0


def test_nesting_depth():
    t = Tracer(enabled=True)
    with t.span("outer"):
        with t.span("inner"):
            pass
    by_name = {s.name: s for s in t.spans()}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    # inner finishes first (recorded first)
    assert t.spans()[0].name == "inner"


def test_threads_get_own_tracks():
    t = Tracer(enabled=True)

    def work():
        with t.span("threaded"):
            pass

    th = threading.Thread(target=work)
    th.start()
    th.join()
    with t.span("main"):
        pass
    tids = {s.tid for s in t.spans()}
    assert len(tids) == 2


def test_ring_buffer_bound_and_drop_count():
    t = Tracer(enabled=True, capacity=4)
    for i in range(10):
        t.instant(f"e{i}")
    assert len(t.spans()) == 4
    assert t.dropped == 6
    assert [s.name for s in t.spans()] == ["e6", "e7", "e8", "e9"]


def test_publish_dropped_watermark_delta():
    """Ring drops surface as the ``trace.spans.dropped`` counter in
    watermark-delta style: each publish adds only the drops since the
    last one, so the periodic settlement hook keeps counter semantics
    (the dark_time doctor rule reads this to tell ring pressure from an
    instrumentation hole)."""
    from sparkucx_tpu.utils.metrics import C_TRACE_DROPPED, Metrics
    t = Tracer(enabled=True, capacity=4)
    m = Metrics()
    assert t.publish_dropped(m) == 0            # nothing dropped yet
    assert m.get(C_TRACE_DROPPED) == 0.0
    for i in range(10):
        t.instant(f"e{i}")
    assert t.publish_dropped(m) == 6
    assert m.get(C_TRACE_DROPPED) == 6.0
    assert t.publish_dropped(m) == 0            # no double counting
    assert m.get(C_TRACE_DROPPED) == 6.0
    t.instant("e10")                            # one more falls off
    assert t.publish_dropped(m) == 1
    assert m.get(C_TRACE_DROPPED) == 7.0


def test_summary_aggregates():
    t = Tracer(enabled=True)
    for _ in range(5):
        with t.span("op"):
            pass
    s = t.summary()["op"]
    assert s["count"] == 5
    assert s["total_ms"] >= 0
    assert s["p50_ms"] <= s["max_ms"]


def test_chrome_export(tmp_path):
    t = Tracer(enabled=True)
    with t.span("exported", k="v"):
        pass
    path = str(tmp_path / "trace.json")
    n = t.export_chrome_trace(path)
    assert n == 1
    doc = json.load(open(path))
    (ev,) = doc["traceEvents"]
    assert ev["name"] == "exported"
    assert ev["ph"] == "X"
    assert ev["args"] == {"k": "v"}


def test_export_nonjsonable_attr(tmp_path):
    t = Tracer(enabled=True)
    t.instant("x", arr=np.arange(3))
    path = str(tmp_path / "t.json")
    t.export_chrome_trace(path)
    doc = json.load(open(path))
    assert "arr" in doc["traceEvents"][0]["args"]


def test_configure_from_conf():
    conf = TpuShuffleConf({"spark.shuffle.tpu.trace.enabled": "true",
                           "spark.shuffle.tpu.trace.capacity": "128"},
                          use_env=False)
    tr = configure_from_conf(conf)
    try:
        assert tr is GLOBAL_TRACER
        assert tr.enabled
        assert tr._capacity == 128
    finally:
        tr.enabled = False
        tr.clear()


def test_resize_shrink_counts_discards_as_dropped():
    """A capacity shrink discards the oldest buffered spans — those must
    land in the drop count (no silent truncation), and the count must
    survive the resize."""
    t = Tracer(enabled=True, capacity=8)
    for i in range(10):
        t.instant(f"e{i}")
    assert t.dropped == 2
    t.resize(4)
    assert len(t.spans()) == 4
    assert t.dropped == 2 + 4          # prior drops + shrink discards
    assert [s.name for s in t.spans()] == ["e6", "e7", "e8", "e9"]
    t.resize(4)                        # no-op resize changes nothing
    assert t.dropped == 6


def test_configure_from_conf_resize_preserves_drop_count():
    conf = TpuShuffleConf({"spark.shuffle.tpu.trace.enabled": "true",
                           "spark.shuffle.tpu.trace.capacity": "8"},
                          use_env=False)
    tr = configure_from_conf(conf)
    try:
        tr.clear()
        for i in range(12):
            tr.instant(f"e{i}")
        assert tr.dropped == 4
        conf.set("spark.shuffle.tpu.trace.capacity", "4")
        tr2 = configure_from_conf(conf)
        assert tr2 is tr
        assert tr.dropped == 4 + 4
        assert len(tr.spans()) == 4
    finally:
        tr.enabled = False
        tr.clear()
        tr.resize(65536)


def test_dropped_read_is_locked_during_concurrent_records():
    """Reading .dropped while writers hammer the ring must never tear or
    race; final count is exact."""
    t = Tracer(enabled=True, capacity=16)
    N, THREADS = 400, 4

    def work():
        for i in range(N):
            t.instant("x")

    threads = [threading.Thread(target=work) for _ in range(THREADS)]
    for th in threads:
        th.start()
    reads = [t.dropped for _ in range(100)]   # concurrent locked reads
    for th in threads:
        th.join()
    assert reads == sorted(reads)             # monotone, never torn
    assert t.dropped == N * THREADS - 16


def test_clear_resets():
    t = Tracer(enabled=True, capacity=2)
    for i in range(5):
        t.instant(str(i))
    t.clear()
    assert t.spans() == []
    assert t.dropped == 0


@pytest.fixture()
def annotations(monkeypatch):
    """Spy on ``jax.profiler.TraceAnnotation``: every annotation the
    tracer builds, as [name, attrs, entered, exited], then the attrs of
    each later ``set_metadata``."""
    import jax.profiler
    from sparkucx_tpu.utils import trace as trace_mod
    seen = []
    real = jax.profiler.TraceAnnotation

    class Spy(real):
        def __init__(self, name, **attrs):
            super().__init__(name, **attrs)
            self._rec = [name, attrs, 0, 0]
            seen.append(self._rec)

        def __enter__(self):
            self._rec[2] += 1
            return super().__enter__()

        def __exit__(self, *exc):
            self._rec[3] += 1
            return super().__exit__(*exc)

        def set_metadata(self, **attrs):
            self._rec.append(attrs)
            return super().set_metadata(**attrs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(trace_mod, "_ANNOTATION", None)   # rebuilt on Spy
    return seen


@pytest.mark.parametrize("enabled", [False, True])
def test_every_span_enters_one_annotation(annotations, enabled):
    """Spans reach the profiler whether or not the ring is on; the ring
    records only when enabled."""
    t = Tracer(enabled=enabled)
    with t.span("shuffle.outer", shuffle_id=4):
        with t.span("shuffle.d2h", bytes=64, what="rows"):
            pass
    assert annotations == [["shuffle.outer", {"shuffle_id": 4}, 1, 1],
                           ["shuffle.d2h", {"bytes": 64, "what": "rows"},
                            1, 1]]
    assert [s.name for s in t.spans()] == (
        ["shuffle.d2h", "shuffle.outer"] if enabled else [])


def test_disabled_span_set_is_noop(annotations):
    """Nothing reaches the ring; the attrs go to the annotation's
    stats."""
    t = Tracer(enabled=False)
    with t.span("shuffle.fetch", partition=2) as sp:
        assert sp.set(bytes=10) is sp
    assert annotations == [["shuffle.fetch", {"partition": 2}, 1, 1,
                            {"bytes": 10}]]
    assert t.spans() == []


def test_enabled_span_set_updates_ring_only(annotations):
    t = Tracer(enabled=True)
    with t.span("shuffle.fetch", partition=2) as sp:
        sp.set(bytes=10)
    (span,) = t.spans()
    assert span.attrs == {"partition": 2, "bytes": 10}
    assert span.depth == 0 and span.dur_us >= 0
    # the annotation took its stats at entry, and set()'s while open
    assert annotations == [["shuffle.fetch", {"partition": 2}, 1, 1,
                            {"bytes": 10}]]


def test_spans_land_in_a_profiler_capture(tmp_path):
    """With a capture running, a disabled tracer's spans are host events
    of the trace, named as the span, its attrs as the event's stats."""
    import glob

    import jax.profiler
    t = Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("shuffle.read.test"):
            with t.span("shuffle.d2h", bytes=4096, what="seg"):
                pass
            with t.span("shuffle.merge", impl="host") as sp:
                sp.set(rows_in=7, rows_out=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = {ev.name: dict(ev.stats)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert events["shuffle.read.test"] == {}
    assert events["shuffle.d2h"] == {"bytes": 4096, "what": "seg"}
    assert events["shuffle.merge"] == {"impl": "host", "rows_in": 7,
                                       "rows_out": 3}


def test_shuffle_read_emits_spans(manager_factory):
    """End-to-end: a real shuffle read leaves plan/pack/exchange/publish
    spans in the node tracer."""
    mgr = manager_factory({"spark.shuffle.tpu.trace.enabled": "true"})
    tracer = mgr.node.tracer
    tracer.clear()
    try:
        h = mgr.register_shuffle(901, num_maps=4, num_partitions=8)
        rng = np.random.default_rng(0)
        for m in range(4):
            w = mgr.get_writer(h, m)
            w.write(rng.integers(0, 1 << 20, size=64))
            w.commit(h.num_partitions)
        mgr.read(h)
        names = {s.name for s in tracer.spans()}
        assert {"shuffle.plan", "shuffle.stage", "shuffle.pack",
                "shuffle.dispatch", "shuffle.publish", "shuffle.write",
                "shuffle.d2h"} <= names
        pub = tracer.spans("shuffle.publish")
        assert len(pub) == 4
        assert {s.attrs["map_id"] for s in pub} == {0, 1, 2, 3}
    finally:
        mgr.unregister_shuffle(901)
        tracer.enabled = False
        tracer.clear()
