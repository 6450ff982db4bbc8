"""The program-span reduction (``progtrace.py``) and the per-layer metrics
that read it: on a hand-built trace whose numbers are counted by hand,
on the first recorded v5e trace (which holds no program span, so the
existing reduction must read exactly as before) and on a small v5e trace
recorded with program spans."""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(TESTS, "data")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import progtrace  # noqa: E402
import run  # noqa: E402

NEW_METRICS = ("exchange.idle_ms", "d2h.ms", "drain.host_ms",
               "merge.host_ms", "map.publish_ms", "compile.window_count")

# One chip, times in ns (offsets are in ps). bench.window [0, 100].
# chip ops busy [0, 5] [20, 25] [50, 55] [70, 72]; idle [5, 20] [25, 50]
#   [55, 70] [72, 100] = 83
# host line "python" (the benchmark's thread):
#   bench.write [0, 10], bench.read [10, 60], bench.drain [60, 100]
#   shuffle.submit [10, 50] > shuffle.dispatch [15, 30],
#     shuffle.exchange.wait [30, 45] > np.asarray(jax.Array) [32, 44]
#   shuffle.fetch [62, 90] > shuffle.d2h [65, 75] (bytes 400, rows),
#     shuffle.merge [78, 88] (impl host)
# host line "worker": shuffle.publish [2, 8], compile.step [40, 42] and
#   compile.step [105, 110], outside the window
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 1 offset_ps: 20000 duration_ps: 5000 }
    events { metadata_id: 1 offset_ps: 50000 duration_ps: 5000 }
    events { metadata_id: 1 offset_ps: 70000 duration_ps: 2000 }
    events { metadata_id: 1 offset_ps: 105000 duration_ps: 1000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 72000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "jit_shuffle_exchange_ordered(1)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 10000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 60000 duration_ps: 40000 }
    events { metadata_id: 5 offset_ps: 10000 duration_ps: 40000 }
    events { metadata_id: 6 offset_ps: 15000 duration_ps: 15000 }
    events { metadata_id: 7 offset_ps: 30000 duration_ps: 15000 }
    events { metadata_id: 8 offset_ps: 32000 duration_ps: 12000 }
    events { metadata_id: 9 offset_ps: 62000 duration_ps: 28000 }
    events { metadata_id: 10 offset_ps: 65000 duration_ps: 10000
      stats { metadata_id: 1 int64_value: 400 }
      stats { metadata_id: 2 str_value: "rows" } }
    events { metadata_id: 11 offset_ps: 78000 duration_ps: 10000
      stats { metadata_id: 3 str_value: "host" } } }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 12 offset_ps: 2000 duration_ps: 6000 }
    events { metadata_id: 13 offset_ps: 40000 duration_ps: 2000 }
    events { metadata_id: 13 offset_ps: 105000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.write" } }
  event_metadata { key: 3 value { id: 3 name: "bench.read" } }
  event_metadata { key: 4 value { id: 4 name: "bench.drain" } }
  event_metadata { key: 5 value { id: 5 name: "shuffle.submit" } }
  event_metadata { key: 6 value { id: 6 name: "shuffle.dispatch" } }
  event_metadata { key: 7 value { id: 7 name: "shuffle.exchange.wait" } }
  event_metadata { key: 8 value { id: 8 name: "np.asarray(jax.Array)" } }
  event_metadata { key: 9 value { id: 9 name: "shuffle.fetch" } }
  event_metadata { key: 10 value { id: 10 name: "shuffle.d2h" } }
  event_metadata { key: 11 value { id: 11 name: "shuffle.merge" } }
  event_metadata { key: 12 value { id: 12 name: "shuffle.publish" } }
  event_metadata { key: 13 value { id: 13 name: "compile.step" } }
  stat_metadata { key: 1 value { id: 1 name: "bytes" } }
  stat_metadata { key: 2 value { id: 2 name: "what" } }
  stat_metadata { key: 3 value { id: 3 name: "impl" } }
}
"""

# the idle stretches cut at every host event, counted by hand (ns)
HAND_GAPS = {
    "bench.write:shuffle.publish": 3,           # [5, 8]
    "bench.write": 2,                           # [8, 10]
    "bench.read:shuffle.submit": 10,            # [10, 15] [45, 50]
    "bench.read:shuffle.dispatch": 10,          # [15, 20] [25, 30]
    "bench.read:shuffle.exchange.wait": 3,      # [30, 32] [44, 45]
    "bench.read:shuffle.exchange.wait:np.asarray(jax.Array)": 10,
    "bench.read:compile.step:np.asarray(jax.Array)": 2,   # [40, 42]
    "bench.read": 5,                            # [55, 60]
    "bench.drain": 12,                          # [60, 62] [90, 100]
    "bench.drain:shuffle.fetch": 8,             # [62, 65] [75, 78] [88, 90]
    "bench.drain:shuffle.d2h": 8,               # [65, 70] [72, 75]
    "bench.drain:shuffle.merge": 10,            # [78, 88]
}
# (count, seconds, self seconds, idle seconds) in ns
HAND_SPANS = {
    "shuffle.submit": (1, 40, 10, 35),
    "shuffle.dispatch": (1, 15, 15, 10),
    "shuffle.exchange.wait": (1, 15, 15, 15),
    "shuffle.fetch": (1, 28, 8, 26),
    "shuffle.d2h": (1, 10, 10, 8),
    "shuffle.merge": (1, 10, 10, 10),
    "shuffle.publish": (1, 6, 6, 3),
    "compile.step": (1, 2, 2, 2),
}
# the six metrics over one shuffle, ms
HAND_METRICS = {
    "exchange.idle_ms": 25e-6,      # [15, 20] [25, 45] idle
    "d2h.ms": 10e-6,
    "drain.host_ms": 18e-6,         # fetch 28 less d2h 10
    "merge.host_ms": 10e-6,
    "map.publish_ms": 6e-6,
    "compile.window_count": 1,
}


def _write_trace(root, cell, text=None, src=None):
    """A checkout-like tree holding the trace where ``run.py`` leaves
    it (``<root>/.bench_trace/<cell>``)."""
    d = os.path.join(root, ".bench_trace", cell, "plugins", "profile", "1")
    os.makedirs(d)
    dest = os.path.join(d, "host.xplane.pb")
    if src is not None:
        shutil.copy(src, dest)
    else:
        from jax.profiler import ProfileData
        with open(dest, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))


def _ctx(root, cell, shuffles=1):
    log_dir = os.path.join(root, ".bench_trace", cell)
    return SimpleNamespace(
        cell={"name": cell}, root=root,
        trace=devtrace.load_summary(log_dir),
        shuffles=[SimpleNamespace(error=None)] * shuffles)


def _read(name, ctx):
    return run.load_metric(ROOT, name).read(ctx)


def test_synthetic_spans_counted_by_hand():
    from jax.profiler import ProfileData
    pt = progtrace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert pt.present and pt.window_s == pytest.approx(100e-9)
    assert dict(pt.idle_gaps()) == pytest.approx(
        {k: v * 1e-9 for k, v in HAND_GAPS.items()})
    got = {k: (v.count, v.seconds, v.self_seconds, v.idle_seconds)
           for k, v in pt.stats().items()}
    assert got == {k: pytest.approx((c, s * 1e-9, se * 1e-9, i * 1e-9))
                   for k, (c, s, se, i) in HAND_SPANS.items()}
    named = 3 + 10 + 3 + 10 + 2 + 8 + 8 + 10    # every span but submit
    assert pt.named_share(["bench.write", "bench.read", "bench.drain"]) \
        == pytest.approx(named / 83)
    assert pt.named_share(["bench.write"]) == pytest.approx(3 / 5)


def test_synthetic_metrics(tmp_path):
    root = str(tmp_path)
    _write_trace(root, "synthetic.cell", text=SYNTHETIC)
    ctx = _ctx(root, "synthetic.cell")
    got = {name: _read(name, ctx) for name in NEW_METRICS}
    assert got == pytest.approx(HAND_METRICS)


def test_no_program_span_gives_devtrace_names_and_no_metric(tmp_path):
    """The trace of two 8,192-record TeraSort shuffles on one v5e,
    from a program that annotated nothing: the existing reduction reads
    exactly as before, the three-part names equal its two-part ones, and
    no program metric is reported."""
    path = os.path.join(DATA, "v5e_terasort_small.xplane.pb")
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    s = devtrace.summarize(*devtrace.read_planes(profile))
    with open(os.path.join(DATA, "v5e_terasort_small.summary.json")) as f:
        pinned = json.load(f)
    assert (s.window_s, s.chips, s.busy_s, s.module_s, s.collective_s) == (
        pinned["window_s"], pinned["chips"], pinned["busy_s"],
        pinned["module_s"], pinned["collective_s"])
    assert s.op_s == pinned["op_s"]
    assert [[n, d] for n, d in s.idle_gaps] == pinned["idle_gaps"]
    pt = progtrace.reduce_profile(profile)
    assert not pt.present
    all_gaps = devtrace.summarize(*devtrace.read_planes(profile),
                                  n_gaps=10_000).idle_gaps
    assert pt.idle_gaps() == all_gaps
    root = str(tmp_path)
    _write_trace(root, "terasort.1chip", src=path)
    ctx = _ctx(root, "terasort.1chip", shuffles=2)
    assert all(_read(name, ctx) is None for name in NEW_METRICS)


def test_recorded_trace_with_program_spans(tmp_path):
    """A v5e trace of three 8,192-record TeraSort shuffles from a program
    that puts its spans on the profiler's clock: every new metric reads,
    the host merge reads 0 (no waves), nothing compiles in the window,
    and most of the device's idle time in the read and the drain is
    named by a program span (at this size the benchmark's own loop over
    the maps is most of the write's)."""
    path = os.path.join(DATA, "v5e_terasort_spans.xplane.pb")
    root = str(tmp_path)
    _write_trace(root, "terasort.1chip", src=path)
    ctx = _ctx(root, "terasort.1chip")
    got = {name: _read(name, ctx) for name in NEW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["merge.host_ms"] == 0 and got["compile.window_count"] == 0
    assert all(got[k] > 0 for k in ("exchange.idle_ms", "d2h.ms",
                                     "drain.host_ms", "map.publish_ms"))
    pt = progtrace.for_run(ctx)
    stats = pt.stats()
    assert {"shuffle.write", "shuffle.publish", "shuffle.dispatch",
            "shuffle.exchange.wait", "shuffle.d2h",
            "shuffle.fetch"} <= set(stats)
    assert all(0 <= v.self_seconds <= v.seconds + 1e-12
               and 0 <= v.idle_seconds <= v.seconds + 1e-12
               for v in stats.values())
    assert pt.named_share(["bench.read"]) >= 0.8
    assert pt.named_share(["bench.drain"]) >= 0.8
    assert any(n.count(":") >= 2 for n, _ in pt.idle_gaps())
