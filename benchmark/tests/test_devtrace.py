"""The trace reduction, on a hand-built trace whose numbers are counted
by hand and on a small trace recorded on a v5e."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Two chips, times in ns (offsets are in ps). Host bench.window: [10, 100].
# chip 0 ops: fusion.1 [10, 30], sort.2 [20, 40], all-to-all.3 [60, 70]
#   busy 30 + 10 = 40, module jit_step [10, 70] = 60; collectives 20:
#   the all-to-all and an asynchronous all-gather [45, 55], which is
#   not busy time
#   idle: [40, 60] under bench.read [35, 80]; [70, 100] cut at 80 into
#   10 under bench.read and 20 under bench.drain [80, 100]
# chip 1 ops: fusion.1 [0, 20] -> [10, 20], all-to-all.3 [90, 120] ->
#   [90, 100]: busy 20, collectives 10, module [0, 120] -> 90
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 20000 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 60000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 10000 duration_ps: 60000 } }
  lines { id: 3 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 45000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" } }
  event_metadata { key: 5 value { id: 5 name: "%all-gather-start.1 = s32[4]{0} all-gather-start(s32[1]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "all-to-all.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(1)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 90000 duration_ps: 30000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 120000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "all-to-all.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(1)" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 90000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 15000 }
    events { metadata_id: 3 offset_ps: 35000 duration_ps: 45000 }
    events { metadata_id: 4 offset_ps: 80000 duration_ps: 20000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.write" } }
  event_metadata { key: 3 value { id: 3 name: "bench.read" } }
  event_metadata { key: 4 value { id: 4 name: "bench.drain" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(step)" } }
}
"""


def _summary(text):
    from jax.profiler import ProfileData
    return devtrace.summarize(*devtrace.read_planes(
        ProfileData.from_text_proto(text)))


def test_union_and_clip():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                 (5, 8)]
    assert devtrace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert devtrace.total([(2, 3), (5, 6)]) == 2


def test_synthetic_trace_counted_by_hand():
    s = _summary(SYNTHETIC)
    assert s.chips == 2
    assert s.window_s == pytest.approx(90e-9)
    assert s.busy_s == pytest.approx((40 + 20) / 2 * 1e-9)
    assert s.collective_s == pytest.approx((20 + 10) / 2 * 1e-9)
    assert s.module_s == pytest.approx((60 + 90) / 2 * 1e-9)
    assert s.op_s["sort.2"] == pytest.approx(20 / 2 * 1e-9)
    assert s.op_s["fusion.1"] == pytest.approx((20 + 10) / 2 * 1e-9)
    assert s.top_ops(1)[0][0] == "fusion.1"
    # chip 0's idle time by the host phase over it, largest first
    assert [name for name, _ in s.idle_gaps] == ["bench.read",
                                                 "bench.drain"]
    assert [d for _, d in s.idle_gaps] == pytest.approx([30e-9, 20e-9])
    b = devtrace.breakdown(s)
    assert set(b) == {"device_ops", "idle_gaps"}


def test_no_window_means_whole_trace():
    text = SYNTHETIC.replace('name: "bench.window"', 'name: "other"')
    s = _summary(text)
    assert s.window_s == pytest.approx(120e-9)


def test_recorded_trace():
    """A trace of two 8,192-record TeraSort shuffles on one v5e."""
    path = os.path.join(RECORDED, "v5e_terasort_small.xplane.pb")
    from jax.profiler import ProfileData
    chips, host = devtrace.read_planes(ProfileData.from_file(path))
    s = devtrace.summarize(chips, host)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.module_s <= s.window_s
    assert s.collective_s == 0
    assert sum(s.op_s.values()) >= s.busy_s * (1 - 1e-9)
    # the idle time is charged to what the host was doing, the read's
    # device-to-host copy among it
    assert sum(d for _, d in s.idle_gaps) <= s.window_s - s.busy_s + 1e-12
    assert "bench.drain:np.asarray(jax.Array)" in dict(s.idle_gaps)
