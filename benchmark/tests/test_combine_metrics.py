"""The readers of ``wordcount.4chip``'s three per-layer metrics:
``combine.wire_share``, ``combine_a2a_roofline`` and
``segreduce_roofline``. Their least-bytes counts against a brute-force
count over small seeded data, each reader on a hand-built trace whose
numbers are counted by hand, each silent without a trace, and the
segment-reduce kernel found in a trace recorded on a v5e."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(TESTS, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)

import devtrace  # noqa: E402
import run  # noqa: E402
import yardstick  # noqa: E402
from test_progtrace import _write_trace  # noqa: E402

CELL = "wordcount.4chip"
METRICS = ("combine.wire_share", "combine_a2a_roofline",
           "segreduce_roofline")
RECORDED = os.path.join(DATA, "v5e_segreduce.xplane.pb")


def _config():
    return run.load_cell(ROOT, CELL)[2]


def _peaks():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


def _inputs(seed, chips, maps=8, rows=500, vocabulary=40):
    rng = np.random.default_rng(seed)
    return yardstick.MapOutputs(
        [(rng.integers(0, vocabulary, size=rows).astype(np.int64) * 131,
          np.ones((rows, 1), np.int32)) for _ in range(maps)],
        16 * chips, None)


def _brute(inputs, config, chips):
    """[src, dst] distinct words, one key at a time."""
    R = inputs.num_partitions
    per = R // chips
    seen = set()
    for m, (keys, _) in enumerate(inputs.maps):
        for k in keys.tolist():
            part = int(yardstick.partition_of(
                np.array([k], np.int64), config, R, None)[0])
            seen.add((m % chips, part // per, k))
    out = np.zeros((chips, chips), np.int64)
    for src, dst, _ in seen:
        out[src, dst] += 1
    return out


@pytest.mark.parametrize("chips", [2, 4])
@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_least_bytes_equal_brute_force(chips, seed):
    config = _config()
    inputs = _inputs(seed, chips)
    a2a = run.load_metric(ROOT, "combine_a2a_roofline")
    seg = run.load_metric(ROOT, "segreduce_roofline")
    brute = _brute(inputs, config, chips)
    assert (a2a.distinct_words(inputs, config, chips) == brute).all()
    # a word id past the counting range takes the sort
    big = yardstick.MapOutputs(
        [(k + (1 << 40), v) for k, v in inputs.maps], inputs.num_partitions,
        None)
    assert (a2a.distinct_words(big, config, chips)
            == _brute(big, config, chips)).all()
    delivered = yardstick.reference(inputs, config).sizes
    want = np.mean([brute[:, d].sum()
                    + delivered[d * 16:(d + 1) * 16].sum()
                    for d in range(chips)]) * 12
    assert seg.least_bytes(inputs, config, chips, delivered) \
        == pytest.approx(want)


# Two chips, times in ns (offsets are in ps). bench.window [0, 100].
# chip 0: ragged_all_to_all [10, 30], segreduce_blocked [40, 50] and
#   [105, 110] (outside the window); chip 1: ragged_all_to_all [10, 40],
#   segreduce_blocked [45, 65]
#   -> collectives (20 + 30) / 2 = 25 ns, the kernel (10 + 20) / 2 = 15
# host: two shuffle.exchange.wait spans with the combine's counts
#   (in 100, wire 30, out 20; in 50, wire 20, out 15), one without
#   -> wire share (30 + 20) / (100 + 50)
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 40000 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 105000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "%ragged_all_to_all.20 = s32[64,1,128]{2,1,0} ragged-all-to-all(%a, %b)" } }
  event_metadata { key: 2 value { id: 2 name: "%segreduce_blocked.3 = (s32[8,256]{1,0}, s32[1,1]{1,0}) custom-call(%x), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 30000 }
    events { metadata_id: 2 offset_ps: 45000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "%ragged_all_to_all.20 = s32[64,1,128]{2,1,0} ragged-all-to-all(%a, %b)" } }
  event_metadata { key: 2 value { id: 2 name: "%segreduce_blocked.3 = (s32[8,256]{1,0}, s32[1,1]{1,0}) custom-call(%x), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 10000
      stats { metadata_id: 1 int64_value: 100 }
      stats { metadata_id: 2 int64_value: 30 }
      stats { metadata_id: 3 int64_value: 20 } }
    events { metadata_id: 2 offset_ps: 55000 duration_ps: 10000
      stats { metadata_id: 1 int64_value: 50 }
      stats { metadata_id: 2 int64_value: 20 }
      stats { metadata_id: 3 int64_value: 15 } }
    events { metadata_id: 2 offset_ps: 70000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "shuffle.exchange.wait" } }
  stat_metadata { key: 1 value { id: 1 name: "rows_in" } }
  stat_metadata { key: 2 value { id: 2 name: "rows_wire" } }
  stat_metadata { key: 3 value { id: 3 name: "rows_out" } }
}
"""


def _ctx(root, chips, inputs, trace=True, shuffles=1):
    config = _config()
    peaks = _peaks()
    log_dir = os.path.join(root, ".bench_trace", CELL)
    return SimpleNamespace(
        cell={"name": CELL}, root=root, chips=chips, config=config,
        inputs=inputs, expected=yardstick.reference(inputs, config),
        trace=devtrace.load_summary(log_dir) if trace else None,
        shuffles=[SimpleNamespace(error=None)] * shuffles,
        peak=lambda key: float(peaks[key]))


def test_synthetic_trace_counted_by_hand(tmp_path):
    root = str(tmp_path)
    _write_trace(root, CELL, text=SYNTHETIC)
    inputs = _inputs(3, 2)
    ctx = _ctx(root, 2, inputs)
    config, peaks = _config(), _peaks()
    brute = _brute(inputs, config, 2)
    got = {name: run.load_metric(ROOT, name).read(ctx) for name in METRICS}
    out = max(brute[0, 1], brute[1, 0]) * 12
    delivered = ctx.expected.sizes
    seg_bytes = np.mean([brute[:, d].sum()
                         + delivered[d * 16:(d + 1) * 16].sum()
                         for d in range(2)]) * 12
    assert got == pytest.approx({
        "combine.wire_share": 50 / 150,
        "combine_a2a_roofline":
            100 * out * 8 / peaks["ici_bits_per_s"] / 25e-9,
        "segreduce_roofline":
            100 * seg_bytes / peaks["hbm_bytes_per_s"] / 15e-9})


def test_silent_without_a_trace(tmp_path):
    ctx = _ctx(str(tmp_path), 4, _inputs(4, 4), trace=False)
    assert all(run.load_metric(ROOT, name).read(ctx) is None
               for name in METRICS)


def test_silent_where_the_program_left_nothing(tmp_path):
    """The TeraSort trace recorded on one v5e holds no combine counts,
    no collective and no segment-reduce kernel."""
    root = str(tmp_path)
    _write_trace(root, CELL,
                 src=os.path.join(DATA, "v5e_terasort_spans.xplane.pb"))
    ctx = _ctx(root, 4, _inputs(5, 4))
    assert all(run.load_metric(ROOT, name).read(ctx) is None
               for name in METRICS)


def test_recorded_trace_finds_the_kernel(tmp_path):
    """Three calls of the segment-reduce over 16,384-row buffers, traced
    on one v5e inside a ``bench.window`` annotation: the kernel's ops
    are found by their name, and the first is clipped where the window
    opens (its start 44,190,848 ns precedes the window's 44,795,629)."""
    from jax.profiler import ProfileData
    seg = run.load_metric(ROOT, "segreduce_roofline")
    got = seg.kernel_seconds(ProfileData.from_file(RECORDED))
    assert got == pytest.approx((45_079_514 - 44_795_629
                                 + 888_667 + 888_668) * 1e-9)
    root = str(tmp_path)
    _write_trace(root, CELL, src=RECORDED)
    inputs = _inputs(6, 4)
    ctx = _ctx(root, 4, inputs)
    least = seg.least_bytes(inputs, _config(), 4, ctx.expected.sizes)
    assert seg.read(ctx) == pytest.approx(
        100 * least / _peaks()["hbm_bytes_per_s"] / got)
