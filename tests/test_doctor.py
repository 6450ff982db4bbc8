"""Doctor + timeline tests — the analysis layer over the telemetry plane.

Golden-finding tests build synthetic snapshots that trip exactly one rule
each (plus a healthy-cluster fixture asserting ZERO findings — the
doctor's "all clear" is a contract, not an absence of code paths);
histogram merge/round-trip property tests pin the exact-aggregation
claim vs numpy; timeline tests pin anchor-based clock alignment and the
anchor-less rejection.
"""

import contextlib
import io
import json

import numpy as np
import pytest

from sparkucx_tpu.utils.doctor import (Finding, Thresholds, build_view,
                                       diagnose, render_findings)
from sparkucx_tpu.utils.metrics import (H_FETCH_FIRST, H_FETCH_WAIT,
                                        H_RETRY_MS, Histogram, Metrics)


# -- synthetic snapshot builders -------------------------------------------
def _anchor():
    import time
    perf = time.perf_counter()
    wall = time.time()
    return {"wall": wall, "perf": perf, "perf_epoch": perf,
            "wall_epoch": wall, "pid": 1.0}


def _hist_snap(values, name="h"):
    h = Histogram(name)
    for v in values:
        h.observe(float(v))
    return h.snapshot()


def _report(sid=1, trace="s1.e0.x1", process_id=0, peer_rows=None,
            skew=1.0, retries=0, programs=0, group_ms=10.0,
            completed=True):
    peer_rows = peer_rows if peer_rows is not None else [100] * 8
    return {
        "shuffle_id": sid, "trace_id": trace, "process_id": process_id,
        "num_maps": 8, "num_partitions": 8, "partitioner": "hash",
        "peer_rows": list(peer_rows),
        "peer_bytes": [r * 8 for r in peer_rows],
        "skew_ratio": skew, "retries": retries,
        "stepcache_programs": programs, "stepcache_hits": 4,
        "group_ms": group_ms, "plan_bucket": [128, 256],
        "completed": completed,
    }


def _healthy_doc():
    """Balanced cluster, steady state: every rule must stay quiet."""
    return {
        "anchor": _anchor(), "process_id": 0,
        "counters": {"compile.step.programs": 2.0,
                     "compile.step.hits": 98.0,
                     "shuffle.read.count": 50.0},
        "histograms": {
            H_FETCH_WAIT: _hist_snap([10.0 + i % 3 for i in range(50)]),
            # present but under the 10x cold-start ratio vs wait p50
            H_FETCH_FIRST: _hist_snap([80.0]),
        },
        "exchange_reports": [
            _report(sid=i, trace=f"s{i}.e0.x{i}") for i in range(1, 5)],
        "pool": {"requests": 100, "allocated": 4096, "preallocated": 64,
                 "in_use": 12},
    }


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# -- healthy baseline ------------------------------------------------------
def test_healthy_cluster_zero_findings():
    assert diagnose(_healthy_doc()) == []
    text = render_findings([])
    assert "healthy" in text


def test_empty_process_zero_findings():
    """A fresh process (pre-registered empty histograms, no reports)
    diagnoses clean — rules need signal, not just keys."""
    m = Metrics()
    from sparkucx_tpu.utils.export import collect_snapshot
    assert diagnose(collect_snapshot(m)) == []


# -- one golden fixture per rule -------------------------------------------
def test_straggler_peer_bytes_outlier():
    doc = _healthy_doc()
    doc["exchange_reports"].append(_report(
        sid=9, trace="s9.e0.x9", peer_rows=[100, 100, 100, 100,
                                            100, 100, 100, 1000]))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["straggler_peer"]
    f = fs[0]
    assert f.grade in ("warn", "critical")
    assert f.evidence["peer"] == 7
    assert f.conf_key == "spark.shuffle.tpu.network.timeoutMs"
    assert "s9.e0.x9" in f.trace_ids


def test_straggler_process_group_ms_outlier():
    """Cluster mode: gathered reports for the SAME exchange, one process
    far over the cluster median group time."""
    docs = []
    for p in range(4):
        doc = {"anchor": _anchor(), "process_id": p, "counters": {},
               "histograms": {},
               "exchange_reports": [_report(
                   sid=3, trace="s3.e0.x7", process_id=p,
                   group_ms=2000.0 if p == 2 else 100.0)]}
        docs.append(doc)
    fs = diagnose(docs)
    assert _rules_of(fs) == ["straggler_peer"]
    f = fs[0]
    assert f.grade == "critical"          # 20x median, >= 2x ratio
    assert f.evidence["process_id"] == 2
    assert f.trace_ids == ["s3.e0.x7"]


def test_straggler_ignores_warmup_reads():
    """The same outlier shape must NOT fire when the outlier report is a
    compile-bearing (warmup) read — the first-wait split exists exactly
    so the doctor can discard these."""
    docs = []
    for p in range(4):
        doc = {"anchor": _anchor(), "process_id": p, "counters": {},
               "histograms": {},
               "exchange_reports": [_report(
                   sid=3, trace="s3.e0.x7", process_id=p,
                   programs=1,            # <- compiled during this read
                   group_ms=2000.0 if p == 2 else 100.0)]}
        docs.append(doc)
    assert diagnose(docs) == []


def test_partition_skew_grades():
    doc = _healthy_doc()
    doc["exchange_reports"].append(_report(sid=5, trace="s5.e0.x5",
                                           skew=6.0))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["partition_skew"]
    assert fs[0].grade == "warn"
    assert fs[0].conf_key == "spark.shuffle.tpu.a2a.capacityFactor"
    doc["exchange_reports"].append(_report(sid=6, trace="s6.e0.x6",
                                           skew=32.0))
    fs = diagnose(doc)
    assert fs[0].grade == "critical"      # most severe first
    assert fs[0].evidence["skew_ratio"] == 32.0
    assert fs[0].trace_ids == ["s6.e0.x6"]


def test_retry_storm():
    doc = _healthy_doc()
    doc["histograms"][H_RETRY_MS] = _hist_snap([50.0] * 12)
    fs = diagnose(doc)
    assert _rules_of(fs) == ["retry_storm"]
    assert fs[0].grade == "critical"      # 12 >= retry_critical
    assert fs[0].evidence["retries"] == 12
    assert fs[0].conf_key == "spark.shuffle.tpu.failure.maxAttempts"


def test_compile_churn():
    doc = _healthy_doc()
    doc["counters"]["compile.step.programs"] = 40.0
    doc["counters"]["compile.step.hits"] = 10.0
    doc["counters"]["compile.step.seconds"] = 80.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["compile_churn"]
    assert fs[0].grade == "critical"      # 80% miss
    assert fs[0].conf_key == "spark.shuffle.tpu.a2a.capBucketGrowth"
    assert fs[0].evidence["compile_seconds"] == 80.0


def test_pool_pressure():
    doc = _healthy_doc()
    doc["pool"] = {"requests": 500, "allocated": 64, "preallocated": 8,
                   "in_use": 62}
    fs = diagnose(doc)
    assert _rules_of(fs) == ["pool_pressure"]
    assert fs[0].conf_key == \
        "spark.shuffle.tpu.memory.preAllocateBuffers"
    assert fs[0].evidence["in_use"] == 62


def test_overflow_loop():
    doc = _healthy_doc()
    doc["exchange_reports"].extend([
        _report(sid=7, trace="s7.e0.x7", retries=2),
        _report(sid=8, trace="s8.e0.x8", retries=1)])
    fs = diagnose(doc)
    assert _rules_of(fs) == ["overflow_loop"]
    assert fs[0].evidence["total_retries"] == 3
    assert fs[0].conf_key == "spark.shuffle.tpu.a2a.capacityFactor"


def test_cold_start_info():
    doc = _healthy_doc()
    doc["histograms"][H_FETCH_FIRST] = _hist_snap([3000.0, 2800.0])
    fs = diagnose(doc)
    assert _rules_of(fs) == ["cold_start"]
    assert fs[0].grade == "info"
    assert fs[0].conf_key == "spark.shuffle.tpu.compile.cacheEnabled"


def _wave_report(sid=9, trace="s9.e0.x9", waves=6, pack_ms=8.0,
                 wait_ms=0.05):
    """A waved report whose steady-state packs cost ``pack_ms`` and whose
    drains waited ``wait_ms`` — the pipeline_stall inputs."""
    r = _report(sid=sid, trace=trace)
    r["waves"] = waves
    r["wave_rows"] = 4096
    tl = []
    t = 0.0
    for i in range(waves):
        tl.append({"wave": i, "rows": 4096,
                   "pack_start_ms": round(t, 3),
                   "pack_ms": pack_ms, "dispatch_ms": 0.5,
                   "hidden": i > 0,
                   "forced_ms": round(t + pack_ms + 0.5, 3),
                   "wait_ms": wait_ms, "retries": 0})
        t += pack_ms + 0.5 + wait_ms
    r["wave_timeline"] = tl
    r["wave_pack_hidden_ms"] = pack_ms * (waves - 1)
    return r


def test_pipeline_stall_pack_bound():
    """Waves whose packs outrun the collective (drain wait ~0 while packs
    cost ms) — the device idles between waves: pipeline_stall fires and
    points at a2a.waveRows/packThreads."""
    doc = _healthy_doc()
    doc["exchange_reports"].append(
        _wave_report(sid=9, trace="s9.e0.x9", pack_ms=8.0, wait_ms=0.05))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["pipeline_stall"]
    assert fs[0].grade == "warn"
    assert fs[0].conf_key == "spark.shuffle.tpu.a2a.waveRows"
    assert "packThreads" in fs[0].remediation
    assert fs[0].evidence["pack_p50_ms"] == 8.0
    assert fs[0].trace_ids == ["s9.e0.x9"]


def test_pipeline_stall_quiet_when_collective_bound():
    """A healthy pipeline — the collective outlives each pack (drain
    waits dominate) — must not fire, and neither must too-few waves or
    sub-noise packs."""
    doc = _healthy_doc()
    # collective-bound: waits far exceed the stall fraction of packs
    doc["exchange_reports"].append(
        _wave_report(sid=9, trace="s9.e0.x9", pack_ms=8.0, wait_ms=20.0))
    # too few waves for a verdict
    doc["exchange_reports"].append(
        _wave_report(sid=10, trace="s10.e0.x10", waves=2, pack_ms=9.0,
                     wait_ms=0.0))
    # sub-noise packs: nothing worth hiding
    doc["exchange_reports"].append(
        _wave_report(sid=11, trace="s11.e0.x11", pack_ms=0.3,
                     wait_ms=0.0))
    assert diagnose(doc) == []


def _hbm_gauges(in_use, limit, device=0):
    from sparkucx_tpu.utils.metrics import (G_HBM_IN_USE, G_HBM_LIMIT,
                                            labeled)
    return {labeled(G_HBM_IN_USE, device=device): in_use,
            labeled(G_HBM_LIMIT, device=device): limit}


def test_hbm_pressure_fires_on_near_limit_device():
    doc = _healthy_doc()
    doc["gauges"] = _hbm_gauges(30.5e9, 32e9, device=3)    # ~95%
    fs = diagnose(doc)
    assert _rules_of(fs) == ["hbm_pressure"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["device"] == "3"
    assert f.evidence["ratio"] == pytest.approx(30.5 / 32, abs=1e-3)
    assert f.conf_key == "spark.shuffle.tpu.a2a.waveRows"
    assert "waveRows" in f.remediation
    # critical past the hard ceiling
    doc["gauges"] = _hbm_gauges(31.6e9, 32e9)              # ~99%
    assert diagnose(doc)[0].grade == "critical"


def test_hbm_pressure_quiet_when_healthy_or_sub_noise():
    doc = _healthy_doc()
    # healthy: half the HBM free
    doc["gauges"] = _hbm_gauges(16e9, 32e9)
    assert diagnose(doc) == []
    # sub-noise: a toy/virtual device limit never counts as pressure
    doc["gauges"] = _hbm_gauges(0.99e6, 1e6)
    assert diagnose(doc) == []
    # partial sample (no limit reported — the CPU shape): quiet
    from sparkucx_tpu.utils.metrics import G_HBM_IN_USE, labeled
    doc["gauges"] = {labeled(G_HBM_IN_USE, device=0): 1e9}
    assert diagnose(doc) == []


def _bw_doc(bw_values, with_report=True):
    from sparkucx_tpu.utils.metrics import H_BW
    doc = _healthy_doc()
    doc["histograms"][H_BW] = _hist_snap(list(bw_values))
    if with_report:
        # a collective-dominated steady exchange as supporting evidence
        r = _report(sid=9, trace="s9.e0.x9", group_ms=400.0)
        r["bw_gbps"] = min(bw_values)
        r["pack_ms"] = 20.0
        r["dispatch_ms"] = 5.0
        doc["exchange_reports"].append(r)
    return doc


def test_bw_underutilization_fires_on_wide_spread():
    """p50 far below the best bw the same link demonstrated, with a
    collective-dominated exchange in the ring: warn, pointing at the
    pipeline depth."""
    doc = _bw_doc([0.2] * 8 + [2.0] * 2)
    fs = diagnose(doc)
    assert _rules_of(fs) == ["bw_underutilization"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["bw_best_gbps"] == pytest.approx(2.0, rel=0.1)
    assert f.evidence["ratio"] >= 4.0
    assert f.evidence["worst_shuffle_id"] == 9
    assert f.conf_key == "spark.shuffle.tpu.a2a.waveDepth"
    assert "packThreads" in f.remediation
    assert "s9.e0.x9" in f.trace_ids


def test_bw_underutilization_quiet_goldens():
    # healthy: a tight distribution is a utilized link
    assert diagnose(_bw_doc([1.0, 1.1, 0.9, 1.0, 1.05, 0.95],
                            with_report=False)) == []
    # sub-noise: the spread is wide but the link never demonstrated
    # real throughput (tiny exchanges time noise, not bandwidth)
    assert diagnose(_bw_doc([0.001] * 8 + [0.01] * 2,
                            with_report=False)) == []
    # signal floor: too few exchanges for a verdict
    assert diagnose(_bw_doc([0.2, 2.0], with_report=False)) == []


def _pad_report(sid=9, trace="s9.e0.x9", pad_ratio=8.0, payload_mb=4.0,
                impl="dense", waves=0):
    """A completed exchange whose wire carried ``pad_ratio`` x its real
    payload — the padding_waste inputs (plan.RaggedLayout accounting)."""
    r = _report(sid=sid, trace=trace)
    r["impl"] = impl
    r["payload_bytes"] = int(payload_mb * 1e6)
    r["wire_bytes"] = int(payload_mb * 1e6 * pad_ratio)
    r["pad_ratio"] = pad_ratio
    r["waves"] = waves
    return r


def test_padding_waste_fires_on_padded_dense_wire():
    """A dense exchange shipping 8x its payload in padded caps: warn,
    pointing at the ragged-capable transport conf."""
    doc = _healthy_doc()
    doc["exchange_reports"].append(_pad_report(pad_ratio=8.0))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["padding_waste"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["pad_ratio"] == 8.0
    assert f.evidence["impl"] == "dense"
    assert f.conf_key == "spark.shuffle.tpu.a2a.impl"
    assert "ragged" in f.remediation
    assert "s9.e0.x9" in f.trace_ids


def test_padding_waste_critical_on_skew_amplified_waste():
    """Skew-regrown caps multiplying the padded wire grade critical, and
    the WORST offender is the one reported."""
    doc = _healthy_doc()
    doc["exchange_reports"].append(_pad_report(sid=9, pad_ratio=8.0))
    doc["exchange_reports"].append(
        _pad_report(sid=10, trace="s10.e0.x10", pad_ratio=40.0, waves=4))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["padding_waste"]
    f = fs[0]
    assert f.grade == "critical"
    assert f.evidence["shuffle_id"] == 10
    assert f.evidence["waves"] == 4
    assert "waved" in f.summary


def test_padding_waste_quiet_goldens():
    # ragged-native path: every wire byte is a real byte — quiet
    doc = _healthy_doc()
    doc["exchange_reports"].append(
        _pad_report(pad_ratio=1.0, impl="native"))
    assert diagnose(doc) == []
    # modest padding below the warn threshold — quiet
    doc = _healthy_doc()
    doc["exchange_reports"].append(_pad_report(pad_ratio=2.5))
    assert diagnose(doc) == []
    # sub-noise: huge ratio but the wire moved almost nothing (tiny test
    # exchange under the min-wire floor, PR-5 discipline)
    doc = _healthy_doc()
    doc["exchange_reports"].append(
        _pad_report(pad_ratio=64.0, payload_mb=0.01))
    assert diagnose(doc) == []
    # reports with no accounting (pre-ragged dumps) — quiet, not a crash
    doc = _healthy_doc()
    assert diagnose(doc) == []


def _wire_report(sid=12, trace="s12.e0.x12", err=0.08, payload_mb=4.0,
                 wire="int8"):
    """A completed int8-wire exchange whose sampled dequantization-error
    estimate is ``err`` — the wire_dequant_error inputs (the manager's
    shuffle/wire.py sampling pass)."""
    r = _report(sid=sid, trace=trace)
    r["impl"] = "dense"
    r["wire"] = wire
    r["wire_dequant_error"] = err
    r["payload_bytes"] = int(payload_mb * 1e6)
    r["wire_bytes"] = int(payload_mb * 1e6 * 0.3)
    r["pad_ratio"] = 0.3
    return r


def test_wire_dequant_fires_on_lossy_payload():
    """An int8-wire exchange rounding away 8% of the signal energy:
    warn, pointing at the exact tiers."""
    doc = _healthy_doc()
    doc["exchange_reports"].append(_wire_report(err=0.08))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["wire_dequant_error"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["wire_dequant_error"] == 0.08
    assert f.evidence["impl"] == "dense"
    assert f.conf_key == "spark.shuffle.tpu.a2a.wire"
    assert "lossless" in f.remediation and "raw" in f.remediation
    assert "s12.e0.x12" in f.trace_ids


def test_wire_dequant_critical_reports_worst_offender():
    """A quarter of the signal energy lost grades critical, and the
    WORST offender is the one reported."""
    doc = _healthy_doc()
    doc["exchange_reports"].append(_wire_report(sid=12, err=0.08))
    doc["exchange_reports"].append(
        _wire_report(sid=13, trace="s13.e0.x13", err=0.4))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["wire_dequant_error"]
    f = fs[0]
    assert f.grade == "critical"
    assert f.evidence["shuffle_id"] == 13
    assert "s13.e0.x13" in f.trace_ids


def test_wire_dequant_quiet_goldens():
    # well-conditioned payload: the estimate sits at the ~0.005 floor
    doc = _healthy_doc()
    doc["exchange_reports"].append(_wire_report(err=0.004))
    assert diagnose(doc) == []
    # raw exchange with a (stale/meaningless) error field — the rule
    # grades the int8 tier only
    doc = _healthy_doc()
    doc["exchange_reports"].append(_wire_report(err=0.4, wire="raw"))
    assert diagnose(doc) == []
    # sub-noise: lossy but the exchange moved almost nothing (tiny test
    # shuffle under the min-payload floor, the PR-5 discipline)
    doc = _healthy_doc()
    doc["exchange_reports"].append(
        _wire_report(err=0.4, payload_mb=0.01))
    assert diagnose(doc) == []
    # pre-wire dumps (no wire field at all) — quiet, not a crash
    doc = _healthy_doc()
    assert diagnose(doc) == []


def _peer_lost_report(sid=11, trace="s11.e0.x11"):
    r = _report(sid=sid, trace=trace, completed=False)
    r["error"] = ("PeerLostError: collective 'metadata allgather' "
                  "outlived failure.collectiveTimeoutMs=500")
    return r


def test_peer_timeout_fires_on_watchdog_expiry():
    """One deadline expiry is already a warn — the fence filtered the
    noise by construction — with the stuck exchange's trace id and the
    probe verdict as evidence."""
    doc = _healthy_doc()
    doc["counters"]["failure.peer_timeout.count"] = 1.0
    doc["exchange_reports"].append(_peer_lost_report())
    fs = diagnose(doc)
    assert _rules_of(fs) == ["peer_timeout"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["timeouts"] == 1
    assert f.evidence["probe_dead_devices"] == 0
    assert 11 in f.evidence["stuck_exchanges"]
    assert "s11.e0.x11" in f.trace_ids
    assert f.conf_key == "spark.shuffle.tpu.failure.collectiveTimeoutMs"
    assert "remesh" in f.remediation


def test_peer_timeout_critical_goldens():
    # a probe-confirmed dead device escalates even a single expiry
    doc = _healthy_doc()
    doc["counters"]["failure.peer_timeout.count"] = 1.0
    doc["counters"]["failure.probe.dead"] = 2.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["peer_timeout"]
    assert fs[0].grade == "critical"
    assert fs[0].evidence["probe_dead_devices"] == 2
    assert "2 dead device" in fs[0].summary
    # so does a repeat offender even with healthy local probes — and the
    # summary redirects suspicion at the remote process / the fabric
    doc = _healthy_doc()
    doc["counters"]["failure.peer_timeout.count"] = 3.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["peer_timeout"]
    assert fs[0].grade == "critical"
    assert "remote process or the fabric" in fs[0].summary


def test_peer_timeout_quiet_without_expiry():
    """No watchdog expiry: quiet even with probe.dead noise from an
    unrelated health check — the deadline counter is the only trigger
    (the rule has no noise floor BECAUSE the fence already is one)."""
    doc = _healthy_doc()
    doc["counters"]["failure.probe.dead"] = 1.0
    assert diagnose(doc) == []


def _replayed_report(sid=12, trace="s12.e1.x12", replays=1,
                     replay_ms=40.0):
    r = _report(sid=sid, trace=trace)
    r["replays"] = replays
    r["replay_ms"] = replay_ms
    return r


def test_replay_storm_fires_and_grades():
    doc = _healthy_doc()
    doc["exchange_reports"].append(_replayed_report(replays=2))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["replay_storm"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["replays"] == 2
    assert 12 in f.evidence["shuffle_ids"]
    assert f.conf_key == "spark.shuffle.tpu.failure.policy"
    assert "s12.e1.x12" in f.trace_ids
    # budget-sized totals across shuffles grade critical, with the wall
    # burned in failed attempts summed as evidence
    doc = _healthy_doc()
    doc["exchange_reports"].append(_replayed_report(replays=2))
    doc["exchange_reports"].append(
        _replayed_report(sid=13, trace="s13.e2.x13", replays=2,
                         replay_ms=60.0))
    fs = diagnose(doc)
    assert _rules_of(fs) == ["replay_storm"]
    assert fs[0].grade == "critical"
    assert fs[0].evidence["replays"] == 4
    assert fs[0].evidence["replay_ms"] == 100.0


def test_replay_storm_counter_backstop():
    """Replays whose reports were evicted from the retained ring still
    count: the cumulative shuffle.replay.count counter floors the
    report-window sum."""
    doc = _healthy_doc()
    doc["counters"]["shuffle.replay.count"] = 5.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["replay_storm"]
    assert fs[0].grade == "critical"
    assert fs[0].evidence["replays"] == 5


def test_block_corruption_fires_warn_and_names_traces():
    """One detected corruption is a warning (the verifier filtered the
    noise by construction), with the corrupt counters and the typed
    reports' trace ids as evidence, remediating toward
    integrity.verify / failure.ledgerDir."""
    doc = _healthy_doc()
    doc["counters"]["shuffle.integrity.verified.bytes"] = 1e8
    doc["counters"]["shuffle.integrity.corrupt.count"] = 1.0
    doc["counters"]["shuffle.integrity.corrupt.bytes"] = 4096.0
    rep = _report(sid=33, trace="s33.e0.x33", completed=False)
    rep["error"] = ("BlockCorruptionError('shuffle 33: block corruption "
                    "detected in map 1')")
    doc["exchange_reports"].append(rep)
    fs = diagnose(doc)
    assert _rules_of(fs) == ["block_corruption"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["corrupt_blocks"] == 1
    assert f.evidence["corrupt_bytes"] == 4096
    assert 33 in f.evidence["shuffle_ids"]
    assert "s33.e0.x33" in f.trace_ids
    assert f.conf_key == "spark.shuffle.tpu.integrity.verify"
    assert "failure.ledgerDir" in f.remediation


def test_block_corruption_critical_goldens():
    # repeated corruption past the corrupt-counter floor -> critical
    doc = _healthy_doc()
    doc["counters"]["shuffle.integrity.corrupt.count"] = 3.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["block_corruption"]
    assert fs[0].grade == "critical"
    # ANY ledger quarantine -> critical, even a single block
    doc = _healthy_doc()
    doc["counters"]["shuffle.integrity.quarantined.count"] = 1.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["block_corruption"]
    assert fs[0].grade == "critical"
    assert fs[0].evidence["quarantined_blocks"] == 1


def test_block_corruption_quiet_goldens():
    # healthy cluster with NO integrity counters: quiet (covered by the
    # shared healthy fixture, asserted explicitly here)
    assert diagnose(_healthy_doc()) == []
    # sub-noise: terabytes VERIFIED with zero corrupt blocks is health,
    # not a finding — verified.bytes alone never fires
    doc = _healthy_doc()
    doc["counters"]["shuffle.integrity.verified.bytes"] = 1e12
    doc["counters"]["shuffle.integrity.corrupt.count"] = 0.0
    doc["counters"]["shuffle.integrity.corrupt.bytes"] = 0.0
    assert diagnose(doc) == []


def test_replay_storm_quiet_on_single_absorbed_blip():
    # one replay is the policy doing its job (sub-noise) — quiet
    doc = _healthy_doc()
    doc["exchange_reports"].append(_replayed_report(replays=1))
    assert diagnose(doc) == []


# -- host_roundtrip (read.sink) --------------------------------------------
def _roundtrip_report(sid=13, trace="s13.e0.x13", d2h_mb=4.0,
                      sink="host"):
    r = _report(sid=sid, trace=trace)
    r["sink"] = sink
    r["d2h_bytes"] = int(d2h_mb * 1e6)
    return r


def test_host_roundtrip_fires_on_reuploaded_drain():
    doc = _healthy_doc()
    doc["exchange_reports"].append(_roundtrip_report())
    doc["counters"]["shuffle.read.d2h.bytes"] = 4e6
    doc["counters"]["shuffle.consume.h2d.bytes"] = 4e6
    fs = [f for f in diagnose(doc) if f.rule == "host_roundtrip"]
    assert len(fs) == 1
    f = fs[0]
    assert f.grade == "warn"
    assert f.conf_key == "spark.shuffle.tpu.read.sink"
    assert f.evidence["roundtrip_bytes"] == int(4e6)
    assert f.evidence["worst_shuffle_id"] == 13
    assert "s13.e0.x13" in f.trace_ids


def test_host_roundtrip_critical_goldens():
    # (a) volume: one read round-tripping past the critical byte floor
    doc = _healthy_doc()
    doc["exchange_reports"].append(_roundtrip_report(d2h_mb=128.0))
    doc["counters"]["shuffle.consume.h2d.bytes"] = 128e6
    fs = [f for f in diagnose(doc) if f.rule == "host_roundtrip"]
    assert fs and fs[0].grade == "critical"
    # (b) repetition: several reads each paying the tax
    doc = _healthy_doc()
    for i in range(3):
        doc["exchange_reports"].append(
            _roundtrip_report(sid=20 + i, trace=f"s{20 + i}.e0.x1"))
    doc["counters"]["shuffle.consume.h2d.bytes"] = 12e6
    fs = [f for f in diagnose(doc) if f.rule == "host_roundtrip"]
    assert fs and fs[0].grade == "critical"
    assert fs[0].evidence["host_sink_reads"] == 3


def test_host_roundtrip_quiet_goldens():
    # device-sink read: d2h 0 on the report, no h2d — the fixed state
    doc = _healthy_doc()
    doc["exchange_reports"].append(
        _roundtrip_report(d2h_mb=0.0, sink="device"))
    assert [f for f in diagnose(doc)
            if f.rule == "host_roundtrip"] == []
    # host-only consumer: big drains but NOTHING re-uploaded — draining
    # is what host sinks are FOR (arrow egress, numpy analytics)
    doc = _healthy_doc()
    doc["exchange_reports"].append(_roundtrip_report(d2h_mb=256.0))
    doc["counters"]["shuffle.read.d2h.bytes"] = 256e6
    assert [f for f in diagnose(doc)
            if f.rule == "host_roundtrip"] == []


def test_host_roundtrip_sub_noise_floor():
    # h2d present but every host read drained below the min-bytes floor
    # — tiny test exchanges, not a round-trip tax
    doc = _healthy_doc()
    doc["exchange_reports"].append(_roundtrip_report(d2h_mb=0.1))
    doc["counters"]["shuffle.consume.h2d.bytes"] = 1e5
    assert [f for f in diagnose(doc)
            if f.rule == "host_roundtrip"] == []


# -- sink_fallback (read.sink, device-merge era) ---------------------------
def test_sink_fallback_fires_and_names_mode_and_reason():
    doc = _healthy_doc()
    doc["counters"]["shuffle.sink.fallback.count"] = 2
    doc["counters"][
        'shuffle.sink.fallback.count{mode="combine",'
        'reason="distributed"}'] = 2
    fs = [f for f in diagnose(doc) if f.rule == "sink_fallback"]
    assert len(fs) == 1
    f = fs[0]
    assert f.grade == "warn"
    assert f.conf_key == "spark.shuffle.tpu.read.sink"
    assert f.evidence["fallbacks"] == 2
    assert f.evidence["by_mode"] == {"combine": 2}
    assert f.evidence["by_reason"] == {"distributed": 2}
    assert "combine" in f.summary and "device" in f.summary


def test_sink_fallback_critical_on_repetition():
    doc = _healthy_doc()
    doc["counters"]["shuffle.sink.fallback.count"] = 12
    doc["counters"][
        'shuffle.sink.fallback.count{mode="ordered",'
        'reason="conf_pins_host"}'] = 12
    fs = [f for f in diagnose(doc) if f.rule == "sink_fallback"]
    assert fs and fs[0].grade == "critical"
    assert fs[0].evidence["by_mode"] == {"ordered": 12}


def test_sink_fallback_quiet_without_device_asks():
    # no read ever asked for a device sink it didn't get — the healthy
    # doc carries no fallback counter at all
    assert [f for f in diagnose(_healthy_doc())
            if f.rule == "sink_fallback"] == []
    # host-sink reads with big drains but no device ask stay quiet too
    doc = _healthy_doc()
    doc["exchange_reports"].append(_roundtrip_report(d2h_mb=64.0))
    doc["counters"]["shuffle.read.d2h.bytes"] = 64e6
    assert [f for f in diagnose(doc)
            if f.rule == "sink_fallback"] == []


# -- kernel_fallback (read.mergeImpl, blocked-kernel era) -------------------
def test_kernel_fallback_fires_and_names_reason():
    doc = _healthy_doc()
    doc["counters"]["shuffle.kernel.fallback.count"] = 3
    doc["counters"][
        'shuffle.kernel.fallback.count{reason="subword_dtype"}'] = 3
    fs = [f for f in diagnose(doc) if f.rule == "kernel_fallback"]
    assert len(fs) == 1
    f = fs[0]
    assert f.grade == "warn"
    assert f.conf_key == "spark.shuffle.tpu.read.mergeImpl"
    assert f.evidence["fallbacks"] == 3
    assert f.evidence["by_reason"] == {"subword_dtype": 3}
    assert "subword_dtype" in f.summary and "pallas" in f.summary
    # the remediation names the capability gates, not just the knob
    assert "TPU" in f.remediation and "4-byte" in f.remediation


def test_kernel_fallback_critical_on_repetition():
    doc = _healthy_doc()
    doc["counters"]["shuffle.kernel.fallback.count"] = 9
    doc["counters"][
        'shuffle.kernel.fallback.count'
        '{reason="backend_unsupported"}'] = 9
    fs = [f for f in diagnose(doc) if f.rule == "kernel_fallback"]
    assert fs and fs[0].grade == "critical"
    assert fs[0].evidence["by_reason"] == {"backend_unsupported": 9}


def test_kernel_fallback_quiet_without_pallas_asks():
    # no read ever pinned mergeImpl=pallas — the healthy doc carries no
    # fallback counter; 'auto' resolving to jnp off-TPU increments
    # NOTHING (resolve_kernel_impl returns reason=None), so a busy
    # CPU-backend doc with reads but no counter stays quiet too
    assert [f for f in diagnose(_healthy_doc())
            if f.rule == "kernel_fallback"] == []
    doc = _healthy_doc()
    doc["exchange_reports"].append(_roundtrip_report(d2h_mb=64.0))
    assert [f for f in diagnose(doc)
            if f.rule == "kernel_fallback"] == []


def test_gauges_attribute_per_process_in_cluster_view():
    """build_view keeps gauges per process (point-in-time values must
    attribute, never sum) and hbm_pressure names the pressed process."""
    docs = []
    for p in range(3):
        doc = {"anchor": _anchor(), "process_id": p, "counters": {},
               "histograms": {},
               "gauges": _hbm_gauges(31e9 if p == 2 else 4e9, 32e9)}
        docs.append(doc)
    view = build_view(docs)
    assert len(view.gauges) == 3
    fs = diagnose(docs)
    assert _rules_of(fs) == ["hbm_pressure"]
    assert fs[0].evidence["process_id"] == 2


def test_findings_sorted_and_jsonable():
    doc = _healthy_doc()
    doc["histograms"][H_FETCH_FIRST] = _hist_snap([3000.0])   # info
    doc["exchange_reports"].append(_report(sid=6, trace="t", skew=32.0))
    fs = diagnose(doc)
    grades = [f.grade for f in fs]
    order = {"critical": 0, "warn": 1, "info": 2}
    assert grades == sorted(grades, key=order.__getitem__)
    json.dumps([f.to_dict() for f in fs])
    text = render_findings(fs)
    assert "spark.shuffle.tpu.a2a.capacityFactor" in text
    with pytest.raises(ValueError):
        Finding(rule="x", grade="fatal", summary="nope")


def test_cluster_view_aggregates_exactly():
    """Counters sum, histograms merge exactly, reports concatenate with
    process attribution."""
    docs = []
    for p in range(3):
        docs.append({
            "process_id": p,
            "counters": {"c": 2.0},
            "histograms": {"h": _hist_snap([10.0 * (p + 1)] * 4)},
            "exchange_reports": [_report(sid=p, process_id=p)],
        })
    view = build_view(docs)
    assert view.processes == 3
    assert view.counters["c"] == 6.0
    assert view.histograms["h"].count == 12
    assert view.histograms["h"].max == pytest.approx(30.0, rel=0.05)
    assert sorted(r["process_id"] for r in view.reports) == [0, 1, 2]


# -- histogram round-trip / merge vs numpy ---------------------------------
@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_histogram_snapshot_roundtrip_exact(dist, rng):
    draws = {
        "lognormal": lambda: rng.lognormal(3.0, 1.5, size=5000),
        "uniform": lambda: rng.uniform(0.1, 1000.0, size=5000),
        "exponential": lambda: rng.exponential(50.0, size=5000),
    }[dist]()
    h = Histogram("t")
    for v in draws:
        h.observe(v)
    snap = json.loads(json.dumps(h.to_snapshot()))   # through the wire
    h2 = Histogram.from_snapshot(snap, "t")
    assert h2.count == h.count
    assert h2.sum == pytest.approx(h.sum)
    assert h2.min == h.min and h2.max == h.max
    for q in (0.5, 0.9, 0.99):
        assert h2.quantile(q) == pytest.approx(h.quantile(q))
    assert h2.buckets() == h.buckets()               # bit-exact ladder


def test_histogram_merge_matches_union(rng):
    """merge(a, b) must equal observing the union — and both track the
    numpy quantiles of the combined sample within the ladder bound."""
    a_draws = rng.lognormal(2.0, 1.0, size=4000)
    b_draws = rng.exponential(200.0, size=4000)
    ha, hb, hu = Histogram("a"), Histogram("b"), Histogram("u")
    for v in a_draws:
        ha.observe(v)
        hu.observe(v)
    for v in b_draws:
        hb.observe(v)
        hu.observe(v)
    ha.merge(hb)
    assert ha.count == hu.count
    assert ha.sum == pytest.approx(hu.sum)
    assert ha.buckets() == hu.buckets()
    union = np.concatenate([a_draws, b_draws])
    for q in (0.5, 0.99):
        ref = float(np.quantile(union, q))
        assert abs(ha.quantile(q) - ref) / ref < 0.10
    # merging preserves non-positive bucket + min/max
    hn, hm = Histogram("n"), Histogram("m")
    hn.observe(-1.0)
    hm.observe(5.0)
    hn.merge(hm)
    assert hn.count == 2 and hn.min == -1.0 and hn.max == 5.0


def test_histogram_empty_roundtrip_and_merge():
    h = Histogram.from_snapshot(Histogram("e").to_snapshot())
    assert h.count == 0 and h.quantile(0.5) == 0.0
    h2 = Histogram("x")
    h2.observe(3.0)
    h2.merge(h)                                      # empty merge no-op
    assert h2.count == 1


# -- timeline merging ------------------------------------------------------
def _span_doc(process_id, wall_epoch, events):
    return {"process_id": process_id,
            "anchor": {"wall": wall_epoch, "perf": 0.0,
                       "perf_epoch": 0.0, "wall_epoch": wall_epoch,
                       "pid": float(100 + process_id)},
            "trace_events": events}


def test_merge_timeline_clock_aligns_tracks():
    from sparkucx_tpu.utils.export import merge_timeline
    # process 1's clock epoch started 2.5 s after process 0's; the same
    # wall moment is ts=3.0s on p0 and ts=0.5s on p1
    ev0 = [{"name": "x", "ph": "X", "ts": 3.0e6, "dur": 1000.0,
            "pid": 0, "tid": 1, "args": {"trace": "s1.e0.x1"}}]
    ev1 = [{"name": "x", "ph": "X", "ts": 0.5e6, "dur": 1000.0,
            "pid": 0, "tid": 1, "args": {"trace": "s1.e0.x1"}}]
    doc = merge_timeline([_span_doc(0, 1000.0, ev0),
                          _span_doc(1, 1002.5, ev1)])
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 2
    by_pid = {e["pid"]: e for e in xs}
    assert set(by_pid) == {0, 1}                   # a track per process
    assert by_pid[0]["ts"] == pytest.approx(by_pid[1]["ts"])
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in meta} == \
        {"process 0", "process 1"}


def test_merge_timeline_negative_skew_aligns():
    """Clock-anchor edge case: process 1's epoch started BEFORE the
    minimum-epoch process 0's view of it — i.e. the joining doc has the
    EARLIEST wall_epoch and every other doc shifts forward off it. The
    same wall moment is ts=0.5s on p0 and ts=3.0s on p1 (p1 booted
    2.5 s earlier); alignment must shift p0 forward, never produce
    negative timestamps for in-range events."""
    from sparkucx_tpu.utils.export import merge_timeline
    ev0 = [{"name": "x", "ph": "X", "ts": 0.5e6, "dur": 1000.0,
            "pid": 0, "tid": 1, "args": {"trace": "s1.e0.x1"}}]
    ev1 = [{"name": "x", "ph": "X", "ts": 3.0e6, "dur": 1000.0,
            "pid": 0, "tid": 1, "args": {"trace": "s1.e0.x1"}}]
    doc = merge_timeline([_span_doc(0, 1000.0, ev0),
                          _span_doc(1, 997.5, ev1)])
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_pid = {e["pid"]: e for e in xs}
    assert by_pid[0]["ts"] == pytest.approx(by_pid[1]["ts"])
    assert all(e["ts"] >= 0 for e in xs)


def test_merge_timeline_duplicate_process_docs_dedupe():
    """A snapshot and a flight postmortem of the SAME process (same
    process_id + anchor pid) must merge to ONE track, not two clones
    of every span."""
    from sparkucx_tpu.utils.export import merge_timeline
    ev = [{"name": "x", "ph": "X", "ts": 1.0e6, "dur": 500.0,
           "pid": 0, "tid": 1, "args": {}}]
    doc = merge_timeline([_span_doc(0, 1000.0, ev),
                          _span_doc(0, 1000.0, ev)])
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 1
    assert doc["metadata"]["processes"] == 1


def test_merge_timeline_rejects_anchorless():
    from sparkucx_tpu.utils.export import merge_timeline, require_anchor
    with pytest.raises(ValueError, match="anchor"):
        merge_timeline([{"process_id": 0, "trace_events": []}])
    with pytest.raises(ValueError, match="anchor"):
        require_anchor({"ts": 1.0}, "x.json")


def test_cli_timeline_and_anchor_rejection(tmp_path):
    from sparkucx_tpu.__main__ import main as cli_main
    d0 = _span_doc(0, 1000.0, [{"name": "a", "ph": "X", "ts": 1e6,
                                "dur": 50.0, "pid": 0, "tid": 1,
                                "args": {}}])
    d1 = _span_doc(1, 1001.0, [{"name": "b", "ph": "X", "ts": 2e6,
                                "dur": 50.0, "pid": 0, "tid": 1,
                                "args": {}}])
    dump_dir = tmp_path / "dumps"
    dump_dir.mkdir()
    (dump_dir / "metrics_100.json").write_text(json.dumps(d0))
    (dump_dir / "metrics_101.json").write_text(json.dumps(d1))
    out = tmp_path / "tl.json"
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["timeline", "--input", str(dump_dir),
                       "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["metadata"]["processes"] == 2
    # anchor-less dump: loud rejection, not silent misalignment — for
    # timeline AND the stats/trace renderers
    bad = tmp_path / "old.json"
    bad.write_text(json.dumps({"counters": {}, "trace_events": []}))
    for argv in (["timeline", "--input", str(bad)],
                 ["stats", "--input", str(bad)],
                 ["trace", "--input", str(bad)]):
        with pytest.raises(ValueError, match="anchor"):
            cli_main(argv)


def test_same_process_dumps_deduped_not_double_counted():
    """A dump dir holding a process's metrics snapshot AND its flight
    postmortem (the CI failure-artifact shape) must diagnose as ONE
    process: 2 real retries must not read as 4 and trip retry_storm,
    and a postmortem-only exchange report still survives the dedup."""
    from sparkucx_tpu.utils.export import dedupe_process_docs
    snap = {"pid": 777, "ts": 100.0,
            "counters": {"x": 2.0},
            "histograms": {H_RETRY_MS: _hist_snap([5.0, 5.0])},
            "exchange_reports": [_report(sid=1, trace="s1.e0.x1")]}
    flight = {"pid": 777, "ts": 101.0,
              "counters": {"x": 2.0},
              "histograms": {H_RETRY_MS: _hist_snap([5.0, 5.0])},
              "contexts": {"exchange_reports": [
                  _report(sid=1, trace="s1.e0.x1"),
                  _report(sid=2, trace="s2.e0.x2")]}}
    docs = dedupe_process_docs([snap, flight])
    assert len(docs) == 1
    view = build_view([snap, flight])
    assert view.counters["x"] == 2.0                  # not 4.0
    assert view.histograms[H_RETRY_MS].count == 2     # not 4
    assert {r["trace_id"] for r in view.reports} == \
        {"s1.e0.x1", "s2.e0.x2"}                      # union, deduped
    assert diagnose([snap, flight]) == []             # below retry_warn
    # distinct processes (cluster gather) stay separate
    other = dict(snap, pid=778, process_id=1)
    assert len(dedupe_process_docs([snap, other])) == 2


def test_timeline_dedupes_same_process_captures():
    """The same span ring embedded in two dumps of one process renders
    ONCE on one track, not twice on two fabricated tracks."""
    from sparkucx_tpu.utils.export import merge_timeline
    ev = [{"name": "a", "ph": "X", "ts": 1e6, "dur": 50.0, "pid": 0,
           "tid": 1, "args": {}}]
    snap = dict(_span_doc(0, 1000.0, ev), pid=777, ts=100.0)
    flight = dict(_span_doc(0, 1000.0, ev), pid=777, ts=101.0)
    del snap["process_id"], flight["process_id"]
    doc = merge_timeline([snap, flight])
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 1 and doc["metadata"]["processes"] == 1


def test_multi_registry_snapshot_merges_histograms():
    """Pre-registered empty histograms in a later registry must not
    clobber an earlier registry's populated one (and two populated ones
    merge exactly) — the compile.step.duration_s visibility bug."""
    from sparkucx_tpu.utils.export import collect_snapshot
    from sparkucx_tpu.utils.metrics import H_COMPILE_SECS
    a, b = Metrics(), Metrics()
    a.observe(H_COMPILE_SECS, 5.0)           # step cache's registry
    doc = collect_snapshot([a, b])           # b pre-registers it empty
    assert doc["histograms"][H_COMPILE_SECS]["count"] == 1
    b.observe(H_COMPILE_SECS, 7.0)
    doc = collect_snapshot([a, b])
    h = doc["histograms"][H_COMPILE_SECS]
    assert h["count"] == 2 and h["max"] == 7.0 and h["min"] == 5.0


def test_cli_empty_input_errors_not_healthy(tmp_path):
    """`doctor --input <empty glob>` must error, not silently diagnose
    this fresh CLI process and print 'healthy'."""
    from sparkucx_tpu.__main__ import main as cli_main
    for argv in (["doctor", "--input"], ["timeline", "--input"]):
        with pytest.raises(FileNotFoundError, match="no paths"):
            cli_main(argv)
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError, match="no metrics"):
        cli_main(["doctor", "--input", str(empty)])


def test_cli_doctor_dumps_and_fail_on(tmp_path):
    from sparkucx_tpu.__main__ import main as cli_main
    doc = _healthy_doc()
    doc["exchange_reports"].append(_report(sid=6, trace="s6.e0.x6",
                                           skew=32.0))
    p = tmp_path / "snap.json"
    p.write_text(json.dumps(doc))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["doctor", "--input", str(p)])
    assert rc == 0                                  # report-only default
    out = buf.getvalue()
    assert "partition_skew" in out and "capacityFactor" in out
    with contextlib.redirect_stdout(io.StringIO()):
        assert cli_main(["doctor", "--input", str(p),
                         "--fail-on", "critical"]) == 3
    # json format parses and carries the schema fields
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cli_main(["doctor", "--input", str(p), "--format", "json"])
    fs = json.loads(buf.getvalue())
    assert fs and {"rule", "grade", "evidence", "conf_key"} <= set(fs[0])
    # live mode runs clean on a fresh process state
    with contextlib.redirect_stdout(io.StringIO()):
        assert cli_main(["doctor"]) == 0


# -- end-to-end through the facade -----------------------------------------
def test_service_doctor_on_skewed_workload(mesh8, rng):
    """The acceptance shape: a synthetic skew + compile-churn workload
    through the REAL stack emits the expected graded findings on both
    facades, with trace ids linking back to gather_reports."""
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.service import ShuffleService
    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense",
                           "spark.shuffle.tpu.io.format": "raw"},
                          use_env=False)
    with ShuffleService(conf) as svc:
        R, M, N = 8, 4, 512
        h = svc.register_shuffle(71, M, R, partitioner="direct")
        for m in range(M):
            svc.write(h, m, np.zeros(N, dtype=np.int64))  # all -> part 0
        svc.read(h)
        fs = svc.doctor()
        rules = _rules_of(fs)
        assert "partition_skew" in rules
        skewf = next(f for f in fs if f.rule == "partition_skew")
        # all rows in 1 of 8 partitions -> max/mean == 8 -> warn tier
        assert skewf.grade == "warn"
        rep = svc.manager.report(71)
        assert rep.trace_id and rep.trace_id in skewf.trace_ids
        assert svc.doctor("text").startswith("doctor:")
        json.dumps(svc.doctor("json"))


def test_exchange_reports_carry_trace_ids(manager_factory, rng):
    from sparkucx_tpu.utils.trace import format_trace_id
    mgr = manager_factory()
    seen = []
    for sid in (11, 12):
        h = mgr.register_shuffle(sid, 2, 4)
        for m in range(2):
            w = mgr.get_writer(h, m)
            w.write(rng.integers(0, 1 << 30, size=32, dtype=np.int64))
            w.commit(4)
        mgr.read(h)
        seen.append(mgr.report(sid).trace_id)
        mgr.unregister_shuffle(sid)
    assert seen[0] == format_trace_id(11, 0, 1)
    assert seen[1] == format_trace_id(12, 0, 2)   # seq is monotone
    # gather_spans: local capture carries anchor + events schema
    blobs = mgr.gather_spans()
    assert len(blobs) == 1
    assert "wall_epoch" in blobs[0]["anchor"]


def test_flight_ring_and_postmortem_carry_trace_ids(manager_factory,
                                                    tmp_path, rng):
    """Flight-recorder correlation: ring events recorded while an
    exchange is in flight carry its trace id, and the postmortem embeds
    the anchor + its own doctor findings — a crash dump links straight
    to its row in gather_reports and its timeline track."""
    mgr = manager_factory({
        "spark.shuffle.tpu.flightRecorder.enabled": "true",
        "spark.shuffle.tpu.flightRecorder.dir": str(tmp_path)})
    mgr.node.faults.arm("fetch", fail_count=1)   # one retried attempt
    h = mgr.register_shuffle(21, 2, 4)
    for m in range(2):
        w = mgr.get_writer(h, m)
        w.write(rng.integers(0, 1 << 30, size=32, dtype=np.int64))
        w.commit(4)
    mgr.read(h)
    tid = mgr.report(21).trace_id
    assert tid
    path = mgr.node.flight.dump("doctor correlation test")
    doc = json.loads(open(path).read())
    assert "wall_epoch" in doc["anchor"]          # timeline-mergeable
    assert isinstance(doc["findings"], list)      # self-diagnosing dump
    tagged = [e for e in doc["events"] if e.get("trace") == tid]
    assert tagged, f"no ring event carries {tid}"
    assert any(e["kind"] == "retry" for e in tagged)
    assert doc["in_flight_traces"] == []          # read completed
    # the dump's reports context carries the same id (the join key)
    reps = doc["contexts"]["exchange_reports"]
    assert any(r.get("trace_id") == tid for r in reps)


def test_v2_facade_doctor(mesh8, rng):
    """The diagnostic surface must not drift with the host-adapter
    contract: v2 exposes the same doctor() as v1."""
    import sparkucx_tpu
    from sparkucx_tpu.compat.v2 import (ShuffleDependency,
                                        ShuffleServiceV2)
    conf = {"spark.shuffle.tpu.a2a.impl": "dense",
            "spark.shuffle.tpu.compat.version": "v2"}
    with sparkucx_tpu.connect(conf, use_env=False) as svc:
        assert isinstance(svc, ShuffleServiceV2)
        h = svc.register(ShuffleDependency(31, 2, 4))
        for m in range(2):
            w = svc.writer(h, m, attempt_id=0)
            w.write(rng.integers(0, 1 << 30, size=32, dtype=np.int64))
            w.commit()
        list(svc.reader(h))
        fs = svc.doctor()
        assert isinstance(fs, list)
        assert svc.doctor("text").startswith("doctor:")


# -- quota_starvation goldens (multi-tenant service plane) -----------------
def _tenant_doc(minnow_cross=20.0, minnow_wait=800.0, whale_share=0.9,
                admits=6):
    """Two-tenant snapshot: whale granted most admission bytes, minnow
    waiting. Knobs select which rule conditions hold."""
    from sparkucx_tpu.utils.metrics import (C_ADMIT_BYTES,
                                            H_ADMIT_CROSS,
                                            H_ADMIT_WAIT, labeled)
    doc = _healthy_doc()
    total = 100e6
    doc["counters"][labeled(C_ADMIT_BYTES, tenant="whale")] = \
        total * whale_share
    doc["counters"][labeled(C_ADMIT_BYTES, tenant="minnow")] = \
        total * (1.0 - whale_share)
    doc["histograms"][labeled(H_ADMIT_WAIT, tenant="minnow")] = \
        _hist_snap([minnow_wait] * admits)
    doc["histograms"][labeled(H_ADMIT_WAIT, tenant="whale")] = \
        _hist_snap([5.0] * admits)
    doc["histograms"][labeled(H_ADMIT_CROSS, tenant="minnow")] = \
        _hist_snap([minnow_cross] * admits)
    doc["histograms"][labeled(H_ADMIT_CROSS, tenant="whale")] = \
        _hist_snap([0.0] * admits)
    # tenant-attributed completed reports give the evidence wall
    for r in doc["exchange_reports"]:
        r["tenant"] = "minnow"
        r["pack_ms"] = 2.0
        r["admit_wait_ms"] = 0.0
    return doc


def test_quota_starvation_fires_and_names_both_tenants():
    fs = [f for f in diagnose(_tenant_doc())
          if f.rule == "quota_starvation"]
    assert len(fs) == 1
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["starved_tenant"] == "minnow"
    assert f.evidence["hog_tenant"] == "whale"
    assert f.evidence["cross_grants_p99"] >= 8
    assert f.conf_key == "spark.shuffle.tpu.tenant.whale.maxBytesInFlight"
    assert "minnow" in f.summary and "whale" in f.summary
    assert "priority" in f.remediation


def test_quota_starvation_critical_on_deep_flood():
    # a whole whale queue (>= quota_cross_critical grants) passed the
    # minnow repeatedly — critical territory
    fs = [f for f in diagnose(_tenant_doc(minnow_cross=30.0))
          if f.rule == "quota_starvation"]
    assert fs and fs[0].grade == "critical"


def test_quota_starvation_quiet_goldens():
    # (a) fair share working: long waits but only a couple of
    # cross-grants — the minnow queued behind ITS OWN reads
    assert [f for f in diagnose(_tenant_doc(minnow_cross=2.0))
            if f.rule == "quota_starvation"] == []
    # (b) no hog: waits + cross-grants but granted bytes are balanced
    assert [f for f in diagnose(_tenant_doc(whale_share=0.5))
            if f.rule == "quota_starvation"] == []
    # (c) healthy single-tenant cluster: rule needs >= 2 tenants
    assert [f for f in diagnose(_healthy_doc())
            if f.rule == "quota_starvation"] == []


def test_quota_starvation_sub_noise_floors():
    # waits under the floor: being passed by fast grants is not harm
    assert [f for f in diagnose(_tenant_doc(minnow_wait=100.0))
            if f.rule == "quota_starvation"] == []
    # too few admissions for a p99 verdict
    assert [f for f in diagnose(_tenant_doc(admits=2))
            if f.rule == "quota_starvation"] == []


# -- slow_tier (topology plane: ICI vs DCN phase attribution) --------------
def _tier_entry(tier, ms, wire_mb=1.0, payload_rows=500):
    return {"tier": tier, "axis": "shuffle" if tier == "ici" else "dcn",
            "impl": "dense", "groups": 2, "group_shards": 4,
            "rows_in": 1000, "payload_rows": payload_rows,
            "payload_bytes": payload_rows * 16, "cross_exact": True,
            "wire_rows": int(wire_mb * 1e6 / 16),
            "wire_bytes": int(wire_mb * 1e6), "pad_ratio": 2.0,
            "wire": "raw", "ms": ms, "bw_gbps": 0.0,
            "effective_bw_gbps": 0.0}


def _hier_report(sid, ici_ms, dcn_ms, ici_mb=2.0, dcn_mb=1.0,
                 programs=0):
    r = _report(sid=sid, trace=f"s{sid}.e0.x{sid}", programs=programs)
    r["hierarchical"] = True
    r["tiers"] = [_tier_entry("ici", ici_ms, wire_mb=ici_mb),
                  _tier_entry("dcn", dcn_ms, wire_mb=dcn_mb)]
    return r


def test_slow_tier_fires_on_dcn_straggler():
    """DCN walls dwarf ICI beyond the byte share on several steady
    reads — the finding names the DCN tier and its deadline knob."""
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, ici_ms=30.0, dcn_ms=400.0) for i in range(1, 4)]
    fs = [f for f in diagnose(doc) if f.rule == "slow_tier"]
    assert len(fs) == 1
    f = fs[0]
    assert f.evidence["tier"] == "dcn"
    assert f.conf_key == "spark.shuffle.tpu.failure.dcn.timeoutMs"
    assert "DCN" in f.summary
    assert f.trace_ids


def test_slow_tier_critical_on_extreme_imbalance():
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, ici_ms=20.0, dcn_ms=2000.0, ici_mb=1.0,
                     dcn_mb=1.0) for i in range(1, 6)]
    fs = [f for f in diagnose(doc) if f.rule == "slow_tier"]
    assert fs and fs[0].grade == "critical"


def test_slow_tier_ici_attribution():
    """The rule attributes to WHICHEVER tier straggles — an ICI
    straggler names ici and its knob."""
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, ici_ms=500.0, dcn_ms=25.0, ici_mb=1.0,
                     dcn_mb=1.0) for i in range(1, 4)]
    fs = [f for f in diagnose(doc) if f.rule == "slow_tier"]
    assert fs and fs[0].evidence["tier"] == "ici"
    assert fs[0].conf_key == "spark.shuffle.tpu.failure.ici.timeoutMs"


def test_slow_tier_quiet_goldens():
    # (a) healthy flat cluster: no tiers at all
    assert [f for f in diagnose(_healthy_doc())
            if f.rule == "slow_tier"] == []
    # (b) balanced hier reads: walls track byte shares
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, ici_ms=60.0, dcn_ms=40.0, ici_mb=2.0,
                     dcn_mb=1.0) for i in range(1, 5)]
    assert [f for f in diagnose(doc) if f.rule == "slow_tier"] == []
    # (c) DCN wall larger but explained by its byte share (padded DCN
    # hop moving 8x the bytes): normalized imbalance stays under ratio
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, ici_ms=20.0, dcn_ms=120.0, ici_mb=0.5,
                     dcn_mb=4.0) for i in range(1, 5)]
    assert [f for f in diagnose(doc) if f.rule == "slow_tier"] == []


def test_slow_tier_sub_noise_floors():
    # (a) sub-noise walls: 4x imbalance on 2ms spans attributes nothing
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, ici_ms=0.5, dcn_ms=8.0) for i in range(1, 5)]
    assert [f for f in diagnose(doc) if f.rule == "slow_tier"] == []
    # (b) one read is not a verdict (tier_min_reads)
    doc = _healthy_doc()
    doc["exchange_reports"] = [_hier_report(1, 30.0, 400.0)]
    assert [f for f in diagnose(doc) if f.rule == "slow_tier"] == []
    # (c) compile-bearing reads are excluded (their walls time XLA)
    doc = _healthy_doc()
    doc["exchange_reports"] = [
        _hier_report(i, 30.0, 400.0, programs=2) for i in range(1, 5)]
    assert [f for f in diagnose(doc) if f.rule == "slow_tier"] == []


# -- SLO burn + latency trend (the PR-14 trend-aware rules) ------------------
def _slo_obj(tenant="", threshold_ms=50.0, target=0.99):
    return {"key": "slo.read.p99Ms", "kind": "latency",
            "tenant": tenant, "threshold_ms": threshold_ms,
            "target": target}


def _slo_policy(fast_s=120.0, slow_s=480.0, fast_burn=14.4,
                slow_burn=6.0, min_events=4):
    return {"fast_window_s": fast_s, "slow_window_s": slow_s,
            "fast_burn": fast_burn, "slow_burn": slow_burn,
            "min_events": min_events}


def _frame_doc(frames, objectives=None, policy=None, process_id=0):
    doc = {"anchor": _anchor(), "process_id": process_id,
           "counters": {}, "histograms": {},
           "history_frames": frames}
    if objectives is not None:
        doc["slo_objectives"] = objectives
    if policy is not None:
        doc["slo_policy"] = policy
    return doc


def _window_frame(t_end, waits=(), tenant=None, seq=1, reads=None,
                  extra_counters=None, extra_hists=None):
    from sparkucx_tpu.utils.metrics import labeled
    name = labeled(H_FETCH_WAIT, tenant=tenant) if tenant \
        else H_FETCH_WAIT
    cname = labeled("shuffle.read.count", tenant=tenant) if tenant \
        else "shuffle.read.count"
    hists = {}
    if waits:
        hists[name] = _hist_snap(list(waits), name)
    hists.update(extra_hists or {})
    counters = {cname: float(reads if reads is not None else len(waits))}
    counters.update(extra_counters or {})
    return {"kind": "history_frame", "seq": seq,
            "t_start": t_end - 60.0, "t_end": t_end, "window_s": 60.0,
            "pid": 1, "process_id": 0, "anchor": _anchor(),
            "counters": counters, "histograms": hists, "gauges": {}}


T0 = 5_000_000.0


def test_slo_burn_fires_critical_and_names_objective():
    frames = [_window_frame(T0 + i * 60.0, waits=[5.0] * 6, seq=i)
              for i in (1, 2)]
    frames += [_window_frame(T0 + i * 60.0, waits=[500.0] * 6, seq=i)
               for i in (3, 4)]
    doc = _frame_doc(frames, [_slo_obj()], _slo_policy())
    fs = [f for f in diagnose(doc) if f.rule == "slo_burn"]
    assert fs and fs[0].grade == "critical"
    assert fs[0].evidence["objective"] == "slo.read.p99Ms"
    assert fs[0].evidence["burn_fast"] >= 14.4
    assert "slo.read.p99Ms" in fs[0].conf_key


def test_slo_burn_self_throttled_capped_at_warn():
    """A tenant whose burning reads sat in its OWN admission queue
    (real admit waits, ~zero cross-grants) is client self-backpressure:
    the finding says so and stays a warning, not a page."""
    from sparkucx_tpu.utils.metrics import (H_ADMIT_CROSS, H_ADMIT_WAIT,
                                            labeled)
    tid = "whale"
    extra = {
        labeled(H_ADMIT_WAIT, tenant=tid):
            _hist_snap([800.0] * 6, labeled(H_ADMIT_WAIT, tenant=tid)),
        labeled(H_ADMIT_CROSS, tenant=tid):
            _hist_snap([0.0] * 6, labeled(H_ADMIT_CROSS, tenant=tid)),
    }
    frames = [_window_frame(T0 + 60.0, waits=[5.0] * 6, tenant=tid,
                            seq=1)]
    frames += [_window_frame(T0 + i * 60.0, waits=[900.0] * 6,
                             tenant=tid, seq=i, extra_hists=extra)
               for i in (2, 3)]
    doc = _frame_doc(frames, [_slo_obj(tenant=tid)], _slo_policy())
    fs = [f for f in diagnose(doc) if f.rule == "slo_burn"]
    assert fs and fs[0].grade == "warn"
    assert fs[0].evidence["self_throttled"] is True
    assert "self-backpressure" in fs[0].summary
    assert fs[0].evidence["tenant"] == tid


def test_slo_burn_quiet_goldens():
    # (a) healthy windows: no finding
    frames = [_window_frame(T0 + i * 60.0, waits=[5.0] * 8, seq=i)
              for i in range(1, 5)]
    doc = _frame_doc(frames, [_slo_obj()], _slo_policy())
    assert [f for f in diagnose(doc) if f.rule == "slo_burn"] == []
    # (b) no objectives declared: frames alone never fire the rule
    doc = _frame_doc(frames)
    assert [f for f in diagnose(doc) if f.rule == "slo_burn"] == []
    # (c) sub-noise: the graded windows hold fewer events than the
    # min_events floor (the old healthy traffic has aged out of both)
    frames2 = frames + [_window_frame(T0 + 1000.0, waits=[500.0] * 2,
                                      seq=5)]
    doc = _frame_doc(frames2, [_slo_obj()],
                     _slo_policy(fast_s=60.0, slow_s=480.0,
                                 min_events=4))
    assert [f for f in diagnose(doc) if f.rule == "slo_burn"] == []


def test_latency_trend_fires_and_grades():
    frames = [_window_frame(T0 + i * 60.0, waits=[10.0] * 10, seq=i)
              for i in range(1, 5)]
    frames += [_window_frame(T0 + i * 60.0, waits=[60.0] * 10, seq=i)
               for i in (5, 6, 7)]
    fs = [f for f in diagnose(_frame_doc(frames))
          if f.rule == "latency_trend"]
    assert fs and fs[0].grade == "warn"
    assert fs[0].evidence["drift_normalized"] >= 3.0
    # critical at an order-of-magnitude drift
    frames = frames[:4] + [
        _window_frame(T0 + i * 60.0, waits=[900.0] * 10, seq=i)
        for i in (5, 6, 7)]
    fs = [f for f in diagnose(_frame_doc(frames))
          if f.rule == "latency_trend"]
    assert fs and fs[0].grade == "critical"


def test_latency_trend_quiet_on_payload_shift():
    """p99 up 5x but bytes/read up 5x too: a load shift, normalized
    away — NOT a regression finding."""
    frames = [_window_frame(T0 + i * 60.0, waits=[10.0] * 10, seq=i,
                            extra_counters={
                                "shuffle.payload.bytes": 10 * 1000.0})
              for i in range(1, 5)]
    frames += [_window_frame(T0 + i * 60.0, waits=[50.0] * 10, seq=i,
                             extra_counters={
                                 "shuffle.payload.bytes": 10 * 5000.0})
               for i in (5, 6, 7)]
    assert [f for f in diagnose(_frame_doc(frames))
            if f.rule == "latency_trend"] == []


def test_latency_trend_sub_noise_floors():
    # (a) too few frames
    frames = [_window_frame(T0 + i * 60.0, waits=[10.0] * 10, seq=i)
              for i in range(1, 4)]
    assert [f for f in diagnose(_frame_doc(frames))
            if f.rule == "latency_trend"] == []
    # (b) too few reads per side
    frames = [_window_frame(T0 + i * 60.0, waits=[10.0] * 2, seq=i)
              for i in range(1, 5)]
    frames += [_window_frame(T0 + i * 60.0, waits=[60.0] * 2, seq=i)
               for i in (5, 6, 7)]
    assert [f for f in diagnose(_frame_doc(frames))
            if f.rule == "latency_trend"] == []
    # (c) drift under the noise floor in absolute ms
    frames = [_window_frame(T0 + i * 60.0, waits=[0.2] * 10, seq=i)
              for i in range(1, 5)]
    frames += [_window_frame(T0 + i * 60.0, waits=[1.0] * 10, seq=i)
               for i in (5, 6, 7)]
    assert [f for f in diagnose(_frame_doc(frames))
            if f.rule == "latency_trend"] == []


def test_build_view_folds_frames_and_objectives_across_processes():
    f0 = _window_frame(T0 + 60.0, waits=[5.0] * 4, seq=1)
    f1 = _window_frame(T0 + 120.0, waits=[7.0] * 4, seq=1)
    del f1["process_id"]   # unstamped frame: build_view attributes it
    f1["slo_objectives"] = [_slo_obj(tenant="t2")]
    d0 = _frame_doc([f0], [_slo_obj()], _slo_policy(), process_id=0)
    d1 = _frame_doc([f1], process_id=1)
    d1["pid"] = 2
    view = build_view([d0, d1])
    assert len(view.frames) == 2
    assert [f["t_end"] for f in view.frames] == [T0 + 60.0, T0 + 120.0]
    assert view.frames[1]["process_id"] == 1
    # objectives union by (key, tenant): global from d0, t2 from f1
    keys = {(o["key"], o.get("tenant", ""))
            for o in view.slo_objectives}
    assert keys == {("slo.read.p99Ms", ""), ("slo.read.p99Ms", "t2")}
    assert view.slo_policy["fast_window_s"] == 120.0


# -- spill_bound (analytics workload plane, ISSUE-15) ----------------------
def _workload_counters(doc, wl, spill_ms, exchange_ms, merge_ms,
                       rows=50000.0, ingest_ms=100.0):
    c = doc["counters"]
    c[f'workload.rows{{workload="{wl}"}}'] = rows
    c["workload.rows"] = c.get("workload.rows", 0.0) + rows
    for ph, ms in (("ingest", ingest_ms), ("spill", spill_ms),
                   ("exchange", exchange_ms), ("merge", merge_ms)):
        c[f'workload.phase.ms{{workload="{wl}",phase="{ph}"}}'] = ms
    c["shuffle.spill.bytes"] = 8e6


def test_spill_bound_fires_and_names_workload():
    doc = _healthy_doc()
    _workload_counters(doc, "terasort", spill_ms=3000.0,
                       exchange_ms=1500.0, merge_ms=500.0)
    fs = [f for f in diagnose(doc) if f.rule == "spill_bound"]
    assert len(fs) == 1
    f = fs[0]
    assert f.grade == "warn"
    assert "terasort" in f.summary and "spill-bound" in f.summary
    assert f.conf_key == "spark.shuffle.tpu.spill.threshold"
    assert f.evidence["workload"] == "terasort"
    assert 0.55 < f.evidence["spill_share"] < 0.65
    # attribution carries every phase wall, ingest included
    assert f.evidence["phase_ms"]["exchange"] == 1500.0


def test_spill_bound_critical_on_extreme_share():
    doc = _healthy_doc()
    _workload_counters(doc, "join", spill_ms=9000.0,
                       exchange_ms=600.0, merge_ms=400.0)
    fs = [f for f in diagnose(doc) if f.rule == "spill_bound"]
    assert fs and fs[0].grade == "critical"
    assert fs[0].evidence["spill_share"] >= 0.7


def test_spill_bound_quiet_when_exchange_dominates():
    # the healthy analytics posture: the engine, not the disk, owns the
    # wall — and a doc with no workload counters at all is quiet too
    assert [f for f in diagnose(_healthy_doc())
            if f.rule == "spill_bound"] == []
    doc = _healthy_doc()
    _workload_counters(doc, "groupby", spill_ms=300.0,
                       exchange_ms=4000.0, merge_ms=2000.0)
    assert [f for f in diagnose(doc)
            if f.rule == "spill_bound"] == []


def test_spill_bound_sub_noise_floors():
    # spill-dominant but under the wall floor: tiny test runs never fire
    doc = _healthy_doc()
    _workload_counters(doc, "terasort", spill_ms=200.0,
                       exchange_ms=50.0, merge_ms=30.0)
    assert [f for f in diagnose(doc)
            if f.rule == "spill_bound"] == []
    # real wall but under the row floor (a few hundred rows of smoke)
    doc2 = _healthy_doc()
    _workload_counters(doc2, "terasort", spill_ms=3000.0,
                       exchange_ms=500.0, merge_ms=100.0, rows=200.0)
    assert [f for f in diagnose(doc2)
            if f.rule == "spill_bound"] == []


# -- desync (agreement divergence) ----------------------------------------
def test_desync_fires_on_single_divergence():
    """ONE divergence is already a warn — the agree() fence means a
    non-unanimous round is a conf split or broken SPMD determinism,
    never load noise (the peer_timeout posture: no noise floor) — and
    the topic maps to the conf key whose split is the usual cause."""
    from sparkucx_tpu.utils.metrics import (C_AGREE_DIVERGENCE,
                                            C_AGREE_ROUNDS, labeled)
    doc = _healthy_doc()
    doc["counters"][C_AGREE_ROUNDS] = 40.0
    doc["counters"][C_AGREE_DIVERGENCE] = 1.0
    doc["counters"][labeled(C_AGREE_DIVERGENCE,
                            topic="hier.dcn.regrow")] = 1.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["desync"]
    f = fs[0]
    assert f.grade == "warn"
    assert f.evidence["divergences"] == 1
    assert f.evidence["by_topic"] == {"hier.dcn.regrow": 1}
    assert f.evidence["agreement_rounds"] == 40
    assert f.conf_key == "spark.shuffle.tpu.a2a.capacityFactor"
    assert "identical" in f.summary
    assert "conf" in f.remediation


def test_desync_critical_and_dominant_topic_conf_key():
    # repeats are systematic: critical, and the finding charges the
    # DOMINANT topic's conf key while every implicated key rides in
    # the evidence
    from sparkucx_tpu.utils.metrics import C_AGREE_DIVERGENCE, labeled
    doc = _healthy_doc()
    doc["counters"][C_AGREE_DIVERGENCE] = 3.0
    doc["counters"][labeled(C_AGREE_DIVERGENCE,
                            topic="async.order")] = 2.0
    doc["counters"][labeled(C_AGREE_DIVERGENCE,
                            topic="a2a.waveRows")] = 1.0
    fs = diagnose(doc)
    assert _rules_of(fs) == ["desync"]
    f = fs[0]
    assert f.grade == "critical"
    assert f.conf_key == "spark.shuffle.tpu.tenant.asyncAgreedOrder"
    assert f.evidence["implicated_conf_keys"] == {
        "spark.shuffle.tpu.tenant.asyncAgreedOrder": 2,
        "spark.shuffle.tpu.a2a.waveRows": 1,
    }
    assert "async.order×2" in f.summary


def test_desync_quiet_goldens():
    """Rounds without divergence are the HEALTHY distributed signal —
    heavy agreement traffic alone never fires (the rule has no noise
    floor because unanimity already is the filter); an unmapped topic
    still fires but charges the conf wildcard."""
    from sparkucx_tpu.utils.metrics import (C_AGREE_DIVERGENCE,
                                            C_AGREE_ROUNDS, labeled)
    doc = _healthy_doc()
    doc["counters"][C_AGREE_ROUNDS] = 5000.0
    assert diagnose(doc) == []
    doc2 = _healthy_doc()
    doc2["counters"][C_AGREE_DIVERGENCE] = 1.0
    doc2["counters"][labeled(C_AGREE_DIVERGENCE,
                             topic="exotic.topic")] = 1.0
    fs = diagnose(doc2)
    assert _rules_of(fs) == ["desync"]
    assert fs[0].conf_key == "spark.shuffle.tpu.*"
