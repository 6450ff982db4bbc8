"""Failure plane tests (runtime/failures.py).

The reference delegates failure handling to Spark and has no fault
injection (SURVEY.md §5); these tests cover the in-framework equivalents:
deterministic injection, bounded retry, liveness probing, numeric checks,
and epoch fencing — plus integration through the shuffle manager."""

import random
import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.runtime.failures import (DeviceUnhealthy, EpochManager,
                                           FaultInjector, HealthMonitor,
                                           InjectedFault, NumericFailure,
                                           PeerLostError, RetryPolicy,
                                           StaleEpochError, TransientError)


# -- FaultInjector --------------------------------------------------------
def test_injector_inactive_is_noop():
    fi = FaultInjector()
    for _ in range(100):
        fi.check("anything")
    assert fi.stats() == {}


def test_injector_fail_count():
    fi = FaultInjector()
    fi.arm("publish", fail_count=2)
    with pytest.raises(InjectedFault):
        fi.check("publish")
    with pytest.raises(InjectedFault):
        fi.check("publish")
    fi.check("publish")  # exhausted
    hits, injected = fi.stats()["publish"]
    assert (hits, injected) == (3, 2)


def test_injector_fail_rate_deterministic():
    a = FaultInjector(seed=42)
    b = FaultInjector(seed=42)
    a.arm("x", fail_rate=0.5)
    b.arm("x", fail_rate=0.5)

    def pattern(fi):
        out = []
        for _ in range(50):
            try:
                fi.check("x")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    pa, pb = pattern(a), pattern(b)
    assert pa == pb
    assert 0 < sum(pa) < 50


def test_injector_from_conf():
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.fault.publish.failCount": "1",
        "spark.shuffle.tpu.fault.fetch.failRate": "0.0",
        "spark.shuffle.tpu.fault.seed": "7",
    }, use_env=False)
    fi = FaultInjector(conf)
    assert fi.active
    with pytest.raises(InjectedFault):
        fi.check("publish")
    fi.check("publish")
    fi.check("fetch")  # rate 0 never fires


def test_injector_env_cased_knobs():
    """Env-derived keys arrive lowercased; knob match must still hit."""
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.fault.publish.failcount": "1",
        "spark.shuffle.tpu.fault.publish.delayms": "1",
    }, use_env=False)
    fi = FaultInjector(conf)
    assert fi.active
    with pytest.raises(InjectedFault):
        fi.check("publish")


def test_retry_rejects_zero_attempts():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)


def test_manager_exchange_fault_site(manager_factory, rng):
    mgr = manager_factory({"spark.shuffle.tpu.fault.exchange.failCount": "1"})
    h = mgr.register_shuffle(913, num_maps=1, num_partitions=4)
    w = mgr.get_writer(h, 0)
    w.write(rng.integers(0, 100, size=8))
    w.commit(4)
    with pytest.raises(InjectedFault):
        mgr.read(h)
    total = sum(k.shape[0] for _, (k, _) in mgr.read(h).partitions())
    assert total == 8
    mgr.unregister_shuffle(913)


def test_injector_disarm():
    fi = FaultInjector()
    fi.arm("s", fail_count=5)
    fi.disarm("s")
    fi.check("s")


# -- RetryPolicy ----------------------------------------------------------
def test_retry_succeeds_after_transient():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientError("boom")
        return "ok"

    assert RetryPolicy(max_attempts=3, backoff_ms=1).run(flaky) == "ok"
    assert len(calls) == 3


def test_retry_exhausts_and_raises():
    def always():
        raise TransientError("nope")

    with pytest.raises(TransientError):
        RetryPolicy(max_attempts=2, backoff_ms=1).run(always)


def test_retry_does_not_catch_fatal():
    def fatal():
        raise ValueError("fatal")

    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=3, backoff_ms=1).run(fatal)


def test_retry_on_retry_hook():
    seen = []

    def flaky():
        if not seen:
            raise TransientError("x")
        return 1

    RetryPolicy(max_attempts=2, backoff_ms=1).run(
        flaky, on_retry=lambda attempt, e: seen.append(attempt))
    assert seen == [1]


def test_retry_from_conf():
    conf = TpuShuffleConf({"spark.shuffle.tpu.failure.maxAttempts": "5"},
                          use_env=False)
    assert RetryPolicy.from_conf(conf).max_attempts == 5


# -- RetryPolicy: decorrelated jitter + backoff cap + total deadline ------
def test_backoff_schedule_deterministic_without_jitter():
    p = RetryPolicy(backoff_ms=10.0, backoff_factor=2.0, jitter=False,
                    max_backoff_ms=65.0)
    delays = []
    prev = None
    for _ in range(5):
        prev = p.next_delay_ms(prev)
        delays.append(prev)
    assert delays == [10.0, 20.0, 40.0, 65.0, 65.0]   # geometric, capped


def test_jittered_schedule_bounds_and_cap():
    p = RetryPolicy(backoff_ms=10.0, backoff_factor=2.0,
                    max_backoff_ms=50.0, rng=random.Random(7))
    first = p.next_delay_ms(None)
    assert 10.0 <= first <= 20.0          # uniform(base, base*factor)
    prev = first
    for _ in range(20):
        nxt = p.next_delay_ms(prev)
        # the decorrelated-jitter recurrence: uniform(base, 3*prev),
        # never above the cap
        assert 10.0 <= nxt <= min(prev * 3.0, 50.0)
        prev = nxt


def test_jitter_decorrelates_processes():
    """Two policies with different entropy draw DIFFERENT schedules —
    the whole point: no synchronized retry storm. The same seed stays
    reproducible for tests."""

    def schedule(seed):
        p = RetryPolicy(backoff_ms=10.0, rng=random.Random(seed))
        out, prev = [], None
        for _ in range(6):
            prev = p.next_delay_ms(prev)
            out.append(prev)
        return out

    assert schedule(1) != schedule(2)
    assert schedule(3) == schedule(3)


def test_backoff_cap_must_cover_base():
    with pytest.raises(ValueError, match="max_backoff_ms"):
        RetryPolicy(backoff_ms=100.0, max_backoff_ms=10.0)


def test_total_deadline_stops_retries_early():
    """With a total budget the schedule may not outlive, the policy
    stops as soon as the NEXT sleep would cross it — raising the real
    error instead of backing off past the collective deadline."""
    calls = []

    def always():
        calls.append(1)
        raise TransientError("persistent")

    p = RetryPolicy(max_attempts=50, backoff_ms=200.0, jitter=False,
                    total_deadline_ms=50.0)
    t0 = time.perf_counter()
    with pytest.raises(TransientError, match="persistent"):
        p.run(always)
    wall_ms = (time.perf_counter() - t0) * 1e3
    assert len(calls) == 1                 # first 200 ms sleep > 50 ms
    assert wall_ms < 5_000.0               # never slept the 50 attempts


def test_total_deadline_none_keeps_attempt_bound():
    calls = []

    def always():
        calls.append(1)
        raise TransientError("x")

    with pytest.raises(TransientError):
        RetryPolicy(max_attempts=3, backoff_ms=1.0,
                    total_deadline_ms=None).run(always)
    assert len(calls) == 3


def test_retry_conf_wires_cap_and_collective_deadline():
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.failure.backoffMs": "20",
        "spark.shuffle.tpu.failure.maxBackoffMs": "5",   # below base
        "spark.shuffle.tpu.failure.collectiveTimeoutMs": "1500",
    }, use_env=False)
    p = RetryPolicy.from_conf(conf)
    assert p.max_backoff_ms == 20.0        # cap never undercuts base
    assert p.total_deadline_ms == 1500.0   # watchdog deadline caps retries
    p2 = RetryPolicy.from_conf(TpuShuffleConf({}, use_env=False))
    assert p2.total_deadline_ms is None and p2.max_backoff_ms == 10_000.0


# -- HealthMonitor --------------------------------------------------------
def test_probe_all_devices_alive(mesh8):
    hm = HealthMonitor(mesh8, timeout_ms=30_000)
    results = hm.probe()
    assert len(results) == 8
    assert all(results.values())
    hm.assert_healthy()


def test_check_finite():
    HealthMonitor.check_finite("loss", np.float32(1.0))
    with pytest.raises(NumericFailure, match="nan=1"):
        HealthMonitor.check_finite("loss", np.array([1.0, np.nan]))
    with pytest.raises(NumericFailure):
        HealthMonitor.check_finite("grad", np.array([np.inf]))


def test_probe_tracks_and_skips_stuck_threads(mesh8, monkeypatch):
    """The probe-leak bugfix: a device op that never returns leaves its
    daemon thread parked holding the device reference. The monitor must
    (a) report that device dead, (b) count the leaked thread, (c) warn
    exactly once, and (d) NOT stack a second hung thread onto the same
    device on the next probe — it stays marked dead until the thread
    returns, after which it ages out of the census and probes again."""
    hm = HealthMonitor(mesh8, timeout_ms=30_000)
    assert all(hm.probe().values())   # warm the probe op's compile first
    hm.timeout_ms = 1_000             # warm op is instant; wedge is not
    gate = threading.Event()
    wedged = str(list(mesh8.devices.reshape(-1))[2])
    spawned = {}
    real_run_one = HealthMonitor._run_one

    def wedge_one(self, dev, out, idx):
        spawned[str(dev)] = spawned.get(str(dev), 0) + 1
        if str(dev) == wedged and not gate.is_set():
            gate.wait(20.0)      # parked past the probe deadline
        real_run_one(self, dev, out, idx)

    monkeypatch.setattr(HealthMonitor, "_run_one", wedge_one)
    # the repo logger does not propagate to root (caplog-invisible):
    # intercept the module logger's warn seam directly
    from sparkucx_tpu.runtime import failures as failures_mod
    warnings = []
    real_warning = failures_mod.log.warning
    monkeypatch.setattr(
        failures_mod.log, "warning",
        lambda msg, *a, **kw: (warnings.append(msg % a if a else msg),
                               real_warning(msg, *a, **kw)))
    try:
        first = hm.probe()
        assert first[wedged] is False
        assert sum(1 for d, ok in first.items() if ok) == 7
        assert hm.leaked_probe_threads == 1
        second = hm.probe()
        assert second[wedged] is False          # still dead, no re-probe
        assert spawned[wedged] == 1             # (d): no stacked thread
        assert hm.leaked_probe_threads == 1
        leak_warnings = [w for w in warnings
                         if "parked holding device references" in w]
        assert len(leak_warnings) == 1          # (c): warn once
    finally:
        gate.set()
    deadline = time.monotonic() + 5
    while hm.leaked_probe_threads and time.monotonic() < deadline:
        time.sleep(0.01)
    assert hm.leaked_probe_threads == 0         # census ages out
    third = hm.probe()
    assert spawned[wedged] == 2                 # probed again...
    assert all(third.values())                  # ...and healthy now


# -- EpochManager ---------------------------------------------------------
def test_epoch_bump_and_validate():
    em = EpochManager()
    assert em.current == 0
    em.validate(0)
    em.bump("device lost")
    assert em.current == 1
    with pytest.raises(StaleEpochError, match="epoch 0"):
        em.validate(0, "shuffle 3")


def test_epoch_listeners():
    em = EpochManager()
    seen = []
    em.on_bump(seen.append)
    em.bump()
    em.bump()
    assert seen == [1, 2]


# -- integration through the manager -------------------------------------
def _write_all(mgr, h, rng, rows=32):
    for m in range(h.num_maps):
        w = mgr.get_writer(h, m)
        w.write(rng.integers(0, 1 << 20, size=rows))
        w.commit(h.num_partitions)


def test_manager_fetch_fault_retried(manager_factory, rng):
    """A transient fetch fault is absorbed by the node retry policy."""
    mgr = manager_factory({"spark.shuffle.tpu.fault.fetch.failCount": "1"})
    h = mgr.register_shuffle(910, num_maps=2, num_partitions=4)
    _write_all(mgr, h, rng)
    result = mgr.read(h)  # first fetch attempt fails, retry succeeds
    total = sum(k.shape[0] for _, (k, _) in result.partitions())
    assert total == 2 * 32
    assert mgr.node.faults.stats()["fetch"] == (2, 1)
    mgr.unregister_shuffle(910)


def test_manager_publish_fault_surfaces(manager_factory, rng):
    """Publish faults surface to the caller (task-retry is above us),
    and a fresh writer can redo the commit — idempotent publish."""
    mgr = manager_factory({"spark.shuffle.tpu.fault.publish.failCount": "1"})
    h = mgr.register_shuffle(911, num_maps=1, num_partitions=4)
    w = mgr.get_writer(h, 0)
    w.write(rng.integers(0, 1 << 20, size=16))
    with pytest.raises(InjectedFault):
        w.commit(h.num_partitions)
    # retry the task: new writer, same map id
    w2 = mgr.get_writer(h, 0)
    w2.write(rng.integers(0, 1 << 20, size=16))
    w2.commit(h.num_partitions)
    result = mgr.read(h)
    total = sum(k.shape[0] for _, (k, _) in result.partitions())
    assert total == 16
    mgr.unregister_shuffle(911)


# -- failure.policy=replay through the manager ----------------------------
def test_replay_absorbs_exchange_fault(manager_factory, rng):
    """Under the replay policy a transient exchange fault is absorbed by
    a whole-exchange re-run: oracle-correct bytes come back, the report
    carries replays/replay_ms, and the metrics plane counts it."""
    from sparkucx_tpu.utils.metrics import C_REPLAYS

    mgr = manager_factory({
        "spark.shuffle.tpu.failure.policy": "replay",
        "spark.shuffle.tpu.fault.exchange.failCount": "1"})
    h = mgr.register_shuffle(914, num_maps=2, num_partitions=4)
    _write_all(mgr, h, rng)
    result = mgr.read(h)                   # fault absorbed, not raised
    total = sum(k.shape[0] for _, (k, _) in result.partitions())
    assert total == 2 * 32
    rep = mgr.report(914)
    assert rep.replays == 1 and rep.replay_ms > 0.0
    assert mgr.node.metrics.get(C_REPLAYS) == 1.0
    assert mgr.node.faults.stats()["exchange"] == (2, 1)
    mgr.unregister_shuffle(914)


def test_replay_budget_exhaustion_falls_back_to_failfast(manager_factory,
                                                         rng):
    """A persistent fault burns the budget and then surfaces TYPED —
    the policy bounds what a shuffle may spend, like
    spark.stage.maxConsecutiveAttempts."""
    mgr = manager_factory({
        "spark.shuffle.tpu.failure.policy": "replay",
        "spark.shuffle.tpu.failure.replayBudget": "1",
        "spark.shuffle.tpu.fault.exchange.failCount": "5"})
    h = mgr.register_shuffle(915, num_maps=1, num_partitions=4)
    _write_all(mgr, h, rng)
    with pytest.raises(InjectedFault):
        mgr.read(h)                        # 1 replay spent, then typed
    assert mgr.node.faults.stats()["exchange"][1] == 2   # original + 1
    # budget is cumulative per shuffle: the next failure cannot replay
    mgr.node.faults.arm("exchange", fail_count=1)
    with pytest.raises(InjectedFault):
        mgr.read(h)
    mgr.unregister_shuffle(915)


def test_peer_lost_replay_spends_single_unit(manager_factory, rng):
    """One PeerLostError = ONE replay unit end to end. The remesh inside
    _replay_after_failure re-pins the handle itself; the retry loop's
    _resolve_handle must not charge (and count) a second unit for the
    same fault — with replayBudget=1 the policy could otherwise never
    absorb a single peer loss, and the default budget would report one
    blip as a storm (replays=2 trips the doctor's replay_storm warn)."""
    mgr = manager_factory({
        "spark.shuffle.tpu.failure.policy": "replay",
        "spark.shuffle.tpu.failure.replayBudget": "1"})
    h = mgr.register_shuffle(917, num_maps=2, num_partitions=4)
    _write_all(mgr, h, rng)
    orig = mgr._submit_local
    state = {"fired": False}

    def lose_peer_once(*args, **kwargs):
        if not state["fired"]:
            state["fired"] = True
            raise PeerLostError("synthetic peer loss")
        return orig(*args, **kwargs)

    mgr._submit_local = lose_peer_once
    result = mgr.read(h)                   # absorbed within budget=1
    total = sum(k.shape[0] for _, (k, _) in result.partitions())
    assert total == 2 * 32
    rep = mgr.report(917)
    assert rep.replays == 1
    assert mgr._replay_counts.get(917) == 1
    mgr.unregister_shuffle(917)


def test_failfast_stale_read_leaves_metrics_window_closed(manager_factory,
                                                          rng):
    """A failfast StaleEpochError read never started: it must not
    increment read.count/read.ms nor observe a ~0 ms sample into the
    fetch-wait histogram (which would skew the doctor's outlier rules)."""
    from sparkucx_tpu.utils.metrics import H_FETCH_WAIT

    mgr = manager_factory()
    h = mgr.register_shuffle(918, num_maps=1, num_partitions=4)
    _write_all(mgr, h, rng)
    mgr.node.epochs.bump("simulated device loss")
    metrics = mgr.node.metrics
    count_before = metrics.get("shuffle.read.count")
    wait_before = metrics.histogram(H_FETCH_WAIT).count
    with pytest.raises(StaleEpochError):
        mgr.read(h)
    assert metrics.get("shuffle.read.count") == count_before
    assert metrics.histogram(H_FETCH_WAIT).count == wait_before
    mgr.unregister_shuffle(918)


def test_failfast_policy_reports_zero_replays(manager_factory, rng):
    mgr = manager_factory(
        {"spark.shuffle.tpu.fault.exchange.failCount": "1"})
    h = mgr.register_shuffle(916, num_maps=1, num_partitions=4)
    _write_all(mgr, h, rng)
    with pytest.raises(InjectedFault):
        mgr.read(h)
    total = sum(k.shape[0] for _, (k, _) in mgr.read(h).partitions())
    assert total == 32
    assert mgr.report(916).replays == 0
    mgr.unregister_shuffle(916)


def test_replay_under_waves_restarts_whole_exchange(manager_factory, rng):
    """A fault mid-wave-pipeline settles in-flight waves and the replay
    re-runs the WHOLE exchange — per-wave learned caps carry over, and
    the waved result is still oracle-correct."""
    mgr = manager_factory({
        "spark.shuffle.tpu.failure.policy": "replay",
        "spark.shuffle.tpu.a2a.waveRows": "16",
        "spark.shuffle.tpu.a2a.waveDepth": "2",
        "spark.shuffle.tpu.fault.wave.failCount": "1"})
    h = mgr.register_shuffle(917, num_maps=2, num_partitions=4)
    keys = {m: rng.integers(0, 1 << 20, size=64) for m in range(2)}
    for m in range(2):
        w = mgr.get_writer(h, m)
        w.write(keys[m])
        w.commit(4)
    result = mgr.read(h)
    got = np.sort(np.concatenate(
        [k for _, (k, _) in result.partitions()]))
    want = np.sort(np.concatenate(list(keys.values())))
    assert got.tolist() == want.tolist()
    rep = mgr.report(917)
    assert rep.replays == 1
    assert rep.waves >= 2                  # the re-run still waved
    mgr.unregister_shuffle(917)


def test_manager_stale_epoch_fenced(manager_factory, rng):
    """After a remesh bump, reads against old handles fail fast instead of
    issuing a collective pinned to dead membership."""
    mgr = manager_factory()
    h = mgr.register_shuffle(912, num_maps=2, num_partitions=4)
    _write_all(mgr, h, rng)
    mgr.node.epochs.bump("simulated device loss")
    with pytest.raises(StaleEpochError):
        mgr.read(h)
    mgr.unregister_shuffle(912)
    # re-registering under the new epoch works
    h2 = mgr.register_shuffle(912, num_maps=2, num_partitions=4)
    _write_all(mgr, h2, rng)
    total = sum(k.shape[0] for _, (k, _) in mgr.read(h2).partitions())
    assert total == 2 * 32
    mgr.unregister_shuffle(912)


# -- one fault per cell, across the read paths --------------------------------
_CELL_MAPS, _CELL_ROWS, _CELL_PARTS, _CELL_WAVE_ROWS = 2, 256, 8, 64


def _cell_stage(mgr, sid, keys, values):
    h = mgr.register_shuffle(sid, _CELL_MAPS, _CELL_PARTS)
    for m in range(_CELL_MAPS):
        w = mgr.get_writer(h, m)
        w.write(keys[m], values[m])
        w.commit(_CELL_PARTS)
    return h


def _canonical(res):
    """Per-partition rows sorted by (key, value row): partitioning and
    content, order-free."""
    out = []
    for r in range(_CELL_PARTS):
        k, v = res.partition(r)
        order = np.lexsort(tuple(v.T[::-1]) + (k,))
        out.append((k[order].tolist(), v[order].tolist()))
    return out


def _wire_values(k):
    """Float value rows of moderate dynamic range, a function of the key
    alone, so a received row's true values can be recomputed."""
    base = (np.asarray(k) % 997).astype(np.float32)
    return base[:, None] * 0.25 + np.arange(8, dtype=np.float32) * 0.5 + 1.0


@pytest.mark.parametrize("cell", [
    "device_sink", "combine_device_sink", "int8_waved", "hier_waved",
    "distributed_replay", "distributed_dcn_failfast"])
def test_fault_cell_recovers_to_oracle(manager_factory, tmp_path, cell):
    """One armed fault per read path. Under failure.policy=replay the read
    absorbs it: the replay lands on the clean run's plan family and
    returns the oracle's rows, and the path's own properties hold
    (device sink with no payload D2H before the consumer's view, a
    combine folded on the device, the int8 wire within its per-row
    rounding bound, a waved hierarchical read whose flight ring names
    the faulted tier, one budget unit spent group-wide with no agreement
    divergence). Under failfast a DCN straggler past its own deadline
    surfaces as PeerLostError naming the tier, and a clean re-read
    returns the oracle's rows."""
    import jax

    from sparkucx_tpu.utils.metrics import (C_AGREE_DIVERGENCE, C_D2H,
                                            GLOBAL_METRICS)
    conf = {"spark.shuffle.tpu.failure.policy": "replay",
            "spark.shuffle.tpu.failure.replayBudget": "2",
            "spark.shuffle.tpu.failure.collectiveTimeoutMs": "2000",
            "spark.shuffle.tpu.network.timeoutMs": "2000"}
    waved = cell in ("combine_device_sink", "int8_waved", "hier_waved")
    if waved:
        conf.update({"spark.shuffle.tpu.a2a.waveRows": str(_CELL_WAVE_ROWS),
                     "spark.shuffle.tpu.a2a.waveDepth": "2"})
    if cell in ("hier_waved", "distributed_replay",
                "distributed_dcn_failfast"):
        conf["spark.shuffle.tpu.mesh.numSlices"] = "2"
    if cell == "int8_waved":
        conf["spark.shuffle.tpu.a2a.wire"] = "int8"
    if cell == "hier_waved":
        conf.update({"spark.shuffle.tpu.flightRecorder.enabled": "true",
                     "spark.shuffle.tpu.flightRecorder.dir": str(tmp_path)})
    if cell == "distributed_dcn_failfast":
        conf.update({"spark.shuffle.tpu.failure.policy": "failfast",
                     "spark.shuffle.tpu.failure.dcn.timeoutMs": "300"})
    mgr = manager_factory(conf)
    node = mgr.node
    node.is_distributed = cell.startswith("distributed")
    rng = np.random.default_rng(0)
    if cell == "int8_waved":
        keys = [np.arange(_CELL_ROWS, dtype=np.int64) + m * (1 << 32)
                for m in range(_CELL_MAPS)]
        values = [_wire_values(k) for k in keys]
    else:
        keys = [rng.integers(-(1 << 62), 1 << 62, size=_CELL_ROWS)
                for _ in range(_CELL_MAPS)]
        values = [rng.integers(-(1 << 30), 1 << 30, size=(_CELL_ROWS, 2))
                  .astype(np.int32) for _ in range(_CELL_MAPS)]
    sink = "device" if cell.endswith("device_sink") else "host"
    combine = "sum" if cell == "combine_device_sink" else None
    site = {"int8_waved": "wave", "hier_waved": "tier.dcn",
            "distributed_dcn_failfast": "tier.dcn"}.get(cell, "exchange")
    try:
        h0 = _cell_stage(mgr, 950, keys, values)
        oracle = _canonical(mgr.read(h0, combine=combine, sink="host"))
        if sink == "device":
            mgr.unregister_shuffle(950)
            h0 = _cell_stage(mgr, 950, keys, values)
            mgr.read(h0, combine=combine, sink="device").close()
        clean = mgr.report(950)
        mgr.unregister_shuffle(950)
        h = _cell_stage(mgr, 951, keys, values)
        div0 = GLOBAL_METRICS.get(C_AGREE_DIVERGENCE)
        if cell == "distributed_dcn_failfast":
            node.faults.arm(site, delay_ms=1500)
            with pytest.raises(PeerLostError, match="dcn"):
                mgr.read(h)
            # a pure delay never raises: its consulted hits are the firing
            assert node.faults.stats()[site][0] >= 1
            node.faults.disarm(site)
            assert _canonical(mgr.read(h)) == oracle
            return
        node.faults.arm(site, fail_count=1)
        d2h0 = GLOBAL_METRICS.get(C_D2H)
        res = mgr.read(h, combine=combine, sink=sink)
        if sink == "device":
            passthru = jax.jit(lambda rows, nv: rows, donate_argnums=(0,))
            outs = res.consume(
                lambda c, rows, nv: (c or []) + [passthru(rows, nv)])
            jax.block_until_ready(outs)
            assert GLOBAL_METRICS.get(C_D2H) - d2h0 == 0
            res = res.host_view(wave_rows=outs)
        rep = mgr.report(951)
        assert node.faults.stats()[site][1] == 1
        assert rep.replays == 1
        assert rep.plan_family == clean.plan_family
        if cell == "int8_waved":
            assert rep.wire == "int8" and rep.waves >= 2
            got_keys = []
            for r in range(_CELL_PARTS):
                k, v = res.partition(r)
                want = _wire_values(k)
                bound = np.abs(want).max(axis=1, keepdims=True) / 127.0
                assert (np.abs(v - want) <= bound + 1e-5).all()
                got_keys += k.tolist()
            assert sorted(got_keys) == sorted(np.concatenate(keys).tolist())
        else:
            assert _canonical(res) == oracle
        if sink == "device":
            assert rep.sink == "device"
        if cell == "combine_device_sink":
            assert rep.waves >= 2
            assert len(outs) == 1 and rep.merge_ms > 0.0
        if cell == "hier_waved":
            assert rep.hierarchical and rep.tiers and rep.waves >= 2
            assert all("ici_ms" in e and "dcn_ms" in e
                       for e in rep.wave_timeline)
            assert any(e.get("kind") == "tier_fault"
                       and e.get("tier") == "dcn"
                       for e in node.flight.events())
        if cell == "distributed_replay":
            assert rep.distributed
            assert mgr._replay_counts.get(951) == 1
            assert GLOBAL_METRICS.get(C_AGREE_DIVERGENCE) == div0
    finally:
        node.faults.disarm(site)
        node.is_distributed = False
