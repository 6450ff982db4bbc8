"""Multi-tenant service plane (shuffle/tenancy.py + manager surgery).

Covers: TenantRegistry conf resolution + validation, the deficit-
round-robin fair-share admission queue (interleave, within-tenant FIFO,
quota-blocked-head bypass, no starvation), per-tenant quotas/budgets/
integrity overrides, tenant-labeled telemetry end to end (counters,
histograms, report column, Prometheus exposition), tenant-aware report-
ring eviction, the async facade plane (futures, in-flight caps,
collective-ordering clamp), and the concurrent-facade thread-safety
sweep (stats/doctor/report racing live reads).

Concurrency note: every test that runs reads from multiple threads pins
``a2a.maxBytesInFlight=1`` — XLA:CPU 0.4.x wedges nondeterministically
on concurrently-dispatched collective programs (the documented env-gap
family), and the serializing cap routes all concurrency through the
admission plane under test anyway."""

import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.tenancy import (AsyncShuffleExecutor,
                                          FairShareQueue, FifoAdmitQueue,
                                          TenantRegistry,
                                          validate_priority)
from sparkucx_tpu.utils.metrics import (C_ADMIT_BYTES, H_ADMIT_CROSS,
                                        H_ADMIT_WAIT, Metrics, labeled)


def _conf(extra=None):
    m = {"spark.shuffle.tpu.a2a.impl": "dense"}
    m.update(extra or {})
    return TpuShuffleConf(m, use_env=False)


# -- registry ---------------------------------------------------------------
def test_priority_validation():
    assert validate_priority("HIGH ") == "high"
    with pytest.raises(ValueError, match="tenant.priority"):
        validate_priority("urgent")


def test_registry_defaults_and_overrides():
    reg = TenantRegistry(_conf({
        "spark.shuffle.tpu.tenant.id": "svc-a",
        "spark.shuffle.tpu.tenant.priority": "batch",
        "spark.shuffle.tpu.tenant.whale.priority": "high",
        "spark.shuffle.tpu.tenant.whale.maxBytesInFlight": "64m",
        "spark.shuffle.tpu.tenant.whale.maxInflightReads": "3",
        "spark.shuffle.tpu.tenant.whale.replayBudget": "0",
        "spark.shuffle.tpu.tenant.whale.integrity.verify": "off",
        "spark.shuffle.tpu.tenant.whale.waveDepth": "1",
    }))
    assert reg.default_id == "svc-a"
    # unknown tenant inherits the conf-default priority, no overrides
    spec = reg.spec("anon")
    assert (spec.priority, spec.max_bytes_in_flight,
            spec.replay_budget, spec.integrity_verify,
            spec.wave_depth) == ("batch", 0, None, None, None)
    w = reg.spec("whale")
    assert w.priority == "high" and w.weight == 4
    assert w.max_bytes_in_flight == 64 << 20
    assert w.max_inflight_reads == 3
    assert w.replay_budget == 0
    assert w.integrity_verify == "off"
    assert w.wave_depth == 1
    # resolve(None) -> conf default; resolve("x") -> itself
    assert reg.resolve(None) == "svc-a" and reg.resolve("x") == "x"


def test_registry_rejects_bad_values():
    with pytest.raises(ValueError, match="priority"):
        TenantRegistry(_conf(
            {"spark.shuffle.tpu.tenant.w.priority": "urgent"})).spec("w")
    with pytest.raises(ValueError, match="replayBudget"):
        TenantRegistry(_conf(
            {"spark.shuffle.tpu.tenant.w.replayBudget": "-1"})).spec("w")
    with pytest.raises(ValueError, match="integrity.verify"):
        TenantRegistry(_conf(
            {"spark.shuffle.tpu.tenant.w.integrity.verify":
             "paranoid"})).spec("w")
    with pytest.raises(ValueError, match="waveDepth"):
        TenantRegistry(_conf(
            {"spark.shuffle.tpu.tenant.w.waveDepth": "99"})).spec("w")


def test_register_shuffle_validates_tenant_conf(manager_factory):
    mgr = manager_factory({
        "spark.shuffle.tpu.tenant.bad.priority": "urgent"})
    with pytest.raises(ValueError, match="priority"):
        mgr.register_shuffle(1, 1, 8, tenant="bad")
    h = mgr.register_shuffle(2, 1, 8, tenant="ok")
    assert h.tenant == "ok"
    # default tenant rides the conf
    assert mgr.register_shuffle(3, 1, 8).tenant == "default"


# -- fair-share queue -------------------------------------------------------
def _fits_all(tenant, nb):
    return True


def _reg(priorities):
    conf = {f"spark.shuffle.tpu.tenant.{t}.priority": p
            for t, p in priorities.items()}
    return TenantRegistry(_conf(conf))


def test_drr_minnows_overtake_whale_flood():
    """The head-of-line fix: a whale's queued flood does not park the
    minnows behind it — small covered tickets are granted past the
    whale's deep head, within-tenant order stays FIFO, and the whale is
    still served (no starvation in either direction)."""
    reg = _reg({"whale": "batch", "minnow": "high"})
    q = FairShareQueue(reg, quantum=1 << 20)
    big, small = 8 << 20, 256 << 10
    for t in range(4):                       # whale flood arrives first
        q.enqueue(t, "whale", big)
    for t in range(10, 16):                  # six minnows behind it
        q.enqueue(t, "minnow", small)
    order = []
    while q:
        tk = q.grantable(_fits_all)
        assert tk is not None
        order.append(tk)
        q.pop(tk, big if tk < 10 else small)
    # every minnow is granted before the LAST whale ticket (no
    # head-of-line starvation) and minnows stay FIFO among themselves
    minnow_pos = [order.index(t) for t in range(10, 16)]
    assert max(minnow_pos) < order.index(3)
    assert minnow_pos == sorted(minnow_pos)
    # whales stay FIFO among themselves too
    whale_pos = [order.index(t) for t in range(4)]
    assert whale_pos == sorted(whale_pos)
    # and every ticket was served exactly once
    assert sorted(order) == list(range(4)) + list(range(10, 16))


def test_drr_weights_bias_byte_share():
    """With both tenants continuously backlogged, granted-byte shares
    track the priority weights (high=4 : batch=1), not arrival order or
    check frequency."""
    reg = _reg({"a": "high", "b": "batch"})
    q = FairShareQueue(reg, quantum=1 << 20)
    nb = 1 << 20
    tid = [0]

    def refill(tenant, base):
        t = base + tid[0]
        tid[0] += 1
        q.enqueue(t, tenant, nb)
        return t

    for _ in range(4):
        refill("a", 0)
        refill("b", 100000)
    grants = {"a": 0, "b": 0}
    for _ in range(100):
        # repeated no-grant checks must not shift the shares (the
        # scan-frequency regression): poll a few times per grant
        for _ in range(3):
            q.grantable(_fits_all)
        tk = q.grantable(_fits_all)
        tenant = "a" if tk < 100000 else "b"
        grants[tenant] += 1
        q.pop(tk, nb)
        refill(tenant, 0 if tenant == "a" else 100000)
    assert grants["a"] + grants["b"] == 100
    # 4:1 weights with equal ticket sizes -> ~80/20; generous envelope
    assert 65 <= grants["a"] <= 92, grants


def test_drr_quota_blocked_head_bypasses():
    """A head whose tenant is blocked on its OWN quota must not
    head-of-line-block other tenants; once its quota frees it is served
    from its kept position. A head blocked by the GLOBAL cap is NOT
    bypassed (it earned the grant — streaming smaller tickets past it
    would starve a big exchange waiting for the drain)."""
    reg = _reg({"a": "normal", "b": "normal"})
    q = FairShareQueue(reg, quantum=1 << 20)
    q.enqueue(1, "a", 1 << 20)
    q.enqueue(2, "b", 1 << 20)
    blocked = {"a"}

    def fits(tenant, nb):
        return tenant not in blocked

    def quota_blocked(tenant, nb):
        return tenant in blocked

    # a's head globally-blocked (quota_blocked says no): NO bypass
    assert q.grantable(fits) is None
    assert q.grantable(fits, lambda t, nb: False) is None
    # a's head blocked on its OWN quota: b granted past it
    assert q.grantable(fits, quota_blocked) == 2
    q.pop(2, 1 << 20)
    blocked.clear()
    assert q.grantable(fits, quota_blocked) == 1
    q.pop(1, 1 << 20)
    assert not q


def test_drr_discard_unblocks():
    reg = _reg({"a": "normal"})
    q = FairShareQueue(reg)
    q.enqueue(1, "a", 1 << 20)
    q.enqueue(2, "a", 1 << 20)
    assert q.grantable(_fits_all) == 1
    q.discard(1)                            # abandoned while queued
    assert q.grantable(_fits_all) == 2
    q.discard(2)
    assert q.grantable(_fits_all) is None and not q


def test_fifo_queue_strict_order():
    q = FifoAdmitQueue()
    q.enqueue(1, "whale", 8 << 20)
    q.enqueue(2, "minnow", 1 << 10)
    assert q.grantable(_fits_all) == 1      # strictly arrival-ordered
    assert 1 in q and len(q) == 2
    q.pop(1, 8 << 20)
    assert q.grantable(_fits_all) == 2


# -- per-tenant admission accounting ---------------------------------------
def test_tenant_quota_and_inflight_accounting(manager_factory):
    mgr = manager_factory({
        "spark.shuffle.tpu.a2a.maxBytesInFlight": "64m",
        "spark.shuffle.tpu.tenant.capped.maxBytesInFlight": "1m"})
    with mgr._inflight_cv:
        # empty-handed tenant: even a bigger-than-quota ask admits alone
        assert mgr._tenant_fits_locked("capped", 2 << 20)
        mgr._grant_inflight_locked("capped", 2 << 20)
        # now at 2m > 1m quota: nothing more fits for it...
        assert not mgr._tenant_fits_locked("capped", 1 << 10)
        # ...while another tenant still has global room
        assert mgr._tenant_fits_locked("other", 1 << 20)
    assert mgr.node.metrics.get(
        labeled(C_ADMIT_BYTES, tenant="capped")) == float(2 << 20)
    assert mgr.node.metrics.get_gauge(
        labeled("shuffle.inflight.bytes", tenant="capped")) \
        == float(2 << 20)
    mgr._release_inflight(2 << 20, tenant="capped")
    with mgr._inflight_cv:
        assert mgr._tenant_fits_locked("capped", 1 << 10)
    assert mgr.node.metrics.get_gauge(
        labeled("shuffle.inflight.bytes", tenant="capped")) == 0.0


def test_pack_share_splits_by_weight(manager_factory):
    mgr = manager_factory({
        "spark.shuffle.tpu.a2a.packThreads": "10",
        "spark.shuffle.tpu.tenant.hi.priority": "high",
        "spark.shuffle.tpu.tenant.lo.priority": "batch"})
    with mgr._lock:
        mgr._packing = {"hi": 1}
    assert mgr._pack_share("hi") == 10      # alone: every worker
    with mgr._lock:
        mgr._packing = {"hi": 1, "lo": 1}
    assert mgr._pack_share("hi") == 8       # 10 * 4/5
    assert mgr._pack_share("lo") == 2       # 10 * 1/5, floored >= 1
    with mgr._lock:
        mgr._packing = {}


# -- end-to-end labeled telemetry ------------------------------------------
def _write_small(mgr, sid, tenant, rows=256, maps=2, R=8, seed=0):
    rng = np.random.default_rng(seed)
    h = mgr.register_shuffle(sid, maps, R, tenant=tenant)
    for m in range(maps):
        w = mgr.get_writer(h, m)
        w.write(rng.integers(0, 1 << 20, rows).astype(np.int64),
                rng.random((rows, 2)).astype(np.float32))
        w.commit(R)
    return h


def test_read_labels_metrics_and_report(manager_factory):
    mgr = manager_factory({
        "spark.shuffle.tpu.a2a.maxBytesInFlight": "1"})
    h = _write_small(mgr, 7, "alice")
    mgr.read(h)
    metrics = mgr.node.metrics
    assert metrics.get(labeled("shuffle.read.count", tenant="alice")) \
        == 1.0
    assert metrics.get(
        labeled("shuffle.payload.bytes", tenant="alice")) > 0
    assert metrics.get(labeled("shuffle.wire.bytes", tenant="alice")) > 0
    # the admit-wait distribution observed (0 for the immediate grant)
    hist = metrics.histogram(labeled(H_ADMIT_WAIT, tenant="alice"))
    assert hist is not None and hist.count >= 1
    rep = mgr.report(7)
    assert rep.tenant == "alice" and rep.completed
    assert rep.to_dict()["tenant"] == "alice"
    # labeled identities render as legal Prometheus series under ONE
    # family TYPE line
    from sparkucx_tpu.utils.export import collect_snapshot, \
        render_prometheus
    text = render_prometheus(collect_snapshot(
        metrics, reports=mgr.exchange_reports()))
    assert 'sparkucx_tpu_shuffle_read_count{tenant="alice"} 1' in text
    assert text.count(
        "# TYPE sparkucx_tpu_shuffle_admit_wait_ms histogram") == 1
    assert 'tenant="alice"' in text


def test_report_ring_tenant_aware_eviction(manager_factory):
    """Satellite regression: capacity conf-able + a chatty tenant evicts
    its OWN oldest reports — 65 interleaved exchanges of two tenants
    cannot flush the quiet tenant's reports before they are read."""
    mgr = manager_factory({
        "spark.shuffle.tpu.metrics.reportCapacity": "8"})
    assert mgr._report_capacity == 8
    quiet = [mgr.register_shuffle(100 + i, 1, 8, tenant="quiet")
             for i in range(3)]
    # 65 interleaved exchanges: chatty floods, quiet's three reports ride
    # along early and must survive the flood
    for i, h in enumerate(quiet):
        mgr._new_report(h, distributed=False)
        mgr.node.flight.end_trace("")       # balance begin_trace
    for i in range(62):
        ch = mgr.register_shuffle(200 + i, 1, 8, tenant="chatty")
        mgr._new_report(ch, distributed=False)
        mgr.node.flight.end_trace("")
    tenants = [r.tenant for r in mgr.reports()]
    assert len(tenants) == 8
    assert tenants.count("quiet") == 3, tenants
    assert all(mgr.report(100 + i) is not None for i in range(3))
    # single tenant degenerates to plain LRU: oldest goes first
    mgr2 = manager_factory({
        "spark.shuffle.tpu.metrics.reportCapacity": "4"})
    for i in range(6):
        h = mgr2.register_shuffle(300 + i, 1, 8)
        mgr2._new_report(h, distributed=False)
        mgr2.node.flight.end_trace("")
    assert [r.shuffle_id for r in mgr2.reports()] == [302, 303, 304, 305]


# -- per-tenant policy overrides -------------------------------------------
def test_replay_budget_override(manager_factory):
    mgr = manager_factory({
        "spark.shuffle.tpu.failure.policy": "replay",
        "spark.shuffle.tpu.failure.replayBudget": "2",
        "spark.shuffle.tpu.tenant.frugal.replayBudget": "0"})
    h_default = mgr.register_shuffle(1, 1, 8)
    h_frugal = mgr.register_shuffle(2, 1, 8, tenant="frugal")
    assert mgr._spend_replay(h_default.shuffle_id)      # global budget 2
    assert not mgr._spend_replay(h_frugal.shuffle_id)   # tenant budget 0
    budget, key = mgr._replay_budget_for(h_frugal.shuffle_id)
    assert budget == 0 and "tenant.frugal.replayBudget" in key


def test_integrity_override_per_tenant(manager_factory):
    from sparkucx_tpu.utils.metrics import C_INTEGRITY_VERIFIED
    mgr = manager_factory({
        "spark.shuffle.tpu.integrity.verify": "staged",
        "spark.shuffle.tpu.tenant.fast.integrity.verify": "off"})
    assert mgr._integrity_for("fast") == "off"
    assert mgr._integrity_for("anyone-else") == "staged"
    h_off = _write_small(mgr, 11, "fast", seed=1)
    mgr.read(h_off)
    assert mgr.node.metrics.get(C_INTEGRITY_VERIFIED) == 0.0
    assert mgr.report(11).integrity == ""
    h_on = _write_small(mgr, 12, "careful", seed=2)
    mgr.read(h_on)
    assert mgr.node.metrics.get(C_INTEGRITY_VERIFIED) > 0
    assert mgr.report(12).integrity == "staged"


def test_wave_depth_override_resolves(manager_factory):
    mgr = manager_factory({
        "spark.shuffle.tpu.a2a.waveDepth": "3",
        "spark.shuffle.tpu.tenant.shallow.waveDepth": "1"})
    assert mgr._tenants.spec("shallow").wave_depth == 1
    assert mgr._tenants.spec("other").wave_depth is None


# -- async futures (both facades) ------------------------------------------
def _service_conf(extra=None):
    m = {"spark.shuffle.tpu.a2a.impl": "dense",
         "spark.shuffle.tpu.io.format": "raw",
         "spark.shuffle.tpu.a2a.maxBytesInFlight": "1"}
    m.update(extra or {})
    return m


def test_v1_async_futures_match_sync(mesh8):
    from sparkucx_tpu.service import connect
    svc = connect(_service_conf(), use_env=False)
    try:
        rng = np.random.default_rng(3)
        h = svc.register_shuffle(1, 2, 8, tenant="alice")
        keys = rng.integers(0, 1 << 30, 800).astype(np.int64)
        for m in range(2):
            svc.write(h, m, keys[m * 400:(m + 1) * 400])
        want = np.sort(np.concatenate(
            [svc.read(h).partition(r)[0] for r in range(8)]))
        fut = svc.read_async(h)
        res = fut.result(timeout=60)
        got = np.sort(np.concatenate(
            [res.partition(r)[0] for r in range(8)]))
        np.testing.assert_array_equal(got, np.sort(keys))
        np.testing.assert_array_equal(got, want)
        assert fut.done() and fut.tenant == "alice" \
            and fut.shuffle_id == 1
        assert fut.wall_ms > 0 and fut.exception() is None
        # submit_async resolves to the same bytes
        res2 = svc.submit_async(h).result(timeout=60)
        got2 = np.sort(np.concatenate(
            [res2.partition(r)[0] for r in range(8)]))
        np.testing.assert_array_equal(got2, want)
        # done-callback fires with the future itself
        seen = []
        f3 = svc.read_async(h)
        f3.add_done_callback(lambda f: seen.append(f.tenant))
        f3.result(timeout=60)
        deadline = time.monotonic() + 5
        while not seen and time.monotonic() < deadline:
            time.sleep(0.01)
        assert seen == ["alice"]
    finally:
        svc.stop()



@pytest.mark.parametrize("cell", ["solo", "fair", "starved"])
def test_async_plane_whale_flood_cells(mesh8, cell):
    """Minnow reads on the async facade plane alone (solo), behind a
    whale's flood of reads under fair share (fair), and behind the same
    flood under strict-FIFO admission (starved): every future resolves
    to its oracle rows and the labeled read counters count each
    tenant's reads. Whether the doctor's quota_starvation rule fires
    needs admission waits past its 300 ms floor, which this shape does
    not reach on every host; the rule's goldens in test_doctor.py and
    test_cross_grants_observed hold the firing and its discriminator."""
    from sparkucx_tpu.service import connect
    from sparkucx_tpu.utils.metrics import labeled
    extra = {"spark.shuffle.tpu.tenant.asyncWorkers": "16",
             "spark.shuffle.tpu.tenant.minnow.priority": "high",
             "spark.shuffle.tpu.tenant.whale.priority": "batch"}
    if cell == "starved":
        extra["spark.shuffle.tpu.tenant.fairShare"] = "false"
    svc = connect(_service_conf(extra), use_env=False)
    rng = np.random.default_rng(7)

    def stage(sid, tenant, maps, rows):
        h = svc.register_shuffle(sid, maps, 8, tenant=tenant)
        keys = rng.integers(0, 1 << 20, maps * rows).astype(np.int64)
        for m in range(maps):
            svc.write(h, m, keys[m * rows:(m + 1) * rows])
        return h, np.sort(keys)

    try:
        minnows = [stage(100 + i, "minnow", 2, 128) for i in range(4)]
        whale = stage(99, "whale", 4, 512) if cell != "solo" else None
        svc.read(minnows[0][0])                 # warm both families
        futs = []
        if whale is not None:
            svc.read(whale[0])
            # the whale floods admission before the minnows arrive
            futs += [(svc.read_async(whale[0]), whale[1])
                     for _ in range(4)]
        futs += [(svc.read_async(h), want) for h, want in minnows]
        for fut, want in futs:
            res = fut.result(timeout=120)
            got = np.sort(np.concatenate(
                [res.partition(r)[0] for r in range(8)]))
            np.testing.assert_array_equal(got, want)
        counters = svc.stats("json")["counters"]
        assert counters[labeled("shuffle.read.count",
                                tenant="minnow")] == 1 + len(minnows)
        whale_reads = labeled("shuffle.read.count", tenant="whale")
        if whale is None:
            assert whale_reads not in counters
        else:
            assert counters[whale_reads] == 1 + 4
    finally:
        svc.stop()

def test_v2_async_futures(mesh8):
    from sparkucx_tpu.compat.v2 import ShuffleDependency, ShuffleServiceV2
    svc = ShuffleServiceV2(TpuShuffleConf(_service_conf(), use_env=False))
    try:
        rng = np.random.default_rng(4)
        dep = ShuffleDependency(shuffle_id=5, num_maps=2,
                                num_partitions=8, tenant="bob")
        h = svc.register(dep)
        assert h.tenant == "bob"
        keys = rng.integers(0, 1 << 30, 600).astype(np.int64)
        for m in range(2):
            w = svc.writer(h, m)
            w.write(keys[m * 300:(m + 1) * 300])
            w.commit()
        fut = svc.read_async(h)
        batch = fut.result(timeout=60)
        got = np.sort(np.concatenate([kv[0] for kv in batch.values()]))
        np.testing.assert_array_equal(got, np.sort(keys))
        res = svc.submit_async(h).result(timeout=60)
        assert res is not None
        assert svc.manager.report(5).tenant == "bob"
    finally:
        svc.stop()


def test_async_inflight_cap_throttles():
    reg = TenantRegistry(_conf(
        {"spark.shuffle.tpu.tenant.t.maxInflightReads": "1"}))
    metrics = Metrics()
    ex = AsyncShuffleExecutor(_conf(), reg, metrics, distributed=False)
    try:
        gate = threading.Event()
        f1 = ex.submit(gate.wait, "t", 1)
        t0 = time.monotonic()
        box = {}

        def second():
            box["f2"] = ex.submit(lambda: "done", "t", 2, timeout=30)

        th = threading.Thread(target=second)
        th.start()
        time.sleep(0.2)
        assert "f2" not in box          # blocked at the cap
        gate.set()
        th.join(timeout=30)
        assert box["f2"].result(30) == "done"
        assert time.monotonic() - t0 >= 0.2
        assert f1.result(30) is True
        assert metrics.get(labeled(
            "shuffle.submit.throttled.count", tenant="t")) == 1.0
        # a timeout at the cap raises typed instead of hanging
        g2 = threading.Event()
        ex.submit(g2.wait, "t", 3)
        with pytest.raises(TimeoutError, match="maxInflightReads"):
            ex.submit(lambda: None, "t", 4, timeout=0.2)
        g2.set()
    finally:
        ex.stop()


def test_async_stop_wakes_capped_submitter():
    """stop() must not strand a submitter blocked at a tenant cap: the
    queued runs it cancels never release their slots, so the waiter is
    woken and raises instead of spinning on a drained pool forever."""
    reg = TenantRegistry(_conf(
        {"spark.shuffle.tpu.tenant.t.maxInflightReads": "1"}))
    ex = AsyncShuffleExecutor(_conf(), reg, Metrics(), distributed=False)
    gate = threading.Event()
    ex.submit(gate.wait, "t", 1)            # holds the only slot
    box = {}

    def blocked():
        try:
            ex.submit(lambda: None, "t", 2)
        except RuntimeError as e:
            box["err"] = str(e)

    th = threading.Thread(target=blocked)
    th.start()
    time.sleep(0.2)
    # stop with the slot STILL held (wait=False — the holder is parked
    # on the gate): the blocked submitter must wake and raise, not spin
    ex.stop(wait=False)
    th.join(timeout=10)
    alive = th.is_alive()
    gate.set()                              # release the worker thread
    assert not alive, "capped submitter hung across stop()"
    assert "stopped" in box.get("err", "")


def test_async_distributed_keeps_k_workers_with_agreed_order():
    """Distributed async keeps the conf'd worker count (the agreed-
    order dispatcher aligns the collective order); the historical
    width-1 clamp survives behind tenant.asyncAgreedOrder=false."""
    reg = TenantRegistry(_conf())
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "8"}),
        reg, Metrics(), distributed=True)
    assert ex.workers == 8
    assert ex._dispatching
    ex_local = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "8"}),
        reg, Metrics(), distributed=False)
    assert ex_local.workers == 8
    assert not ex_local._dispatching
    ex.stop()
    ex_local.stop()


def test_async_distributed_opt_out_clamps_single_worker():
    reg = TenantRegistry(_conf())
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "8",
               "spark.shuffle.tpu.tenant.asyncAgreedOrder": "false"}),
        reg, Metrics(), distributed=True)
    assert ex.workers == 1          # collective order == submission order
    # FIFO execution on the single worker: completion order == submit
    # order even when the first task is the slowest
    order = []

    def job(i, delay):
        time.sleep(delay)
        order.append(i)

    futs = [ex.submit(lambda i=i, d=d: job(i, d), None, i)
            for i, d in enumerate([0.1, 0.0, 0.0])]
    for f in futs:
        f.result(30)
    assert order == [0, 1, 2]
    ex.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        ex.submit(lambda: None, None, 9)


def test_async_agreed_order_dispatch_single_process():
    """The agreed-order dispatcher end to end at nproc=1: the agreement
    rounds degenerate to identity, reads execute in the agreed DRR
    order, futures resolve with results."""
    reg = TenantRegistry(_conf())
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "4"}),
        reg, Metrics(), distributed=True)
    assert ex._dispatching
    futs = [ex.submit(lambda i=i: i * 10, None, i) for i in range(5)]
    assert [f.result(30) for f in futs] == [0, 10, 20, 30, 40]
    ex.stop()


def test_async_turnstile_orders_execution_not_just_submission():
    """The agreed DRR order is enforced at EXECUTION: each read's body
    runs under a collective-turnstile ticket issued in the agreed
    order, so bodies never overlap and never reorder even on a 4-wide
    pool — the cross-process collective-interleave hazard OS thread
    scheduling would otherwise reintroduce (review round: submission
    order alone left K worker threads racing their collectives)."""
    reg = TenantRegistry(_conf({
        "spark.shuffle.tpu.tenant.hi.priority": "high"}))
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "4"}),
        reg, Metrics(), distributed=True)
    try:
        # pin the dispatcher slot so the loop can't race the batch: one
        # deterministic batch driven through _dispatch_batch directly
        ex._dispatcher = threading.current_thread()
        spans = []

        def body(tag):
            t0 = time.monotonic()
            time.sleep(0.02)
            spans.append((tag, t0, time.monotonic()))
            return tag

        subs = [("lo0", "lo"), ("hi0", "hi"), ("hi1", "hi"),
                ("lo1", "lo"), ("hi2", "hi")]
        futs = [ex.submit(lambda t=t: body(t), tid, i)
                for i, (t, tid) in enumerate(subs)]
        ex._dispatch_batch(len(subs))
        assert [f.result(30) for f in futs] == [t for t, _ in subs]
        ran = [s[0] for s in sorted(spans, key=lambda s: s[1])]
        # agreed DRR: lo (normal, weight 2) serves both its reads in
        # round 1, then hi (weight 4) drains its three — and execution
        # matches that schedule exactly
        assert ran == ["lo0", "lo1", "hi0", "hi1", "hi2"]
        ordered = sorted(spans, key=lambda s: s[1])
        for (_, _, end), (_, start, _) in zip(ordered, ordered[1:]):
            assert end <= start      # collective sections never overlap
    finally:
        ex._dispatcher = None
        ex.stop()


def test_async_dispatch_failure_after_pop_frees_popped_batch(monkeypatch):
    """A dispatcher failure AFTER the batch is popped (here: the order
    round dying mid-agreement) must resolve the popped futures and free
    their tenant slots — before the fix only still-queued items were
    failed, so the popped batch leaked its maxInflightReads slots and
    submitters blocked forever."""
    from sparkucx_tpu.shuffle import agreement
    reg = TenantRegistry(_conf(
        {"spark.shuffle.tpu.tenant.t.maxInflightReads": "2"}))
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "4"}),
        reg, Metrics(), distributed=True)
    real = agreement.agree

    def boom(topic, *a, **k):
        if topic == "async.order":
            raise RuntimeError("order channel down")
        return real(topic, *a, **k)

    try:
        ex._dispatcher = threading.current_thread()
        f1 = ex.submit(lambda: 1, "t", 1)
        f2 = ex.submit(lambda: 2, "t", 2)
        monkeypatch.setattr(agreement, "agree", boom)
        with pytest.raises(RuntimeError, match="order channel down"):
            ex._dispatch_batch(2)
        for f in (f1, f2):
            with pytest.raises(RuntimeError, match="order channel down"):
                f.result(10)
        assert ex.inflight("t") == 0    # slots freed, not leaked
        # the turnstile advanced past the abandoned batch: a fresh
        # batch dispatches cleanly once the channel recovers
        monkeypatch.setattr(agreement, "agree", real)
        f3 = ex.submit(lambda: 3, "t", 3)
        ex._dispatch_batch(1)
        assert f3.result(10) == 3
    finally:
        ex._dispatcher = None
        ex.stop()


def test_async_dispatcher_survives_batch_fault_live_loop(monkeypatch):
    """Through the LIVE dispatcher loop: a fault that strikes after the
    batch is popped fails that batch only — the dispatcher keeps
    serving, so a read submitted after the fault surfaced succeeds
    instead of being drained by a dying dispatcher (the old behavior
    raced post-fault submissions into 'dispatcher failed')."""
    from sparkucx_tpu.shuffle import agreement
    reg = TenantRegistry(_conf())
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "4"}),
        reg, Metrics(), distributed=True)
    real = agreement.agree

    def boom(topic, *a, **k):
        if topic == "async.order":
            raise RuntimeError("order channel down")
        return real(topic, *a, **k)

    try:
        monkeypatch.setattr(agreement, "agree", boom)
        f1 = ex.submit(lambda: 1, "default", 1)
        with pytest.raises(RuntimeError, match="order channel down"):
            f1.result(10)
        assert ex.inflight("default") == 0
        monkeypatch.setattr(agreement, "agree", real)
        # same executor, same (still-alive) dispatcher: recovers
        f2 = ex.submit(lambda: 2, "default", 2)
        assert f2.result(10) == 2
        assert ex.inflight("default") == 0
    finally:
        ex.stop()


def test_async_dispatch_divergence_fails_batch_typed(monkeypatch):
    """An order-round divergence fails the whole popped batch with the
    typed error (dissenter named), frees the slots, and leaves the
    dispatcher alive for the next batch."""
    from sparkucx_tpu.shuffle import agreement
    from sparkucx_tpu.shuffle.agreement import AgreementDivergenceError
    reg = TenantRegistry(_conf())
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "2"}),
        reg, Metrics(), distributed=True)
    real = agreement.agree

    def dissent(topic, *a, **k):
        if topic == "async.order":
            raise AgreementDivergenceError(
                topic, "value", [1], [[0], [9]],
                conf_key="spark.shuffle.tpu.tenant.asyncAgreedOrder")
        return real(topic, *a, **k)

    try:
        ex._dispatcher = threading.current_thread()
        f1 = ex.submit(lambda: 1, None, 1)
        monkeypatch.setattr(agreement, "agree", dissent)
        ex._dispatch_batch(1)           # returns, does NOT raise
        with pytest.raises(AgreementDivergenceError,
                           match="asyncAgreedOrder"):
            f1.result(10)
        assert ex.inflight("default") == 0
        monkeypatch.setattr(agreement, "agree", real)
        f2 = ex.submit(lambda: 2, None, 2)
        ex._dispatch_batch(1)
        assert f2.result(10) == 2
    finally:
        ex._dispatcher = None
        ex.stop()


def test_async_stop_unblocks_turnstiled_read():
    """stop() closes the turnstile BEFORE draining the pool: a read
    parked on its collective turn behind a long-running predecessor
    fails typed instead of hanging shutdown forever."""
    reg = TenantRegistry(_conf())
    ex = AsyncShuffleExecutor(
        _conf({"spark.shuffle.tpu.tenant.asyncWorkers": "2"}),
        reg, Metrics(), distributed=True)
    gate = threading.Event()
    ex._dispatcher = threading.current_thread()
    f1 = ex.submit(gate.wait, None, 1)          # will hold the turn
    f2 = ex.submit(lambda: "late", None, 2)     # parks behind it
    ex._dispatch_batch(2)
    time.sleep(0.1)
    ex._dispatcher = None
    ex.stop(wait=False)
    with pytest.raises(RuntimeError, match="closed"):
        f2.result(10)
    gate.set()                                  # let the holder finish
    assert f1.result(10) is True


def test_agreed_submission_order_deterministic_drr():
    """agreed_submission_order is a pure function of the batch: two
    simulated processes holding the same (seq, tenant) pairs compute
    the identical dispatch order, with weight-proportional interleave
    (high=4 reads per round vs batch=1) and FIFO within a tenant."""
    from sparkucx_tpu.shuffle.tenancy import agreed_submission_order
    weights = {"hi": 4, "lo": 1}
    pending = [(1, "lo"), (2, "hi"), (3, "hi"), (4, "hi"),
               (5, "hi"), (6, "hi"), (7, "lo")]
    a = agreed_submission_order(pending, lambda t: weights[t])
    b = agreed_submission_order(list(pending), lambda t: weights[t])
    assert a == b                           # simulated-process parity
    assert sorted(a) == [1, 2, 3, 4, 5, 6, 7]
    # lo arrived first -> one read (weight 1), then hi's 4-read round
    assert a[:5] == [1, 2, 3, 4, 5]
    assert a.index(2) < a.index(3) < a.index(4)   # FIFO within hi


# -- satellite: concurrent facade access sweep ------------------------------
def test_facade_race_stats_doctor_report(mesh8):
    """stats()/doctor()/report()/gather_reports racing N concurrent
    read()s from worker threads: the metrics registry, report ring and
    step cache all get hit concurrently once async futures land — the
    sweep asserts no exceptions and structurally-sane snapshots
    throughout."""
    from sparkucx_tpu.service import connect
    svc = connect(_service_conf(
        {"spark.shuffle.tpu.tenant.m.priority": "high"}), use_env=False)
    errs = []
    try:
        rng = np.random.default_rng(5)
        handles = []
        for i in range(4):
            h = svc.register_shuffle(50 + i, 2, 8,
                                     tenant="m" if i % 2 else "w")
            for m in range(2):
                svc.write(h, m, rng.integers(
                    0, 1 << 20, 256).astype(np.int64))
            handles.append(h)
        svc.read(handles[0])                  # warm the program
        stop = threading.Event()

        def reader(h):
            try:
                for _ in range(3):
                    res = svc.read(h)
                    assert res.partitions_ready(poll_s=0.001) or True
            except Exception as e:  # pragma: no cover
                errs.append(("read", repr(e)))

        def scraper():
            try:
                while not stop.is_set():
                    doc = svc.stats("json")
                    assert isinstance(doc.get("counters"), dict)
                    assert isinstance(svc.stats("prometheus"), str)
                    findings = svc.doctor("findings")
                    assert isinstance(findings, list)
                    for h in handles:
                        svc.manager.report(h.shuffle_id)
                    svc.manager.exchange_reports()
                    svc.manager.gather_reports(handles[0].shuffle_id)
            except Exception as e:  # pragma: no cover
                errs.append(("scrape", repr(e)))

        threads = [threading.Thread(target=reader, args=(h,))
                   for h in handles]
        scrapers = [threading.Thread(target=scraper) for _ in range(2)]
        for t in threads + scrapers:
            t.start()
        for t in threads:
            t.join(timeout=300)
        stop.set()
        for t in scrapers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads + scrapers), \
            "facade race deadlocked"
        assert not errs, errs
        # the per-tenant plane saw both tenants
        counters = svc.stats("json")["counters"]
        assert counters.get(
            labeled("shuffle.read.count", tenant="m"), 0) > 0
        assert counters.get(
            labeled("shuffle.read.count", tenant="w"), 0) > 0
    finally:
        svc.stop()


# -- cross-grants discriminator --------------------------------------------
def test_cross_grants_observed(manager_factory):
    """A deferred tenant records how many grants OTHER tenants received
    while it waited — the quota_starvation discriminator (self-queueing
    observes ~0; parked-behind-a-flood observes the flood)."""
    mgr = manager_factory({
        "spark.shuffle.tpu.a2a.maxBytesInFlight": "1",
        "spark.shuffle.tpu.tenant.fairShare": "false"})
    whale = [_write_small(mgr, 60 + i, "whale", rows=512, seed=i)
             for i in range(3)]
    minnow = _write_small(mgr, 70, "minnow", rows=64, seed=9)
    pending = [mgr.submit(h) for h in whale]
    p_minnow = mgr.submit(minnow)
    for p in pending:
        p.result()
    p_minnow.result()
    hist = mgr.node.metrics.histogram(
        labeled(H_ADMIT_CROSS, tenant="minnow"))
    assert hist is not None and hist.count == 1
    # FIFO: at least the two whale exchanges still queued ahead passed it
    assert hist.max >= 2.0, hist.max
