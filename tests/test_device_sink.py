"""Device-resident consumption (read.sink) — ISSUE-10.

Pins the tentpole's contracts: sink resolution and fallbacks, the
zero-D2H device result (single-shot and waved), donation-safe consume,
HBM-residency admission, the (shape family, sink) program key, report
accounting (sink / d2h_bytes), the MoE read-path dispatch flagship, the
ring/ulysses device-sink consumers, and the lazy-result concurrent
first-touch regression (reader._fetch_lock)."""

import threading

import numpy as np
import pytest

from sparkucx_tpu.utils.metrics import (C_D2H, C_H2D, COMPILE_PROGRAMS,
                                        GLOBAL_METRICS)


@pytest.fixture(scope="module")
def base_manager(mesh8):
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense"},
                          use_env=False)
    node = TpuNode.start(conf)
    m = TpuShuffleManager(node, conf)
    yield m
    m.stop()
    node.close()


@pytest.fixture(scope="module")
def managers(base_manager):
    """Conf-override managers sharing the module node (the wire_managers
    discipline)."""
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    cache = {}

    def get(**overrides):
        key = tuple(sorted(overrides.items()))
        if key not in cache:
            cmap = {"spark.shuffle.tpu.a2a.impl": "dense"}
            cmap.update({"spark.shuffle.tpu." + k: str(v)
                         for k, v in overrides.items()})
            conf = TpuShuffleConf(cmap, use_env=False)
            cache[key] = TpuShuffleManager(base_manager.node, conf)
        return cache[key]

    yield get
    for m in cache.values():
        m.stop()


_SID = [70_000]


def _stage(mgr, M=4, R=16, n=400, vw=4, seed=0, partitioner="hash",
           bounds=None, keys=None, values=None):
    rng = np.random.default_rng(seed)
    _SID[0] += 1
    sid = _SID[0]
    h = mgr.register_shuffle(sid, M, R, partitioner=partitioner,
                             bounds=bounds)
    staged = []
    for mid in range(M):
        k = keys[mid] if keys is not None else \
            rng.integers(0, 1 << 40, size=n, dtype=np.int64)
        v = values[mid] if values is not None else \
            rng.integers(-(1 << 30), 1 << 30, size=(n, vw)).astype(np.int32)
        w = mgr.get_writer(h, mid)
        w.write(k, v)
        w.commit(R)
        staged.append((k, v))
    return h, staged


def _passthru():
    import jax
    return jax.jit(lambda rows, nv: rows, donate_argnums=(0,))


# -- conf + resolution ------------------------------------------------------
def test_conf_sink_validation():
    from sparkucx_tpu.config import TpuShuffleConf
    with pytest.raises(ValueError, match="read.sink"):
        TpuShuffleConf({"spark.shuffle.tpu.read.sink": "hbm"},
                       use_env=False)
    for v in ("host", "device", "auto"):
        conf = TpuShuffleConf({"spark.shuffle.tpu.read.sink": v},
                              use_env=False)
        assert conf.read_sink == v
    keys = {r["key"] for r in TpuShuffleConf.describe_keys()}
    assert "spark.shuffle.tpu.read.sink" in keys


def test_sink_resolution_and_fallbacks(managers):
    from sparkucx_tpu.shuffle.reader import (DeviceShuffleReaderResult,
                                             LazyShuffleReaderResult)
    m = managers()                           # conf auto (the default)
    h, _ = _stage(m)
    # auto + no declaration = host
    res = m.read(h)
    assert isinstance(res, LazyShuffleReaderResult)
    assert m.report(h.shuffle_id).sink == "host"
    # auto + declared device = device
    res = m.read(h, sink="device")
    assert isinstance(res, DeviceShuffleReaderResult)
    assert m.report(h.shuffle_id).sink == "device"
    res.close()
    # ordered/combine are device-legal now (the device merge): a device
    # ask stays device and no sink-fallback is counted for it
    from sparkucx_tpu.utils.metrics import C_SINK_FALLBACK, labeled
    fb0 = m.node.metrics.get(C_SINK_FALLBACK)
    res = m.read(h, sink="device", ordered=True)
    assert isinstance(res, DeviceShuffleReaderResult)
    assert m.report(h.shuffle_id).sink == "device"
    res.close()
    assert m.node.metrics.get(C_SINK_FALLBACK) - fb0 == 0
    m.unregister_shuffle(h.shuffle_id)
    # conf=host pins the drain even under a per-read device ask — and
    # the intent mismatch is COUNTED (the doctor's sink_fallback
    # evidence), labeled with the read mode
    mh = managers(**{"read.sink": "host"})
    h2, _ = _stage(mh)
    res = mh.read(h2, sink="device", ordered=True)
    assert not isinstance(res, DeviceShuffleReaderResult)
    assert mh.report(h2.shuffle_id).sink == "host"
    assert mh.node.metrics.get(C_SINK_FALLBACK) - fb0 >= 1
    assert mh.node.metrics.get(labeled(
        C_SINK_FALLBACK, mode="ordered", reason="conf_pins_host")) >= 1
    mh.unregister_shuffle(h2.shuffle_id)
    # conf=device makes device the default ask
    md = managers(**{"read.sink": "device"})
    h3, _ = _stage(md)
    res = md.read(h3)
    assert isinstance(res, DeviceShuffleReaderResult)
    res.close()
    # ...but read_partitions pins host (it hands out numpy views)
    out = list(md.read_partitions(h3, 0, 4))
    assert all(isinstance(ks, np.ndarray) for _r, (ks, _v) in out)
    md.unregister_shuffle(h3.shuffle_id)


# -- the device result ------------------------------------------------------
def test_device_single_shot_zero_d2h_matches_oracle(managers):
    import jax
    m = managers()
    h, _ = _stage(m, seed=1)
    oracle = {r: (np.sort(ks), vs[np.argsort(ks, kind="stable")])
              for r, (ks, vs) in m.read(h, sink="host").partitions()}
    d0 = GLOBAL_METRICS.get(C_D2H)
    res = m.read(h, sink="device")
    rep = m.report(h.shuffle_id)
    outs = res.consume(lambda c, rows, nv: (c or []) + [_passthru()(
        rows, nv)])
    jax.block_until_ready(outs)
    assert GLOBAL_METRICS.get(C_D2H) - d0 == 0
    assert rep.sink == "device" and rep.d2h_bytes == 0
    hv = res.host_view(wave_rows=outs)
    for r, (ks, vs) in hv.partitions():
        want_k, _ = oracle[r]
        assert np.array_equal(np.sort(ks), want_k)
    m.unregister_shuffle(h.shuffle_id)


def test_device_waved_views_chain_in_wave_order(managers):
    import jax
    m = managers(**{"a2a.waveRows": "64"})
    h, _ = _stage(m, seed=2, n=500)
    res = m.read(h, sink="device")
    rep = m.report(h.shuffle_id)
    assert rep.waves >= 2 and res.waves == rep.waves
    assert rep.sink == "device"
    # the fold sees one (rows, totals) pair per wave, in wave order:
    # per-wave delivered totals must equal the report's agreed
    # wave_payload_rows — the ragged wave contract on the device path
    seen = []
    outs = res.consume(lambda c, rows, nv: (
        seen.append(int(np.asarray(jax.device_get(nv)).sum())),
        (c or []) + [_passthru()(rows, nv)])[1])
    assert seen == [int(x) for x in rep.wave_payload_rows]
    assert rep.d2h_bytes == 0
    # after-consume host view restores every row
    total = sum(len(ks) for _r, (ks, _v)
                in res.host_view(wave_rows=outs).partitions())
    assert total == sum(seen)
    m.unregister_shuffle(h.shuffle_id)


def test_device_result_single_consumer_contract(managers):
    m = managers()
    h, _ = _stage(m, seed=3, n=100)
    res = m.read(h, sink="device")
    with pytest.raises(RuntimeError, match="consume"):
        res.partition(0)
    res.consume(lambda c, rows, nv: None)
    with pytest.raises(RuntimeError, match="consumed"):
        res.consume(lambda c, rows, nv: None)
    with pytest.raises(RuntimeError, match="consumed"):
        res.host_view()
    with pytest.raises(RuntimeError, match="consumed"):
        res.device_rows()
    m.unregister_shuffle(h.shuffle_id)


def test_sink_keys_program_family(managers):
    m = managers()
    h, _ = _stage(m, seed=4)
    m.read(h, sink="host")
    host_family = m.report(h.shuffle_id).plan_family
    m.read(h, sink="device").close()
    dev_family = m.report(h.shuffle_id).plan_family
    assert host_family != dev_family
    assert "'device'" in dev_family
    # a second same-shape device read shares the compiled program
    p0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
    m.read(h, sink="device").close()
    assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p0 == 0
    m.unregister_shuffle(h.shuffle_id)


def test_warmup_warms_device_family(managers):
    m = managers()
    h, staged = _stage(m, seed=5, n=320, vw=4)
    m.warmup(h, rows_per_map=320, val_shape=(4,), val_dtype=np.int32,
             sink="device")
    p0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
    m.read(h, sink="device").close()
    assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p0 == 0, \
        "device read after device warmup must hit the warmed program"
    m.unregister_shuffle(h.shuffle_id)


def test_admission_hbm_residency_released_on_consume(managers):
    m = managers(**{"a2a.maxBytesInFlight": "1g"})
    h, _ = _stage(m, seed=6)
    res = m.read(h, sink="device")
    # the reservation (pinned stage + device buffers) holds until the
    # consumer takes the buffers — HBM residency, not drain lifetime
    assert m._inflight_bytes > 0
    res.consume(lambda c, rows, nv: None)
    assert m._inflight_bytes == 0
    # close() is the abandon path: same release
    res2 = m.read(h, sink="device")
    assert m._inflight_bytes > 0
    res2.close()
    assert m._inflight_bytes == 0
    m.unregister_shuffle(h.shuffle_id)


def test_lossless_device_sink_inert_codec(managers):
    m = managers(**{"a2a.wire": "lossless", "a2a.waveRows": "64"})
    h, _ = _stage(m, seed=7, n=500)
    res = m.read(h, sink="device")
    rep = m.report(h.shuffle_id)
    assert rep.sink == "device" and rep.wire == "lossless"
    assert rep.lossless_bytes == 0      # host-only codec never engaged
    res.consume(lambda c, rows, nv: None)
    assert rep.d2h_bytes == 0
    m.unregister_shuffle(h.shuffle_id)


def test_host_path_reports_d2h_bytes(managers):
    m = managers()
    h, _ = _stage(m, seed=8)
    res = m.read(h, sink="host")
    rep = m.report(h.shuffle_id)
    assert rep.d2h_bytes == 0           # nothing touched yet (lazy)
    res.partition(0)                     # first touch drains one shard
    one_shard = rep.d2h_bytes
    assert one_shard > 0
    for r, _kv in res.partitions():
        pass
    assert rep.d2h_bytes >= one_shard
    # every shard drained exactly once: P x cap x width x 4
    Pn = m.node.num_devices
    assert rep.d2h_bytes % Pn == 0
    m.unregister_shuffle(h.shuffle_id)



@pytest.mark.parametrize("sink", ["device", "host"])
def test_waved_combine_lands_merged(managers, sink):
    """A waved combine="sum" read folds its per-wave runs into one
    merged result under either sink: the sums equal the oracle's, the
    device sink moves no payload to the host before its host view is
    drained, and the host sink pays the drain. The first read compiles
    at most the exchange, the fold's zero accumulator and the merge; a
    warm re-read compiles nothing."""
    m = managers(**{"a2a.waveRows": "128"})
    rng = np.random.default_rng(12)
    keys = [rng.integers(0, 128, size=512) for _ in range(4)]
    values = [rng.integers(-100, 100, size=(512, 2)).astype(np.int32)
              for _ in range(4)]
    h, _ = _stage(m, keys=keys, values=values)
    want = {}
    for k, v in zip(np.concatenate(keys).tolist(),
                    np.concatenate(values).tolist()):
        acc = want.setdefault(k, [0, 0])
        acc[0] += v[0]
        acc[1] += v[1]
    d0 = GLOBAL_METRICS.get(C_D2H)
    p0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
    res = m.read(h, combine="sum", sink=sink)
    assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p0 <= 3
    rep = m.report(h.shuffle_id)
    assert rep.sink == sink and rep.waves >= 2
    if sink == "device":
        assert GLOBAL_METRICS.get(C_D2H) - d0 == 0
        assert rep.d2h_bytes == 0 and rep.merge_ms > 0.0
        res = res.host_view()
    got = {}
    for _r, (ks, vs) in res.partitions():
        assert ks.shape[0] == np.unique(ks).shape[0]
        got.update(zip(ks.tolist(), vs.tolist()))
    assert got == want
    if sink == "host":
        assert rep.d2h_bytes > 0
    p1 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
    warm = m.read(h, combine="sum", sink=sink)
    assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p1 == 0
    if sink == "device":
        warm.close()
    m.unregister_shuffle(h.shuffle_id)

# -- the lazy-materialization race (satellite 1) ----------------------------
def test_lazy_result_concurrent_first_touch_race(managers):
    """Concurrent first-touch of ONE shared lazy result — a pack-executor
    thread draining (drain_wave_result) while consumer threads fetch
    partitions — must materialize each shard exactly ONCE (the
    reader._fetch_lock contract) and never drop device buffers early.
    The d2h counter is the detector: a double-materialization
    double-counts, a dropped buffer raises KeyError."""
    from sparkucx_tpu.shuffle.reader import drain_wave_result
    m = managers()
    h, _ = _stage(m, seed=9, R=16)
    res = m.read(h, sink="host")
    Pn = m.node.num_devices
    shard_bytes = None
    errs = []
    d0 = GLOBAL_METRICS.get(C_D2H)
    start = threading.Barrier(10)

    def consumer(tid):
        try:
            start.wait()
            rng = np.random.default_rng(tid)
            for r in rng.permutation(16):
                res.partition(int(r))
        except Exception as e:          # pragma: no cover - the failure
            errs.append(e)

    def drainer():
        try:
            start.wait()
            drain_wave_result(res)
        except Exception as e:          # pragma: no cover - the failure
            errs.append(e)

    threads = [threading.Thread(target=consumer, args=(t,))
               for t in range(8)] + \
              [threading.Thread(target=drainer) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    # exactly one pull per shard: P x (cap_shard x width x 4)
    pulled = GLOBAL_METRICS.get(C_D2H) - d0
    shard_bytes = pulled / Pn
    assert pulled == Pn * int(shard_bytes), pulled
    rep = m.report(h.shuffle_id)
    assert rep.d2h_bytes == pulled
    # and the data is intact: a fresh read agrees partition by partition
    res2 = m.read(h, sink="host")
    for r, (ks, vs) in res2.partitions():
        k1, _ = res.partition(r)
        assert np.array_equal(np.sort(k1), np.sort(ks))
    m.unregister_shuffle(h.shuffle_id)


# -- MoE flagship -----------------------------------------------------------
def test_moe_device_dispatch_end_to_end(managers):
    import jax

    from sparkucx_tpu.models import moe
    m = managers()
    mesh = m.exchange_mesh
    cfg = moe.MoEConfig(d_model=16, d_hidden=32, num_experts=16)
    rng = np.random.default_rng(0)
    N = 2000
    tokens = rng.standard_normal((N, cfg.d_model)).astype(np.float32)
    eids = rng.integers(0, cfg.num_experts, size=N)
    _SID[0] += 1
    h = m.register_shuffle(_SID[0], 4, cfg.num_experts,
                           partitioner="direct")
    moe.stage_tokens_by_expert(m, h, tokens, eids)
    d0 = GLOBAL_METRICS.get(C_D2H)
    res = m.read(h, sink="device")
    cap = res.device_rows().shape[0] // m.node.num_devices
    init, step = moe.make_device_dispatch_step(mesh, cfg, cap,
                                               axis=m.axis)
    params = init(jax.random.PRNGKey(0))
    params, loss0 = res.consume(
        lambda c, rows, nv: step(c[0], rows, nv), (params, None))
    assert np.isfinite(float(loss0))
    assert GLOBAL_METRICS.get(C_D2H) - d0 == 0
    # fwd+bwd really trains: more steps over fresh reads shrink the loss
    for _ in range(4):
        r = m.read(h, sink="device")
        params, loss = r.consume(
            lambda c, rows, nv: step(c[0], rows, nv), (params, None))
    assert float(loss) < float(loss0)
    assert GLOBAL_METRICS.get(C_D2H) - d0 == 0
    # host-staged arm: same staged shuffle, same step, identical loss
    # from fresh params (the A/B is purely the landing zone) — and it
    # PAYS the round-trip (d2h + h2d move)
    h2d0 = GLOBAL_METRICS.get(C_H2D)
    rh = m.read(h, sink="host")
    params2 = init(jax.random.PRNGKey(0))
    params2, hloss = moe.host_staged_consume(
        rh, step, params2, mesh, cap, 2 + cfg.d_model, axis=m.axis)
    assert abs(float(hloss) - float(loss0)) < 1e-6
    assert GLOBAL_METRICS.get(C_H2D) - h2d0 > 0
    assert m.report(h.shuffle_id).d2h_bytes > 0
    m.unregister_shuffle(h.shuffle_id)


def test_doctor_host_roundtrip_fires_on_live_telemetry(managers):
    """End-to-end doctor integration: a host-staged MoE consumer at a
    real payload size leaves exactly the evidence the host_roundtrip
    rule reads (report d2h_bytes + the h2d counter) in the node's own
    telemetry snapshot."""
    import jax

    from sparkucx_tpu.models import moe
    from sparkucx_tpu.utils.doctor import diagnose
    m = managers()
    mesh = m.exchange_mesh
    cfg = moe.MoEConfig(d_model=30, d_hidden=32, num_experts=16)
    rng = np.random.default_rng(1)
    N = 4096
    tokens = rng.standard_normal((N, cfg.d_model)).astype(np.float32)
    eids = rng.integers(0, cfg.num_experts, size=N)
    _SID[0] += 1
    h = m.register_shuffle(_SID[0], 4, cfg.num_experts,
                           partitioner="direct")
    moe.stage_tokens_by_expert(m, h, tokens, eids)
    res = m.read(h, sink="host")
    cap = m.report(h.shuffle_id).plan_bucket[1]
    init, step = moe.make_device_dispatch_step(mesh, cfg, cap,
                                               axis=m.axis)
    moe.host_staged_consume(res, step, init(jax.random.PRNGKey(0)),
                            mesh, cap, 2 + cfg.d_model, axis=m.axis)
    doc = m.node.telemetry_snapshot(reports=m.exchange_reports())
    fs = [f for f in diagnose(doc) if f.rule == "host_roundtrip"]
    assert fs, "host-staged consume at payload scale must fire the rule"
    assert fs[0].conf_key == "spark.shuffle.tpu.read.sink"
    m.unregister_shuffle(h.shuffle_id)


# -- parallel consumers -----------------------------------------------------
def _stage_seq_qkv(m, heads, head_dim, t, maps=4, seed=2):
    Pn = m.node.num_devices
    T = Pn * t
    rng = np.random.default_rng(seed)
    qkv = rng.standard_normal((T, 3, heads, head_dim)).astype(np.float32)
    pos = rng.permutation(T)
    bounds = tuple(int(t * (i + 1)) for i in range(Pn - 1))
    _SID[0] += 1
    h = m.register_shuffle(_SID[0], maps, Pn, partitioner="range",
                           bounds=bounds)
    per = T // maps
    for mid in range(maps):
        sel = pos[mid * per:(mid + 1) * per]
        w = m.get_writer(h, mid)
        w.write(sel.astype(np.int64), qkv[sel].reshape(len(sel), -1))
        w.commit(Pn)
    return h, qkv


def _dense_attention_ref(qkv, head_dim):
    q = qkv[:, 0].transpose(1, 0, 2)[None]
    k = qkv[:, 1].transpose(1, 0, 2)[None]
    v = qkv[:, 2].transpose(1, 0, 2)[None]
    s = (q @ np.swapaxes(k, -1, -2)) * head_dim ** -0.5
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return w @ v


@pytest.mark.parametrize("which", ("ring", "ulysses"))
def test_attention_device_sink_consumers(managers, which):
    m = managers()
    mesh = m.exchange_mesh
    H, D, t = 8, 8, 16
    h, qkv = _stage_seq_qkv(m, H, D, t, seed=3 if which == "ring" else 4)
    d0 = GLOBAL_METRICS.get(C_D2H)
    res = m.read(h, sink="device")
    if which == "ring":
        from sparkucx_tpu.parallel.ring import ring_attention_consumer
        step = ring_attention_consumer(mesh, m.axis, t, H, D)
    else:
        from sparkucx_tpu.parallel.ulysses import \
            ulysses_attention_consumer
        step = ulysses_attention_consumer(mesh, m.axis, t, H, D)
    out = res.consume(lambda c, rows, nv: step(rows, nv))
    assert GLOBAL_METRICS.get(C_D2H) - d0 == 0
    ref = _dense_attention_ref(qkv, D)
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-5)
    m.unregister_shuffle(h.shuffle_id)


# -- facades ----------------------------------------------------------------
def test_v2_facade_device_read(base_manager):
    # v2's read_device serves the device result; its range reader stays
    # pinned to the host sink (numpy contract) even under conf=device
    from sparkucx_tpu.compat.v2 import ShuffleServiceV2
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.reader import DeviceShuffleReaderResult
    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense",
                           "spark.shuffle.tpu.read.sink": "device",
                           "spark.shuffle.tpu.io.format": "raw"},
                          use_env=False)
    svc = ShuffleServiceV2.__new__(ShuffleServiceV2)
    # ride the module node instead of booting a second stack
    svc.conf = conf
    svc.io_format = "raw"
    svc.node = base_manager.node
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    svc.manager = TpuShuffleManager(base_manager.node, conf)
    svc._deps = {}
    svc._attempts = {}
    svc._results = {}
    svc._read_locks = {}
    import threading as _threading
    svc._results_guard = _threading.Lock()
    svc._lease_lock = _threading.Lock()
    try:
        from sparkucx_tpu.compat.v2 import ShuffleDependency
        _SID[0] += 1
        sid = _SID[0]
        dep = ShuffleDependency(sid, 2, 8)
        h = svc.register(dep)
        rng = np.random.default_rng(5)
        for mid in range(2):
            w = svc.writer(h, mid, attempt_id=0)
            w.write(rng.integers(0, 1 << 30, size=64).astype(np.int64))
            w.commit()
        res = svc.read_device(h)
        assert isinstance(res, DeviceShuffleReaderResult)
        res.close()
        # range readers keep the numpy contract under conf=device
        got = dict(iter(svc.reader(h, 0, 8)))
        assert got and all(isinstance(k, np.ndarray)
                           for k, _v in got.values())
        svc.unregister(sid)
        # combine-declaring dependencies ride read_device too now (the
        # device merge made aggregation-shaped reads device-legal)
        _SID[0] += 1
        sid2 = _SID[0]
        dep2 = ShuffleDependency(sid2, 2, 8, combine="sum")
        h2 = svc.register(dep2)
        for mid in range(2):
            w = svc.writer(h2, mid, attempt_id=0)
            k = rng.integers(0, 50, size=64).astype(np.int64)
            w.write(k, (k[:, None] * np.arange(1, 3)).astype(np.int32))
            w.commit()
        res2 = svc.read_device(h2)
        assert isinstance(res2, DeviceShuffleReaderResult)
        assert svc.manager.report(sid2).sink == "device"
        res2.close()
        svc.unregister(sid2)
    finally:
        svc.manager.stop()


# -- review-round regressions ----------------------------------------------
def test_conf_device_numpy_consumers_fail_closed(managers):
    """A host-contract consumer (workloads, arrow-style iteration)
    handed a device result by conf read.sink=device gets the
    remediation, not an AttributeError — and the arrow egress itself
    pins sink='host' (io/arrow.read_batches)."""
    md = managers(**{"read.sink": "device"})
    h, _ = _stage(md, seed=20, n=64)
    res = md.read(h)
    with pytest.raises(RuntimeError, match="sink='host'"):
        list(res.partitions())
    with pytest.raises(RuntimeError, match="consume"):
        list(res.partitions_ready())
    res.close()
    md.unregister_shuffle(h.shuffle_id)


def test_consume_failure_drops_remaining_wave_buffers(managers):
    """A consumer that dies mid-fold must not free the admission budget
    while the remaining waves' receive buffers stay pinned — the views
    drop with the reservation (the close() discipline)."""
    m = managers(**{"a2a.waveRows": "64", "a2a.maxBytesInFlight": "1g"})
    h, _ = _stage(m, seed=21, n=500)
    res = m.read(h, sink="device")
    assert res.waves >= 2
    assert m._inflight_bytes > 0

    def boom(c, rows, nv):
        raise ValueError("consumer died on wave 0")

    with pytest.raises(ValueError, match="wave 0"):
        res.consume(boom)
    assert m._inflight_bytes == 0
    assert res._views is None, \
        "remaining waves' device buffers must drop with the reservation"
    m.unregister_shuffle(h.shuffle_id)


def test_host_view_drain_releases_admission(managers):
    """The live host_view() escape hatch transfers the HBM-residency
    release to the drain: once every shard is host-side the device
    buffers are gone, and the reservation must free with them — not
    wait for the result's GC."""
    m = managers(**{"a2a.maxBytesInFlight": "1g"})
    h, _ = _stage(m, seed=22)
    res = m.read(h, sink="device")
    assert m._inflight_bytes > 0
    hv = res.host_view()
    for _r, _kv in hv.partitions():
        pass
    assert m._inflight_bytes == 0, \
        "fully drained device result still charges maxBytesInFlight"
    # res is still alive — the release must not double-fire at close
    res.close()
    assert m._inflight_bytes == 0
    m.unregister_shuffle(h.shuffle_id)
