"""Wave-pipelined exchange (a2a.waveRows) — the streaming-read suite.

Pins the three pipeline contracts:
waved results are equivalent to single-shot (the fuzz sweep in
test_fuzz_e2e composes this with random schemas), wave *i+1*'s pack
starts before wave *i*'s result is forced (overlap proof), and an
overflow regrows + re-runs ONLY the offending wave. Plus the satellites
that ride the same machinery: the persistent pack executor, the
partition-block cache, the pool byte watermark, and the wave plan
helpers.
"""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.plan import (ShufflePlan, make_plan, wave_count,
                                       wave_step_plan)


# -- plan/conf surface -----------------------------------------------------
def test_wave_count_arithmetic():
    assert wave_count(np.array([100, 10, 0]), 0) == 1
    assert wave_count(np.array([100, 10, 0]), 64) == 2
    assert wave_count(np.array([128]), 64) == 2
    assert wave_count(np.array([129]), 64) == 3
    assert wave_count(np.zeros(4, np.int64), 64) == 1


def test_wave_step_plan_fixed_signature():
    """The dispatched wave plan must not vary with this exchange's total
    rows or wave count — one compiled program per wave-shape family."""
    conf = TpuShuffleConf({}, use_env=False)
    import dataclasses
    plans = set()
    for total in (10_000, 55_000, 200_000):
        p = make_plan(np.full(8, total), 8, 16, conf)
        outer = dataclasses.replace(p, wave_rows=4096,
                                    num_waves=wave_count(
                                        np.full(8, total), 4096))
        plans.add(wave_step_plan(outer, conf))
    assert len(plans) == 1
    wp = plans.pop()
    assert wp.wave_rows == 0 and wp.num_waves == 1
    assert wp.cap_in >= 4096


def test_wave_step_plan_rejects_unwaved():
    conf = TpuShuffleConf({}, use_env=False)
    p = make_plan(np.full(8, 100), 8, 4, conf)
    with pytest.raises(ValueError):
        wave_step_plan(p, conf)


def test_wave_conf_validation():
    with pytest.raises(ValueError):
        TpuShuffleConf({"spark.shuffle.tpu.a2a.waveRows": "-1"},
                       use_env=False)
    with pytest.raises(ValueError):
        TpuShuffleConf({"spark.shuffle.tpu.a2a.waveDepth": "0"},
                       use_env=False)
    with pytest.raises(ValueError):
        TpuShuffleConf({"spark.shuffle.tpu.a2a.waveDepth": "99"},
                       use_env=False)
    with pytest.raises(ValueError):
        TpuShuffleConf({"spark.shuffle.tpu.a2a.packThreads": "-2"},
                       use_env=False)
    c = TpuShuffleConf({"spark.shuffle.tpu.a2a.waveRows": "4096"},
                       use_env=False)
    assert c.wave_rows == 4096 and c.wave_depth == 2


def test_agree_wave_count_single_process():
    from sparkucx_tpu.shuffle.distributed import agree_wave_count
    assert agree_wave_count(3) == 3


# -- shared job helper -----------------------------------------------------
def _run_job(mgr, sid, maps, partitions, rng, rows_per_map, key_space,
             **read_kw):
    h = mgr.register_shuffle(sid, maps, partitions)
    oracle = {}
    for m in range(maps):
        w = mgr.get_writer(h, m)
        keys = rng.integers(0, key_space, size=rows_per_map)
        vals = rng.integers(-100, 100,
                            size=(rows_per_map, 2)).astype(np.int32)
        w.write(keys, vals)
        w.commit(partitions)
        for k, v in zip(keys, vals):
            oracle.setdefault(int(k), []).append(tuple(v.tolist()))
    res = mgr.read(h, **read_kw)
    got = {}
    for r, (ks, vs) in res.partitions():
        for i, k in enumerate(ks):
            got.setdefault(int(k), []).append(tuple(vs[i].tolist()))
    rep = mgr.report(sid)
    mgr.unregister_shuffle(sid)
    return oracle, got, rep, res


# -- equivalence + report plumbing -----------------------------------------
def test_waved_read_matches_oracle_and_reports(manager_factory):
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "256"})
    rng = np.random.default_rng(3)
    oracle, got, rep, res = _run_job(mgr, 61000, 8, 16, rng, 2000,
                                     1 << 40)
    assert set(got) == set(oracle)
    for k in oracle:
        assert sorted(got[k]) == sorted(oracle[k])
    # report carries the wave split + a full timeline
    assert rep.waves == res.waves == len(rep.wave_timeline)
    assert rep.waves >= 2 and rep.wave_rows == 256
    assert rep.completed and rep.retries == 0
    # hidden is MEASURED (collective provably still in flight when the
    # pack finished), so later waves may or may not be hidden at tiny
    # CPU shapes — but wave 0 has nothing in flight, ever, and the
    # hidden total can never exceed the pack total
    assert not rep.wave_timeline[0]["hidden"]
    assert rep.wave_pack_hidden_ms <= rep.pack_ms
    assert rep.wave_pack_hidden_ms == pytest.approx(sum(
        t["pack_ms"] for t in rep.wave_timeline if t["hidden"]), abs=0.1)
    # per-wave partial views stream in wave order
    assert len(res.wave_results()) == res.waves
    wave_rows_total = sum(
        w.partition(0)[0].shape[0] for w in res.wave_results())
    assert wave_rows_total == res.partition(0)[0].shape[0]
    # partitions_ready honors the exactly-once contract on the composed
    # result (everything is host-resident once result() returned)
    seen = [r for r, _ in res.partitions_ready()]
    assert seen == sorted(set(seen))


def test_overlap_proof(manager_factory):
    """Wave i+1's pack STARTS before wave i's result is forced — the
    depth-2 software pipeline's defining property, read straight off the
    report's wave timeline."""
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "512"})
    rng = np.random.default_rng(4)
    _, _, rep, _ = _run_job(mgr, 61001, 8, 16, rng, 4000, 1 << 40)
    tl = rep.wave_timeline
    assert len(tl) >= 4
    for prv, cur in zip(tl[:-1], tl[1:]):
        assert cur["pack_start_ms"] < prv["forced_ms"], (
            f"wave {cur['wave']} packed only after wave {prv['wave']} "
            f"was forced — no overlap: {tl}")
    # and every wave was forced only after its own dispatch
    for t in tl:
        assert t["forced_ms"] >= t["pack_start_ms"] + t["pack_ms"]



def test_waved_read_compiles_one_program_for_all_waves(manager_factory):
    """Every wave of a waved read dispatches through ONE compiled step
    (the wave plan's signature is fixed), and a warm read of the same
    shape compiles nothing."""
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    from sparkucx_tpu.utils.metrics import COMPILE_PROGRAMS, GLOBAL_METRICS
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "256"})
    GLOBAL_STEP_CACHE.clear()
    p0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
    _, _, rep, _ = _run_job(mgr, 61020, 4, 8, np.random.default_rng(6),
                            2048, 1 << 40)
    assert rep.waves >= 4
    assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p0 == 1
    p1 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
    _run_job(mgr, 61021, 4, 8, np.random.default_rng(7), 2048, 1 << 40)
    assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p1 == 0

def test_wave_depth_one_serializes(manager_factory):
    """depth=1 degenerates to serial per-wave execution: correct results,
    no hidden packs (each wave drains before the next packs)."""
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "256",
                           "spark.shuffle.tpu.a2a.waveDepth": "1"})
    rng = np.random.default_rng(5)
    oracle, got, rep, _ = _run_job(mgr, 61002, 4, 8, rng, 1500, 1000)
    assert set(got) == set(oracle)
    assert rep.waves >= 2
    assert rep.wave_pack_hidden_ms == 0.0
    assert not any(t["hidden"] for t in rep.wave_timeline)


def test_wave_overflow_retries_only_offending_wave(manager_factory):
    """Skew confined to one wave: the overflow regrows and re-runs THAT
    wave alone (single-shot re-dispatches the whole exchange), and the
    grown capacity seeds both the rest of this exchange and the next
    same-shape exchange (no second overflow)."""
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "512"})

    def skewed_job(sid):
        h = mgr.register_shuffle(sid, 8, 8, partitioner="direct")
        for m in range(8):
            w = mgr.get_writer(h, m)
            balanced = np.arange(512, dtype=np.int64) % 8   # wave 0
            hot = np.zeros(512, np.int64)                   # wave 1 -> p0
            w.write(np.concatenate([balanced, hot]))
            w.commit(8)
        res = mgr.read(h)
        n0 = res.partition(0)[0].shape[0]
        assert n0 == 8 * 512 + 8 * 64          # all hot rows + its share
        rep = mgr.report(sid)
        mgr.unregister_shuffle(sid)
        return rep

    rep = skewed_job(61003)
    per_wave = [t["retries"] for t in rep.wave_timeline]
    assert rep.waves == 2
    assert per_wave[0] == 0 and per_wave[1] >= 1, per_wave
    assert rep.retries == sum(per_wave)
    # learned wave cap: the SAME shape re-run starts at the grown
    # capacity — zero retries, zero fresh programs
    rep2 = skewed_job(61004)
    assert rep2.retries == 0
    assert rep2.stepcache_programs == 0


def test_waves_disabled_below_one_wave(manager_factory):
    """Data smaller than one wave falls back to the single-shot path —
    no wave fields on the report."""
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "100000"})
    rng = np.random.default_rng(6)
    oracle, got, rep, _ = _run_job(mgr, 61005, 4, 8, rng, 500, 1000)
    assert set(got) == set(oracle)
    assert rep.waves == 0 and rep.wave_timeline == []


def test_waved_ordered_and_combine(manager_factory):
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "256"})
    rng = np.random.default_rng(7)
    # ordered: key-sorted partitions across waves
    oracle, got, rep, res = _run_job(mgr, 61006, 6, 12, rng, 1500, 300,
                                     ordered=True)
    assert rep.waves >= 2
    for r, (ks, _) in res.partitions():
        assert list(ks) == sorted(ks), f"partition {r} lost key order"
    assert set(got) == set(oracle)
    for k in oracle:
        assert sorted(got[k]) == sorted(oracle[k])
    # combine: ONE row per distinct key, summed across waves
    h = mgr.register_shuffle(61007, 6, 12)
    want = {}
    for m in range(6):
        w = mgr.get_writer(h, m)
        keys = rng.integers(0, 150, size=2000)
        vals = rng.integers(-40, 40, size=(2000, 2)).astype(np.int32)
        w.write(keys, vals)
        w.commit(12)
        for k, v in zip(keys, vals):
            want[int(k)] = want.get(int(k), np.zeros(2, np.int64)) + v
    res = mgr.read(h, combine="sum")
    assert mgr.report(61007).waves >= 2
    seen = {}
    for r, (ks, vs) in res.partitions():
        assert list(ks) == sorted(ks)
        for i, k in enumerate(ks):
            assert int(k) not in seen, f"combine left duplicate key {k}"
            seen[int(k)] = vs[i].astype(np.int64)
    assert set(seen) == set(want)
    for k in want:
        np.testing.assert_array_equal(
            seen[k], want[k].astype(np.int32).astype(np.int64),
            err_msg=f"key {k}")
    mgr.unregister_shuffle(61007)


def test_wave_gap_histogram_observed(manager_factory):
    from sparkucx_tpu.utils.metrics import H_WAVE_GAP
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "256"})
    rng = np.random.default_rng(8)
    _, _, rep, _ = _run_job(mgr, 61008, 8, 16, rng, 2000, 1 << 30)
    h = mgr.node.metrics.histogram(H_WAVE_GAP)
    assert h.count == rep.waves - 1


# -- satellites ------------------------------------------------------------
def test_persistent_pack_executor_reused(manager_factory):
    """One executor across reads (and across the waves within a read) —
    the per-read spawn/teardown is gone."""
    mgr = manager_factory({"spark.shuffle.tpu.a2a.waveRows": "256"})
    ex = mgr._pack_executor()
    assert mgr._pack_executor() is ex
    rng = np.random.default_rng(9)
    _run_job(mgr, 61009, 4, 8, rng, 1200, 1000)
    assert mgr._pack_executor() is ex
    mgr.stop()
    assert mgr._pack_pool is None


def test_pack_threads_conf_sizes_executor(manager_factory):
    mgr = manager_factory({"spark.shuffle.tpu.a2a.packThreads": "3"})
    assert mgr._pack_executor()._max_workers == 3


def test_partition_block_cache_identity(manager_factory):
    """Repeat partition(r) calls serve the SAME dense block object for
    multi-run partitions instead of re-concatenating every time."""
    mgr = manager_factory({})
    rng = np.random.default_rng(10)
    h = mgr.register_shuffle(61010, 8, 4)
    for m in range(8):
        w = mgr.get_writer(h, m)
        w.write(rng.integers(0, 1 << 40, size=600))
        w.commit(4)
    res = mgr.read(h)
    shard = int(res._part_to_shard[1])
    b1 = res._partition_block(1, shard)
    b2 = res._partition_block(1, shard)
    assert b1 is b2
    k1, _ = res.partition(1)
    k2, _ = res.partition(1)
    np.testing.assert_array_equal(k1, k2)
    mgr.unregister_shuffle(61010)


def test_pool_byte_watermark():
    from sparkucx_tpu.runtime.memory import HostMemoryPool
    pool = HostMemoryPool(TpuShuffleConf({}, use_env=False))
    try:
        base = pool.stats()["in_use_bytes"]
        a = pool.get(4096)
        b = pool.get(8192)
        st = pool.stats()
        assert st["in_use_bytes"] >= base + 4096 + 8192
        peak_at_two = st["peak_bytes"]
        pool.put(a)
        assert pool.stats()["in_use_bytes"] < st["in_use_bytes"]
        assert pool.stats()["peak_bytes"] == peak_at_two   # monotone
        prior = pool.reset_peak_bytes()
        assert prior == peak_at_two
        assert pool.stats()["peak_bytes"] <= peak_at_two
        pool.put(b)
    finally:
        pool.close()


def test_waved_peak_pinned_below_single_shot(manager_factory):
    """The bounded-footprint claim at test scale: the waved read's pack
    working set (pool byte watermark during the read) stays below the
    single-shot read's full-shuffle block."""
    rng_data = np.random.default_rng(11)
    keys = [rng_data.integers(0, 1 << 40, size=4096) for _ in range(8)]
    vals = [rng_data.integers(0, 100, size=(4096, 8)).astype(np.int32)
            for _ in range(8)]

    def peak_of(overrides, sid):
        mgr = manager_factory(overrides)
        h = mgr.register_shuffle(sid, 8, 16)
        for m in range(8):
            w = mgr.get_writer(h, m)
            w.write(keys[m], vals[m])
            w.commit(16)
        mgr.node.pool.reset_peak_bytes()
        res = mgr.read(h)
        for r in range(16):
            res.partition(r)
        peak = mgr.node.pool.stats()["peak_bytes"]
        rep = mgr.report(sid)
        mgr.unregister_shuffle(sid)
        return peak, rep

    single_peak, single_rep = peak_of({}, 61011)
    waved_peak, waved_rep = peak_of(
        {"spark.shuffle.tpu.a2a.waveRows": "512"}, 61012)
    assert single_rep.waves == 0 and waved_rep.waves >= 4
    assert waved_peak < single_peak, (waved_peak, single_peak)
