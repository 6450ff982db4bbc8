"""End-to-end manager lifecycle on the 8-device CPU mesh — the
GroupBy-style correctness workload (SURVEY.md §4 lesson: unit + e2e)."""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.runtime.node import TpuNode
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.writer import _hash32_np


@pytest.fixture()
def manager(mesh8):
    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense"},
                          use_env=False)
    node = TpuNode.start(conf)
    m = TpuShuffleManager(node, conf)
    yield m
    m.stop()
    node.close()


def expected_partition(keys, R):
    return (_hash32_np(np.asarray(keys)) % np.uint32(R)).astype(np.int64)


def test_register_duplicate_rejected(manager):
    manager.register_shuffle(0, 4, 8)
    with pytest.raises(ValueError):
        manager.register_shuffle(0, 4, 8)
    manager.unregister_shuffle(0)


def test_full_lifecycle_keys_only(manager, rng):
    R = 16
    M = 8
    h = manager.register_shuffle(1, M, R)
    all_keys = []
    for m in range(M):
        w = manager.get_writer(h, m)
        keys = rng.integers(0, 1 << 31, size=200).astype(np.int64)
        w.write(keys)
        w.commit(R)
        all_keys.append(keys)
    res = manager.read(h)
    got_total = 0
    for r, (k, v) in res.partitions():
        assert v is None
        assert (expected_partition(k, R) == r).all()
        got_total += k.size
    assert got_total == M * 200
    # global multiset preserved
    got = np.sort(np.concatenate(
        [res.partition(r)[0] for r in range(R)]))
    np.testing.assert_array_equal(got, np.sort(np.concatenate(all_keys)))
    manager.unregister_shuffle(1)


def test_full_lifecycle_with_values(manager, rng):
    R = 8
    M = 4
    h = manager.register_shuffle(2, M, R)
    kv = {}
    for m in range(M):
        w = manager.get_writer(h, m)
        keys = rng.integers(0, 10_000, size=100).astype(np.int64)
        vals = rng.normal(size=(100, 3)).astype(np.float32)
        w.write(keys, vals)
        w.commit(R)
        for k, v in zip(keys, vals):
            kv.setdefault(int(k), []).append(v)
    res = manager.read(h)
    seen = 0
    for r in range(R):
        k, v = res.partition(r)
        assert v is not None and v.shape == (k.size, 3)
        for ki, vi in zip(k, v):
            cands = kv[int(ki)]
            assert any(np.allclose(vi, c) for c in cands)
        seen += k.size
    assert seen == M * 100
    manager.unregister_shuffle(2)


def test_read_times_out_on_missing_map(manager, rng):
    h = manager.register_shuffle(3, 4, 8)
    w = manager.get_writer(h, 0)
    w.write(rng.integers(0, 100, size=10).astype(np.int64))
    w.commit(8)  # maps 1..3 never commit
    with pytest.raises(TimeoutError, match="1/4"):
        manager.read(h, timeout=0.2)
    manager.unregister_shuffle(3)


def test_empty_map_outputs(manager):
    """Empty map outputs publish zero rows and the shuffle still runs
    (reference skips empties, ref: UcxShuffleBlockResolver 2.4:35-38)."""
    R = 8
    h = manager.register_shuffle(4, 4, R)
    for m in range(4):
        w = manager.get_writer(h, m)
        if m == 0:
            w.write(np.arange(50, dtype=np.int64))
        w.commit(R)
    res = manager.read(h)
    total = sum(res.partition(r)[0].size for r in range(R))
    assert total == 50
    manager.unregister_shuffle(4)


def test_skewed_keys_trigger_retry(manager):
    """All keys identical: one partition takes everything; the reader must
    retry with a grown plan and still succeed."""
    R = 16
    M = 8
    conf = manager.conf
    conf.set("spark.shuffle.tpu.a2a.capacityFactor", 1.0)
    h = manager.register_shuffle(5, M, R)
    for m in range(M):
        w = manager.get_writer(h, m)
        w.write(np.full(100, 42, dtype=np.int64))
        w.commit(R)
    res = manager.read(h)
    sizes = [res.partition(r)[0].size for r in range(R)]
    assert sum(sizes) == M * 100
    assert max(sizes) == M * 100  # all on one partition
    manager.unregister_shuffle(5)


def test_writer_validation(manager, rng):
    h = manager.register_shuffle(6, 2, 4)
    w = manager.get_writer(h, 0)
    with pytest.raises(ValueError, match="1-D"):
        w.write(np.zeros((2, 2)))
    with pytest.raises(ValueError, match="rows"):
        w.write(np.zeros(3, dtype=np.int64), np.zeros((2, 1)))
    w.write(np.arange(4, dtype=np.int64))
    w.commit(4)
    with pytest.raises(RuntimeError, match="committed"):
        w.commit(4)
    with pytest.raises(IndexError):
        manager.get_writer(h, 9)
    manager.unregister_shuffle(6)


def test_read_after_unregister_clear_error(manager, rng):
    h = manager.register_shuffle(7, 1, 4)
    w = manager.get_writer(h, 0)
    w.write(np.arange(5, dtype=np.int64))
    w.commit(4)
    manager.unregister_shuffle(7)
    with pytest.raises(RuntimeError, match="not registered"):
        manager.read(h)


def test_values_with_empty_map_outputs(manager, rng):
    """Empty map output in a values-bearing shuffle must not misalign the
    key/value pairing."""
    R = 8
    h = manager.register_shuffle(8, 4, R)
    truth = {}
    for m in range(4):
        w = manager.get_writer(h, m)
        if m != 1:  # map 1 is empty
            keys = rng.integers(0, 100, size=50).astype(np.int64)
            vals = (keys * 10).astype(np.float32).reshape(-1, 1)
            w.write(keys, vals)
        w.commit(R)
    res = manager.read(h)
    n = 0
    for r in range(R):
        k, v = res.partition(r)
        np.testing.assert_allclose(v[:, 0], k * 10)  # pairing intact
        n += k.size
    assert n == 150
    manager.unregister_shuffle(8)


def test_multislice_mesh_read(rng):
    """2-D (dcn x shuffle) mesh: manager flattens for the exchange."""
    conf = TpuShuffleConf(
        {"spark.shuffle.tpu.a2a.impl": "dense",
         "spark.shuffle.tpu.mesh.numSlices": "2"}, use_env=False)
    node = TpuNode.start(conf)
    try:
        m = TpuShuffleManager(node, conf)
        assert node.mesh.axis_names == ("dcn", "shuffle")
        h = m.register_shuffle(0, 4, 8)
        allk = []
        for mp in range(4):
            w = m.get_writer(h, mp)
            keys = rng.integers(0, 1000, size=64).astype(np.int64)
            allk.append(keys)
            w.write(keys)
            w.commit(8)
        res = m.read(h)
        got = np.sort(np.concatenate(
            [res.partition(r)[0] for r in range(8)]))
        np.testing.assert_array_equal(got, np.sort(np.concatenate(allk)))
        m.stop()
    finally:
        node.close()


def test_conf_set_case_insensitive():
    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense"},
                          use_env=False)
    conf.set("spark.shuffle.tpu.A2A.impl", "gather")
    assert conf.a2a_impl == "gather"


def test_writer_non_contiguous_input(manager, rng):
    h = manager.register_shuffle(9, 1, 4)
    w = manager.get_writer(h, 0)
    base = np.arange(20, dtype=np.int64)
    w.write(base[::2])  # strided view must be accepted
    assert w.num_rows == 10
    w.commit(4)
    manager.unregister_shuffle(9)


def test_direct_partitioner_rejects_out_of_range(manager):
    h = manager.register_shuffle(10, 1, 4, partitioner="direct")
    w = manager.get_writer(h, 0)
    w.write(np.array([0, 3, 99], dtype=np.int64))
    with pytest.raises(ValueError, match=r"\[0, 4\)"):
        w.commit(4)
    manager.unregister_shuffle(10)


def test_committed_writer_is_immutable(manager, rng):
    """First-commit-wins: a retried/speculative map task must not replace
    a committed writer — that would drop the committed rows while the
    metadata table still counts them (silent data loss)."""
    h = manager.register_shuffle(11, 2, 4)
    w0 = manager.get_writer(h, 0)
    w0.write(np.arange(10, dtype=np.int64))
    w0.commit(4)
    # uncommitted writer may be replaced (failed-task retry)
    manager.get_writer(h, 1)
    w1b = manager.get_writer(h, 1)
    with pytest.raises(RuntimeError, match="already committed"):
        manager.get_writer(h, 0)
    w1b.write(np.arange(5, dtype=np.int64))
    w1b.commit(4)
    total = sum(k.size for _, (k, _) in manager.read(h).partitions())
    assert total == 15
    manager.unregister_shuffle(11)


def test_capacity_learning_skips_retry(manager):
    """Second same-shape shuffle starts at the capacity the first one
    settled at after overflow retries (no overflow on run 2)."""
    from sparkucx_tpu.shuffle import reader as reader_mod

    R, M, N = 8, 8, 400
    skewed = np.zeros(N, dtype=np.int64)  # all keys identical -> one shard

    def run(sid):
        h = manager.register_shuffle(sid, M, R)
        for m in range(M):
            w = manager.get_writer(h, m)
            w.write(skewed)
            w.commit(R)
        res = manager.read(h)
        total = sum(k.size for _, (k, _) in res.partitions())
        assert total == M * N
        manager.unregister_shuffle(sid)
        return res.cap_out_used

    cap1 = run(20)
    # balanced share would be M*N/8 * factor 2 = 800 < 3200 needed rows;
    # the first run must have grown
    assert cap1 is not None and cap1 >= M * N
    grown = []
    orig = reader_mod.ShufflePlan.grown

    def spy(self):
        grown.append(self.cap_out)
        return orig(self)

    reader_mod.ShufflePlan.grown = spy
    try:
        cap2 = run(21)
    finally:
        reader_mod.ShufflePlan.grown = orig
    assert grown == [], "second run should start at the learned capacity"
    # the learned hint tracks the observed requirement (needed x 1.15
    # headroom), not the power-of-two capacity the retries settled at —
    # big enough to skip every retry, small enough not to carry the
    # doubling ladder's slack forever
    assert M * N <= cap2 <= int(M * N * 1.3)


def test_read_fails_loudly_on_lost_map_output(manager):
    """Metadata says complete but staged rows are gone -> loud error, not
    a silently smaller result."""
    h = manager.register_shuffle(12, 1, 4)
    w = manager.get_writer(h, 0)
    w.write(np.arange(8, dtype=np.int64))
    w.commit(4)
    # simulate the lost-output state: writer dropped but table published
    with manager._lock:
        manager._writers[12].clear()
    with pytest.raises(RuntimeError, match="no committed staged rows"):
        manager.read(h)
    manager.unregister_shuffle(12)


def test_submit_poll_and_stream(manager, rng):
    """Async read: submit() returns before forcing results; partitions are
    readable per shard; two pipelined shuffles overlap pack with exchange."""
    R, M, N = 16, 8, 300

    def stage(sid):
        h = manager.register_shuffle(sid, M, R)
        for m in range(M):
            w = manager.get_writer(h, m)
            w.write(rng.integers(0, 1 << 31, size=N).astype(np.int64))
            w.commit(R)
        return h

    hA, hB = stage(30), stage(31)
    pA = manager.submit(hA)
    pB = manager.submit(hB)     # packed+dispatched while A is in flight
    assert isinstance(pA.done(), bool)
    resA, resB = pA.result(), pB.result()
    # partition-0 readable without touching other shards
    k0, _ = resA.partition(0)
    assert (expected_partition(k0, R) == 0).all()
    totals = [sum(k.size for _, (k, _) in r.partitions())
              for r in (resA, resB)]
    assert totals == [M * N, M * N]
    # done() is true after result()
    assert pA.done() and pB.done()
    manager.unregister_shuffle(30)
    manager.unregister_shuffle(31)


def test_submit_overflow_retries_to_result(manager):
    """Overflow discovered at result() time still resolves via regrowth."""
    R, M, N = 8, 4, 200
    h = manager.register_shuffle(32, M, R)
    for m in range(M):
        w = manager.get_writer(h, m)
        w.write(np.zeros(N, dtype=np.int64))   # max skew: one destination
        w.commit(R)
    res = manager.submit(h).result()
    assert sum(k.size for _, (k, _) in res.partitions()) == M * N
    assert res.cap_out_used >= M * N
    manager.unregister_shuffle(32)


def test_read_partitions_range(manager):
    """Partition-range getReader analog: only [start, end) materializes."""
    h = manager.register_shuffle(77, 2, 8)
    rng = np.random.default_rng(1)
    for m in range(2):
        w = manager.get_writer(h, m)
        k = rng.integers(0, 100, size=300).astype(np.int64)
        w.write(k, np.stack([k, k], axis=1).astype(np.int32))
        w.commit(8)
    got = dict(manager.read_partitions(h, 2, 5))
    assert sorted(got) == [2, 3, 4]
    full = manager.read(h)
    for r in (2, 3, 4):
        np.testing.assert_array_equal(
            np.sort(got[r][0]), np.sort(full.partition(r)[0]))
    with pytest.raises(IndexError):
        list(manager.read_partitions(h, 5, 9))
    manager.unregister_shuffle(77)


def test_warmup_precompiles_the_read_step(manager, rng):
    """warmup(handle) must leave the exchange step compiled so the first
    real read() is a jit-cache hit — the preconnect analog (ref:
    UcxWorkerWrapper.scala:125-127: dial every peer while the map publish
    is in flight, so the first fetch pays no setup)."""
    from sparkucx_tpu.shuffle import reader as reader_mod

    h = manager.register_shuffle(97, num_maps=4, num_partitions=16)
    plan = manager.warmup(h, rows_per_map=100)
    width = 2  # keys-only
    step = reader_mod._build_step(manager.exchange_mesh, manager.axis,
                                  plan, width)
    assert step._cache_size() == 1, "warmup must have executed the step"

    for mid in range(4):
        w = manager.get_writer(h, mid)
        w.write(rng.integers(0, 1 << 40, size=100).astype(np.int64))
        w.commit(16)
    res = manager.read(h)
    assert sum(res.partition(r)[0].shape[0]
               for r in range(16)) == 400
    # same lru entry, no new compile: the read's plan matched the warmed
    # plan and hit the warmed executable
    step_after = reader_mod._build_step(manager.exchange_mesh,
                                        manager.axis, plan, width)
    assert step_after is step
    assert step._cache_size() == 1, \
        "first read after warmup must not compile a second program"


def test_warmup_argument_validation(manager):
    h = manager.register_shuffle(98, num_maps=2, num_partitions=4)
    with pytest.raises(ValueError, match="exactly one"):
        manager.warmup(h)
    with pytest.raises(ValueError, match="exactly one"):
        manager.warmup(h, rows_per_map=10, rows_per_shard=[1] * 8)
    with pytest.raises(ValueError, match="rows_per_shard must be"):
        manager.warmup(h, rows_per_shard=[1, 2])


def test_max_bytes_in_flight_queues_and_completes(mesh8, rng):
    """Three pipelined submits under a cap that fits roughly one exchange:
    later submits queue (done() False, no dispatch) and complete when
    earlier results release capacity — Spark's maxBytesInFlight throttle
    (ref: UcxShuffleReader.scala:56-70), as a deferred-dispatch queue
    because a blocking submit would deadlock the single-threaded caller
    that resolves handles in order."""
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.a2a.impl": "dense",
        # roughly one exchange's footprint at this shape: cap_in ~ 1000
        # rows x 2 words x 4 B x 8 shards plus pack buffer + cap_out
        "spark.shuffle.tpu.a2a.maxBytesInFlight": "200k",
    }, use_env=False)
    node = TpuNode.start(conf)
    m = TpuShuffleManager(node, conf)
    try:
        pendings, expected = [], {}
        for sid in range(3):
            h = m.register_shuffle(sid, 2, 8)
            keys = rng.integers(0, 1 << 40, size=2000).astype(np.int64)
            expected[sid] = np.sort(keys)
            for mid in range(2):
                w = m.get_writer(h, mid)
                w.write(keys[mid * 1000:(mid + 1) * 1000])
                w.commit(8)
            pendings.append(m.submit(h))
        # at least one later submit must have been deferred by the cap
        assert any(not p.done() for p in pendings[1:]), \
            "cap of ~1 exchange must defer at least one of 3 submits"
        assert m._inflight_bytes > 0
        for sid, p in enumerate(pendings):
            res = p.result()
            got = np.sort(np.concatenate(
                [res.partition(r)[0] for r in range(8)]))
            np.testing.assert_array_equal(got, expected[sid])
        assert m._inflight_bytes == 0, "all reservations must be released"
    finally:
        m.stop()
        node.close()


def test_max_bytes_in_flight_single_big_exchange_admitted(mesh8, rng):
    """An exchange larger than the cap must still run (admitted alone) —
    the cap is backpressure, not a hard rejection."""
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.a2a.impl": "dense",
        "spark.shuffle.tpu.a2a.maxBytesInFlight": "1k",
    }, use_env=False)
    node = TpuNode.start(conf)
    m = TpuShuffleManager(node, conf)
    try:
        h = m.register_shuffle(7, 1, 4)
        keys = rng.integers(0, 1 << 40, size=5000).astype(np.int64)
        w = m.get_writer(h, 0)
        w.write(keys)
        w.commit(4)
        res = m.read(h)
        got = np.sort(np.concatenate(
            [res.partition(r)[0] for r in range(4)]))
        np.testing.assert_array_equal(got, np.sort(keys))
    finally:
        m.stop()
        node.close()


def test_max_bytes_in_flight_fifo_no_starvation(mesh8, rng):
    """A later submit must NOT steal capacity freed for an earlier
    deferred exchange: resolve-in-submit-order always completes without
    timeouts (the FIFO deferral of Spark's fetch iterator)."""
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.a2a.impl": "dense",
        "spark.shuffle.tpu.a2a.maxBytesInFlight": "200k",
    }, use_env=False)
    node = TpuNode.start(conf)
    m = TpuShuffleManager(node, conf)
    try:
        def make(sid):
            h = m.register_shuffle(sid, 1, 8)
            keys = rng.integers(0, 1 << 40, size=2000).astype(np.int64)
            w = m.get_writer(h, 0)
            w.write(keys)
            w.commit(8)
            return keys, m.submit(h)

        ka, pa = make(0)
        kb, pb = make(1)          # deferred (cap fits ~one exchange)
        assert not pb.done()
        ra = pa.result()          # frees capacity...
        kc, pc = make(2)          # ...which C must NOT steal from B
        assert not pc.done()
        for keys, p in ((ka, None), (kb, pb), (kc, pc)):
            res = ra if p is None else p.result()
            got = np.sort(np.concatenate(
                [res.partition(r)[0] for r in range(8)]))
            np.testing.assert_array_equal(got, np.sort(keys))
        assert m._inflight_bytes == 0 and not m._admit_queue
    finally:
        m.stop()
        node.close()


def test_unregister_deferred_while_read_in_flight(manager, rng):
    """unregister_shuffle during a read's materialize->pack window must
    park the writers in the graveyard, not release them inline (same
    use-after-free as the remesh path)."""
    h = manager.register_shuffle(60, 1, 4)
    w = manager.get_writer(h, 0)
    w.write(rng.integers(0, 1 << 30, size=64).astype(np.int64))
    w.commit(4)
    in_use = manager.node.pool.stats()["in_use"]
    assert in_use > 0
    g = manager._read_started()           # a read is mid-materialize
    manager.unregister_shuffle(60)
    assert manager.node.pool.stats()["in_use"] == in_use, \
        "buffers must survive until the in-flight read finishes"
    manager._read_finished(g)
    assert manager.node.pool.stats()["in_use"] < in_use


def test_cap_hint_decays_after_skew_spike(manager, rng):
    """One pathological skewed run must not inflate every later same-shape
    plan forever (round-3 verdict weak #5): the learned skew-factor hint
    decays toward the observed per-run requirement within a few runs."""
    R, M, N = 16, 8, 400

    def run(keys_fn, sid):
        h = manager.register_shuffle(sid, M, R)
        for m in range(M):
            w = manager.get_writer(h, m)
            w.write(keys_fn(m))
            w.commit(R)
        res = manager.read(h)
        for r in range(R):
            res.partition(r)
        manager.unregister_shuffle(sid)
        return h

    # spike: every key identical -> one shard receives everything
    h = run(lambda m: np.zeros(N, dtype=np.int64), 900)
    key = manager._cap_key(h)
    spike = manager._cap_hints[key]
    assert spike > 2.0, f"skew spike not recorded: {spike}"

    prev = spike
    for i in range(5):
        run(lambda m: rng.integers(0, 1 << 31, size=N).astype(np.int64),
            901 + i)
        cur = manager._cap_hints[key]
        assert cur <= prev + 1e-9, "hint ratcheted up on a balanced run"
        prev = cur
    assert prev < spike / 2, (
        f"hint failed to decay: spike {spike:.2f} -> {prev:.2f}")


def test_cap_hint_keeps_headroom_for_sustained_skew(manager):
    """Decay must not strip a genuinely skewed workload's headroom: the
    same skewed run repeated keeps a hint near its requirement."""
    R, M, N = 16, 8, 400
    h = None
    for i in range(4):
        h = manager.register_shuffle(930 + i, M, R)
        for m in range(M):
            w = manager.get_writer(h, m)
            w.write(np.zeros(N, dtype=np.int64))
            w.commit(R)
        res = manager.read(h)
        res.partition(0)
        manager.unregister_shuffle(930 + i)
    factor = manager._cap_hints[manager._cap_key(h)]
    # all rows land on one shard: requirement = M*N over balanced share
    # N, x1.15 headroom
    assert factor > 0.9 * (M * 1.15)


def test_combine_unstable_compaction_e2e(manager_factory, rng):
    """conf a2a.combineCompaction=unstable rides the whole manager
    combine path and produces the same sums as the host oracle (the
    bit-identical-variants property, end to end)."""
    m = manager_factory(
        {"spark.shuffle.tpu.a2a.combineCompaction": "unstable"})
    h = m.register_shuffle(950, 2, 8)
    oracle = {}
    for mid in range(2):
        k = rng.integers(0, 50, size=500).astype(np.int64)
        v = rng.integers(0, 100, size=(500, 1)).astype(np.int32)
        w = m.get_writer(h, mid)
        w.write(k, v)
        w.commit(8)
        for kk, vv in zip(k.tolist(), v[:, 0].tolist()):
            oracle[kk] = oracle.get(kk, 0) + vv
    res = m.read(h, combine="sum")
    got = {}
    for r in range(8):
        kk, vv = res.partition(r)
        got.update(dict(zip(kk.tolist(), vv[:, 0].tolist())))
    assert got == oracle
    m.unregister_shuffle(950)


def test_fetch_granularity_partition(manager_factory, rng):
    """io.fetchGranularity=partition: every partition fetch device-
    slices only its runs (no whole-shard pull), and the data is
    bit-identical to the shard-granularity read."""
    m = manager_factory(
        {"spark.shuffle.tpu.io.fetchGranularity": "partition"})
    R, M = 16, 4
    h = m.register_shuffle(960, M, R)
    allk = []
    for mid in range(M):
        k = rng.integers(0, 1 << 40, size=400).astype(np.int64)
        w = m.get_writer(h, mid)
        w.write(k, (k & 0x7FFF)[:, None].astype(np.int32))
        w.commit(R)
        allk.append(k)
    res = m.read(h)
    assert getattr(res, "fetch_granularity", None) == "partition"
    got = []
    for r in range(R):
        k, v = res.partition(r)
        assert (v[:, 0] == (k & 0x7FFF)).all()
        got.append(k)
    assert res._shards == {}, "partition mode must not pull whole shards"
    np.testing.assert_array_equal(
        np.sort(np.concatenate(got)), np.sort(np.concatenate(allk)))
    m.unregister_shuffle(960)


def test_fetch_granularity_conf_rejects_bogus():
    with pytest.raises(ValueError, match="fetchGranularity"):
        TpuShuffleConf(
            {"spark.shuffle.tpu.io.fetchGranularity": "block"},
            use_env=False)


def test_fetch_granularity_partition_releases_device_buffers(
        manager_factory, rng):
    """Partition mode caches fetched blocks and drops the device buffers
    once every partition has been fetched (the HBM-release discipline of
    shard mode), and re-reads come from the cache."""
    m = manager_factory(
        {"spark.shuffle.tpu.io.fetchGranularity": "partition"})
    R = 8
    h = m.register_shuffle(961, 1, R)
    k = rng.integers(0, 1 << 40, size=500).astype(np.int64)
    w = m.get_writer(h, 0)
    w.write(k)
    w.commit(R)
    res = m.read(h)
    first = [res.partition(r)[0] for r in range(R)]
    assert res._rows_dev is None, "device buffers retained after full scan"
    again = [res.partition(r)[0] for r in range(R)]  # cache, no device
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    m.unregister_shuffle(961)


def test_partitions_ready_arrival_order(manager_factory, rng):
    """partitions_ready(): a slow shard must not head-of-line block —
    partitions of already-transferred shards come first; every
    partition still arrives exactly once with correct content (the
    reference's deliver-blocks-as-they-arrive iterator,
    ref: OnBlocksFetchCallback.java:45-53)."""
    m = manager_factory()
    R, M = 16, 4
    h = m.register_shuffle(975, M, R)
    allk = []
    for mid in range(M):
        k = rng.integers(0, 1 << 31, size=200).astype(np.int64)
        w = m.get_writer(h, mid)
        w.write(k)
        w.commit(R)
        allk.append(k)
    res = m.read(h)

    # wrap shard 0's device array: its completion wait (the iterator's
    # per-shard block_until_ready event) does not fire until shard 1's
    # rows were consumed, proving the iterator reorders around it
    import threading
    consumed = []
    shard1_consumed = threading.Event()
    wait_timed_out = []

    class _SlowDev:
        def __init__(self, real):
            self._real = real
            self.shape = real.shape
            self.nbytes = real.nbytes

        def is_ready(self):
            return False            # force the event-driven waiter path

        def block_until_ready(self):
            # NOTE: runs inside the reader's waiter thread where raised
            # exceptions are swallowed — record the failure for the main
            # thread instead of asserting here
            if not shard1_consumed.wait(timeout=30):
                wait_timed_out.append(True)
            return self

        def __array__(self, dtype=None, copy=None):
            return np.asarray(self._real)

    real_shard_dev = res._shard_dev

    def patched(shard):
        dev = real_shard_dev(shard)
        if shard == 0 and dev is not None:
            return _SlowDev(dev)
        return dev

    res._shard_dev = patched
    order = []
    got = {}
    for r, (k, v) in res.partitions_ready(poll_s=0.001):
        shard = int(res._part_to_shard[r])
        if shard not in consumed:
            consumed.append(shard)
        if shard == 1:
            shard1_consumed.set()
        order.append(r)
        got[r] = k
    assert not wait_timed_out, "consumer never reached shard 1"
    assert sorted(order) == list(range(R)), "every partition exactly once"
    slow_rs = np.nonzero(np.asarray(res._part_to_shard) == 0)[0].tolist()
    assert order[-len(slow_rs):] == slow_rs, \
        f"slow shard 0's partitions must arrive last, got {order}"
    all_sorted = np.sort(np.concatenate([got[r] for r in range(R)]))
    np.testing.assert_array_equal(
        all_sorted, np.sort(np.concatenate(allk)))
    m.unregister_shuffle(975)
