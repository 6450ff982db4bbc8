"""Strip-sorted single-shard plain path (ops/partition.
destination_sort_strips + reader.py step_body fast path).

The strips lever batches S independent destination sorts into one
shallower sort network and serves each partition as S runs through the
SAME multi-sender run index the flat exchange uses (strips = virtual
senders, _RunIndex(align_chunk=strip_rows)). These tests pin the
grouping contract (multisets per destination), the physical layout the
run index assumes, and the end-to-end manager read."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from sparkucx_tpu.ops.partition import (destination_sort,
                                        destination_sort_strips)
from sparkucx_tpu.shuffle.plan import ShufflePlan
from sparkucx_tpu.shuffle.reader import _RunIndex, step_body


def _mk(rng, cap, nvalid, R, W=6):
    rows = rng.integers(0, 1 << 31, size=(cap, W),
                        dtype=np.int64).astype(np.int32)
    dest = rng.integers(0, R, size=cap).astype(np.int32)
    rows[nvalid:] = -1          # poison padding: must never be served
    return rows, dest


def _by_dest(rows, dest, nvalid, R):
    """Oracle: per-destination row multisets (sorted bytes)."""
    out = {}
    for r in range(R):
        sel = rows[:nvalid][dest[:nvalid] == r]
        out[r] = np.sort(sel.view([("", sel.dtype)] * sel.shape[1]),
                         axis=0)
    return out


@pytest.mark.parametrize("strips,cap,nvalid", [
    (4, 256, 256), (7, 256, 200), (16, 1024, 1000),
    (8, 120, 77), (3, 65, 1), (5, 64, 0),
])
def test_strips_grouping_contract(rng, strips, cap, nvalid):
    R = 13
    rows, dest = _mk(rng, cap, nvalid, R)
    srt, counts, M = jax.jit(
        destination_sort_strips, static_argnums=(3, 4))(
            rows, dest, jnp.int32(nvalid), R, strips)
    srt, counts = np.asarray(srt), np.asarray(counts)
    S = min(strips, cap)
    assert counts.shape == (S, R)
    assert M == -(-cap // S)
    assert srt.shape[0] == S * M
    assert counts.sum() == nvalid
    # flat sort agrees on totals per destination
    _, flat_counts = jax.jit(
        destination_sort, static_argnums=(3,))(
            rows, dest, jnp.int32(nvalid), R)
    np.testing.assert_array_equal(counts.sum(axis=0),
                                  np.asarray(flat_counts))
    # strip layout: strip s's real rows for dest r are contiguous at
    # s*M + cumsum(counts[s, :r]) — and their multiset matches the oracle
    oracle = _by_dest(rows, dest, nvalid, R)
    for r in range(R):
        got = []
        for s in range(S):
            off = s * M + int(counts[s, :r].sum())
            got.append(srt[off:off + counts[s, r]])
        got = np.concatenate(got) if got else srt[:0]
        gv = np.sort(got.view([("", got.dtype)] * got.shape[1]), axis=0)
        np.testing.assert_array_equal(gv, oracle[r])
        assert not (got == -1).all(axis=1).any(), "padding row served"


def test_strips_int8_key_variant(rng):
    cap, nvalid, R, S = 512, 400, 16, 8
    rows, dest = _mk(rng, cap, nvalid, R)
    a, ca, _ = destination_sort_strips(rows, dest, jnp.int32(nvalid), R,
                                       S, key_impl="multisort8")
    b, cb, _ = destination_sort_strips(rows, dest, jnp.int32(nvalid), R,
                                       S, key_impl="auto")
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    # same grouping multisets (order within a group may differ)
    a, b = np.asarray(a), np.asarray(b)
    for s in range(S):
        off = s * (cap // S)
        n = int(np.asarray(ca)[s].sum())
        sa = np.sort(a[off:off + n].view(
            [("", a.dtype)] * a.shape[1]), axis=0)
        sb = np.sort(b[off:off + n].view(
            [("", b.dtype)] * b.shape[1]), axis=0)
        np.testing.assert_array_equal(sa, sb)


def test_runindex_serves_strip_layout(rng):
    """_RunIndex(align_chunk=M) over the [S, R] seg matrix locates
    exactly the rows destination_sort_strips laid down."""
    cap, nvalid, R, S = 512, 437, 11, 8
    rows, dest = _mk(rng, cap, nvalid, R)
    srt, counts, M = destination_sort_strips(
        rows, dest, jnp.int32(nvalid), R, S)
    srt, counts = np.asarray(srt), np.asarray(counts)
    ri = _RunIndex(counts, 0, R, align_chunk=M)
    oracle = _by_dest(rows, dest, nvalid, R)
    for r in range(R):
        runs = ri.runs(r)
        got = np.concatenate([srt[o:o + n] for o, n in runs]) \
            if runs else srt[:0]
        gv = np.sort(got.view([("", got.dtype)] * got.shape[1]), axis=0)
        np.testing.assert_array_equal(gv, oracle[r])


def test_step_body_strip_fast_path(rng):
    """The jitted production step on a 1-device mesh: [S, R] seg, no
    overflow, every partition reconstructible."""
    cap, nvalid, R, S = 1024, 900, 16, 8
    plan = ShufflePlan(num_shards=1, num_partitions=R, cap_in=cap,
                       cap_out=cap, impl="dense", partitioner="direct",
                       sort_strips=S)
    rows = rng.integers(0, 1 << 31, size=(cap, 4),
                        dtype=np.int64).astype(np.int32)
    # direct partitioner: key IS the partition id (key_lo col 0, col 1=0)
    part = rng.integers(0, R, size=cap).astype(np.int64)
    rows[:, 0] = part.view(np.uint64).astype(np.uint32).view(np.int32)
    rows[:, 1] = 0
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("shuffle",))
    step = step_body(plan, "shuffle")
    sm = jax.jit(jax.shard_map(
        step, mesh=mesh1, in_specs=(P("shuffle"), P("shuffle")),
        out_specs=(P("shuffle"), P(), P("shuffle"), P("shuffle")),
        check_vma=False))
    out, seg, total, ovf = sm(jnp.asarray(rows),
                              jnp.full((1,), nvalid, jnp.int32))
    out, seg = np.asarray(out), np.asarray(seg)
    assert seg.shape == (S, R)
    assert not np.asarray(ovf).any()
    assert int(np.asarray(total)[0]) == nvalid
    assert int(seg.sum()) == nvalid
    M = plan.strip_rows()
    ri = _RunIndex(seg, 0, R, align_chunk=M)
    oracle = _by_dest(rows, part.astype(np.int32), nvalid, R)
    for r in range(R):
        runs = ri.runs(r)
        got = np.concatenate([out[o:o + n] for o, n in runs]) \
            if runs else out[:0]
        gv = np.sort(got.view([("", got.dtype)] * got.shape[1]), axis=0)
        np.testing.assert_array_equal(gv, oracle[r])


def test_manager_e2e_strips(rng):
    """Full register->write->commit->read over a 1-device mesh with
    a2a.sortStrips set: the resolve plumbs align_chunk=strip_rows and
    partition() serves the strip runs (global multiset preserved)."""
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    from sparkucx_tpu.shuffle.writer import _hash32_np

    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense",
                           "spark.shuffle.tpu.a2a.sortStrips": "8"},
                          use_env=False)
    node = TpuNode.start(conf)
    try:
        node.remesh(devices=list(jax.devices())[:1], reason="strip test")
        m = TpuShuffleManager(node, conf)
        try:
            R, Mw = 16, 4
            h = m.register_shuffle(7, Mw, R)
            all_keys = []
            for mi in range(Mw):
                w = m.get_writer(h, mi)
                keys = rng.integers(0, 1 << 31, size=300).astype(np.int64)
                vals = rng.normal(size=(300, 2)).astype(np.float32)
                w.write(keys, vals)
                w.commit(R)
                all_keys.append(keys)
            res = m.read(h)
            tot = 0
            for r, (k, v) in res.partitions():
                exp_r = (_hash32_np(np.asarray(k)) % np.uint32(R))
                assert (exp_r.astype(np.int64) == r).all()
                assert v is not None and v.shape == (k.size, 2)
                tot += k.size
            assert tot == Mw * 300
            got = np.sort(np.concatenate(
                [res.partition(r)[0] for r in range(R)]))
            np.testing.assert_array_equal(
                got, np.sort(np.concatenate(all_keys)))
            m.unregister_shuffle(7)
        finally:
            m.stop()
    finally:
        node.close()


@pytest.mark.slow
def test_strip_step_aot_proof():
    """The strip-sorted step lowers for the v5e chip via the local
    libtpu: pure sort — no collective, no scatter.
    Skips where libtpu/topology construction is unavailable."""
    from sparkucx_tpu.shuffle.aot import aot_compile_strip_step
    rep = aot_compile_strip_step(strips=16, rows=1 << 16)
    if "topology" not in rep:
        pytest.skip(f"no TPU topology support here: {rep.get('error')}")
    assert rep["ok"], rep
    assert rep["hlo_no_collective"] and rep["hlo_no_scatter"]


def test_strips_partition_granularity_fetch(manager_factory, rng):
    """strips x io.fetchGranularity=partition: per-partition device
    slicing must honor the strip-aligned run index (align_chunk wired
    through the lazy result's on-device run arithmetic)."""
    import jax as _jax
    m = manager_factory(
        {"spark.shuffle.tpu.a2a.sortStrips": "8",
         "spark.shuffle.tpu.io.fetchGranularity": "partition"})
    m.node.remesh(devices=list(_jax.devices())[:1], reason="strips gran")
    R, M = 16, 3
    h = m.register_shuffle(971, M, R)
    allk = []
    for mid in range(M):
        k = rng.integers(0, 1 << 40, size=300).astype(np.int64)
        w = m.get_writer(h, mid)
        w.write(k, (k & 0x7FFF)[:, None].astype(np.int32))
        w.commit(R)
        allk.append(k)
    res = m.read(h)
    assert res._align_chunk > 0, "strip layout should be align-indexed"
    got = []
    for r in range(R):
        k, v = res.partition(r)
        assert (v[:, 0] == (k & 0x7FFF)).all()
        got.append(k)
    assert res._shards == {}, "partition mode must not pull whole shards"
    np.testing.assert_array_equal(
        np.sort(np.concatenate(got)), np.sort(np.concatenate(allk)))
    m.unregister_shuffle(971)


def test_strips_spill_roundtrip(manager_factory, rng, tmp_path):
    """strips x disk spill: spilled map outputs mmap back through the
    same staging and the strip-sorted read serves them intact."""
    import jax as _jax
    m = manager_factory(
        {"spark.shuffle.tpu.a2a.sortStrips": "4",
         "spark.shuffle.tpu.spill.threshold": "4k",
         "spark.shuffle.tpu.spill.dir": str(tmp_path)})
    m.node.remesh(devices=list(_jax.devices())[:1], reason="strips spill")
    R, M = 8, 2
    h = m.register_shuffle(972, M, R)
    allk = []
    for mid in range(M):
        w = m.get_writer(h, mid)
        for _ in range(4):                    # several batches -> spill
            k = rng.integers(0, 1 << 31, size=500).astype(np.int64)
            w.write(k)
            allk.append(k)
        assert w._spill is not None, "threshold should have spilled"
        w.commit(R)
    res = m.read(h)
    got = np.sort(np.concatenate(
        [res.partition(r)[0] for r in range(R)]))
    np.testing.assert_array_equal(got, np.sort(np.concatenate(allk)))
    m.unregister_shuffle(972)


def test_strips_noop_on_multi_shard(rng):
    """sort_strips must be ignored off the 1-shard path: the 8-device
    exchange still returns the flat [P, R] seg contract."""
    R = 16
    plan = ShufflePlan(num_shards=8, num_partitions=R, cap_in=64,
                       cap_out=256, impl="dense", partitioner="direct",
                       sort_strips=8)
    rows = rng.integers(0, 1 << 31, size=(8 * 64, 4),
                        dtype=np.int64).astype(np.int32)
    part = rng.integers(0, R, size=8 * 64).astype(np.int64)
    rows[:, 0] = part.view(np.uint64).astype(np.uint32).view(np.int32)
    rows[:, 1] = 0
    mesh8 = Mesh(np.array(jax.devices()[:8]), ("shuffle",))
    step = step_body(plan, "shuffle")
    sm = jax.jit(jax.shard_map(
        step, mesh=mesh8, in_specs=(P("shuffle"), P("shuffle")),
        out_specs=(P("shuffle"), P(), P("shuffle"), P("shuffle")),
        check_vma=False))
    out, seg, total, ovf = sm(
        jnp.asarray(rows), jnp.full((8,), 64, jnp.int32))
    assert np.asarray(seg).shape == (8, R)     # senders, not strips
    assert not np.asarray(ovf).any()
    assert int(np.asarray(seg).sum()) == 8 * 64


from tests.conftest import FUZZ_SEEDS


@pytest.mark.parametrize("seed", range(min(FUZZ_SEEDS, 64)))
def test_random_strips_roundtrip(manager_factory, seed):
    """Strip-path fuzz: random shapes/strip counts/value schemas over a
    1-device mesh (where sortStrips activates) vs the host oracle —
    routing exactness + global multiset + value binding."""
    import jax as _jax

    from sparkucx_tpu.shuffle.writer import _hash32_np

    rng = np.random.default_rng(10_000 + seed)
    strips = int(rng.choice([2, 3, 5, 8, 16, 64]))
    m = manager_factory(
        {"spark.shuffle.tpu.a2a.sortStrips": str(strips)})
    m.node.remesh(devices=list(_jax.devices())[:1],
                  reason=f"strips fuzz {seed}")
    M = int(rng.integers(1, 6))
    R = int(rng.integers(1, 24))
    with_vals = bool(rng.integers(0, 2))
    h = m.register_shuffle(20_000 + seed, M, R)
    kv = {}
    total = 0
    for mid in range(M):
        n = int(rng.integers(0, 900))         # incl. zero-row writers
        keys = rng.integers(-(1 << 62), 1 << 62, size=n).astype(np.int64)
        w = m.get_writer(h, mid)
        if with_vals:
            vals = rng.integers(-1000, 1000,
                                size=(n, 2)).astype(np.int32)
            w.write(keys, vals)
            for k, v in zip(keys, vals):
                kv.setdefault(int(k), []).append(tuple(v))
        else:
            w.write(keys)
            for k in keys:
                kv.setdefault(int(k), []).append(None)
        w.commit(R)
        total += n
    res = m.read(h)
    got = {}
    seen = 0
    for r, (k, v) in res.partitions():
        exp_r = (_hash32_np(np.asarray(k)) % np.uint32(R)).astype(int)
        assert (exp_r == r).all(), f"misrouted rows in partition {r}"
        for i, ki in enumerate(k):
            got.setdefault(int(ki), []).append(
                tuple(v[i]) if with_vals else None)
        seen += k.size
    assert seen == total
    # full multiset equality: a duplicated row cannot mask a dropped one
    assert set(got) == set(kv), "key sets differ"
    for ki in kv:
        assert sorted(got[ki], key=repr) == sorted(kv[ki], key=repr), \
            f"multiset mismatch for key {ki}"
    m.unregister_shuffle(20_000 + seed)


def test_warmup_precompiles_strip_step(manager_factory, rng):
    """warmup on a 1-device mesh with sortStrips set must compile the
    STRIP step (plan.sort_strips threaded through), so the first read
    is a jit-cache hit on the same executable."""
    import jax as _jax

    from sparkucx_tpu.shuffle import reader as reader_mod

    m = manager_factory({"spark.shuffle.tpu.a2a.sortStrips": "8"})
    m.node.remesh(devices=list(_jax.devices())[:1], reason="strip warm")
    h = m.register_shuffle(973, num_maps=2, num_partitions=8)
    plan = m.warmup(h, rows_per_map=100)
    assert plan.sort_strips == 8 and plan.strips_active()
    step = reader_mod._build_step(m.exchange_mesh, m.axis, plan, 2)
    assert step._cache_size() == 1
    for mid in range(2):
        w = m.get_writer(h, mid)
        w.write(rng.integers(0, 1 << 40, size=100).astype(np.int64))
        w.commit(8)
    res = m.read(h)
    assert sum(res.partition(r)[0].shape[0] for r in range(8)) == 200
    step_after = reader_mod._build_step(m.exchange_mesh, m.axis, plan, 2)
    assert step_after is step and step._cache_size() == 1, \
        "first strip read after warmup must not compile a second program"
    m.unregister_shuffle(973)


def test_strip_step_static_cap_guard():
    """The strip branch's trace-time guard: a payload whose cap differs
    from plan.cap_in must raise at trace (the resolve derives
    align_chunk from cap_in — a silent mismatch would misindex)."""
    plan = ShufflePlan(num_shards=1, num_partitions=8, cap_in=256,
                       cap_out=256, impl="dense", partitioner="direct",
                       sort_strips=4)
    step = step_body(plan, "shuffle")
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("shuffle",))
    sm = jax.shard_map(step, mesh=mesh1,
                       in_specs=(P("shuffle"), P("shuffle")),
                       out_specs=(P("shuffle"), P(), P("shuffle"),
                                  P("shuffle")), check_vma=False)
    with pytest.raises(ValueError, match="cap_in"):
        jax.eval_shape(sm,
                       jax.ShapeDtypeStruct((128, 4), jnp.int32),
                       jax.ShapeDtypeStruct((1,), jnp.int32))
