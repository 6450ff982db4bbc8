"""Device segmented merge & segment-reduce (ops/pallas/segmented.py) —
the on-device half of ordered/combine device-sink reads.

Covers: the jnp/XLA primary path and the pallas lineage kernels against
numpy oracles and each other; the NUMERICS CONTRACT against
``reader.combine_packed_rows`` (the host cross-wave merge the device
fold replaces): integer ring arithmetic (int32 lane wrap), float32
accumulation, carried lanes, and exact key/partition lanes; the
conf/validation seam (read.mergeImpl); and the merge-fold program-family
discipline (one merge program per family, 0 warm recompiles)."""

import json

import numpy as np
import pytest

from sparkucx_tpu.ops.pallas import segmented as S

R = 7
W = 6  # 2 key words + 4 value words


def _sorted_rows(rng, n, cap, key_lo=0, key_hi=1000, vals=None):
    """[cap, W] transport rows: n valid rows sorted by (hash-partition,
    key), sentinel part ids past them — the merge input contract."""
    from sparkucx_tpu.shuffle.integrity import host_partition_ids
    keys = rng.integers(key_lo, key_hi, size=n).astype(np.int64)
    part = host_partition_ids(keys, R).astype(np.int32)
    order = np.lexsort((keys, part))
    keys, part = keys[order], part[order]
    rows = np.zeros((cap, W), np.int32)
    if n:
        rows[:n, :2] = keys.view(np.int32).reshape(n, 2)
        rows[:n, 2:] = (vals[order] if vals is not None else
                        rng.integers(-(1 << 30), 1 << 30,
                                     size=(n, W - 2))).astype(np.int32)
    p = np.full(cap, R, np.int32)
    p[:n] = part
    return rows, p, keys


def _keys_of(rows, n):
    return np.ascontiguousarray(rows[:n, :2]).view(np.int64).ravel()


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_merge_rows_matches_sorted_concat_oracle(impl):
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    a_rows, a_p, ka = _sorted_rows(rng, 37, 48)
    b_rows, b_p, kb = _sorted_rows(rng, 21, 24)
    rows, part, pcounts = S.merge_rows(
        jnp.asarray(a_rows), jnp.asarray(a_p), jnp.asarray(b_rows),
        jnp.asarray(b_p), R, impl=impl)
    rows, part, pcounts = map(np.asarray, (rows, part, pcounts))
    n = int(pcounts.sum())
    assert n == 58
    keys = _keys_of(rows, n)
    # merged order is (partition, signed key) — numpy lexsort oracle
    order = np.lexsort((keys, part[:n]))
    assert np.array_equal(order, np.arange(n)), impl
    # content: the multiset of (key, value row) pairs is preserved
    want = sorted(map(tuple, np.concatenate([a_rows[:37], b_rows[:21]])
                      .tolist()))
    got = sorted(map(tuple, rows[:n].tolist()))
    assert got == want
    # sentinels landed last
    assert (part[n:] == R).all()


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_merge_rows_empty_and_one_sided(impl):
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    a_rows, a_p, _ = _sorted_rows(rng, 0, 16)
    b_rows, b_p, _ = _sorted_rows(rng, 9, 16)
    rows, part, pcounts = S.merge_rows(
        jnp.asarray(a_rows), jnp.asarray(a_p), jnp.asarray(b_rows),
        jnp.asarray(b_p), R, impl=impl)
    assert int(np.asarray(pcounts).sum()) == 9
    got = sorted(map(tuple, np.asarray(rows)[:9].tolist()))
    assert got == sorted(map(tuple, b_rows[:9].tolist()))


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_segment_reduce_int32_wrap_matches_host_combiner(impl):
    """Integer numerics pin: the device segment-reduce and the HOST
    cross-wave combiner (reader.combine_packed_rows) wrap identically —
    int32 ring arithmetic, however wide the true sum."""
    import jax.numpy as jnp

    from sparkucx_tpu.shuffle.reader import combine_packed_rows
    rng = np.random.default_rng(5)
    n, cap = 24, 32
    # values near the int32 edge so the sums genuinely wrap
    vals = rng.integers(1 << 30, (1 << 31) - 1, size=(n, W - 2),
                        dtype=np.int64).astype(np.uint32).view(np.int32)
    rows, part, _ = _sorted_rows(rng, n, cap, key_lo=0, key_hi=5,
                                 vals=vals)
    ro, pc, _ = S.segment_reduce_rows(
        jnp.asarray(rows), jnp.asarray(part), R, W - 2, np.int32,
        impl=impl)
    ro, pc = np.asarray(ro), np.asarray(pc)
    n_out = int(pc.sum())
    # host oracle: combine_packed_rows over the SAME rows (its input is
    # per-wave combined blocks; a single uncombined block is the
    # degenerate case with every duplicate key in one block)
    host = combine_packed_rows([rows[:n]], W - 2, np.int32)
    # host output is globally key-sorted; device output is
    # (partition, key)-sorted — compare as key->value-row maps
    dev_map = {int(k): tuple(ro[i, 2:]) for i, k in
               enumerate(_keys_of(ro, n_out))}
    host_map = {int(k): tuple(host[i, 2:]) for i, k in
                enumerate(_keys_of(host, host.shape[0]))}
    assert dev_map == host_map, impl


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_segment_reduce_f32_and_carry_match_host_combiner(impl):
    """Float numerics + the summed/carried lane split: float32
    accumulation, carried lanes byte-identical per key."""
    import jax.numpy as jnp

    from sparkucx_tpu.shuffle.reader import combine_packed_rows
    rng = np.random.default_rng(6)
    n, cap, sum_words = 30, 32, 2
    keys = rng.integers(0, 6, size=n).astype(np.int64)
    fv = rng.normal(size=(n, sum_words)).astype(np.float32)
    carry = np.repeat(keys[:, None].astype(np.int32) * 7 + 3,
                      W - 2 - sum_words, axis=1)   # per-key-constant
    vals = np.concatenate([fv.view(np.int32), carry], axis=1)
    rows, part, _ = _sorted_rows(rng, n, cap, vals=vals)
    # keys must drive the partition/sort — rebuild with the drawn keys
    from sparkucx_tpu.shuffle.integrity import host_partition_ids
    p = host_partition_ids(keys, R).astype(np.int32)
    order = np.lexsort((keys, p))
    rows = np.zeros((cap, W), np.int32)
    rows[:n, :2] = keys[order].view(np.int32).reshape(n, 2)
    rows[:n, 2:] = vals[order]
    part = np.full(cap, R, np.int32)
    part[:n] = p[order]
    ro, pc, _ = S.segment_reduce_rows(
        jnp.asarray(rows), jnp.asarray(part), R, W - 2, np.float32,
        sum_words=sum_words, impl=impl)
    ro, pc = np.asarray(ro), np.asarray(pc)
    n_out = int(pc.sum())
    host = combine_packed_rows([rows[:n]], W - 2, np.float32,
                               sum_words=sum_words)
    dev_map = {int(k): ro[i] for i, k in
               enumerate(_keys_of(ro, n_out))}
    host_map = {int(k): host[i] for i, k in
                enumerate(_keys_of(host, host.shape[0]))}
    assert set(dev_map) == set(host_map)
    for k in host_map:
        # carried lanes byte-identical
        assert np.array_equal(dev_map[k][2 + sum_words:],
                              host_map[k][2 + sum_words:]), (impl, k)
        # f32 sums: same accumulation dtype; ordering differences allow
        # ulp-level drift between the prefix-sum-difference (host) and
        # the running-sum (pallas) formulations
        dv = dev_map[k][2:2 + sum_words].view(np.float32)
        hv = host_map[k][2:2 + sum_words].view(np.float32)
        np.testing.assert_allclose(dv, hv, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_merge_reduce_rows_spanning_key(impl):
    """A key present in BOTH inputs collapses to one row with the sum —
    one fold step of the device combine."""
    import jax.numpy as jnp
    cap = 8

    def mk(key, val):
        from sparkucx_tpu.shuffle.integrity import host_partition_ids
        rows = np.zeros((cap, W), np.int32)
        rows[0, :2] = np.array([key], np.int64).view(np.int32)
        rows[0, 2:] = val
        p = np.full(cap, R, np.int32)
        p[0] = host_partition_ids(np.array([key], np.int64), R)[0]
        return rows, p

    a_rows, a_p = mk(42, 10)
    b_rows, b_p = mk(42, 32)
    ro, pc, _ = S.merge_reduce_rows(
        jnp.asarray(a_rows), jnp.asarray(a_p), jnp.asarray(b_rows),
        jnp.asarray(b_p), R, W - 2, np.int32, impl=impl)
    ro, pc = np.asarray(ro), np.asarray(pc)
    assert int(pc.sum()) == 1
    assert int(_keys_of(ro, 1)[0]) == 42
    assert (ro[0, 2:] == 42).all()


def test_pallas_reduce_supported_gates_subword_dtypes():
    assert S.pallas_reduce_supported(np.int32)
    assert S.pallas_reduce_supported(np.float32)
    assert not S.pallas_reduce_supported(np.int16)
    assert not S.pallas_reduce_supported(np.int8)
    with pytest.raises(ValueError, match="4-byte"):
        import jax.numpy as jnp
        S.segment_reduce_rows(jnp.zeros((8, W), jnp.int32),
                              jnp.full((8,), R, jnp.int32), R, W - 2,
                              np.int16, impl="pallas")


def test_interpret_gate_and_conf_seam():
    # the kernels run natively on a TPU and in interpret mode on the
    # CPU; any other backend is named in the gate's reason
    assert S.kernel_gate_reason("tpu") is None
    assert S.kernel_gate_reason("cpu") is None
    assert "backend='gpu'" in S.kernel_gate_reason("gpu")
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.alltoall import ALLOWED_MERGE_IMPLS
    assert ALLOWED_MERGE_IMPLS == ("auto", "jnp", "pallas")
    with pytest.raises(ValueError, match="read.mergeImpl"):
        TpuShuffleConf({"spark.shuffle.tpu.read.mergeImpl": "cuda"},
                       use_env=False)
    for v in ALLOWED_MERGE_IMPLS:
        conf = TpuShuffleConf(
            {"spark.shuffle.tpu.read.mergeImpl": v}, use_env=False)
        assert conf.read_merge_impl == v


def test_resolve_merge_impl_falls_back_for_subword_combine():
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.plan import ShufflePlan
    from sparkucx_tpu.shuffle.reader import resolve_merge_impl
    conf = TpuShuffleConf(
        {"spark.shuffle.tpu.read.mergeImpl": "pallas"}, use_env=False)
    plan16 = ShufflePlan(num_shards=1, num_partitions=4, cap_in=8,
                         cap_out=8, impl="dense", combine="sum",
                         combine_words=2, combine_dtype="<i2")
    assert resolve_merge_impl(conf, plan16) == "jnp"
    plan32 = ShufflePlan(num_shards=1, num_partitions=4, cap_in=8,
                         cap_out=8, impl="dense", combine="sum",
                         combine_words=2, combine_dtype="<f4")
    assert resolve_merge_impl(conf, plan32) == "pallas"
    ordered = ShufflePlan(num_shards=1, num_partitions=4, cap_in=8,
                          cap_out=8, impl="dense", ordered=True)
    assert resolve_merge_impl(conf, ordered) == "pallas"
    auto = TpuShuffleConf({}, use_env=False)
    assert resolve_merge_impl(auto, ordered) == "jnp"


def test_merge_family_drops_exchange_capacities():
    """Two reads whose exchanges differ but whose merge shapes agree
    share ONE merge program — the 0-warm-recompile contract."""
    import dataclasses

    from sparkucx_tpu.shuffle.plan import ShufflePlan, merge_family
    p1 = ShufflePlan(num_shards=8, num_partitions=16, cap_in=128,
                     cap_out=256, impl="dense", combine="sum",
                     combine_words=4, combine_dtype="<f4")
    p2 = dataclasses.replace(p1, cap_in=512, cap_out=1024, wire="int8",
                             wire_words=4)
    assert merge_family(p1, 64, 32, 6, "jnp") \
        == merge_family(p2, 64, 32, 6, "jnp")
    # mode, caps and impl DO key the family
    assert merge_family(p1, 64, 32, 6, "jnp") \
        != merge_family(p1, 128, 32, 6, "jnp")
    assert merge_family(p1, 64, 32, 6, "jnp") \
        != merge_family(p1, 64, 32, 6, "pallas")
    assert merge_family(dataclasses.replace(p1, combine=None,
                                            combine_words=0,
                                            combine_dtype="",
                                            ordered=True),
                        64, 32, 6, "jnp") \
        != merge_family(p1, 64, 32, 6, "jnp")


@pytest.mark.slow
def test_device_fold_reuses_one_merge_program_per_family():
    """E2E program-count pin: two same-shaped waved combine device
    reads — the second compiles NOTHING (exchange, seed and merge all
    served warm from the step cache). Slow-marked for the tier-1
    budget: the same warm==0 contract is gated in-tier by
    test_devcombine_measure_small (programs_warm) and in CI by the
    devcombine stage gate; this is the targeted unit for debugging a
    regression there."""
    import jax

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    from sparkucx_tpu.utils.metrics import (COMPILE_PROGRAMS,
                                            GLOBAL_METRICS)
    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense",
                           "spark.shuffle.tpu.a2a.waveRows": "48"},
                          use_env=False)
    node = TpuNode.start(conf)
    m = TpuShuffleManager(node, conf)
    try:
        def run(sid):
            rng = np.random.default_rng(11)      # identical staging
            h = m.register_shuffle(sid, 4, 16)
            for mid in range(4):
                k = rng.integers(0, 300, size=200).astype(np.int64)
                v = (k[:, None] * np.arange(1, 3)).astype(np.int32)
                w = m.get_writer(h, mid)
                w.write(k, v)
                w.commit(16)
            res = m.read(h, combine="sum", sink="device")
            outs = res.consume(
                lambda c, rows, nv: (c or []) + [rows])
            jax.block_until_ready(outs)
            rep = m.report(sid)
            m.unregister_shuffle(sid)
            return rep

        rep1 = run(96001)
        assert rep1.waves >= 2
        assert rep1.merge_ms > 0.0
        p0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
        rep2 = run(96002)
        assert GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p0 == 0, \
            "warm same-shaped device-combine read must not compile"
        assert rep2.stepcache_programs == 0
    finally:
        m.stop()


# ---------------------------------------------------------------------------
# Parity fuzz sweep (blocked kernels vs the jnp oracle across ragged
# shapes). Every skip goes through S.kernel_gate_reason — the ONE
# shared gate — so a pallas-less env skips with the same reason string
# the microbench artifact and impl resolution record.

def _require_blocked_kernels():
    reason = S.kernel_gate_reason()
    if reason is not None:
        pytest.skip(reason)


def _fuzz_rows(rng, n, cap, num_parts, width, groups, sum_words,
               float_vals):
    """Sorted-contract rows with PER-KEY-CONSTANT carried lanes (the
    data contract: keysort is unstable, so the group representative is
    arbitrary — any non-constant carried lane is a bug in the data,
    not the kernel) and exactly-summable f32 (integer-valued) so the
    bit-exact grade is meaningful on the float arm."""
    import jax.numpy as jnp
    _FLIP = np.int32(-0x80000000)
    groups = max(1, min(groups, n)) if n else 1
    part = np.sort(rng.integers(0, num_parts, size=groups)
                   .astype(np.int32))
    hi = rng.integers(-5, 5, size=groups).astype(np.int32)
    lo = rng.integers(-2**31, 2**31, size=groups,
                      dtype=np.int64).astype(np.int32)
    order = np.lexsort((lo ^ _FLIP, hi, part))
    part, hi, lo = part[order], hi[order], lo[order]
    gid = np.sort(rng.integers(0, groups, size=n)) if n \
        else np.zeros(0, np.int64)
    sw = sum_words if sum_words > 0 else width - 2
    rows = np.zeros((cap, width), np.int32)
    p = np.full(cap, num_parts, np.int32)
    rows[:n, 0] = lo[gid]
    rows[:n, 1] = hi[gid]
    p[:n] = part[gid]
    carried = rng.integers(-1000, 1000,
                           size=(groups, width - 2 - sw)).astype(np.int32)
    if float_vals:
        rows[:n, 2:2 + sw] = rng.integers(
            -64, 64, size=(n, sw)).astype(np.float32).view(np.int32)
    else:
        rows[:n, 2:2 + sw] = rng.integers(
            -2**31, 2**31, size=(n, sw),
            dtype=np.int64).astype(np.int32)
    rows[:n, 2 + sw:] = carried[gid]
    return jnp.asarray(rows), jnp.asarray(p)


# (n, cap, parts, width, groups) with the kernel's tiles past the live
# rows (n << cap), which the kernel skips: live rows inside the first
# tile, across many tiles, ending exactly on a tile edge, none at all,
# and one segment crossing into the last live tile
_DEAD_TILE_SHAPES = (
    (39, 1024, 4, 6, 20),
    (200, 2048, 4, 6, 50),
    (128, 1024, 4, 6, 40),
    (0, 1024, 4, 6, 3),
    (200, 1024, 2, 6, 1),
)

# (n, cap, parts, width, groups): empty, sub-tile, non-tile-aligned n,
# single row, single segment spanning every tile, all-valid full cap,
# nearly-singleton groups (group-per-row stress), then the dead tiles
_FUZZ_SHAPES = (
    (0, 128, 4, 6, 3),
    (39, 128, 4, 6, 38),
    (129, 256, 4, 6, 129),
    (1, 256, 4, 6, 1),
    (384, 384, 2, 6, 1),
    (384, 384, 6, 6, 380),
    (300, 384, 4, 6, 38),
    (250, 256, 4, 7, 17),
) + _DEAD_TILE_SHAPES


@pytest.mark.parametrize("shape", _FUZZ_SHAPES,
                         ids=lambda s: f"n{s[0]}_cap{s[1]}_g{s[4]}")
@pytest.mark.parametrize("sum_words", (0, 2))
@pytest.mark.parametrize("float_vals", (False, True),
                         ids=("i32", "f32"))
def test_blocked_segment_reduce_parity_fuzz(shape, sum_words,
                                            float_vals):
    """Blocked segment-reduce vs the jnp oracle: n_out, pcounts and
    every live row bit-exact — int32 sums exact mod 2^32 under any
    order, f32 sums exactly summable by construction."""
    _require_blocked_kernels()
    n, cap, parts, width, groups = shape
    rng = np.random.default_rng(n * 31 + cap + sum_words)
    rows, part = _fuzz_rows(rng, n, cap, parts, width, groups,
                            sum_words, float_vals)
    vdt = np.float32 if float_vals else np.int32
    jr, jc, jn = S.segment_reduce_rows(
        rows, part, parts, width - 2, vdt, sum_words=sum_words,
        impl="jnp")
    pr, pc, pn = S.segment_reduce_rows(
        rows, part, parts, width - 2, vdt, sum_words=sum_words,
        impl="pallas", interpret=None)
    k = int(np.asarray(jn)[0])
    assert k == int(np.asarray(pn)[0])
    assert np.array_equal(np.asarray(jc), np.asarray(pc))
    assert np.array_equal(np.asarray(jr)[:k], np.asarray(pr)[:k])


@pytest.mark.parametrize("shape", _DEAD_TILE_SHAPES + ((300, 384, 4, 6, 38),),
                         ids=lambda s: f"n{s[0]}_cap{s[1]}_g{s[4]}")
@pytest.mark.parametrize("float_vals", (False, True),
                         ids=("i32", "f32"))
def test_blocked_segment_reduce_whole_buffer_matches_oracle(shape,
                                                            float_vals):
    """The whole returned buffer, not only its live prefix: the blocked
    path returns the jnp oracle's rows byte for byte (the live rows,
    then zero rows) and, past n_out, the sentinel partition, whatever
    the kernel left in the output blocks of the tiles it skipped."""
    _require_blocked_kernels()
    n, cap, parts, width, groups = shape
    rng = np.random.default_rng(n * 17 + cap)
    rows, part = _fuzz_rows(rng, n, cap, parts, width, groups, 2,
                            float_vals)
    vdt = np.float32 if float_vals else np.int32
    jr, jc, jn = S.segment_reduce_rows(rows, part, parts, width - 2, vdt,
                                       sum_words=2, impl="jnp")
    pr, pc, pn = S.segment_reduce_rows(rows, part, parts, width - 2, vdt,
                                       sum_words=2, impl="pallas")
    k = int(np.asarray(jn)[0])
    assert k == int(np.asarray(pn)[0])
    assert np.array_equal(np.asarray(jc), np.asarray(pc))
    assert np.array_equal(np.asarray(jr), np.asarray(pr))
    assert not np.asarray(pr)[k:].any()
    rows_out, part2, n_out = S._segreduce_pallas(
        rows, part, parts, 2, float_acc=float_vals,
        interpret=S._resolve_interpret(None))
    _, part_m = S._mask_past_n(rows_out, part2, n_out.reshape(-1)[0],
                               parts)
    want = np.full(cap, parts, np.int32)
    want[:k] = np.repeat(np.arange(parts, dtype=np.int32), np.asarray(jc))
    assert np.array_equal(np.asarray(part_m), want)


@pytest.mark.parametrize("shape", ((0, 128, 4, 4), (39, 128, 4, 4),
                                   (129, 256, 4, 4), (300, 384, 3, 4),
                                   (250, 256, 2, 8), (39, 1024, 4, 4),
                                   (128, 1024, 4, 4), (0, 1024, 4, 4),
                                   (200, 2048, 3, 4)),
                         ids=lambda s: f"n{s[0]}_vw{s[3]}"
                         + (f"_cap{s[1]}" if s[1] > 384 else ""))
def test_blocked_fused_wire_reduce_parity_fuzz(shape):
    """int8-dequant-fused segment-reduce vs the jnp unpack-then-reduce
    oracle: keys/partitions/n_out bit-exact, dequantized f32 sums
    within the wire dequant bound (the ONLY tolerance in the sweep —
    both sides sum the SAME dequantized values, but tile-local
    accumulation vs global cumsum-differencing may part at the last
    ulp; the dequant itself is bit-identical)."""
    _require_blocked_kernels()
    import jax.numpy as jnp
    from sparkucx_tpu.shuffle.alltoall import wire_pack_rows
    n, cap, parts, vw = shape
    width = 2 + vw
    rng = np.random.default_rng(n * 13 + vw)
    rows, part = _fuzz_rows(rng, n, cap, parts, width,
                            max(1, n // 8) if n else 1, 0, True)
    f = np.asarray(rows).copy()
    fl = f[:n, 2:].view(np.float32) * np.float32(0.37)
    f[:n, 2:] = fl.view(np.int32)
    wired = wire_pack_rows(jnp.asarray(f), vw, jnp.uint32(7))
    jr, jc, jn = S.segment_reduce_wire_rows(
        wired, part, parts, width, vw, impl="jnp")
    pr, pc, pn = S.segment_reduce_wire_rows(
        wired, part, parts, width, vw, impl="pallas", interpret=None)
    k = int(np.asarray(jn)[0])
    assert k == int(np.asarray(pn)[0])
    assert np.array_equal(np.asarray(jc), np.asarray(pc))
    ja, pa = np.asarray(jr)[:k], np.asarray(pr)[:k]
    assert np.array_equal(ja[:, :2], pa[:, :2])
    assert np.allclose(ja[:, 2:].view(np.float32),
                       pa[:, 2:].view(np.float32),
                       rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("split,cap", [
    pytest.param(sp, cap, id=str(sp) if cap == 64 else f"{sp}_cap{cap}")
    for sp, cap in ((0, 64), (1, 64), (29, 64), (64, 64), (0, 512),
                    (29, 512), (64, 1024))])
def test_blocked_merge_reduce_parity_fuzz(split, cap):
    """Blocked merge-path merge+reduce vs the jnp oracle on two sorted
    runs of every skew (one side empty, singleton, balanced), 64 rows
    in all; in runs of ``cap`` rows each the merged buffer ends in
    sentinel tiles the reduce skips."""
    _require_blocked_kernels()
    rng = np.random.default_rng(split + 5)
    a_rows, a_p = _fuzz_rows(rng, split, max(split, cap), 4, W,
                             max(1, split // 2), 2, False)
    b_rows, b_p = _fuzz_rows(rng, 64 - split, cap, 4, W,
                             max(1, (64 - split) // 2), 2, False)
    outs = {}
    for impl in ("jnp", "pallas"):
        outs[impl] = S.merge_reduce_rows(
            a_rows, a_p, b_rows, b_p, 4, W - 2, np.int32,
            sum_words=2, impl=impl)
    jr, jc, jn = outs["jnp"]
    pr, pc, pn = outs["pallas"]
    k = int(np.asarray(jn)[0])
    assert k == int(np.asarray(pn)[0])
    assert np.array_equal(np.asarray(jc), np.asarray(pc))
    assert np.array_equal(np.asarray(jr)[:k], np.asarray(pr)[:k])


# per-tile live counts of the blocked kernel's gridded output (tile 128,
# block 256) and the dense capacity they compact into
_COMPACT_CASES = {
    "all_empty": ([0] * 8, 1000),
    "all_full_flush_at_cap": ([128] * 7 + [129], 7 * 128 + 129),
    "empty_runs_between_live": ([5, 0, 0, 0, 17, 0, 128, 0, 0, 3], 200),
    "single_live_first": ([40, 0, 0, 0, 0], 640),
    "single_live_last": ([0, 0, 0, 0, 129], 640),
    "ends_at_cap": ([3, 0, 60, 1], 64),
}


@pytest.mark.parametrize("case", sorted(_COMPACT_CASES))
def test_compact_src_matches_searchsorted_oracle(case):
    """The compaction index (one scatter-add and a prefix sum) reads, for
    every live dense slot, the column a binary search of the slot over
    the counts' prefix sums names."""
    import jax.numpy as jnp
    counts, cap = _COMPACT_CASES[case]
    counts = np.asarray(counts, np.int64)
    nt, blk = counts.size, 2 * S._TILE
    cum = np.cumsum(counts)
    j = np.arange(cap)
    b = np.minimum(np.searchsorted(cum, j, side="right"), nt - 1)
    want = np.clip(b * blk + j - (cum - counts)[b], 0, nt * blk - 1)
    got = np.asarray(S._compact_src(jnp.asarray(counts, jnp.int32), cap,
                                    blk))
    live = int(cum[-1])
    assert got.shape == (cap,)
    assert np.array_equal(got[:live], want[:live])


def test_gate_helper_is_the_single_skip_authority():
    """The sweep's skip reason IS kernel_gate_reason's string: on a
    gated backend every parity test above skips with it verbatim, and
    resolve_kernel_impl's fallback evidence matches the same gate (one
    helper, uniform reasons everywhere — microbench, tests, manager)."""
    assert S.kernel_gate_reason("tpu") is None
    assert S.kernel_gate_reason("cpu") is None  # interpret path
    r = S.kernel_gate_reason("gpu")
    assert r is not None and "backend='gpu'" in r
    impl, reason = S.resolve_kernel_impl("pallas", "gpu")
    assert (impl, reason) == ("jnp", "backend_unsupported")


def test_kernelbench_smoke_emits_artifact_with_explicit_skip(tmp_path):
    """Satellite: the kernel microbench on CPU — jnp arm runs and is
    timed, the pallas arm records status=skipped with a reason (never
    an interpret wall-time wearing a perf claim), parity still grades
    via interpret, and the compile.step.programs invariant gates inside
    the artifact (one program per shape family per impl on the first
    pass, zero on the warm pass)."""
    from sparkucx_tpu.ops.pallas.microbench import run_microbench
    from sparkucx_tpu.utils.atomicio import atomic_write_json

    doc = run_microbench(reps=1, rows_log2=8)
    assert doc["ok"], doc["programs"]
    assert doc["backend"] == "cpu" and doc["native_pallas"] is False
    for c in doc["cases"]:
        assert c["jnp"]["status"] == "ok"
        assert c["jnp"]["rows_per_s"] > 0
        assert c["pallas"]["status"] == "skipped"
        assert c["pallas"]["reason"] == "backend_unsupported"
        assert "rows_per_s" not in c["pallas"]
        assert c["parity"]["status"] == "ok"
        assert c["parity"]["mode"] == "interpret"
        assert c["parity"]["ok"] is True
    # the invariant the acceptance bar names, gated in the artifact
    p = doc["programs"]
    assert p["first_pass"] == p["expected"] > 0
    assert p["warm_recompiles"] == 0 and p["ok"]
    # the artifact lands as real JSON (the CLI --out path)
    path = str(tmp_path / "kernelbench.json")
    atomic_write_json(path, doc, indent=1)
    assert json.load(open(path))["metric"] == "kernelbench"
