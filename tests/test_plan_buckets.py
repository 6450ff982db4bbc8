"""Plan-shape bucketing (a2a.capBuckets) — the quantizer's contract.

The compiled-step signature keys on exact capacities, so the quantizer's
properties ARE the subsystem's correctness surface: up-only rounding
(overflow semantics unchanged), monotonicity (a bigger input can never
get a smaller buffer), bounded over-provisioning (the growth factor is
the worst case), and TPU tiling (multiples of 8)."""

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.plan import (CAP_BUCKET_CEILING,
                                       CAP_BUCKET_GROWTH_RANGE, bucket_cap,
                                       bucket_cap_conf, make_plan)

# property sweep: seeded random (cap, growth) samples plus the adversarial
# edges (rung boundaries, round-to-8 remainders, the floor, the ceiling)
_RNG = np.random.default_rng(1234)
_CAPS = sorted(set(
    [0, 1, 7, 8, 9, 15, 16, 17, 63, 64, 65, 4095, 4096, 4097]
    + [int(x) for x in _RNG.integers(0, 1 << 26, size=200)]))
_GROWTHS = [CAP_BUCKET_GROWTH_RANGE[0], 1.1, 1.25, 1.5, 2.0, 3.7,
            CAP_BUCKET_GROWTH_RANGE[1]]


@pytest.mark.parametrize("growth", _GROWTHS)
def test_bucket_cap_properties(growth):
    for cap in _CAPS:
        q = bucket_cap(cap, growth)
        # up-only: a bucketed capacity never shrinks below the request
        assert q >= cap
        # TPU tiling + floor
        assert q % 8 == 0 and q >= 8
        # bounded over-provisioning: the next rung is at most ~growth
        # away (the +8 inside and +16 outside absorb the round-to-8
        # slack on both the input and the rung)
        assert q <= max(16, int(np.ceil((cap + 8) * growth)) + 16)
        assert q <= CAP_BUCKET_CEILING


@pytest.mark.parametrize("growth", _GROWTHS)
def test_bucket_cap_monotone(growth):
    qs = [bucket_cap(c, growth) for c in _CAPS]   # _CAPS is sorted
    assert qs == sorted(qs)


def test_bucket_cap_idempotent():
    """A rung maps to itself: re-quantizing (the manager's cap-hint path
    quantizes what make_plan already quantized) is stable."""
    for cap in (1, 8, 100, 4096, 1 << 20):
        q = bucket_cap(cap, 1.25)
        assert bucket_cap(q, 1.25) == q


def test_bucket_cap_growth_validated():
    with pytest.raises(ValueError, match="growth"):
        bucket_cap(100, 1.0)
    with pytest.raises(ValueError, match="growth"):
        bucket_cap(100, 100.0)


def test_bucket_cap_ceiling_clamped():
    assert bucket_cap(CAP_BUCKET_CEILING + 5, 1.25) == CAP_BUCKET_CEILING
    assert bucket_cap(CAP_BUCKET_CEILING - 3, 1.25) == CAP_BUCKET_CEILING


def test_bucket_conf_gate_and_drift_collapse():
    """Bucketing off -> exact capacities; on -> a +/-20% drifting sweep
    of row counts lands on a handful of (cap_in, cap_out) signatures
    instead of one per shape — the compile-amortization property
    test_bucketed_reads_match_exact_and_compile_fewer checks through
    the manager."""
    off = TpuShuffleConf({"spark.shuffle.tpu.a2a.capBuckets": "false",
                          "spark.shuffle.tpu.a2a.impl": "dense"},
                         use_env=False)
    on = TpuShuffleConf({"spark.shuffle.tpu.a2a.capBuckets": "true",
                         "spark.shuffle.tpu.a2a.impl": "dense"},
                        use_env=False)
    assert bucket_cap_conf(1000, off) == 1000
    rng = np.random.default_rng(0)
    shapes_off, shapes_on = set(), set()
    for _ in range(40):
        n = int(4096 * (1 + rng.uniform(-0.2, 0.2)))
        rows = np.full(8, n, dtype=np.int64)
        p_off = make_plan(rows, 8, 16, off)
        p_on = make_plan(rows, 8, 16, on)
        shapes_off.add((p_off.cap_in, p_off.cap_out))
        shapes_on.add((p_on.cap_in, p_on.cap_out))
        # up-only: the bucketed plan dominates the exact one
        assert p_on.cap_in >= p_off.cap_in
        assert p_on.cap_out >= p_off.cap_out
    assert len(shapes_off) > 5 * len(shapes_on), (shapes_off, shapes_on)



def test_bucketed_reads_match_exact_and_compile_fewer(manager_factory):
    """The same drifting row counts read through the manager with
    bucketing off and on: every partition is identical (bucketing only
    pads capacities up, and padding never reaches a partition view),
    and the bucketed reads compile fewer step programs."""
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    from sparkucx_tpu.utils.metrics import COMPILE_PROGRAMS, GLOBAL_METRICS
    rng = np.random.default_rng(3)
    maps, R = 8, 16
    counts = (512 * (1 + rng.uniform(-0.2, 0.2, size=6))).astype(int)
    data = [[rng.integers(0, 1 << 40, size=int(n), dtype=np.int64)
             for _ in range(maps)] for n in counts]
    compiles, parts = {}, {}
    for mode in ("false", "true"):
        # a fresh step cache per mode: one mode's entries must not serve
        # the other's reads
        GLOBAL_STEP_CACHE.clear()
        mgr = manager_factory({"spark.shuffle.tpu.a2a.capBuckets": mode})
        p0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
        outs = []
        for i, staged in enumerate(data):
            h = mgr.register_shuffle(41000 + i, maps, R)
            for m, keys in enumerate(staged):
                w = mgr.get_writer(h, m)
                w.write(keys)
                w.commit(R)
            res = mgr.read(h)
            outs.append([res.partition(r)[0] for r in range(R)])
            mgr.unregister_shuffle(41000 + i)
        compiles[mode] = GLOBAL_METRICS.get(COMPILE_PROGRAMS) - p0
        parts[mode] = outs
    for exact, bucketed in zip(parts["false"], parts["true"]):
        for a, b in zip(exact, bucketed):
            np.testing.assert_array_equal(a, b)
    assert compiles["false"] >= 4, compiles
    assert compiles["true"] < compiles["false"], compiles

def test_compile_conf_keys_round_trip():
    """The compile.* keys parse, validate, and appear in the
    self-describing table (python -m sparkucx_tpu must list them). The
    cache's directory is not a conf key: JAX_COMPILATION_CACHE_DIR or
    the fixed in-checkout default places it (runtime/compile_cache)."""
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.compile.cacheEnabled": "false",
        "spark.shuffle.tpu.compile.minCompileTimeSecs": "2.5",
    }, use_env=False)
    assert conf.compile_cache_enabled is False
    assert conf.compile_min_compile_time_secs == 2.5
    d = TpuShuffleConf(use_env=False)
    assert d.compile_cache_enabled is True
    assert d.compile_min_compile_time_secs == 1.0
    with pytest.raises(ValueError, match="minCompileTimeSecs"):
        TpuShuffleConf({
            "spark.shuffle.tpu.compile.minCompileTimeSecs": "-1"},
            use_env=False)
    with pytest.raises(ValueError, match="capBucketGrowth"):
        TpuShuffleConf({
            "spark.shuffle.tpu.a2a.capBucketGrowth": "0.5"},
            use_env=False)
    keys = {r["key"] for r in TpuShuffleConf.describe_keys()}
    for k in ("compile.cacheEnabled",
              "compile.minCompileTimeSecs", "a2a.capBuckets",
              "a2a.capBucketGrowth"):
        assert f"spark.shuffle.tpu.{k}" in keys, k
