"""Compressed wire plane — the ``a2a.wire=raw|int8|lossless`` contract.

ISSUE-8: wire compression as a first-class production axis orthogonal to
``a2a.impl``. These tests pin the validation seam, the lane arithmetic
(one formula shared by the packing kernel and the accounting), the
per-tier RaggedLayout figures, the lossless codec's bit-exact
round-trip, the dequant-error estimator's firing shape, the stochastic
rounding's unbiasedness on BOTH quantizer streams (jnp + pallas
interpret), the one-program-per-(shape,wire-mode) step-cache contract,
the raw fallbacks int8 must take (int lanes stay exact), and the MoE
traffic accounting that routes expert dispatch into the same telemetry
counters as every other exchange. The cross-impl/skew exactness matrix
lives in tests/test_fuzz_e2e.py (test_wire_sweep_vs_oracle).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkucx_tpu.shuffle.alltoall import (
    ALLOWED_WIRES, int8_wire_words, validate_wire, wire_noise_seed,
    wire_pack_rows, wire_unpack_rows)
from sparkucx_tpu.shuffle.plan import (ShufflePlan, plan_takes_seed,
                                       ragged_layout, wire_row_words)
from sparkucx_tpu.shuffle.wire import (LosslessBlock, decode_block,
                                       encode_block,
                                       estimate_dequant_error)


def _plan(impl="dense", wire="raw", wire_words=0, P=8, cap_in=256,
          cap_out=128, **kw):
    return ShufflePlan(num_shards=P, num_partitions=16, cap_in=cap_in,
                       cap_out=cap_out, impl=impl, wire=wire,
                       wire_words=wire_words, **kw)


# -- validation seam + conf ------------------------------------------------
def test_conf_rejects_unknown_wire_naming_key():
    from sparkucx_tpu.config import TpuShuffleConf
    with pytest.raises(ValueError, match="spark.shuffle.tpu.a2a.wire"):
        TpuShuffleConf({"spark.shuffle.tpu.a2a.wire": "fp8"},
                       use_env=False)
    for ok in ALLOWED_WIRES:
        assert TpuShuffleConf({"spark.shuffle.tpu.a2a.wire": ok},
                              use_env=False).a2a_wire == ok
    with pytest.raises(ValueError, match="wireErrorSampleRows"):
        TpuShuffleConf(
            {"spark.shuffle.tpu.a2a.wireErrorSampleRows": "-1"},
            use_env=False)
    assert validate_wire("lossless") == "lossless"


# -- lane arithmetic + plan family -----------------------------------------
def test_int8_wire_words_formula():
    # packed 4-per-lane plus ONE scale lane
    assert int8_wire_words(1) == 2
    assert int8_wire_words(4) == 2
    assert int8_wire_words(8) == 3
    assert int8_wire_words(64) == 17


def test_wire_row_words_per_tier():
    raw = _plan()
    assert wire_row_words(raw, 10) == 10
    lossless = _plan(wire="lossless")
    assert wire_row_words(lossless, 10) == 10      # device rows untouched
    q = _plan(wire="int8", wire_words=8)
    assert wire_row_words(q, 10) == 2 + 3          # keys + packed + scale
    q64 = _plan(wire="int8", wire_words=64)
    assert wire_row_words(q64, 66) == 2 + 17
    # the <=0.30x narrowing at the 64-lane contract row
    assert (2 + 17) / 66 < 0.30
    assert plan_takes_seed(q) and not plan_takes_seed(raw)
    assert not plan_takes_seed(lossless)


def test_wire_mode_is_its_own_program_family():
    fams = {_plan(wire=w, wire_words=8 if w == "int8" else 0).family()
            for w in ALLOWED_WIRES}
    assert len(fams) == 3


def test_wave_step_plan_preserves_wire():
    import dataclasses
    from sparkucx_tpu.shuffle.plan import wave_step_plan
    p = dataclasses.replace(_plan(wire="int8", wire_words=8),
                            wave_rows=64, num_waves=3)
    w = wave_step_plan(p)
    assert w.wire == "int8" and w.wire_words == 8
    assert w.grown().wire == "int8"


# -- layout formulas per (tier, transport) ---------------------------------
def test_layout_int8_narrows_every_transport():
    rows = np.asarray([100] * 8)
    width, vw = 10, 8
    row_w = 10 - 8 + int8_wire_words(8)            # 5 lanes vs 10
    for impl, wire_rows in (("native", 800),
                            ("dense", 8 * 8 * 128),
                            ("gather", 8 * 8 * 256)):
        lay = ragged_layout(_plan(impl, wire="int8", wire_words=vw),
                            rows, width=width)
        assert lay.wire == "int8"
        assert lay.wire_row_bytes == row_w * 4
        assert lay.wire_bytes == wire_rows * row_w * 4
        assert lay.scale_bytes == wire_rows * 4    # one f32 per wire row
        # payload stays the REAL full-width bytes — the tier narrows the
        # wire, never the payload figure
        assert lay.payload_bytes == 800 * width * 4
    # native int8: fewer wire bytes than payload — pad_ratio below 1.0
    lay_n = ragged_layout(_plan("native", wire="int8", wire_words=vw),
                          rows, width=width)
    assert lay_n.pad_ratio == 0.5


def test_layout_pallas_chunk_follows_wire_width():
    from sparkucx_tpu.ops.pallas.ragged_a2a import chunk_rows_for
    vw, width = 8, 10
    lay = ragged_layout(_plan("pallas", wire="int8", wire_words=vw),
                        np.asarray([100] * 8), width=width)
    row_w = wire_row_words(_plan("pallas", wire="int8", wire_words=vw),
                           width)
    chunk = chunk_rows_for(row_w)
    assert lay.wire_rows == 800 + 8 * 8 * (chunk - 1)
    assert lay.wire_bytes == lay.wire_rows * row_w * 4


def test_layout_raw_and_lossless_unchanged():
    rows = np.asarray([100] * 8)
    raw = ragged_layout(_plan("dense"), rows, width=10)
    ll = ragged_layout(_plan("dense", wire="lossless"), rows, width=10)
    # the lossless tier is a HOST codec: device wire identical to raw
    assert raw.wire_bytes == ll.wire_bytes == 8 * 8 * 128 * 10 * 4
    assert raw.wire == "raw" and ll.wire == "lossless"
    assert raw.scale_bytes == ll.scale_bytes == 0


# -- lane pack/unpack round trip -------------------------------------------
def test_wire_pack_unpack_bounded_and_head_exact(rng):
    n, head, vw = 64, 2, 6           # vw deliberately not a multiple of 4
    keys = rng.integers(-(1 << 31), 1 << 31,
                        size=(n, head)).astype(np.int32)
    vals = rng.normal(size=(n, vw)).astype(np.float32) * 10.0
    rows = np.concatenate(
        [keys, vals.view(np.int32)], axis=1)
    packed = wire_pack_rows(jnp.asarray(rows), vw, 7)
    assert packed.shape == (n, head + int8_wire_words(vw))
    out = np.asarray(wire_unpack_rows(packed, head + vw, vw))
    assert np.array_equal(out[:, :head], keys)     # exact head lanes
    got = out[:, head:].view(np.float32)
    step = np.abs(vals).max(axis=1, keepdims=True) / 127.0 + 1e-6
    assert (np.abs(got - vals) <= step).all()
    # zero rows (transport padding) round-trip to zero
    z = np.asarray(wire_unpack_rows(
        jnp.zeros((4, head + int8_wire_words(vw)), jnp.int32),
        head + vw, vw))
    assert not z.any()


# -- stochastic rounding: unbiased on both quantizer streams ---------------
# interpret leg slow-marked for the tier-1 budget: the pallas kernel
# shares the jnp path's rounding formula (caller-supplied uniforms),
# so the jnp leg pins the statistics in-tier and the interpreter leg
# re-pins the kernel plumbing in the soak/full lanes
@pytest.mark.parametrize("impl", (
    "jnp", pytest.param("interpret", marks=pytest.mark.slow)))
def test_stochastic_rounding_unbiased(impl, rng):
    from sparkucx_tpu.ops.pallas.quant import (dequantize_rows,
                                               quantize_rows)
    x = (rng.normal(size=(32, 16)) * 5.0).astype(np.float32)
    xj = jnp.asarray(x)
    if impl == "interpret":
        try:
            quantize_rows(xj, 0, impl=impl)
        except Exception as e:  # pragma: no cover - env-dependent
            pytest.skip(f"pallas interpret unavailable here: {e!r}")
    # interpret-mode kernel calls cost ~100ms each; K=24 still puts the
    # 0.5-step acceptance bound at ~8.5 sigma of the mean's spread
    K = 24 if impl == "interpret" else 48
    acc = np.zeros_like(x)
    for seed in range(K):
        q, s = quantize_rows(xj, seed, impl=impl)
        acc += np.asarray(dequantize_rows(q, s))
    mean = acc / K
    step = np.abs(x).max(axis=1, keepdims=True) / 127.0
    # the mean of K unbiased draws sits well inside one rounding step
    assert (np.abs(mean - x) <= step * 0.5 + 1e-6).all()


def test_wire_noise_seed_streams_distinct():
    seeds = {wire_noise_seed(7, s) for s in range(4)}
    assert len(seeds) == 4
    # traced scalars work too (the in-step derivation)
    t = wire_noise_seed(jnp.int32(7), 3)
    assert int(t) == wire_noise_seed(7, 3)


# -- lossless codec --------------------------------------------------------
def test_lossless_roundtrip_exact(rng):
    for arr in (
            rng.integers(-(1 << 31), 1 << 31,
                         size=(100, 10)).astype(np.int32),
            (rng.normal(size=(37, 5)) * 1e3).astype(np.float32),
            np.zeros((0, 8), np.int32),                 # empty
            np.asfortranarray(                          # non-contiguous
                rng.integers(0, 100, size=(16, 4)).astype(np.int32))):
        blk = encode_block(arr)
        assert isinstance(blk, LosslessBlock)
        out = decode_block(blk)
        assert out.dtype == arr.dtype and out.shape == arr.shape
        assert np.array_equal(out, np.ascontiguousarray(arr))


def test_lossless_compresses_structured_payload():
    # byte planes: sign/exponent/high bytes of real payloads are
    # low-entropy — the codec must actually win on a structured block
    k = np.arange(4096, dtype=np.int64)
    v = ((k % 997)[:, None] * 0.25
         + np.arange(16, dtype=np.float32)[None, :]).astype(np.float32)
    blk = encode_block(v)
    assert blk.nbytes < 0.5 * blk.raw_bytes
    assert np.array_equal(decode_block(blk), v)


def test_dequant_error_estimator_shape():
    rng = np.random.default_rng(3)
    # well-conditioned rows: near the ~0.005 theoretical floor
    good = rng.normal(size=(512, 32)).astype(np.float32)
    e_good = estimate_dequant_error(good)
    assert 0.0 < e_good < 0.02
    # outlier-dominated rows: one huge element stretches the per-row
    # amax so the int8 grid rounds the rest to junk — the firing shape
    bad = rng.normal(size=(512, 32)).astype(np.float32)
    bad[:, 0] = 1e6
    assert estimate_dequant_error(bad) > 10 * e_good
    assert estimate_dequant_error(np.zeros((4, 4), np.float32)) == 0.0
    assert estimate_dequant_error(np.zeros((0, 4), np.float32)) == 0.0
    # sampling is deterministic (stride, no RNG) — SPMD-safe
    assert estimate_dequant_error(good, 64) \
        == estimate_dequant_error(good, 64)


# -- manager integration ---------------------------------------------------
def _stage(m, sid, val_dtype=np.float32, vw=8, maps=4, R=16, rows=300):
    h = m.register_shuffle(sid, maps, R)
    rng = np.random.default_rng(sid)
    for mid in range(maps):
        k = rng.integers(0, 1 << 40, size=rows).astype(np.int64)
        if val_dtype is None:
            m_w = m.get_writer(h, mid)
            m_w.write(k)
            m_w.commit(R)
            continue
        v = rng.normal(size=(rows, vw)).astype(val_dtype) \
            if np.issubdtype(np.dtype(val_dtype), np.floating) \
            else rng.integers(0, 1 << 20, size=(rows, vw)).astype(val_dtype)
        w = m.get_writer(h, mid)
        w.write(k, v)
        w.commit(R)
    return h


def test_int8_resolves_raw_for_exact_lane_payloads(manager_factory):
    """The contract's exactness guarantees: int payloads and keys-only
    reads NEVER ride the lossy tier — the ask resolves to raw and the
    report says which tier actually ran."""
    m = manager_factory({"spark.shuffle.tpu.a2a.wire": "int8"})
    h = _stage(m, 61001, val_dtype=np.int32)
    res = m.read(h)
    for r in range(16):
        res.partition(r)
    rep = m.report(61001)
    assert rep.wire == "raw"
    assert rep.wire_dequant_error == 0.0
    assert rep.effective_bw_gbps == rep.bw_gbps
    m.unregister_shuffle(61001)
    h = _stage(m, 61002, val_dtype=None)
    m.read(h)
    assert m.report(61002).wire == "raw"
    m.unregister_shuffle(61002)


def test_int8_report_and_effective_bandwidth(manager_factory):
    m = manager_factory({"spark.shuffle.tpu.a2a.wire": "int8"})
    h = _stage(m, 61003)
    res = m.read(h)
    for r in range(16):
        res.partition(r)
    rep = m.report(61003)
    assert rep.wire == "int8"
    width, vw = 10, 8
    row_w = width - vw + int8_wire_words(vw)
    P = m.node.num_devices
    if not rep.retries:
        assert rep.wire_bytes == P * P * rep.plan_bucket[1] * row_w * 4
    # effective bandwidth = payload rate x raw/wire row-width gain
    # (both fields round at 1e-6 GB/s independently — allow the quantum)
    assert rep.effective_bw_gbps == pytest.approx(
        rep.bw_gbps * width / row_w, rel=1e-4, abs=1.1e-6)
    assert 0.0 < rep.wire_dequant_error < 0.05
    d = rep.to_dict()
    for k in ("wire", "wire_dequant_error", "effective_bw_gbps",
              "lossless_bytes", "lossless_ratio"):
        assert k in d
    m.unregister_shuffle(61003)


def test_one_program_per_wire_mode_zero_warm(manager_factory):
    """The acceptance bar: wire joins the compiled-step family — each
    tier compiles once for a shape, and warm reads compile NOTHING."""
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    m = manager_factory({"spark.shuffle.tpu.a2a.wire": "int8"})
    GLOBAL_STEP_CACHE.clear()      # earlier tests share this shape family
    h = _stage(m, 61004)
    m.read(h)
    first = m.report(61004).stepcache_programs
    assert first >= 1                      # int8 is its own program
    m.unregister_shuffle(61004)
    h = _stage(m, 61005)
    m.read(h)
    assert m.report(61005).stepcache_programs == 0   # 0 warm recompiles
    m.unregister_shuffle(61005)


def test_warmup_covers_the_seeded_step(manager_factory):
    """A warmed int8 plan and the read that follows share one program —
    the seeded [count, seed] signature must match exactly."""
    m = manager_factory({"spark.shuffle.tpu.a2a.wire": "int8"})
    h = _stage(m, 61006)
    plan = m.warmup(h, rows_per_map=300, val_shape=(8,),
                    val_dtype=np.float32)
    assert plan.wire == "int8" and plan.wire_words == 8
    res = m.read(h)
    for r in range(16):
        res.partition(r)
    assert m.report(61006).stepcache_programs == 0   # warmed
    m.unregister_shuffle(61006)


def test_seeded_nvalid_widens_counts_with_per_shard_seeds():
    from sparkucx_tpu.shuffle.reader import seeded_nvalid
    p = _plan(wire="int8", wire_words=8, P=4)
    nv = seeded_nvalid(p, np.asarray([5, 6, 7, 8]), base_seed=3)
    assert nv.shape == (8,) and nv.dtype == np.int32
    assert nv[0::2].tolist() == [5, 6, 7, 8]
    assert nv[1::2].tolist() == [3 * 4 + i for i in range(4)]
    # global-shard keyed in distributed mode
    nv2 = seeded_nvalid(p, np.asarray([5, 6]), 3, shard_ids=[2, 3])
    assert nv2[1::2].tolist() == [14, 15]
    # raw plans pass through untouched
    raw = seeded_nvalid(_plan(P=4), np.asarray([5, 6, 7, 8]), 3)
    assert raw.tolist() == [5, 6, 7, 8]


def test_waved_lossless_blocks_decompress_on_touch(manager_factory):
    """The codec's home: waved lossless reads hold compressed blocks
    after the drain, measure REAL bytes, and restore bit-exact rows on
    consumer touch (covered value-wise by the fuzz sweep; this pins the
    report accounting end to end)."""
    m = manager_factory({"spark.shuffle.tpu.a2a.wire": "lossless",
                         "spark.shuffle.tpu.a2a.waveRows": "48"})
    rng = np.random.default_rng(9)
    h = m.register_shuffle(61007, 4, 16)
    truth = {}
    for mid in range(4):
        k = np.arange(220, dtype=np.int64) + mid * 1000
        v = (rng.normal(size=(220, 8)) * 100).astype(np.float32)
        w = m.get_writer(h, mid)
        w.write(k, v)
        w.commit(16)
        for i, kk in enumerate(k):
            truth[int(kk)] = v[i]
    res = m.read(h)
    rep = m.report(61007)
    assert rep.wire == "lossless"
    assert rep.waves >= 2
    assert rep.lossless_bytes > 0
    assert rep.lossless_ratio == pytest.approx(
        rep.lossless_bytes / rep.payload_bytes, abs=1e-6)
    n = 0
    for r in range(16):
        ks, vs = res.partition(r)
        for i, kk in enumerate(ks):
            assert np.array_equal(vs[i], truth[int(kk)])   # bit-exact
            n += 1
    assert n == 4 * 220
    m.unregister_shuffle(61007)


# -- MoE on the wire contract ----------------------------------------------
def test_moe_exchange_traffic_math():
    from sparkucx_tpu.models import moe
    cfg = moe.MoEConfig(d_model=64, wire="raw")
    p, w = moe.exchange_traffic(cfg, tokens=100)
    assert p == w == 2 * 100 * 64 * 4
    cfg_q = moe.MoEConfig(d_model=64, wire="int8")
    p, w = moe.exchange_traffic(cfg_q, tokens=100)
    # the exact expert-id exchange is a real third collective: its
    # bytes count on BOTH sides of the quotient
    assert p == 2 * 100 * 64 * 4 + 100 * 4
    # 17 wire lanes per 64 f32 lanes, twice, plus the exact id exchange
    assert w == 2 * 100 * 17 * 4 + 100 * 4
    assert w < 0.30 * p
    # legacy alias + rejection
    assert moe.MoEConfig(wire="f32").wire_int8 is False
    with pytest.raises(ValueError, match="raw|int8"):
        _ = moe.MoEConfig(wire="lossless").wire_int8


def test_moe_forward_lands_in_exchange_telemetry(devices):
    """The satellite's contract: MoE dispatch traffic shows up in the
    SAME cumulative counters the production read path feeds."""
    from jax.sharding import Mesh
    from sparkucx_tpu.models import moe
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.utils.metrics import GLOBAL_METRICS
    assert TpuNode._instance is None or TpuNode._instance._closed
    cfg = moe.MoEConfig(d_model=8, d_hidden=16, num_experts=4,
                        tokens_per_shard=8, impl="dense", wire="int8")
    # 1x4 mesh: the counters are what's under test, not the dp split —
    # this is the only int8 MoE forward in tier-1, so keep it minimal
    mesh = Mesh(np.array(devices[:4]).reshape(1, 4), ("dp", "ep"))
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4 * 8, 8))
    pay0 = GLOBAL_METRICS.get("shuffle.payload.bytes")
    wire0 = GLOBAL_METRICS.get("shuffle.wire.bytes")
    cnt0 = GLOBAL_METRICS.get("moe.exchange.count")
    out = moe.forward(params, x, mesh, cfg, seed=1)
    assert np.isfinite(np.asarray(out)).all()
    p, w = moe.exchange_traffic(cfg, tokens=32)
    assert GLOBAL_METRICS.get("shuffle.payload.bytes") - pay0 == p
    assert GLOBAL_METRICS.get("shuffle.wire.bytes") - wire0 == w
    assert GLOBAL_METRICS.get("moe.exchange.count") - cnt0 == 2.0


def test_int8_wire_lane_arithmetic_pinned():
    """Regression pin for the chunk-alignment audit (blocked-kernel PR):
    every consumer of the int8 wire geometry — the packing kernel, the
    plan accounting, and the reader's chunk alignment — must derive
    from ONE formula, and that formula is pinned here value-by-value so
    a drift in any copy breaks loudly."""
    import dataclasses

    import jax.numpy as jnp

    from sparkucx_tpu.ops.pallas.ragged_a2a import chunk_rows_for
    from sparkucx_tpu.shuffle.alltoall import (int8_wire_words,
                                               wire_pack_rows)
    from sparkucx_tpu.shuffle.plan import ShufflePlan, wire_row_words

    # the formula itself: ceil(vw/4) packed words + 1 f32 scale word
    assert [int8_wire_words(v) for v in (1, 2, 3, 4, 5, 8, 9)] == \
        [2, 2, 2, 2, 3, 3, 4]

    plan = ShufflePlan(num_shards=1, num_partitions=4, cap_in=64,
                       cap_out=64, impl="dense")
    # raw tier: wire width IS the payload width
    assert wire_row_words(plan, 10) == 10
    # int8 tier: exact head + packed values + scale — NARROWER, and the
    # reader's chunk must follow the narrowed width (the kernel tiles
    # over wire rows, not payload rows)
    p8 = dataclasses.replace(plan, wire="int8", wire_words=8)
    assert wire_row_words(p8, 10) == 10 - 8 + int8_wire_words(8) == 5
    chunk = chunk_rows_for(wire_row_words(p8, 10))
    assert chunk == 128 and chunk != chunk_rows_for(10)
    # the alignment invariant the kernel needs: a chunk of wire rows is
    # a 128-lane multiple of int32 words
    assert (chunk * wire_row_words(p8, 10)) % 128 == 0

    # the packing kernel's output shape agrees with the accounting
    rows = jnp.zeros((8, 10), jnp.int32)
    packed = wire_pack_rows(rows, 8, jnp.uint32(1))
    assert packed.shape == (8, wire_row_words(p8, 10))

    # the fused-reduce seam: every combine+int8 plan has wire_words ==
    # combine_words, so the fused kernel's input width is exactly
    # 2 + int8_wire_words(combine_words) and its output re-widens to
    # 2 + combine_words — the widths the reader's fused gate checks
    pc = dataclasses.replace(plan, combine="sum", combine_words=8,
                             combine_dtype="<f4", wire="int8",
                             wire_words=8, kernel_impl="pallas")
    assert wire_row_words(pc, 2 + 8) == 2 + int8_wire_words(8)
