"""Hierarchical (multi-slice) shuffle tests — shuffle/hierarchical.py.

Runs the two-stage ICI->DCN exchange on a virtual 2x4 mesh (2 "slices" of
4 CPU devices) and checks it against the flat exchange and a numpy oracle.
This is the dry-run form of SURVEY.md §7 hard part (d)."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.hierarchical import read_shuffle_hierarchical
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.plan import ShufflePlan
from sparkucx_tpu.shuffle.reader import (KEY_WORDS, pack_rows, read_shuffle,
                                         unpack_rows)
from sparkucx_tpu.shuffle.writer import _hash32_np


@pytest.fixture(scope="module")
def mesh2x4(request):
    devs = jax.devices()
    assert len(devs) == 8
    return Mesh(np.array(devs).reshape(2, 4), ("dcn", "shuffle"))


def make_inputs(rng, Pn, rows_per_shard, R, width=KEY_WORDS):
    keys = [rng.integers(0, 1 << 20, size=rows_per_shard)
            for _ in range(Pn)]
    cap_in = rows_per_shard
    shard_rows = np.zeros((Pn, cap_in, width), np.int32)
    for p, k in enumerate(keys):
        shard_rows[p] = pack_rows(k, None, width)
    nvalid = np.full(Pn, rows_per_shard, np.int64)
    return keys, shard_rows, nvalid


def partition_of(keys, R):
    return (_hash32_np(np.asarray(keys)) % np.uint32(R)).astype(np.int64)


def collect(result, R):
    """partition id -> sorted key list."""
    out = {}
    for r in range(R):
        k, _ = result.partition(r)
        out[r] = sorted(k.tolist())
    return out


@pytest.mark.parametrize("R", [8, 16, 13])
def test_hierarchical_matches_flat(mesh2x4, rng, R):
    Pn, rows = 8, 64
    keys, shard_rows, nvalid = make_inputs(rng, Pn, rows, R)
    plan = ShufflePlan(Pn, R, cap_in=rows, cap_out=256, impl="dense")
    hier = read_shuffle_hierarchical(
        mesh2x4, "dcn", "shuffle", plan, shard_rows, nvalid, None, None)

    flat_mesh = Mesh(mesh2x4.devices.reshape(-1), ("shuffle",))
    flat = read_shuffle(flat_mesh, "shuffle", plan, shard_rows, nvalid,
                        None, None)
    assert collect(hier, R) == collect(flat, R)

    # and against the numpy oracle
    all_keys = np.concatenate(keys)
    parts = partition_of(all_keys, R)
    want = {r: sorted(all_keys[parts == r].tolist()) for r in range(R)}
    assert collect(hier, R) == want


def test_hierarchical_with_values(mesh2x4, rng):
    Pn, rows, R = 8, 32, 8
    width = KEY_WORDS + 1
    all_keys, all_vals = [], []
    shard_rows = np.zeros((Pn, rows, width), np.int32)
    for p in range(Pn):
        k = rng.integers(0, 1 << 16, size=rows)
        v = rng.standard_normal((rows, 1)).astype(np.float32)
        shard_rows[p] = pack_rows(k, v, width)
        all_keys.append(k)
        all_vals.append(v)
    nvalid = np.full(Pn, rows, np.int64)
    plan = ShufflePlan(Pn, R, cap_in=rows, cap_out=128, impl="dense")
    res = read_shuffle_hierarchical(
        mesh2x4, "dcn", "shuffle", plan, shard_rows, nvalid,
        (1,), np.float32)

    ak = np.concatenate(all_keys)
    av = np.concatenate(all_vals)
    parts = partition_of(ak, R)
    got_pairs, want_pairs = set(), set()
    for r in range(R):
        k, v = res.partition(r)
        assert (partition_of(k, R) == r).all()
        got_pairs |= {(int(a), float(b)) for a, b in zip(k, v[:, 0])}
        sel = parts == r
        want_pairs |= {(int(a), float(b))
                       for a, b in zip(ak[sel], av[sel, 0])}
    assert got_pairs == want_pairs


def test_hierarchical_overflow_retry(mesh2x4, rng):
    """All keys land in one partition -> tiny cap_out overflows, the retry
    loop grows it, and the result is still complete. cap_out starts at 48
    (two regrows to the needed 128), not 8: each regrow compiles a fresh
    fused program (~1.5 s on XLA:CPU), and a 5-rung ladder proved the
    same loop at 3x the tier-1 wall (the PR-12 budget discipline)."""
    Pn, rows, R = 8, 16, 8
    shard_rows = np.zeros((Pn, rows, KEY_WORDS), np.int32)
    key = 12345  # every row identical -> single destination
    for p in range(Pn):
        shard_rows[p] = pack_rows(np.full(rows, key, np.int64), None,
                                  KEY_WORDS)
    nvalid = np.full(Pn, rows, np.int64)
    plan = ShufflePlan(Pn, R, cap_in=rows, cap_out=48, impl="dense")
    res = read_shuffle_hierarchical(
        mesh2x4, "dcn", "shuffle", plan, shard_rows, nvalid, None, None)
    r = int(partition_of([key], R)[0])
    k, _ = res.partition(r)
    assert k.shape[0] == Pn * rows
    assert (k == key).all()


def test_hierarchical_direct_partitioner(mesh2x4, rng):
    Pn, rows, R = 8, 24, 16
    shard_rows = np.zeros((Pn, rows, KEY_WORDS), np.int32)
    all_parts = []
    for p in range(Pn):
        part_ids = rng.integers(0, R, size=rows)
        shard_rows[p] = pack_rows(part_ids.astype(np.int64), None, KEY_WORDS)
        all_parts.append(part_ids)
    nvalid = np.full(Pn, rows, np.int64)
    plan = ShufflePlan(Pn, R, cap_in=rows, cap_out=128, impl="dense",
                       partitioner="direct")
    res = read_shuffle_hierarchical(
        mesh2x4, "dcn", "shuffle", plan, shard_rows, nvalid, None, None)
    ap = np.concatenate(all_parts)
    for r in range(R):
        k, _ = res.partition(r)
        assert k.shape[0] == int((ap == r).sum())
        assert (k == r).all()


def test_manager_uses_hierarchical_on_2d_mesh(rng):
    """A manager on a (dcn=2, shuffle=4) mesh routes reads through the
    two-stage path and still produces correct partitions."""
    from sparkucx_tpu.runtime.node import TpuNode

    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense",
                           "spark.shuffle.tpu.mesh.numSlices": "2"},
                          use_env=False)
    node = TpuNode.start(conf)
    try:
        mgr = TpuShuffleManager(node, conf)
        assert mgr.hierarchical
        R, M = 8, 4
        h = mgr.register_shuffle(930, M, R)
        all_keys = []
        for m in range(M):
            w = mgr.get_writer(h, m)
            k = rng.integers(0, 1 << 18, size=50)
            w.write(k)
            w.commit(R)
            all_keys.append(k)
        res = mgr.read(h)
        ak = np.concatenate(all_keys)
        parts = partition_of(ak, R)
        for r in range(R):
            k, _ = res.partition(r)
            assert sorted(k.tolist()) == sorted(ak[parts == r].tolist())
        mgr.unregister_shuffle(930)
        span = [s for s in node.tracer.spans("shuffle.dispatch")]
        # tracer disabled by default -> no spans; flag lives on manager
        mgr.stop()
    finally:
        node.close()


def test_manager_hierarchical_optout(rng):
    from sparkucx_tpu.runtime.node import TpuNode

    conf = TpuShuffleConf({"spark.shuffle.tpu.a2a.impl": "dense",
                           "spark.shuffle.tpu.mesh.numSlices": "2",
                           "spark.shuffle.tpu.a2a.hierarchical": "false"},
                          use_env=False)
    node = TpuNode.start(conf)
    try:
        mgr = TpuShuffleManager(node, conf)
        assert not mgr.hierarchical
        mgr.stop()
    finally:
        node.close()


@pytest.mark.slow
def test_hier_step_aot_proof():
    """The two-stage (ICI, DCN) exchange lowers for TPU at a 2x4
    topology via the local libtpu: BOTH collectives survive post-opt
    HLO — ICI groups of 4, DCN groups of 2 (the multi-slice half of the
    distributed-backend evidence). Skips where libtpu/topology
    support is unavailable."""
    import pytest as _pytest

    from sparkucx_tpu.shuffle.aot import aot_compile_hier_step
    rep = aot_compile_hier_step()
    if "topology" not in rep:
        _pytest.skip(f"no TPU topology support here: {rep.get('error')}")
    assert rep["ok"], rep
    assert set(rep["group_sizes"]) >= {2, 4}


def test_two_stage_proof_decision_closes_equal_size_hole():
    """ADVICE r5 low: the slices == per_slice case must demand TWO
    collectives OF THAT SIZE — one required-size line plus one of an
    unrelated size used to pass vacuously through the summed count."""
    from sparkucx_tpu.shuffle.aot import _two_stage_ok

    # general case: both sizes present, regardless of extras
    assert _two_stage_ok({2: 1, 4: 1}, slices=2, per_slice=4)
    assert not _two_stage_ok({4: 2}, slices=2, per_slice=4)
    assert not _two_stage_ok({2: 2}, slices=2, per_slice=4)
    # degenerate slices == per_slice: the size must occur twice
    assert _two_stage_ok({4: 2}, slices=4, per_slice=4)
    assert not _two_stage_ok({4: 1}, slices=4, per_slice=4)
    # THE hole: one required-size collective + one unrelated size
    assert not _two_stage_ok({4: 1, 8: 1}, slices=4, per_slice=4)


# -- manager-path fuzz sweep vs the host oracle (topology plane) -----------
# impl x wire x mode x skew cells through the production manager on the
# 2-D mesh. ONE cell runs in tier-1 (the suite sits within ~40 s of the
# 870 s fence on this box — the PR-12 budget discipline); the rest are
# slow-marked and verified under -m slow. The in-tier cell is
# deliberately int8 x combine x one-hot: a single hot key is the shape
# that stresses the RELAY combine (every row converges on one (slice,
# device-column) relay, which must merge its whole slice's rows before
# the DCN hop), and it exercises both narrowed hops at once.
_SWEEP_CELLS = [
    ("dense", "raw", "plain", "zipf", True),
    ("dense", "int8", "combine", "onehot", False),
    ("gather", "raw", "ordered", "uniform", True),
    ("dense", "raw", "combine", "uniform", True),
    ("dense", "int8", "plain", "uniform", True),
    ("dense", "raw", "ordered", "onehot", True),
    ("gather", "int8", "ordered", "zipf", True),
    ("gather", "raw", "plain", "onehot", True),
    ("gather", "int8", "combine", "zipf", True),
    ("dense", "int8", "plain", "zipf", True),
]


def _sweep_keys(rng, skew, n):
    if skew == "uniform":
        return rng.permutation(np.arange(4 * n, dtype=np.int64))[:n]
    if skew == "zipf":
        return (rng.zipf(1.6, size=n) % 512).astype(np.int64)
    return np.full(n, 7, dtype=np.int64)          # one-hot


@pytest.mark.parametrize(
    "impl,wire,mode,skew",
    [pytest.param(i, w, m, s,
                  marks=[pytest.mark.slow] if slow else [],
                  id=f"{i}-{w}-{m}-{s}")
     for i, w, m, s, slow in _SWEEP_CELLS])
def test_hier_sweep_vs_oracle(rng, impl, wire, mode, skew):
    """Hierarchical manager reads across impl x wire x read mode x skew
    vs the numpy oracle: partitioning exact, keys exact every tier,
    values exact on raw and rounding-bounded on int8 (two hops = two
    stochastic rounding steps), per-tier accounting present with the
    headline wire equal to the two-hop sum."""
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.a2a.impl": impl,
        "spark.shuffle.tpu.a2a.wire": wire,
        "spark.shuffle.tpu.mesh.numSlices": "2"}, use_env=False)
    node = TpuNode.start(conf)
    mgr = TpuShuffleManager(node, conf)
    try:
        assert mgr.hierarchical
        R, M, rows, VW = 8, 4, 96, 4
        h = mgr.register_shuffle(880, M, R)
        ks, vs = [], []
        for m in range(M):
            w = mgr.get_writer(h, m)
            k = _sweep_keys(rng, skew, rows)
            v = rng.random((rows, VW), dtype=np.float32) + 0.5
            w.write(k, v)
            w.commit(R)
            ks.append(k)
            vs.append(v)
        ak, av = np.concatenate(ks), np.concatenate(vs)
        parts = partition_of(ak, R)
        res = mgr.read(h, combine="sum" if mode == "combine" else None,
                       ordered=(mode == "ordered"))
        rep = mgr.report(880)
        assert rep.hierarchical and rep.completed
        assert [t["tier"] for t in rep.tiers] == ["ici", "dcn"]
        assert rep.wire_bytes == sum(t["wire_bytes"] for t in rep.tiers)
        assert rep.wire == (wire if wire == "int8" else "raw")
        lossy = rep.wire == "int8"
        total = 0
        for r in range(R):
            k, v = res.partition(r)
            sel = parts == r
            total += k.shape[0]
            if mode == "combine":
                want_k = np.unique(ak[sel])
                assert np.array_equal(k, want_k)
            else:
                assert sorted(k.tolist()) == sorted(ak[sel].tolist())
                if mode == "ordered":
                    assert (np.diff(k) >= 0).all()
            # value contract per key: SUM over the key's rows (exact on
            # raw; int8 pays one rounding step per row per hop)
            for kk in np.unique(ak[sel]):
                want = av[sel][ak[sel] == kk].sum(axis=0)
                got = v[k == kk].sum(axis=0)
                cnt = int((ak[sel] == kk).sum())
                if lossy:
                    vmax = float(np.abs(av[sel][ak[sel] == kk]).max())
                    smax = max(vmax * cnt, vmax)
                    atol = 2 * (cnt + 2) * (smax / 127.0) + 1e-3
                else:
                    atol = 1e-3 * max(cnt, 1)
                np.testing.assert_allclose(got, want, atol=atol,
                                           rtol=1e-4)
        if mode != "combine":
            assert total == ak.shape[0]
        mgr.unregister_shuffle(880)
    finally:
        mgr.stop()
        node.close()
