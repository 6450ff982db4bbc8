"""Device combine-by-key (ops/aggregate.py + the combined read path).

Oracle: numpy groupby-sum. The reference's reduce side runs Spark's stock
aggregate+sort on the executor CPU (ref: compat/spark_2_4/
UcxShuffleReader.scala:80-144); here the same semantics execute on device,
so these tests pin (a) the kernel against numpy and (b) the end-to-end
combined read against an uncombined read of the same shuffle.
"""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.ops.aggregate import (
    check_combinable, combine_rows)
from sparkucx_tpu.shuffle.manager import TpuShuffleManager
from sparkucx_tpu.shuffle.reader import pack_rows, value_words
from sparkucx_tpu.shuffle.writer import _hash32_np


def _oracle_sums(keys, vals):
    out = {}
    for k, v in zip(keys.tolist(), vals):
        if k in out:
            out[k] = out[k] + v.astype(np.int64) if \
                np.issubdtype(v.dtype, np.integer) else out[k] + v
        else:
            out[k] = v.astype(np.int64) if \
                np.issubdtype(v.dtype, np.integer) else v.copy()
    return out


@pytest.mark.parametrize("vdtype,vtail", [
    (np.int32, (2,)), (np.float32, (3,)), (np.int16, (2,)),
    (np.float16, (4,)),
])
def test_combine_rows_vs_numpy(vdtype, vtail):
    rng = np.random.default_rng(3)
    n, cap, R = 900, 1024, 8
    keys = rng.integers(-40, 40, size=n).astype(np.int64)
    if np.issubdtype(np.dtype(vdtype), np.integer):
        vals = rng.integers(-50, 50, size=(n,) + vtail).astype(vdtype)
    else:
        vals = rng.standard_normal((n,) + vtail).astype(vdtype)
    vw = value_words(vtail, vdtype)
    W = 2 + vw
    rows = np.zeros((cap, W), dtype=np.int32)
    rows[:n] = pack_rows(keys, vals, W)
    part = np.zeros(cap, dtype=np.int32)
    part[:n] = _hash32_np(keys) % R

    rows_out, pcounts, n_out = jax.jit(
        lambda r, p: combine_rows(r, p, jnp.int32(n), R, vw, vdtype))(
        jnp.asarray(rows), jnp.asarray(part))
    rows_out, pcounts, n_out = map(np.asarray, (rows_out, pcounts, n_out))

    want = _oracle_sums(keys, vals)
    assert int(n_out[0]) == len(want)
    assert int(pcounts.sum()) == len(want)
    from sparkucx_tpu.shuffle.reader import unpack_rows
    gk, gv = unpack_rows(rows_out[: int(n_out[0])], vtail, vdtype)
    # output sorted by (partition, key): keys unique, every sum right
    assert len(set(gk.tolist())) == len(gk)
    parts_out = _hash32_np(gk) % R
    assert (np.diff(parts_out) >= 0).all(), "not partition-major"
    for i, k in enumerate(gk.tolist()):
        w = want[k]
        if np.issubdtype(np.dtype(vdtype), np.integer):
            w = w.astype(np.int64).astype(vdtype)  # wrap like the kernel
            np.testing.assert_array_equal(gv[i], w)
        else:
            np.testing.assert_allclose(
                gv[i].astype(np.float64), w.astype(np.float64),
                rtol=2e-2 if vdtype == np.float16 else 1e-5,
                atol=2e-2 if vdtype == np.float16 else 1e-4)
    # keys sorted within each partition
    for r in range(R):
        ks = gk[parts_out == r]
        assert (np.diff(ks) > 0).all()
    # rows past n_out are zero
    assert not rows_out[int(n_out[0]):].any()


def test_combine_rows_empty():
    rows = jnp.zeros((16, 4), jnp.int32)
    part = jnp.zeros(16, jnp.int32)
    rows_out, pcounts, n_out = combine_rows(
        rows, part, jnp.int32(0), 4, 2, np.int32)
    assert int(np.asarray(n_out)[0]) == 0
    assert not np.asarray(pcounts).any()
    assert not np.asarray(rows_out).any()


def test_check_combinable_rejects():
    with pytest.raises(ValueError, match="numeric"):
        check_combinable((2,), np.dtype("V8"), "sum")
    with pytest.raises(ValueError, match="keys-only"):
        check_combinable(None, None, "sum")
    with pytest.raises(ValueError, match="whole transport words"):
        check_combinable((3,), np.int8, "sum")
    with pytest.raises(ValueError, match="unknown combiner"):
        check_combinable((2,), np.int32, "mean")
    with pytest.raises(ValueError, match="4 bytes"):
        check_combinable((2,), np.int64, "sum")


def _mgr(**extra):
    from sparkucx_tpu.runtime.node import TpuNode
    conf = TpuShuffleConf(
        {"spark.shuffle.tpu.a2a.impl": "dense", **extra}, use_env=False)
    node = TpuNode.start(conf)
    return TpuShuffleManager(node, conf), node


def test_combined_read_end_to_end():
    mgr, node = _mgr()
    try:
        R = 16
        h = mgr.register_shuffle(31, 4, R)
        rng = np.random.default_rng(7)
        allk, allv = [], []
        for m in range(4):
            w = mgr.get_writer(h, m)
            n = [2000, 5, 0, 1200][m]
            k = rng.integers(0, 37, size=n).astype(np.int64)  # heavy dups
            v = np.stack([k, np.ones_like(k)], axis=1).astype(np.int32)
            if n:
                w.write(k, v)
            w.commit(R)
            allk.append(k)
            allv.append(v)
        allk = np.concatenate(allk)
        allv = np.concatenate(allv)

        res = mgr.read(h, combine="sum")
        want = _oracle_sums(allk, allv)
        got_total = 0
        parts = _hash32_np(allk) % R
        for r, (gk, gv) in res.partitions():
            wk = sorted(set(allk[parts == r].tolist()))
            assert gk.tolist() == wk, f"partition {r} keys"
            for i, k in enumerate(gk.tolist()):
                np.testing.assert_array_equal(
                    gv[i].astype(np.int64), want[k])
            got_total += len(gk)
        assert got_total == len(want)
    finally:
        mgr.stop()
        node.close()


def test_combined_matches_uncombined_totals():
    """Per-partition value totals must be identical with and without the
    device combine — combining must never lose or duplicate mass."""
    mgr, node = _mgr()
    try:
        R = 8
        rng = np.random.default_rng(11)
        k = rng.integers(0, 100, size=3000).astype(np.int64)
        v = rng.integers(-5, 6, size=(3000, 2)).astype(np.int32)
        handles = {}
        for sid in (41, 42):
            h = mgr.register_shuffle(sid, 2, R)
            for m in range(2):
                w = mgr.get_writer(h, m)
                w.write(k[m::2], v[m::2])
                w.commit(R)
            handles[sid] = h
        res_p = mgr.read(handles[41])
        res_c = mgr.read(handles[42], combine="sum")
        for r in range(R):
            _, pv = res_p.partition(r)
            _, cv = res_c.partition(r)
            np.testing.assert_array_equal(
                pv.astype(np.int64).sum(axis=0),
                cv.astype(np.int64).sum(axis=0))
    finally:
        mgr.stop()
        node.close()


def test_combine_rejected_for_keys_only():
    mgr, node = _mgr()
    try:
        h = mgr.register_shuffle(51, 1, 4)
        w = mgr.get_writer(h, 0)
        w.write(np.arange(10, dtype=np.int64))
        w.commit(4)
        with pytest.raises(ValueError, match="keys-only"):
            mgr.read(h, combine="sum")
    finally:
        mgr.stop()
        node.close()


def test_combined_read_hierarchical():
    """Two-stage ICI/DCN exchange with combine at all three hops: map-side,
    relay-side (the rows it shrinks are the ones crossing DCN), and
    receive-side. Same oracle as the flat path."""
    mgr, node = _mgr(**{"spark.shuffle.tpu.mesh.numSlices": "2"})
    try:
        assert mgr.hierarchical, "fixture must select the two-stage path"
        R = 16
        h = mgr.register_shuffle(52, 4, R)
        rng = np.random.default_rng(13)
        allk, allv = [], []
        for m in range(4):
            w = mgr.get_writer(h, m)
            k = rng.integers(0, 23, size=700).astype(np.int64)  # heavy dups
            v = np.stack([k, np.ones_like(k)], axis=1).astype(np.int32)
            w.write(k, v)
            w.commit(R)
            allk.append(k)
            allv.append(v)
        allk, allv = np.concatenate(allk), np.concatenate(allv)
        res = mgr.read(h, combine="sum")
        want = _oracle_sums(allk, allv)
        parts = _hash32_np(allk) % R
        seen = 0
        for r, (gk, gv) in res.partitions():
            assert gk.tolist() == sorted(set(allk[parts == r].tolist()))
            for i, k in enumerate(gk.tolist()):
                np.testing.assert_array_equal(gv[i].astype(np.int64),
                                              want[k])
            seen += len(gk)
        assert seen == len(want)
    finally:
        mgr.stop()
        node.close()


@pytest.mark.parametrize("width,want_sorts,form",
                         [(3, 2, "carried"), (4, 4, "perm")])
def test_combined_read_single_shard_skips_receive_merge(width, want_sorts,
                                                        form):
    """On a 1-shard exchange the step returns the map-side combine's rows
    directly (there is nothing to merge); results must match the same
    job's multi-shard oracle semantics, and the compiled HLO must contain
    exactly ONE combine's sorts (no second combine): a 3-word row takes
    the carried form — two carried sorts and no cap-length gather — and
    a 4-word row the permutation form's three LSD passes + compaction."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from sparkucx_tpu.ops.aggregate import combine_form
    from sparkucx_tpu.shuffle.plan import ShufflePlan
    from sparkucx_tpu.shuffle.reader import (pack_rows, step_body,
                                             unpack_rows)

    R, n = 8, 500
    vw = width - 2
    assert combine_form(R, vw, np.int32) == form
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 29, size=n)                 # heavy duplication
    vals = rng.integers(-50, 50, size=(n, vw)).astype(np.int32)
    rows = pack_rows(keys.astype(np.int64), vals, width)
    cap = 512
    payload = np.zeros((cap, width), np.int32)
    payload[:n] = rows

    plan = ShufflePlan(num_shards=1, num_partitions=R, cap_in=cap,
                       cap_out=768, impl="auto", combine="sum",
                       combine_words=vw, combine_dtype="<i4")
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("x",))
    jitted = jax.jit(jax.shard_map(
        step_body(plan, "x"), mesh=mesh1, in_specs=(P("x"), P("x")),
        out_specs=(P("x"), P("x"), P("x"), P("x")), check_vma=False))
    out_rows, seg, total, ovf = jitted(
        jnp.asarray(payload), jnp.asarray(np.array([n], np.int32)))
    assert not bool(np.asarray(ovf)[0])

    want = {}
    for k, v in zip(keys.tolist(), vals):
        want[k] = want.get(k, 0) + v.astype(np.int64)
    got_k, got_v = unpack_rows(
        np.asarray(out_rows)[:int(np.asarray(total)[0])], (vw,), np.int32)
    assert len(got_k) == len(want)
    from sparkucx_tpu.ops.partition import hash32
    import jax.numpy as _jnp
    parts = np.asarray(hash32(_jnp.asarray(got_k)) % np.uint32(R))
    assert (np.diff(parts) >= 0).all(), "rows not partition-major"
    for k, v in zip(got_k.tolist(), got_v):
        np.testing.assert_array_equal(v.astype(np.int64), want[k])
    # seg matrix row must equal per-partition combined counts
    pc = np.asarray(seg).reshape(R)
    counts = np.bincount(parts, minlength=R)
    np.testing.assert_array_equal(pc, counts)
    # exactly one combine chain — a receive-side merge would add its
    # sorts again
    txt = jitted.lower(
        jax.ShapeDtypeStruct((cap, width), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    nsorts = txt.count("stablehlo.sort")
    assert nsorts == want_sorts, \
        f"expected {want_sorts} sorts (grouping + compaction), got {nsorts}"
    # a gather indexed by a cap-length index vector moves cap rows
    cap_gathers = re.findall(
        r'"stablehlo\.gather".*: \(tensor<[^>]*>, tensor<%dx1xi32>\)' % cap,
        txt)
    assert bool(cap_gathers) == (form == "perm"), cap_gathers


def test_combine_compaction_variants_agree(mesh8, rng):
    """stable and unstable compaction must be bit-identical on live
    outputs (the unstable form re-establishes order with explicit keys;
    it exists as the measured candidate for the TPU combine cost)."""
    import jax.numpy as jnp

    from sparkucx_tpu.ops.aggregate import combine_rows

    cap, W, R = 512, 6, 8
    n_valid = 400
    rows = np.zeros((cap, W), np.int32)
    keys = rng.integers(-1 << 60, 1 << 60, size=n_valid, dtype=np.int64)
    keys[100:200] = keys[:100]            # force duplicates
    rows[:n_valid, :2] = keys.view(np.int32).reshape(-1, 2)
    rows[:n_valid, 2:] = rng.integers(0, 1000, size=(n_valid, W - 2))
    part = rng.integers(0, R, size=cap).astype(np.int32)
    outs = {}
    for comp in ("stable", "unstable"):
        o, pc, n = combine_rows(
            jnp.asarray(rows), jnp.asarray(part), jnp.int32(n_valid), R,
            W - 2, np.int32, "sum", sum_words=2, compaction=comp)
        outs[comp] = (np.asarray(o), np.asarray(pc), int(n[0]))
    assert outs["stable"][2] == outs["unstable"][2]
    np.testing.assert_array_equal(outs["stable"][1], outs["unstable"][1])
    n = outs["stable"][2]
    np.testing.assert_array_equal(outs["stable"][0][:n],
                                  outs["unstable"][0][:n])


# keys around the lo/hi word split: signed int64 order must hold across it
_SPLIT_KEYS = np.array([-(1 << 40), -(1 << 32) - 1, -(1 << 32), -(1 << 31) - 1,
                        -(1 << 31), -2, -1, 0, 1, (1 << 31) - 1, 1 << 31,
                        (1 << 32) - 1, 1 << 32, 1 << 40], np.int64)


@pytest.mark.parametrize("vdtype", [np.int32, np.float32])
@pytest.mark.parametrize("case", ["duplicates", "negative_keys", "empty",
                                  "one_row", "sentinel_partition",
                                  "carried_lane"])
def test_combine_forms_bit_identical(vdtype, case, monkeypatch):
    """The carried form (columns ride the sorts) and the permutation form
    (lexsort passes + gathers) give bit-identical (rows_out, pcounts,
    n_out): equal (partition, key) rows keep their input order wherever
    that order shows in the result, float sums included. The carried form
    is also pinned one operand past its bound, with a carried payload
    lane. The bound is moved to force each form."""
    from sparkucx_tpu.ops import aggregate

    cap, R = 256, 8
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    width, sum_words = (4, 1) if case == "carried_lane" else (3, 0)
    vw = width - 2
    n_valid = {"empty": 0, "one_row": 1, "sentinel_partition": cap}.get(
        case, 200)
    if case == "negative_keys":
        keys = rng.choice(_SPLIT_KEYS, size=cap)
    else:
        keys = rng.integers(-6, 6, size=cap).astype(np.int64) \
            * rng.choice(np.array([1, 1 << 31, 1 << 33]), size=cap)
    rows = np.zeros((cap, width), np.int32)
    rows[:, :2] = keys.view(np.int32).reshape(-1, 2)
    if vdtype == np.float32:
        rows[:, 2] = rng.standard_normal(cap).astype(np.float32).view(
            np.int32)
    else:
        rows[:, 2] = rng.integers(-(1 << 31), (1 << 31) - 1, size=cap)
    if case == "carried_lane":
        rows[:, 3] = (keys * 7 + 3).astype(np.int32)   # constant per key
    part = rng.integers(0, R, size=cap).astype(np.int32)
    if case == "sentinel_partition":
        part[rng.random(cap) < 0.25] = R     # marked rows, passed as valid
    if case != "carried_lane":
        assert aggregate.combine_form(R, vw, vdtype) == "carried"

    def run(max_operands, compaction="stable"):
        monkeypatch.setattr(aggregate, "CARRIED_MAX_OPERANDS", max_operands)
        assert aggregate.combine_form(R, vw, vdtype, sum_words) == \
            ("carried" if max_operands else "perm")
        fn = jax.jit(lambda r, p, n: combine_rows(
            r, p, n, R, vw, vdtype, sum_words=sum_words,
            compaction=compaction))
        return [np.asarray(x) for x in fn(rows, part, jnp.int32(n_valid))]

    carried = run(1 + width)
    for perm in (run(0), run(0, "unstable")):
        for a, b in zip(carried, perm):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))
    n_out = int(carried[2][0])
    assert (n_out == 0) == (n_valid == 0)
    assert not carried[0][n_out:].any()
