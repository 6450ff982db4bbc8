"""The yardstick's generators, reference, control and byte counts.

The pinned values fix what every later run of the benchmark generates: a
change to the package cannot move them, and a change here shows."""

import collections
import hashlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import yardstick as y  # noqa: E402

SEED = 2**31 + 77


def _digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mix64_is_splitmix64():
    # the first outputs of splitmix64 seeded with 0, 1, 2
    assert y.mix64(np.arange(3, dtype=np.uint64)).tolist() == [
        16294208416658607535, 10451216379200822465, 10905525725756348110]


def test_generators_pinned():
    assert y.keystream(SEED, 5, 3).tolist() == [
        3911308859432121007, 1986743643257248572, 3384649325965320087]
    assert _digest(y.keystream(SEED, 5, 1000)) == "ff1c0b17559c7bf1"
    assert _digest(y.payload_lanes(SEED, 5, 1000, 23)) == "adb554b36dcee7c8"
    assert y.zipf_words(SEED, 8, 1 << 20, 1.1).tolist() == [
        2381, 1041, 12931, 11, 444, 742, 9, 0]
    assert _digest(y.zipf_words(SEED, 1000, 1 << 20, 1.1)) == \
        "f0a93ec96f5ca831"


def test_keys_are_62_bit_and_seeds_differ():
    k = y.keystream(3, 0, 10000)
    assert k.min() >= 0 and k.max() < (1 << 62)
    assert not np.array_equal(k, y.keystream(4, 0, 10000))


def test_inputs_same_sizes_for_every_seed():
    cfg = _config("hibench-terasort")
    a = y.make_inputs(cfg, 1, 1, rows_per_chip=1000)
    b = y.make_inputs(cfg, 1, 2**33 + 1, rows_per_chip=1000)
    assert [k.shape for k, _ in a.maps] == [k.shape for k, _ in b.maps]
    assert np.array_equal(a.bounds, b.bounds)
    assert a.rows == 1000 and len(a.maps) == cfg["num_maps"]
    assert a.maps[0][1].shape[1] == 23


def test_uniform_bounds():
    b = y.uniform_bounds(4, 62)
    assert b.tolist() == [1 << 60, 2 << 60, 3 << 60]


def test_hash_partition_by_hand():
    cfg = {"partitioner": {"kind": "hash"}}
    # murmur3 fmix32(1) = 0x514E28B7
    assert y.fmix32(np.array([1], np.uint32))[0] == 0x514E28B7
    got = y.partition_of(np.array([1, (5 << 32) | 1]), cfg, 7, None)
    assert got.tolist() == [0x514E28B7 % 7] * 2


def test_reference_ordered_by_hand():
    cfg = {"partitioner": {"kind": "range"}, "read": {"ordered": True}}
    keys = np.array([50, 10, 30, 20], np.int64)
    vals = np.arange(4, dtype=np.int32).reshape(4, 1)
    inp = y.MapOutputs([(keys[:2], vals[:2]), (keys[2:], vals[2:])], 2,
                       np.array([25], np.int64))
    e = y.reference(inp, cfg)
    assert e.sizes.tolist() == [2, 2]
    got_keys = np.ascontiguousarray(e.rows[:, :2]).view(np.int64).ravel()
    assert got_keys.tolist() == [10, 20, 30, 50]
    assert e.rows[:, 2].tolist() == [1, 3, 2, 0]


def test_reference_combine_by_hand():
    cfg = {"partitioner": {"kind": "hash"}, "read": {"combine": "sum"}}
    keys = np.array([3, 1, 3, 3, 2], np.int64)
    vals = np.ones((5, 1), np.int32)
    inp = y.MapOutputs([(keys, vals)], 1, None)
    e = y.reference(inp, cfg)
    got_keys = np.ascontiguousarray(e.rows[:, :2]).view(np.int64).ravel()
    assert got_keys.tolist() == [1, 2, 3]
    assert e.rows[:, 2].tolist() == [1, 1, 3]


def _sizes(parts):
    return {r: k.shape[0] for r, (k, _) in parts.items()}


@pytest.mark.parametrize("name", ["hibench-terasort", "wordcount-zipf"])
def test_compare_reads_zero_on_reference(name):
    cfg = _config(name)
    inp = y.make_inputs(cfg, 1, 11, rows_per_chip=5000)
    e = y.reference(inp, cfg)
    parts = y.split_rows(e)
    assert y.compare(e, _sizes(parts), parts, cfg) == {
        "rows_wrong": 0, "partition_sizes_wrong": 0, "partitions_missing": 0}


@pytest.mark.parametrize("name,rows", [("hibench-terasort", 300_000),
                                       ("wordcount-zipf", 1_000_000)])
def test_control_fails(name, rows):
    """The control, one precision step down in the program's place, fails
    a limit: a 32-bit sort key misorders keys whose high words tie, an
    int16 accumulator wraps the counts of frequent words."""
    cfg = _config(name)
    inp = y.make_inputs(cfg, 1, 12, rows_per_chip=rows)
    parts = y.split_rows(y.control(inp, cfg))
    got = y.compare(y.reference(inp, cfg), _sizes(parts), parts, cfg)
    assert any(got[k] > y.LIMITS[k] for k in got), got


def test_compare_catches_each_fault():
    cfg = _config("hibench-terasort")
    inp = y.make_inputs(cfg, 1, 13, rows_per_chip=4000)
    e = y.reference(inp, cfg)
    parts = y.split_rows(e)
    sizes = _sizes(parts)
    k, v = parts[5]
    v2 = v.copy()
    v2[0, 3] ^= 1
    assert y.compare(e, sizes, {5: (k, v2)}, cfg)["rows_wrong"] == 1
    # a record in the wrong partition: both partitions' rows and sizes
    k6, v6 = parts[6]
    moved = {5: (k[:-1], v[:-1]),
             6: (np.r_[k[-1:], k6], np.r_[v[-1:], v6])}
    got = y.compare(e, {**sizes, **_sizes(moved)}, moved, cfg)
    assert got["partition_sizes_wrong"] == 2 and got["rows_wrong"] > 0
    halved = {r: n // 2 for r, n in sizes.items()}
    assert y.compare(e, halved, {}, cfg)["partition_sizes_wrong"] > 0
    missing = dict(sizes)
    del missing[0]
    assert y.compare(e, missing, {}, cfg)["partitions_missing"] == 1


def _plain(name):
    """``name``'s records under a hash partitioner and a plain read."""
    return dict(_config(name), partitioner={"kind": "hash"}, read={})


@pytest.mark.parametrize("name", ["hibench-terasort", "wordcount-zipf"])
def test_plain_reference_is_each_partitions_multiset(name):
    cfg = _plain(name)
    inp = y.make_inputs(cfg, 1, SEED, rows_per_chip=3000)
    want = {}
    for keys, vals in inp.maps:
        for r, k, v in zip(y.partition_of(keys, cfg, inp.num_partitions,
                                          None), keys, vals):
            want.setdefault(int(r), collections.Counter())[
                (int(k), *v.tolist())] += 1
    e = y.reference(inp, cfg)
    assert e.sizes.sum() == inp.rows
    for r, (k, v) in y.split_rows(e).items():
        got = collections.Counter((int(a), *b.tolist())
                                  for a, b in zip(k, v))
        assert got == want.get(r, collections.Counter()), r


def test_plain_order_is_a_lexsort_of_the_whole_row():
    """Zipf keys repeat, splitmix lanes differ: the rows whose key repeats
    are ordered by their lanes, as one lexsort over every column puts
    them."""
    cfg = dict(_plain("wordcount-zipf"),
               value={"kind": "splitmix64", "lanes": 3})
    inp = y.make_inputs(cfg, 1, SEED, rows_per_chip=20_000)
    keys = np.concatenate([k for k, _ in inp.maps])
    rows = y.as_rows(keys, np.concatenate([v for _, v in inp.maps]))
    part = y.partition_of(keys, cfg, inp.num_partitions, None)
    full = np.lexsort((*rows[:, :1:-1].T, keys, part))
    assert np.array_equal(y.plain_order(keys, rows, part), full)
    assert np.array_equal(y.plain_order(keys, rows),
                          np.lexsort((*rows[:, :1:-1].T, keys)))


def test_plain_reference_pinned():
    cfg = _plain("hibench-terasort")
    e = y.reference(y.make_inputs(cfg, 1, SEED, rows_per_chip=5000), cfg)
    assert _digest(e.sizes) == "20951b7cea3b4f9e"
    assert _digest(e.rows) == "8c0ab5885653ba29"


def test_plain_compare_is_order_free():
    """A plain read's partitions in any order compare equal; the same
    rows out of key order fail an ordered read."""
    cfg = _plain("hibench-terasort")
    inp = y.make_inputs(cfg, 1, 14, rows_per_chip=5000)
    e = y.reference(inp, cfg)
    rng = np.random.default_rng(14)
    shuffled = {}
    for r, (k, v) in y.split_rows(e).items():
        p = rng.permutation(k.shape[0])
        shuffled[r] = (k[p], v[p])
    sizes = _sizes(shuffled)
    assert y.compare(e, sizes, shuffled, cfg) == {
        "rows_wrong": 0, "partition_sizes_wrong": 0, "partitions_missing": 0}
    ordered = dict(cfg, read={"ordered": True})
    assert y.compare(e, sizes, shuffled, ordered)["rows_wrong"] > 0


@pytest.mark.parametrize("name", ["hibench-terasort", "wordcount-zipf"])
@pytest.mark.parametrize("seed", [21, 2**31 + 22, 2**33 + 23])
def test_plain_control_fails(name, seed):
    """The plain read's control carries each word in 16 bits: 62-bit keys
    lose their high word, and Zipf words of 2^16 and above come back
    wrong."""
    cfg = _plain(name)
    inp = y.make_inputs(cfg, 1, seed, rows_per_chip=100_000)
    parts = y.split_rows(y.control(inp, cfg))
    got = y.compare(y.reference(inp, cfg), _sizes(parts), parts, cfg)
    assert got["rows_wrong"] > 0, got


def test_sample_is_an_eighth_drawn_from_the_seed():
    a = y.sample_partitions(2**31 + 5, 0, 64)
    assert len(a) == 8 and a <= set(range(64))
    assert a == y.sample_partitions(2**31 + 5, 0, 64)
    assert a != y.sample_partitions(2**31 + 5, 1, 64)
    assert len(y.sample_partitions(1, 0, 4)) == 1


def test_byte_counts_by_hand():
    cfg = _config("hibench-terasort")
    assert y.record_bytes(cfg) == 100
    assert y.record_bytes(_config("wordcount-zipf")) == 12
    assert y.exchange_min_bytes(10, 10, cfg) == 2000
    # 4 partitions over 2 chips: chip 0 owns 0-1, chip 1 owns 2-3
    tcfg = {"partitioner": {"kind": "range"}, "value": {"lanes": 23}}
    b = y.uniform_bounds(4, 62)
    m0 = np.array([0, 3 << 60], np.int64)           # partitions 0, 3
    m1 = np.array([1 << 60, (2 << 60) + 1, 3 << 60], np.int64)  # 1, 2, 3
    inp = y.MapOutputs([(m0, np.zeros((2, 23), np.int32)),
                        (m1, np.zeros((3, 23), np.int32))], 4, b)
    # map 0 on chip 0 sends one record (partition 3); map 1 on chip 1
    # sends one (partition 1)
    assert y.ici_bytes_out(inp, tcfg, 2).tolist() == [100, 100]
