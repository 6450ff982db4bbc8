"""The combine's row counts on the read's program spans.

A combining exchange (each wave of a waved read, or a single-shot read)
sets ``rows_in`` (pairs staged), ``rows_wire`` (rows its map-side
combines sent, summed over chips) and ``rows_out`` (rows after the
receive-side reduce) on its ``shuffle.exchange.wait`` span, read back
with the overflow flag. A waved read's host cross-wave combine sets
``rows_in`` and ``rows_out`` on its ``shuffle.merge`` span. Each count
is checked against numpy over the same staged pairs: map m is staged on
shard m % P, and wave i is rows [i*W, (i+1)*W) of each shard's maps in
order. Plain and ordered reads set none of them.

Where the receive side reduces with the Pallas segment-reduce
(``read.mergeImpl=pallas``, on more than one shard or on the Pallas
transport), the same span also carries ``reduce_tiles``, the kernel's
grid of 128-row tiles over the receive capacity, and
``reduce_tiles_live``, the tiles that hold a received row, each summed
over shards."""

import jax
import numpy as np
import pytest

M, R, N, VOCAB, WAVE = 8, 16, 600, 64, 256
TRACE_ON = {"spark.shuffle.tpu.trace.enabled": "true"}
COUNTS = ("rows_in", "rows_wire", "rows_out")
_SID = [9100]


def _manager(manager_factory, chips, waved, **extra):
    conf = dict(TRACE_ON, **extra)
    if waved:
        conf["spark.shuffle.tpu.a2a.waveRows"] = str(WAVE)
    mgr = manager_factory(conf)
    mgr.node.remesh(devices=jax.devices()[:chips], reason="test mesh")
    assert mgr.node.num_devices == chips
    mgr.node.tracer.clear()
    return mgr


def _maps(seed, vocab=VOCAB):
    """(keys, ones) per map: words from a small vocabulary, so the
    combines merge and every word turns up in every wave."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=N).astype(np.int64) * 7919 + 3,
             np.ones((N, 1), np.int32)) for _ in range(M)]


def _stage(mgr, maps, **kw):
    _SID[0] += 1
    h = mgr.register_shuffle(_SID[0], M, R, **kw)
    for m, (keys, vals) in enumerate(maps):
        w = mgr.get_writer(h, m)
        w.write(keys, vals)
        w.commit(R)
    return h


def _shard_waves(maps, chips, wave):
    """Each exchange's keys a shard stages: map m on shard m % chips,
    wave i rows [i*wave, (i+1)*wave) of each shard's maps in order."""
    shards = [np.concatenate([k for m, (k, _) in enumerate(maps)
                              if m % chips == p]) for p in range(chips)]
    longest = max(s.shape[0] for s in shards)
    wave = wave or longest
    return [[s[lo:lo + wave] for s in shards]
            for lo in range(0, longest, wave)]


def _expected(maps, chips, wave):
    """[(rows_in, rows_wire, rows_out)] of each exchange, by numpy: a
    shard's combine sends one row per distinct word it holds (the
    partition is a function of the word), the receive side keeps one per
    distinct word of the exchange."""
    return [(sum(p.shape[0] for p in parts),
             sum(np.unique(p).shape[0] for p in parts),
             np.unique(np.concatenate(parts)).shape[0])
            for parts in _shard_waves(maps, chips, wave)]


def _rows_received(maps, chips, wave):
    """[[rows each shard received] of each exchange]: every sender's
    distinct words go to the shard that owns the word's partition."""
    from sparkucx_tpu.shuffle.integrity import host_partition_ids
    from sparkucx_tpu.shuffle.reader import _device_bounds
    bounds = _device_bounds(R, chips)
    out = []
    for parts in _shard_waves(maps, chips, wave):
        recv = np.zeros(chips, np.int64)
        for p in parts:
            words = np.unique(p)
            owner = np.searchsorted(
                bounds, host_partition_ids(words, R), side="right") - 1
            recv += np.bincount(owner, minlength=chips)
        out.append(recv)
    return out


def _drain(res):
    return {r: kv for r, kv in res.partitions_ready()}


@pytest.mark.parametrize("waved", [False, True], ids=["single", "waved"])
@pytest.mark.parametrize("chips", [1, 4])
def test_combine_counts_match_numpy(manager_factory, chips, waved):
    mgr = _manager(manager_factory, chips, waved)
    maps = _maps(chips * 10 + waved)
    h = _stage(mgr, maps)
    try:
        got = _drain(mgr.read(h, combine="sum"))
        assert mgr.report(h.shuffle_id).waves == (
            -(-M * N // chips // WAVE) if waved else 0)
        spans = [s for s in mgr.node.tracer.spans("shuffle.exchange.wait")
                 if "rows_wire" in s.attrs]
        counts = [tuple(s.attrs[k] for k in COUNTS) for s in spans]
        assert sorted(counts) == sorted(_expected(
            maps, chips, WAVE if waved else 0))
        distinct = np.unique(np.concatenate([k for k, _ in maps])).shape[0]
        assert sum(k.shape[0] for k, _ in got.values()) == distinct
        merges = [s.attrs for s in mgr.node.tracer.spans("shuffle.merge")
                  if s.attrs.get("impl") == "host"]
        if not waved:
            assert not merges
            return
        # every word is in every wave, so every partition merges: the
        # host combine's rows out are the reference's, its rows in the
        # waves' rows out
        assert sum(a["rows_out"] for a in merges) == distinct
        assert sum(a["rows_in"] for a in merges) == sum(c[2]
                                                         for c in counts)
    finally:
        mgr.unregister_shuffle(h.shuffle_id)
        mgr.node.tracer.clear()


def _range_bounds():
    return tuple(int(x) for x in np.linspace(0, VOCAB * 7919, R + 1)[1:-1])


@pytest.mark.parametrize("waved", [False, True], ids=["single", "waved"])
@pytest.mark.parametrize("mode", ["plain", "ordered"])
def test_plain_and_ordered_reads_set_no_counts(manager_factory, mode,
                                               waved):
    mgr = _manager(manager_factory, 4, waved)
    kw = {"partitioner": "range", "bounds": _range_bounds()} \
        if mode == "ordered" else {}
    h = _stage(mgr, _maps(5), **kw)
    try:
        _drain(mgr.read(h, ordered=(mode == "ordered")))
        spans = mgr.node.tracer.spans("shuffle.exchange.wait") \
            + mgr.node.tracer.spans("shuffle.merge")
        assert mgr.node.tracer.spans("shuffle.exchange.wait")
        assert not [s for s in spans if set(COUNTS) & set(s.attrs)]
    finally:
        mgr.unregister_shuffle(h.shuffle_id)
        mgr.node.tracer.clear()


def _kernel_rows(transport, chips, cap_out):
    """Rows of a shard's receive buffer the segment-reduce scans, 0 where
    it does not run: one shard's exchange on the dense transport has no
    receive-side reduce; the Pallas transport's buffer is ``cap_out``
    aligned to its DMA chunk, plus a chunk a sender."""
    if transport == "dense":
        return cap_out if chips > 1 else 0
    from sparkucx_tpu.ops.pallas.ragged_a2a import align_rows, chunk_rows_for
    chunk = chunk_rows_for(3)
    return align_rows(cap_out, chunk) + chips * chunk


@pytest.mark.parametrize("transport,chips,waved", [
    ("dense", 1, False), ("dense", 1, True), ("dense", 4, False),
    ("dense", 4, True), ("pallas", 1, False), ("pallas", 4, False)])
def test_reduce_tile_counts_match_numpy(manager_factory, transport, chips,
                                        waved):
    """The segment-reduce's grid and live tiles on each exchange's span:
    ``chips * ceil(rows it scans / 128)`` and the sum over shards of
    ``ceil(rows received / 128)``. Where no such kernel runs, the spans
    carry neither."""
    mgr = _manager(manager_factory, chips, waved,
                   **{"spark.shuffle.tpu.read.mergeImpl": "pallas",
                      "spark.shuffle.tpu.a2a.impl": transport})
    # a wide vocabulary, so a shard receives more than one tile of rows
    maps = _maps(chips * 10 + waved + 1, vocab=4096)
    h = _stage(mgr, maps)
    try:
        got = _drain(mgr.read(h, combine="sum"))
        distinct = np.unique(np.concatenate([k for k, _ in maps])).shape[0]
        assert sum(k.shape[0] for k, _ in got.values()) == distinct
        spans = [s.attrs for s in mgr.node.tracer.spans(
            "shuffle.exchange.wait") if "rows_wire" in s.attrs]
        assert spans
        scanned = _kernel_rows(transport, chips,
                               mgr.report(h.shuffle_id).plan_bucket[1])
        if not scanned:
            assert not [a for a in spans if "reduce_tiles" in a
                        or "reduce_tiles_live" in a]
            return
        recv = _rows_received(maps, chips, WAVE if waved else 0)
        assert sorted((a["rows_wire"], a["reduce_tiles_live"])
                      for a in spans) == sorted(
            (int(r.sum()), int((-(-r // 128)).sum())) for r in recv)
        assert {a["reduce_tiles"] for a in spans} == {
            chips * -(-scanned // 128)}
        assert all(a["reduce_tiles_live"] < a["reduce_tiles"]
                   for a in spans)
    finally:
        mgr.unregister_shuffle(h.shuffle_id)
        mgr.node.tracer.clear()
