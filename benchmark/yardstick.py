"""The benchmark's yardstick: input generators, the plain reference, the
comparison that decides ``correct``, and the byte counts of the work.

Everything here is numpy and imports nothing of ``sparkucx_tpu``: a
change to the package cannot move what the benchmark generates or how it
judges the result. The generators (splitmix64 key and payload streams,
the Zipf word stream) live here alone: the chip smoke
(``chip_smoke.py``) imports them, and ``tests/test_yardstick.py`` pins
their output.

A configuration file (``configs/<name>.json``) describes the records
(key distribution, value lanes), the partitioner and the read mode; the
functions below take that dict, so a new deployment of the same record
family is data only. Three read modes are known: plain (``"read": {}``,
every record delivered once to its partition, in no promised order),
ordered (``{"ordered": true}``, each partition sorted by key) and
combine sum (``{"combine": "sum"}``, one row a key with its lanes'
sums).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

_U64 = np.uint64
KEY_BYTES = 8
PAYLOAD_STREAM = 1 << 48            # payload index stream, disjoint from keys


# -- generators --------------------------------------------------------------
def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over a uint64 array (returns a new array)."""
    x = x.astype(_U64, copy=True)
    x += _U64(0x9E3779B97F4A7C15)
    x ^= x >> _U64(30)
    x *= _U64(0xBF58476D1CE4E5B9)
    x ^= x >> _U64(27)
    x *= _U64(0x94D049BB133111EB)
    x ^= x >> _U64(31)
    return x


def keystream(seed: int, start: int, n: int) -> np.ndarray:
    """62-bit uniform int64 keys of global rows [start, start+n):
    splitmix64 of the row index stream offset by the seed."""
    offset = (seed * 0x9E3779B97F4A7C15) % (1 << 64)
    idx = np.arange(start, start + n, dtype=_U64) + _U64(offset)
    return (mix64(idx) >> _U64(2)).astype(np.int64)


def payload_lanes(seed: int, start: int, n: int, lanes: int) -> np.ndarray:
    """[n, lanes] int32 payload of rows [start, start+n): splitmix64 of a
    second index stream, 12 words (24 lanes) per row, the first ``lanes``
    kept."""
    words = keystream(seed, PAYLOAD_STREAM + 12 * start, 12 * n)
    return words.view(np.int32).reshape(n, 24)[:, :lanes].copy()


def _pool() -> ThreadPoolExecutor:
    """Threads for making inputs in set-up (numpy releases the GIL)."""
    return ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1))


def zipf_words(seed: int, n: int, vocabulary: int, s: float) -> np.ndarray:
    """n int64 word ids drawn from Zipf(s) over [0, vocabulary)."""
    rank = np.arange(1, vocabulary + 1, dtype=np.float64)
    cdf = np.cumsum(rank ** -s)
    cdf /= cdf[-1]
    u = np.random.default_rng(seed).random(n)
    out = np.empty(n, dtype=np.int64)

    def fill(ab):
        a, b = ab
        out[a:b] = np.minimum(np.searchsorted(cdf, u[a:b]), vocabulary - 1)
    with _pool() as pool:
        list(pool.map(fill, slices(n, 32)))
    return out


def slices(total: int, parts: int) -> List[Tuple[int, int]]:
    edges = [total * i // parts for i in range(parts + 1)]
    return list(zip(edges[:-1], edges[1:]))


@dataclass
class MapOutputs:
    """One cell's map outputs: ``maps[m] = (keys [n] int64, values [n, L]
    int32)``, plus the range bounds when the partitioner takes them."""

    maps: List[Tuple[np.ndarray, np.ndarray]]
    num_partitions: int
    bounds: Optional[np.ndarray]

    @property
    def rows(self) -> int:
        return sum(k.shape[0] for k, _ in self.maps)


def make_inputs(config: Dict, chips: int, seed: int,
                rows_per_chip: Optional[int] = None) -> MapOutputs:
    """The map outputs of one shuffle of ``config`` over ``chips`` chips
    from ``seed``. ``rows_per_chip`` overrides the configured scale (the
    tests' small sizes)."""
    rows = (rows_per_chip or config["records_per_chip"]) * chips
    nparts = config["partitions_per_chip"] * chips
    key, val = config["key"], config["value"]
    if key["dist"] == "zipf":
        all_keys = zipf_words(seed, rows, key["vocabulary"], key["s"])

    def one_map(ab):
        a, b = ab
        if key["dist"] == "splitmix64":
            keys = keystream(seed, a, b - a)
        elif key["dist"] == "zipf":
            keys = all_keys[a:b]
        else:
            raise ValueError(f"unknown key distribution {key['dist']!r}")
        if val["kind"] == "splitmix64":
            vals = payload_lanes(seed, a, b - a, val["lanes"])
        elif val["kind"] == "ones":
            vals = np.ones((b - a, val["lanes"]), np.int32)
        else:
            raise ValueError(f"unknown value kind {val['kind']!r}")
        return keys, vals
    with _pool() as pool:
        maps = list(pool.map(one_map, slices(rows, config["num_maps"])))
    bounds = None
    part = config["partitioner"]
    if part["kind"] == "range":
        bounds = uniform_bounds(nparts, key["bits"])
    return MapOutputs(maps, nparts, bounds)


def uniform_bounds(num_partitions: int, key_bits: int) -> np.ndarray:
    """[R-1] split points at the exact quantiles of keys uniform over
    [0, 2**key_bits): what a sampled range partitioner converges to, and
    the same for every seed."""
    span = 1 << key_bits
    return np.array([span * i // num_partitions
                     for i in range(1, num_partitions)], dtype=np.int64)


# -- the plain reference -----------------------------------------------------
def fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer."""
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def partition_of(keys: np.ndarray, config: Dict, num_partitions: int,
                 bounds: Optional[np.ndarray]) -> np.ndarray:
    """Reduce partition of each key. range: partition r holds keys in
    [bounds[r-1], bounds[r]); hash: murmur3 finalizer of the key's low
    32 bits, modulo the partition count."""
    kind = config["partitioner"]["kind"]
    if kind == "range":
        return np.searchsorted(bounds, keys, side="right").astype(np.int64)
    if kind == "hash":
        low = (keys & 0xFFFFFFFF).astype(np.uint32)
        return (fmix32(low) % np.uint32(num_partitions)).astype(np.int64)
    raise ValueError(f"unknown partitioner {kind!r}")


def _canonical_ties(keys: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Rows with equal keys in a fixed order (by their bytes): the order of
    equal keys is unspecified by the ordered and combine contracts."""
    dup = np.flatnonzero(np.diff(keys) == 0)
    if dup.size == 0:
        return rows
    rows = rows.copy()
    starts = dup[np.r_[True, np.diff(dup) > 1]]
    for s in starts:
        e = s + 1
        while e < keys.shape[0] and keys[e] == keys[s]:
            e += 1
        block = rows[s:e]
        order = np.lexsort(block.T[::-1])
        rows[s:e] = block[order]
    return rows


def plain_order(keys: np.ndarray, rows: np.ndarray,
                part: Optional[np.ndarray] = None) -> np.ndarray:
    """Indices that put ``rows`` (``as_rows``) in the plain read's one
    canonical order: ``np.lexsort`` over the whole row, by partition
    first where ``part`` is given, then by the key (its two words as one
    int64), then by the value lanes, the first lane first.

    A partition is a function of the key, so only rows whose key repeats
    need the lanes: the whole row is sorted on for those rows alone, in
    one call, and written back over the places their keys hold."""
    primary = (keys,) if part is None else (keys, part)
    order = np.lexsort(primary)
    k = keys[order]
    tie = np.zeros(k.shape[0], dtype=bool)
    same = k[1:] == k[:-1]
    tie[1:] |= same
    tie[:-1] |= same
    if tie.any():
        sub = order[tie]
        order[tie] = sub[np.lexsort((*rows[sub, :1:-1].T,
                                     *(c[sub] for c in primary)))]
    return order


@dataclass
class Expected:
    """What a correct read of the inputs delivers: per partition row
    counts and, partition after partition, the rows as int32 lanes
    (key lo, key hi, value lanes)."""

    sizes: np.ndarray
    rows: np.ndarray


def split_rows(expected: Expected) -> Dict[int, Tuple[np.ndarray,
                                                     np.ndarray]]:
    """``{r: (keys, values)}``: the rows of each partition, as a read
    delivers them."""
    out, off = {}, 0
    for r, n in enumerate(expected.sizes):
        blk = expected.rows[off:off + n]
        off += n
        out[r] = (np.ascontiguousarray(blk[:, :2]).view(np.int64).ravel(),
                  blk[:, 2:])
    return out


def as_rows(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """[n, 2 + L] int32: key as two words, then the value lanes."""
    kw = np.ascontiguousarray(keys, dtype=np.int64).view(np.int32)
    return np.concatenate([kw.reshape(-1, 2),
                           np.asarray(values, np.int32).reshape(
                               keys.shape[0], -1)], axis=1)


def reference(inputs: MapOutputs, config: Dict,
              sum_dtype=np.int64, key_words: int = 2) -> Expected:
    """The plain reference of the configured read over ``inputs``.

    plain: each partition's rows, every record once, in ``plain_order``.
    ordered: each partition's rows sorted by key. combine sum: one row per
    distinct key of the partition, key-sorted, its lanes the sums
    (accumulated in ``sum_dtype``, stored in int32). ``key_words`` = 1
    orders by the key's high word alone: the control's 32-bit key."""
    keys = np.concatenate([k for k, _ in inputs.maps])
    vals = np.concatenate([v for _, v in inputs.maps])
    read = config["read"]
    if read.get("combine") == "sum":
        # the partition is a function of the key: group by key alone
        ukeys, seg = np.unique(keys, return_inverse=True)
        sums = np.stack([np.bincount(seg, weights=vals[:, lane],
                                     minlength=ukeys.shape[0])
                         for lane in range(vals.shape[1])], axis=1)
        # exact in float64 below 2**53; the cast wraps like an
        # accumulator of sum_dtype would
        sums = sums.astype(np.int64).astype(sum_dtype).astype(np.int32)
        upart = partition_of(ukeys, config, inputs.num_partitions,
                             inputs.bounds)
        order = np.lexsort((ukeys, upart))
        sizes = np.bincount(upart, minlength=inputs.num_partitions)
        return Expected(sizes, as_rows(ukeys[order], sums[order]))
    if not read.get("ordered") and not read.get("combine"):
        part = partition_of(keys, config, inputs.num_partitions,
                            inputs.bounds)
        rows = as_rows(keys, vals)
        return Expected(np.bincount(part, minlength=inputs.num_partitions),
                        rows[plain_order(keys, rows, part)])
    if not read.get("ordered"):
        raise ValueError(f"read mode {read!r} has no reference")
    part = partition_of(keys, config, inputs.num_partitions, inputs.bounds)
    sort_key = keys if key_words == 2 else (keys >> 32)
    order = np.lexsort((sort_key, part))
    keys, part = keys[order], part[order]
    out = as_rows(keys, vals[order])
    if key_words == 2:
        out = _canonical_ties(keys, out)
    sizes = np.bincount(part, minlength=inputs.num_partitions)
    return Expected(sizes, out)


def control(inputs: MapOutputs, config: Dict) -> Expected:
    """The reference one precision step down, in the program's place:
    ordered reads sort on a 32-bit key (the high word), combined reads
    accumulate in int16, plain reads carry each record's words in 16 bits
    (the key's high word dropped, its low word and every value lane cut
    to 16 bits). Each breaks a guarantee the configuration states (global
    key order; exact per-key sums; every record delivered intact)."""
    read = config["read"]
    if read.get("combine") == "sum":
        return reference(inputs, config, sum_dtype=np.int16)
    if read.get("ordered"):
        return reference(inputs, config, key_words=1)
    expected = reference(inputs, config)
    rows = expected.rows.copy()
    rows[:, 0] &= 0xFFFF
    rows[:, 1] = 0
    rows[:, 2:] = rows[:, 2:].astype(np.int16)
    return Expected(expected.sizes, rows)


# -- the comparison ----------------------------------------------------------
# every number compared has the limit 0: the comparison is exact
LIMITS = {"rows_wrong": 0, "partition_sizes_wrong": 0,
          "partitions_missing": 0}
SAMPLE_SHARE = 8        # one partition in this many has its rows compared


def sample_partitions(seed: int, shuffle: int, num_partitions: int):
    """The partitions of the ``shuffle``-th shuffle of a run whose rows are
    compared: an eighth of them, drawn from the seed."""
    rng = np.random.default_rng([seed % (1 << 64), shuffle])
    n = max(1, num_partitions // SAMPLE_SHARE)
    return set(rng.choice(num_partitions, size=n, replace=False).tolist())


def compare(expected: Expected, sizes: Dict[int, int],
            parts: Dict[int, Tuple[np.ndarray, np.ndarray]],
            config: Dict) -> Dict[str, int]:
    """Numbers that a correct read reads 0 on.

    ``sizes[r]`` is the row count the read delivered for partition r,
    ``parts[r] = (keys, values)`` its rows, for the partitions held.
    partitions_missing counts partitions never delivered;
    partition_sizes_wrong those whose row count differs from the
    reference's; rows_wrong the held rows that differ from the reference's
    row at the same place in the partition, plus rows missing or extra
    there. A plain read promises no order within a partition: its rows
    are put in ``plain_order`` first, so they match where the partition
    holds the same records."""
    R = expected.sizes.shape[0]
    missing = sum(1 for r in range(R) if r not in sizes)
    sizes_wrong = sum(1 for r in range(R)
                      if sizes.get(r, 0) != expected.sizes[r])
    offsets = np.concatenate([[0], np.cumsum(expected.sizes)])
    ordered = bool(config["read"].get("ordered"))
    plain = not ordered and not config["read"].get("combine")
    width = expected.rows.shape[1]
    wrong = 0
    for r, (keys, values) in parts.items():
        want = expected.rows[offsets[r]:offsets[r + 1]]
        got = as_rows(keys, values) if keys.shape[0] else \
            np.zeros((0, width), np.int32)
        if got.shape[1] != width:
            wrong += max(got.shape[0], want.shape[0])
            continue
        if ordered:
            got = _canonical_ties(keys, got)
        elif plain:
            got = got[plain_order(keys, got)]
        n = min(got.shape[0], want.shape[0])
        wrong += int(np.any(got[:n] != want[:n], axis=1).sum())
        wrong += abs(got.shape[0] - want.shape[0])
    return {"rows_wrong": wrong, "partition_sizes_wrong": sizes_wrong,
            "partitions_missing": missing}


# -- the work's byte counts --------------------------------------------------
def record_bytes(config: Dict) -> int:
    """Bytes of one record as the user hands it over: key plus lanes."""
    return KEY_BYTES + 4 * config["value"]["lanes"]


def exchange_min_bytes(rows_in: int, rows_out: int, config: Dict) -> int:
    """Least HBM traffic of one exchange: every staged record read once
    and every delivered record written once."""
    return (rows_in + rows_out) * record_bytes(config)


def ici_bytes_out(inputs: MapOutputs, config: Dict, chips: int) -> np.ndarray:
    """[chips] bytes each chip must send to other chips in one exchange.

    Map m is staged on chip ``m % chips`` (map tasks spread round-robin
    over executors); reduce partitions are owned by chips in contiguous
    blocks. A record leaves its chip when its partition is owned by
    another one."""
    R = inputs.num_partitions
    owner = np.repeat(np.arange(chips), [R // chips + (1 if c < R % chips
                                                      else 0)
                                         for c in range(chips)])
    out = np.zeros(chips, dtype=np.int64)
    for m, (keys, _) in enumerate(inputs.maps):
        src = m % chips
        dest = owner[partition_of(keys, config, R, inputs.bounds)]
        out[src] += int((dest != src).sum())
    return out * record_bytes(config)
