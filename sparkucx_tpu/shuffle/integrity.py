"""Block integrity — checksums for staged bytes, wire-crossed rows, and
spill/ledger files.

The reference trusts RDMA + the filesystem end to end: the only checksum
in its whole data path is nothing at all (our reproduction's one was the
CRC32 on the 300 B metadata record, meta/segments.py pack_record). This
module makes corruption a TYPED, SURVIVABLE fault instead of silent
wrong answers — the Exoshuffle thesis that durability/corruption policy
is an application-level contract once shuffle is a library:

* at ``commit()`` the writer computes an :class:`IntegrityRecord` over
  its staged key/value bytes (spill-file ranges included — the record is
  computed from the same mmap views the read path consumes) and
  publishes it in the registry beside the size row;
* ``integrity.verify=staged`` re-verifies those bytes at pack time,
  before they enter the exchange;
* ``integrity.verify=full`` additionally verifies the host-drained
  result after the collective, per reduce partition, against
  order-independent digests (the rows cross the wire destination-sorted
  and interleaved, so a positional checksum cannot survive the
  transport; a per-row digest SUM can, and decomposes by partition
  exactly like the size rows do).

Three checksum tiers, by path temperature:

=============  =======================  ==============================
checksum       used on                  why this one
=============  =======================  ==============================
crc32 (zlib)   disk: spill files, the   the standard, tool-friendly
               commit manifest, the     file checksum; restart
               restart ledger scan      validation is a cold path
fold64         hot pack-time verify     xor-fold of the uint64 lanes
               (staged level)           runs at memory bandwidth
                                        (~8 GB/s here vs crc32's
                                        ~1 GB/s), detects any single
                                        bit flip, and the <3% verify
                                        overhead gate needs it
row digests    full-level post-         splitmix64 per row, summed per
(mix64 sum)    collective verify        reduce partition — invariant
                                        under the destination sort and
                                        the wave split, so the receive
                                        side can check what it drained
                                        against what every sender
                                        published
=============  =======================  ==============================

The int8 wire tier dequantizes value lanes (legitimately lossy), so its
full-level check uses the KEY-only digest rows — the exact
key/partition/size lanes are still end-to-end verified; raw and
lossless wires verify the full rows bit-for-bit-equivalent.

Everything here is host-side numpy: no compiled-program signature grows
a verification argument, so ``compile.step.programs`` is identical at
every verify level (the one-program invariant).
"""

from __future__ import annotations

import os
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from sparkucx_tpu.utils.logging import get_logger

log = get_logger("shuffle.integrity")

VERIFY_LEVELS = ("off", "staged", "full")


def validate_verify_level(v: str, conf_key: str = "integrity.verify") -> str:
    if v not in VERIFY_LEVELS:
        raise ValueError(
            f"{conf_key}={v!r}: want one of {'|'.join(VERIFY_LEVELS)}")
    return v


# -- primitives ------------------------------------------------------------
_U64 = np.uint64
_FOLD_LEN_SALT = _U64(0x9E3779B97F4A7C15)


def _as_bytes_view(arr: np.ndarray) -> np.ndarray:
    """Flat uint8 view of an array's bytes (copies only when the input
    is non-contiguous — staged batches, spill views and packed rows are
    all contiguous by construction)."""
    return np.ascontiguousarray(arr).reshape(-1).view(np.uint8)


def fold64(arr: Optional[np.ndarray]) -> int:
    """Memory-bandwidth checksum: xor-fold of the uint64 lanes plus a
    length binding. Any single flipped bit flips the fold; the hot
    pack-time verify compares THIS (crc32 at ~1 GB/s would eat the
    whole <3% overhead budget by itself at pack-bound shapes)."""
    if arr is None:
        return 0
    b = _as_bytes_view(arr)
    n8 = (b.nbytes // 8) * 8
    acc = _U64(0)
    if n8:
        acc ^= np.bitwise_xor.reduce(b[:n8].view(_U64))
    if b.nbytes > n8:
        tail = np.zeros(8, np.uint8)
        tail[: b.nbytes - n8] = b[n8:]
        acc ^= tail.view(_U64)[0]
    # length binding in python ints: numpy SCALAR ops warn on wrap
    # (array ops wrap silently — the digest math relies on that)
    return int(acc) ^ ((b.nbytes * 0x9E3779B97F4A7C15)
                       & 0xFFFFFFFFFFFFFFFF)


def crc32_of(arr: Optional[np.ndarray]) -> int:
    """zlib crc32 over an array's bytes — the DISK checksum (manifest
    rows, restart-scan validation). Cold paths only."""
    if arr is None:
        return 0
    return zlib.crc32(_as_bytes_view(arr)) & 0xFFFFFFFF


def crc32_file(path: str, chunk: int = 1 << 22) -> int:
    """Streaming crc32 of a file (restart ledger scan)."""
    acc = 0
    with open(path, "rb") as f:
        while True:
            b = f.read(chunk)
            if not b:
                break
            acc = zlib.crc32(b, acc)
    return acc & 0xFFFFFFFF


def _mix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer (in-place temps — this runs over
    every staged byte at the full verify level)."""
    x = x.astype(_U64, copy=True)
    x += _U64(0x9E3779B97F4A7C15)
    x ^= x >> _U64(30)
    x *= _U64(0xBF58476D1CE4E5B9)
    x ^= x >> _U64(27)
    x *= _U64(0x94D049BB133111EB)
    x ^= x >> _U64(31)
    return x


def row_digests(keys: np.ndarray,
                values: Optional[np.ndarray]) -> np.ndarray:
    """[N] uint64 per-row digests of (key, value-row bytes). Row
    identity only — deliberately order-free so the sum over any subset
    of rows is invariant under the destination sort, the wave split and
    the run concatenation the transport performs."""
    n = keys.shape[0]
    h = _mix64(np.ascontiguousarray(keys, dtype=np.int64).view(_U64))
    if values is not None and n:
        v = np.ascontiguousarray(values)
        row_bytes = v.dtype.itemsize * int(
            np.prod(v.shape[1:], dtype=np.int64) or 1)
        raw = v.view(np.uint8).reshape(n, row_bytes)
        pad = (-row_bytes) % 8
        if pad:
            raw = np.concatenate(
                [raw, np.zeros((n, pad), np.uint8)], axis=1)
        words = raw.view(_U64)                      # [N, K]
        salts = _FOLD_LEN_SALT * (
            np.arange(1, words.shape[1] + 1, dtype=_U64))
        # per-column salt binds word POSITION within the row, then the
        # mixed words sum (mod 2^64) into one lane per row
        h = h + _mix64(words ^ salts[None, :]).sum(axis=1, dtype=_U64)
    return h


def partition_digests(keys: np.ndarray, values: Optional[np.ndarray],
                      parts: np.ndarray, num_partitions: int,
                      key_only_too: bool = True
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """(full_digests[R], key_digests[R]) — per-reduce-partition sums of
    the row digests. ``key_digests`` covers the key lane alone: the
    int8 wire tier dequantizes values, so its receive-side check runs
    on the exact lanes only."""
    full = np.zeros(num_partitions, dtype=_U64)
    keyd = np.zeros(num_partitions, dtype=_U64)
    if keys.shape[0]:
        p = np.ascontiguousarray(parts, dtype=np.int64)
        np.add.at(full, p, row_digests(keys, values))
        if key_only_too:
            if values is None:
                keyd[:] = full
            else:
                np.add.at(keyd, p, row_digests(keys, None))
    return full, keyd


def digest_sum(keys: np.ndarray, values: Optional[np.ndarray]) -> int:
    """Sum (mod 2^64) of one row set's digests — the receive side's
    per-partition figure."""
    if keys.shape[0] == 0:
        return 0
    return int(row_digests(keys, values).sum(dtype=_U64))


# -- the published record --------------------------------------------------
@dataclass
class IntegrityRecord:
    """What one committed map output publishes beside its size row.

    ``keys_fold``/``vals_fold`` feed the hot staged verify;
    ``keys_crc``/``vals_crc`` are the disk checksums the manifest and
    the restart scan validate; the digest rows (present only when the
    writer ran at ``integrity.verify=full``) feed the post-collective
    receive-side check."""

    rows: int
    keys_bytes: int
    vals_bytes: int
    keys_fold: int
    vals_fold: int
    keys_crc: int
    vals_crc: int
    digests: Optional[List[int]] = None       # [R] uint64 full-row sums
    key_digests: Optional[List[int]] = None   # [R] key-lane sums
    # value schema snapshot so a manifest row alone can rebuild the view
    val_dtype: Optional[str] = None
    val_tail: Optional[Tuple[int, ...]] = None

    def to_dict(self) -> Dict:
        d = {"rows": self.rows, "keys_bytes": self.keys_bytes,
             "vals_bytes": self.vals_bytes, "keys_fold": self.keys_fold,
             "vals_fold": self.vals_fold, "keys_crc": self.keys_crc,
             "vals_crc": self.vals_crc, "val_dtype": self.val_dtype,
             "val_tail": list(self.val_tail)
             if self.val_tail is not None else None}
        if self.digests is not None:
            d["digests"] = [int(x) for x in self.digests]
            d["key_digests"] = [int(x) for x in (self.key_digests or [])]
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "IntegrityRecord":
        return cls(
            rows=int(d["rows"]), keys_bytes=int(d["keys_bytes"]),
            vals_bytes=int(d["vals_bytes"]),
            keys_fold=int(d["keys_fold"]), vals_fold=int(d["vals_fold"]),
            keys_crc=int(d["keys_crc"]), vals_crc=int(d["vals_crc"]),
            digests=[int(x) for x in d["digests"]]
            if d.get("digests") is not None else None,
            key_digests=[int(x) for x in d["key_digests"]]
            if d.get("key_digests") is not None else None,
            val_dtype=d.get("val_dtype"),
            val_tail=tuple(d["val_tail"])
            if d.get("val_tail") is not None else None)


def compute_record(keys: Optional[np.ndarray],
                   values: Optional[np.ndarray],
                   parts: Optional[np.ndarray], num_partitions: int,
                   with_digests: bool,
                   with_crc: bool = False) -> IntegrityRecord:
    """Build the commit-time record. ``parts`` is the per-row partition
    vector the commit already derived for the size row (None for empty
    outputs). ``with_crc`` adds the zlib crc32 disk checksums — only the
    durable ledger consumes them (manifest rows + restart scan), so a
    ledger-less commit skips the ~1 GB/s pass and publishes the fold64
    pair alone (the hot verify never reads the CRCs)."""
    if keys is None or keys.shape[0] == 0:
        rec = IntegrityRecord(0, 0, 0, 0, 0, 0, 0)
        if with_digests:
            rec.digests = [0] * num_partitions
            rec.key_digests = [0] * num_partitions
        return rec
    rec = IntegrityRecord(
        rows=int(keys.shape[0]),
        keys_bytes=int(keys.nbytes),
        vals_bytes=int(values.nbytes) if values is not None else 0,
        keys_fold=fold64(keys), vals_fold=fold64(values),
        keys_crc=crc32_of(keys) if with_crc else 0,
        vals_crc=crc32_of(values) if with_crc else 0,
        val_dtype=np.dtype(values.dtype).str if values is not None
        else None,
        val_tail=tuple(int(x) for x in values.shape[1:])
        if values is not None else None)
    if with_digests:
        full, keyd = partition_digests(keys, values, parts,
                                       num_partitions)
        rec.digests = [int(x) for x in full]
        rec.key_digests = [int(x) for x in keyd]
    return rec


def verify_staged(keys: np.ndarray, values: Optional[np.ndarray],
                  rec: IntegrityRecord) -> int:
    """Pack-time staged verify: the fold over the bytes about to enter
    the exchange must match what commit published. Returns verified
    bytes; raises :class:`~sparkucx_tpu.runtime.failures
    .BlockCorruptionError` via the caller's wrapper on mismatch (this
    helper returns the mismatch description instead of raising so the
    caller can name the block)."""
    problems = []
    if int(keys.nbytes) != rec.keys_bytes:
        problems.append(f"keys {keys.nbytes} B != committed "
                        f"{rec.keys_bytes} B")
    elif fold64(keys) != rec.keys_fold:
        problems.append("keys bytes changed since commit (fold mismatch)")
    vb = int(values.nbytes) if values is not None else 0
    if vb != rec.vals_bytes:
        problems.append(f"values {vb} B != committed {rec.vals_bytes} B")
    elif values is not None and fold64(values) != rec.vals_fold:
        problems.append("value bytes changed since commit (fold mismatch)")
    if problems:
        raise _StagedMismatch("; ".join(problems))
    return int(keys.nbytes) + vb


class _StagedMismatch(Exception):
    """Internal: verify_staged's mismatch signal — the manager wraps it
    into BlockCorruptionError with the shuffle/map/block names."""


def aggregate_digests(entry, num_maps: int, key_only: bool
                      ) -> Optional[np.ndarray]:
    """[R] uint64 expected per-partition digest sums over every map
    output of ``entry``, or None when any record lacks digest rows
    (committed below the full level — the read degrades to staged with
    a warning, never a false alarm)."""
    acc = None
    for m in range(num_maps):
        rec = entry.fetch_integrity(m)
        rows = rec.key_digests if (rec is not None and key_only) \
            else (rec.digests if rec is not None else None)
        if rows is None:
            return None
        v = np.asarray(rows, dtype=_U64)
        acc = v.copy() if acc is None else acc + v
    return acc


# -- fault injection (the `corrupt` site) ----------------------------------
def host_partition_ids(keys: np.ndarray, num_partitions: int,
                       partitioner: str = "hash",
                       bounds=None) -> np.ndarray:
    """Host twin of the device partitioners (ops/partition.py) over
    int64 keys — bit-for-bit the routing the compiled step ran, so a
    post-collective check can re-derive where every received key MUST
    have been sent. hash: the 32-bit mixing hash over the low key word
    (exactly what hash_partition consumes); direct: the clipped key;
    range: searchsorted over the static split points (side='right' =
    #(b <= key), matching range_partition_words)."""
    keys = np.asarray(keys, dtype=np.int64)
    if partitioner == "direct":
        # the device clips the LOW int32 word (reader._make_part_fn
        # reads rows[:, 0]), not the full int64 — mirror it exactly or
        # a >int32 key verifies against a partition the step never
        # computed
        lo = (keys & np.int64(0xFFFFFFFF)).astype(np.uint32) \
            .view(np.int32)
        return np.clip(lo.astype(np.int64), 0, num_partitions - 1)
    if partitioner == "range":
        b = np.asarray(bounds, dtype=np.int64)
        return np.searchsorted(b, keys, side="right").astype(np.int64)
    from sparkucx_tpu.shuffle.writer import _hash32_np
    return (_hash32_np(keys)
            % np.uint32(num_partitions)).astype(np.int64)


def verify_key_routing(rows: np.ndarray, totals: np.ndarray,
                       num_partitions: int, num_shards: int,
                       partitioner: str = "hash", bounds=None) -> int:
    """Post-collective key-lane check over a DEVICE receive buffer's
    host-side copy (the ``integrity.verify=full`` posture for device-
    sink reads): every valid row on shard p must carry a key whose
    partition — re-derived through the exact host twin of the device
    routing — lies in the partition range the blocked map assigns p.
    Key lanes are exact on EVERY wire tier (the int8 wire narrows value
    lanes only), so this holds bit-for-bit even where the per-row
    digests cannot (combine legitimately rewrites rows; dequantized
    values are legitimately lossy).

    ``rows`` — [P*cap, width] int32 transport rows; ``totals`` — [P]
    valid-row counts per shard. Returns verified KEY bytes; raises
    :class:`_StagedMismatch` naming the shard and the stray partition
    on any violation (the manager wraps it typed)."""
    from sparkucx_tpu.ops.partition import blocked_partition_map
    rows = np.asarray(rows)
    totals = np.asarray(totals, dtype=np.int64).reshape(-1)
    cap = rows.shape[0] // max(num_shards, 1)
    p2d = np.asarray(blocked_partition_map(num_partitions, num_shards))
    verified = 0
    for s in range(num_shards):
        n = int(totals[s])
        if n <= 0:
            continue
        blk = rows[s * cap:s * cap + min(n, cap)]
        keys = np.ascontiguousarray(blk[:, :2]).view(np.int64).ravel()
        part = host_partition_ids(keys, num_partitions, partitioner,
                                  bounds)
        owner = p2d[np.clip(part, 0, num_partitions - 1)]
        bad = np.nonzero((owner != s)
                         | (part < 0) | (part >= num_partitions))[0]
        if bad.size:
            i = int(bad[0])
            raise _StagedMismatch(
                f"shard {s} row {i}: key {int(keys[i])} routes to "
                f"partition {int(part[i])} (owner shard "
                f"{int(owner[i]) if 0 <= part[i] < num_partitions else '?'}) "
                f"— delivered to the wrong shard, or key lanes "
                f"corrupted in flight")
        verified += int(keys.nbytes)
    return verified


class _FlipToken:
    """One injected bit flip + how to undo it. The corrupt site models
    TRANSIENT corruption — a flipped bit observed in flight: the flip
    exists exactly for the duration of the verification read, so
    detection always fires while a replay (re-verify, re-pack) finds
    the bytes intact and recovers to oracle-exact output. Persistent
    corruption (a genuinely rotten file) keeps failing verification
    until the replay budget exhausts and the typed error surfaces —
    both behaviors are exercised by the chaos matrix."""

    def __init__(self, restore):
        self._restore = restore
        self.done = False

    def restore(self) -> None:
        if not self.done:
            self.done = True
            self._restore()


def flip_array_byte(arr: np.ndarray, offset: int) -> _FlipToken:
    """XOR one bit into a writable staged array."""
    b = arr.reshape(-1).view(np.uint8)
    off = int(offset) % b.nbytes
    b[off] ^= 0x01

    def _undo():
        b[off] ^= 0x01
    return _FlipToken(_undo)


def flip_file_byte(path: str, offset: int) -> _FlipToken:
    """XOR one bit into a spill/ledger file on disk. Read-only mmaps of
    the file (MAP_SHARED) observe the flip through the page cache, so
    the staged verify over the mmap views detects it without re-opening
    anything."""
    size = os.path.getsize(path)
    off = int(offset) % max(size, 1)
    with open(path, "r+b") as f:
        f.seek(off)
        orig = f.read(1)
        f.seek(off)
        f.write(bytes([orig[0] ^ 0x01]))
        f.flush()

    def _undo():
        with open(path, "r+b") as f:
            f.seek(off)
            f.write(orig)
            f.flush()
    return _FlipToken(_undo)
