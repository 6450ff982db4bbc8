"""Map-side writer — stage records, publish metadata.

The reference's map side is Spark's stock sort-shuffle writer; the plugin
hooks the commit: after the index/data files land, it mmaps + registers
them and publishes the 300 B metadata record to the driver table
(ref: CommonUcxShuffleBlockResolver.scala:33-107). Reproduced here:

* ``write`` stages key/value arrays into pool-backed host buffers (the
  mmapped-data-file role: bytes sit in registered host memory, ready for
  zero-copy ``device_put``).
* ``commit`` computes the per-reduce-partition size row (the index file)
  and publishes it to the shuffle registry (the one-sided put into the
  driver table). Empty outputs publish an all-zero row — the reference
  skips empty outputs entirely (ref: compat/spark_2_4/
  UcxShuffleBlockResolver.scala:35-38); a zero row is the table-native way
  to say the same thing.

* spill: past ``spill.threshold`` staged bytes, batches append to a
  per-writer ``.keys``/``.vals`` file pair and are MMAPPED back at
  materialize time — the sort-shuffle ``data``+``index`` file contract
  (ref: CommonUcxShuffleManager.scala:22, UnsafeUtils.java:48-65) as an
  overflow valve: datasets larger than the host arena stage through the
  page cache with bounded RSS, and the read path consumes the mapped
  views without copying them back wholesale.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional, Tuple

import numpy as np

from sparkucx_tpu.meta.registry import ShuffleEntry
from sparkucx_tpu.runtime.failures import TruncatedBlockError
from sparkucx_tpu.runtime.memory import ArenaBuffer, HostMemoryPool, \
    MappedFile
from sparkucx_tpu.utils.atomicio import atomic_write_text, fsync_dir
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.metrics import Timer
from sparkucx_tpu.utils.trace import GLOBAL_TRACER

log = get_logger("shuffle.writer")


class SpillFiles:
    """Disk-backed map-output staging: append-only ``.keys``/``.vals``
    files plus a tiny ``.index`` sidecar (schema + row count), mmapped
    back as zero-copy numpy views at materialize time.

    Two append-only files instead of the reference's interleaved
    data+index pair because our columns are homogeneous: the whole keys
    file IS one int64 array, the whole vals file one [n, ...] array — so
    ``mmap`` + ``ndarray.view`` replaces the offset arithmetic the
    reference needs (ref: UnsafeUtils.java:48-65,
    CommonUcxShuffleBlockResolver.scala:33-57).

    TORN-WRITE-PROOF: appends land in ``*.tmp`` files; :meth:`finish`
    SEALS them — flush + fsync + atomic rename to the final names, the
    ``.index`` sidecar written the same way (utils/atomicio) — so a
    process killed mid-spill leaves only ``.tmp`` debris, never a
    plausible-looking short file under the final name. :meth:`load`
    validates the sealed file lengths against the sidecar BEFORE mmap:
    truncation is a typed :class:`TruncatedBlockError` naming the file,
    not a garbage view."""

    def __init__(self, directory: str, shuffle_id: int, map_id: int):
        os.makedirs(directory, exist_ok=True)
        stem = os.path.join(directory,
                            f"shuffle_{shuffle_id}_map_{map_id}")
        self.keys_path = stem + ".keys"
        self.vals_path = stem + ".vals"
        self.index_path = stem + ".index"
        # "wb", not "ab": the stem is exclusively this writer's (first-
        # commit-wins upstream), so leftover bytes from a crashed
        # predecessor with the same name must be truncated, not extended
        self._kf = open(self.keys_path + ".tmp", "wb")
        self._vf = open(self.vals_path + ".tmp", "wb")
        self.rows = 0
        self.sealed = False
        self._maps: List[MappedFile] = []

    @classmethod
    def open_sealed(cls, directory: str, shuffle_id: int,
                    map_id: int) -> "SpillFiles":
        """Adopt an already-sealed file set (restart recovery from the
        durable ledger, shuffle/durable.py): no write fds, rows/schema
        from the sealed sidecar; :meth:`load` serves the mmap views."""
        obj = cls.__new__(cls)
        stem = os.path.join(directory,
                            f"shuffle_{shuffle_id}_map_{map_id}")
        obj.keys_path = stem + ".keys"
        obj.vals_path = stem + ".vals"
        obj.index_path = stem + ".index"
        obj._kf = obj._vf = None
        obj.sealed = True
        obj._maps = []
        with open(obj.index_path) as f:
            obj.rows = int(json.load(f)["rows"])
        return obj

    def append(self, keys: np.ndarray, values: Optional[np.ndarray]) -> None:
        if self.sealed:
            raise RuntimeError(
                f"{self.keys_path}: sealed spill files are immutable "
                f"(append after finish)")
        self._kf.write(keys.tobytes())
        if values is not None:
            self._vf.write(values.tobytes())
        self.rows += keys.shape[0]

    def finish(self, val_tail, val_dtype) -> None:
        """SEAL: flush + fsync + atomic rename tmp -> final, then the
        ``.index`` sidecar (schema + row count) written atomically too.
        Idempotent — recovered/cached file sets re-finish as a no-op.
        After the seal the bytes are crash-durable: a SIGKILL one
        instruction later leaves a fully valid file set."""
        if self.sealed:
            return
        for f in (self._kf, self._vf):
            f.flush()
            os.fsync(f.fileno())
            f.close()
        self._kf = self._vf = None
        os.replace(self.keys_path + ".tmp", self.keys_path)
        os.replace(self.vals_path + ".tmp", self.vals_path)
        atomic_write_text(self.index_path, json.dumps({
            "rows": self.rows,
            "val_dtype": (np.dtype(val_dtype).str
                          if val_dtype is not None else None),
            "val_tail": list(val_tail) if val_tail is not None else None,
        }))
        fsync_dir(os.path.dirname(self.keys_path))
        self.sealed = True

    def load(self) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """mmap the sealed files back as arrays (read-only views,
        page-cache backed — RSS stays bounded). File lengths are
        validated against the sidecar FIRST: a shorter-than-declared
        file raises typed, naming the file, instead of returning a
        short or garbage view."""
        with open(self.index_path) as f:
            idx = json.load(f)
        n = idx["rows"]
        keys = np.zeros(0, dtype=np.int64)
        values = None
        if n:
            need = n * 8
            got = os.path.getsize(self.keys_path)
            if got != need:
                raise TruncatedBlockError(
                    f"{self.keys_path}: {got} B on disk but the sealed "
                    f"sidecar declares {n} rows = {need} B — torn write "
                    f"or external truncation")
            km = MappedFile(self.keys_path)
            self._maps.append(km)
            keys = km.data[:need].view(np.int64)
        if idx["val_dtype"] is not None:
            vdt = np.dtype(idx["val_dtype"])
            tail = tuple(idx["val_tail"])
            if n:
                nbytes = n * int(np.prod(tail, dtype=np.int64) or 1) \
                    * vdt.itemsize
                got = os.path.getsize(self.vals_path)
                if got != nbytes:
                    raise TruncatedBlockError(
                        f"{self.vals_path}: {got} B on disk but the "
                        f"sealed sidecar declares {n} x {vdt.str}{tail} "
                        f"= {nbytes} B — torn write or external "
                        f"truncation")
                vm = MappedFile(self.vals_path)
                self._maps.append(vm)
                values = vm.data[:nbytes].view(vdt).reshape((n,) + tail)
            else:
                values = np.zeros((0,) + tail, dtype=vdt)
        return keys, values

    def drop_views(self) -> None:
        """Close the mmaps only (keep files) — the integrity verifier's
        reload seam after a quarantine move."""
        for m in self._maps:
            m.close()
        self._maps.clear()

    def close(self, delete: bool = True) -> None:
        for f in (self._kf, self._vf):
            if f is None:
                continue
            try:
                f.close()
            except OSError:  # pragma: no cover
                pass
        self._kf = self._vf = None
        for m in self._maps:
            m.close()
        self._maps.clear()
        if delete:
            for p in (self.keys_path, self.vals_path, self.index_path,
                      self.keys_path + ".tmp", self.vals_path + ".tmp"):
                try:
                    os.unlink(p)
                except OSError:
                    pass


def _hash32_np(keys: np.ndarray) -> np.ndarray:
    """numpy twin of ops.partition.hash32 — must match bit-for-bit so the
    host-published size row agrees with device-side routing."""
    x = keys.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


class MapOutputWriter:
    """Writer for one map task's output (one row of the segment table)."""

    def __init__(self, entry: ShuffleEntry, map_id: int,
                 pool: HostMemoryPool, partitioner: str = "hash",
                 faults=None, spill_dir: Optional[str] = None,
                 spill_threshold: int = 0, bounds=None,
                 integrity_level: str = "off", ledger=None):
        self.entry = entry
        self.map_id = map_id
        self.pool = pool
        self.partitioner = partitioner
        self.bounds = bounds  # range split points (partitioner="range")
        self.faults = faults  # runtime.failures.FaultInjector, site "publish"
        self._keys: List[np.ndarray] = []
        self._values: List[np.ndarray] = []
        self._staged: List[ArenaBuffer] = []
        self._committed = False
        self._released = False
        # spill plumbing (threshold 0 = arena-only staging)
        self._spill_dir = spill_dir
        self._spill_threshold = spill_threshold if spill_dir else 0
        self._spill: Optional[SpillFiles] = None
        self._staged_bytes = 0
        self._val_tail: Optional[Tuple[int, ...]] = None
        self._val_dtype = None
        self._spill_views = None  # cached (keys, values) mmap views
        # -- integrity + durability plane --------------------------------
        # integrity_level != "off": commit() computes an IntegrityRecord
        # (shuffle/integrity.py) over the staged bytes and publishes it
        # beside the size row; "full" additionally includes per-
        # partition digest rows for the post-collective verify.
        self._integrity_level = integrity_level
        # the published record (tests / the manager's verify read it)
        self.integrity = None
        # durable ledger (shuffle/durable.py): commit() force-seals the
        # staged output into the ledger's shuffle dir (spill_dir points
        # there when the ledger is on) and records the manifest row.
        # Durable spill files SURVIVE release()/stop() — deleting them
        # is the ledger's job (explicit unregister), that is the point.
        self._ledger = ledger
        self._durable = ledger is not None

    def write(self, keys: np.ndarray,
              values: Optional[np.ndarray] = None) -> None:
        """Append a batch of records. ``keys`` [N] integer; ``values``
        [N, ...] optional payload rows."""
        # committed FIRST: a committed writer released by normal
        # teardown must keep reporting the accurate immutability error,
        # not claim a speculative supersede discarded its rows
        if self._committed:
            raise RuntimeError("writer already committed")
        if self._released:
            raise RuntimeError(
                f"map {self.map_id}: writer was released (superseded "
                f"attempt, failed-task retry, or shuffle teardown); its "
                f"staged rows are gone — obtain a fresh writer")
        with GLOBAL_TRACER.span("shuffle.write", map_id=self.map_id,
                                shuffle_id=self.entry.shuffle_id):
            self._stage(keys, values)

    def _stage(self, keys: np.ndarray, values: Optional[np.ndarray]) -> None:
        """Validate one batch and copy it into the staging arena."""
        keys = np.ascontiguousarray(keys)
        if keys.ndim != 1:
            raise ValueError("keys must be 1-D")
        if not np.issubdtype(keys.dtype, np.integer):
            raise ValueError(
                f"keys must be integers, got {keys.dtype}; put non-integer "
                f"sort keys in the value payload")
        if keys.dtype != np.int64:
            keys = keys.astype(np.int64)
        if values is not None:
            values = np.ascontiguousarray(values)
            if values.shape[0] != keys.shape[0]:
                raise ValueError(
                    f"values rows {values.shape[0]} != keys {keys.shape[0]}")
            if self._val_dtype is None:
                if self.num_rows:
                    # earlier batches were keys-only; pairing this values
                    # batch with them would misalign the two column files
                    raise ValueError(
                        "mixed batches with and without values")
                self._val_tail, self._val_dtype = \
                    values.shape[1:], values.dtype
            elif (values.shape[1:], values.dtype) != (self._val_tail,
                                                      self._val_dtype):
                raise ValueError(
                    f"mixed value schema within one writer: "
                    f"{values.dtype}{values.shape[1:]} after "
                    f"{self._val_dtype}{self._val_tail}")
        elif self._val_dtype is not None:
            raise ValueError("mixed batches with and without values")
        # Stage through the pool: bytes land in pinned host memory so the
        # later device_put can DMA without a bounce copy (the
        # mmap+register step, ref: CommonUcxShuffleBlockResolver.scala:45-57).
        kbuf = self.pool.get(max(keys.nbytes, 1))
        kbuf.view()[:keys.nbytes] = keys.view(np.uint8).ravel()
        self._staged.append(kbuf)
        staged_keys = kbuf.view()[:keys.nbytes].view(keys.dtype)
        self._keys.append(staged_keys)
        if values is not None:
            vbuf = self.pool.get(max(values.nbytes, 1))
            vbuf.view()[:values.nbytes] = values.view(np.uint8).ravel()
            self._staged.append(vbuf)
            self._values.append(
                vbuf.view()[:values.nbytes].view(values.dtype).reshape(
                    values.shape))
        self._staged_bytes += keys.nbytes + (values.nbytes
                                             if values is not None else 0)
        if self._spill_threshold and \
                self._staged_bytes >= self._spill_threshold:
            self._flush_to_disk()

    def _flush_to_disk(self) -> None:
        """Move staged arena batches to the spill files and return the
        arena blocks to the pool (the writer's RSS valve)."""
        from sparkucx_tpu.utils.metrics import (C_SPILL_BYTES,
                                                C_SPILL_COUNT,
                                                GLOBAL_METRICS)
        if self.faults is not None:
            # armed via spark.shuffle.tpu.fault.spill.* — disk-full /
            # IO-error drills for the spill valve, same surface as
            # publish/fetch/exchange
            self.faults.check("spill")
        if self._spill is None:
            self._spill = SpillFiles(self._spill_dir, self.entry.shuffle_id,
                                     self.map_id)
            log.info("map %d spilling to %s (threshold %d B)", self.map_id,
                     self._spill.keys_path, self._spill_threshold)
        # anatomy span (spill phase): a spill forced DURING a read (the
        # budget valve) lands inside the exchange wall by containment; a
        # map-time threshold spill simply predates any wall and is
        # ignored by the fold
        with GLOBAL_TRACER.span("shuffle.spill", map_id=self.map_id,
                                shuffle_id=self.entry.shuffle_id):
            for i, keys in enumerate(self._keys):
                self._spill.append(
                    keys, self._values[i] if self._values else None)
            self._keys.clear()
            self._values.clear()
            for b in self._staged:
                self.pool.put(b)
            self._staged.clear()
            moved = self._staged_bytes
            self._staged_bytes = 0
        if moved:
            # the spill-proven evidence (the doctor's spill_bound rule
            # carries it) — counted at the ONE seam every spill
            # passes through, threshold-triggered and budget-forced alike
            GLOBAL_METRICS.inc(C_SPILL_BYTES, float(moved))
            GLOBAL_METRICS.inc(C_SPILL_COUNT, 1.0)

    def spill(self) -> int:
        """Force the currently-staged arena batches onto the spill files
        NOW, returning the bytes moved (0 when nothing was staged or the
        writer has no spill dir). The external-memory workloads' budget
        valve: chunked ingest calls this when the POOL watermark crosses
        the configured memory budget — the per-writer ``spill.threshold``
        bounds one writer, this bounds their sum. The moved batches ride
        the exact ``SpillFiles`` path threshold spills use (sealed at
        commit through the same ``finish()``), so a budget-forced spill
        is torn-write-proof and restart-adoptable like any other."""
        if self._committed or self._released:
            raise RuntimeError(
                f"map {self.map_id}: spill() on a "
                f"{'committed' if self._committed else 'released'} writer")
        if self._spill_dir is None or not self._keys:
            return 0
        moved = self._staged_bytes
        self._flush_to_disk()
        return moved

    @property
    def num_rows(self) -> int:
        spilled = self._spill.rows if self._spill is not None else 0
        return spilled + sum(k.shape[0] for k in self._keys)

    @property
    def committed(self) -> bool:
        return self._committed

    @property
    def released(self) -> bool:
        """Whether release() dropped the staged rows — a released writer
        is NOT recoverable state (the manager's recovery ledger checks
        this before carrying a shuffle across an epoch bump)."""
        return self._released

    def commit(self, num_partitions: int) -> np.ndarray:
        """Compute and publish this map output's size row; returns it.

        The writeIndexFileAndCommit hook: stock commit is our staging,
        the publish is the put to the driver table
        (ref: CommonUcxShuffleBlockResolver.scala:78-103)."""
        # committed before released: a committed-then-released writer
        # (normal unregister/remesh teardown) reports immutability, the
        # accurate diagnosis
        if self._committed:
            raise RuntimeError("writer already committed")
        if self._released:
            # A superseded speculative attempt committing late must fail
            # HERE, not publish: release() cleared its staged rows, so a
            # publish would mark the map complete with a zero size row —
            # the reader would silently lose that map's data (ADVICE r5
            # high: the late-committing-attempt hole in first-commit-wins)
            raise RuntimeError(
                f"map {self.map_id}: writer was released (superseded "
                f"attempt?) — its staged rows are gone and it may not "
                f"publish; first commit wins")
        if self.faults is not None:
            self.faults.check("publish")
        with Timer() as t, GLOBAL_TRACER.span(
                "shuffle.publish", map_id=self.map_id, rows=self.num_rows):
            keys = values = parts = None
            if self.num_rows:
                if self._ledger is not None and self._spill is None:
                    # durable commit: the staged bytes must be SEALED on
                    # disk before the size row is published — a commit
                    # the registry reports must survive a restart
                    # (materialize() below runs finish(), the fsync +
                    # atomic-rename seal)
                    self._flush_to_disk()
                keys, values = self.materialize()
                parts = self.partition_of(keys, num_partitions)
                sizes = np.bincount(parts, minlength=num_partitions)
            else:
                sizes = np.zeros(num_partitions, dtype=np.int64)
            rec = None
            if self._integrity_level != "off" or self._ledger is not None:
                from sparkucx_tpu.shuffle.integrity import compute_record
                rec = compute_record(
                    keys, values, parts, num_partitions,
                    with_digests=self._integrity_level == "full",
                    # the crc32 disk checksums exist for the ledger's
                    # manifest + restart scan; without a ledger only the
                    # fold64 pair is consumed — skip the slower pass
                    with_crc=self._ledger is not None)
            self.entry.publish(self.map_id, sizes, integrity=rec)
            self.integrity = rec
            if self._ledger is not None:
                self._ledger.record_commit(self.entry, self.map_id,
                                           sizes, rec)
        self._committed = True
        log.debug("map %d publish overhead: %.2f ms (%d rows)",
                  self.map_id, t.ms, self.num_rows)
        return sizes

    def partition_of(self, keys: np.ndarray,
                     num_partitions: int) -> np.ndarray:
        """Host-side partition ids for ``keys`` — the ONE partitioner
        twin (bit-for-bit with the device routing) shared by the size
        row, the integrity digests and tests."""
        if self.partitioner == "direct":
            if (keys < 0).any() or (keys >= num_partitions).any():
                bad = keys[(keys < 0) | (keys >= num_partitions)][:4]
                raise ValueError(
                    f"direct partitioner: keys must be partition "
                    f"ids in [0, {num_partitions}); got e.g. "
                    f"{bad.tolist()}")
            return keys.astype(np.int64)
        if self.partitioner == "range":
            # host twin of ops/partition.range_partition_words —
            # searchsorted side='right' over the split points
            return np.searchsorted(
                np.asarray(self.bounds, dtype=np.int64), keys,
                side="right").astype(np.int64)
        return (_hash32_np(keys)
                % np.uint32(num_partitions)).astype(np.int64)

    @classmethod
    def recovered(cls, entry: ShuffleEntry, map_id: int,
                  pool: HostMemoryPool, directory: str, rec,
                  partitioner: str = "hash", bounds=None,
                  integrity_level: str = "staged") -> "MapOutputWriter":
        """Adopt one checksum-validated map output from the durable
        ledger (shuffle/durable.py restart scan): a COMMITTED writer
        whose staged state is the sealed spill file set on disk — reads
        consume its mmap views exactly like a live spill writer, with
        zero recompute. ``rec`` is the manifest's IntegrityRecord (the
        schema + checksums the read-path verify re-checks)."""
        w = cls(entry, map_id, pool, partitioner=partitioner,
                spill_dir=directory, spill_threshold=0, bounds=bounds,
                integrity_level=integrity_level, ledger=None)
        w._durable = True                # release() must keep the files
        if rec.rows:
            w._spill = SpillFiles.open_sealed(directory,
                                              entry.shuffle_id, map_id)
        if rec.val_dtype is not None:
            w._val_tail = tuple(rec.val_tail or ())
            w._val_dtype = np.dtype(rec.val_dtype)
        w.integrity = rec
        w._committed = True
        return w

    def materialize(self) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Concatenated (keys, values) staged by this writer. When spill is
        active, remaining batches flush and the result is a pair of
        READ-ONLY mmap views over the spill files (page-cache backed) —
        the read path streams them into the pack buffer without a second
        host-RAM copy of the whole output."""
        if self._spill is not None:
            # cache the mapped views: materialize() is called once per
            # read/submit/export, and re-running finish()+load() each time
            # would accumulate mmaps/fds until release()
            if self._keys or self._spill_views is None:
                if self._keys:
                    self._flush_to_disk()
                self._spill.finish(self._val_tail, self._val_dtype)
                self._spill_views = self._spill.load()
            return self._spill_views
        if not self._keys:
            return np.zeros(0, dtype=np.int64), None
        keys = np.concatenate(self._keys)
        values = np.concatenate(self._values) if self._values else None
        return keys, values

    def release(self) -> None:
        """Return staging buffers to the pool and delete spill files
        (removeShuffle's parallel deregister+munmap,
        ref: CommonUcxShuffleBlockResolver.scala:109-121).

        The writer is DEAD afterwards: write()/commit() raise. Idempotent
        (the graveyard/stop paths may release a batch more than once).

        DURABLE writers (failure.ledgerDir) keep their sealed files on
        disk: release() closes the mappings only — surviving process
        death is the ledger's whole point (Spark's external shuffle
        service keeps a dead executor's files the same way). Deleting
        durable state is the explicit-unregister path's job
        (shuffle/durable.ShuffleLedger.forget)."""
        self._released = True
        for b in self._staged:
            self.pool.put(b)
        self._staged.clear()
        self._keys.clear()
        self._values.clear()
        if self._spill is not None:
            self._spill_views = None   # views die with the mappings
            self._spill.close(delete=not self._durable)
            self._spill = None
