"""Reduce-side reader — the hot path, one collective per shuffle.

The reference's reduce side is a per-(mapper, reducer) storm of one-sided
reads driven by a spinning progress thread (call stack at SURVEY.md §3.4).
The TPU build collapses all of it into ONE jitted SPMD step over the mesh:

    stage:   [P, cap_in, W] int32 row matrix staged per shard (host pool)
    device:  route -> ONE partition-major sort -> ragged all-to-all
    fetch:   per-reduce-partition runs, located by prefix sums over the
             per-sender count matrix (no receive-side sort: the blocked
             partition->device map is monotone, so partition order IS
             device order and every delivered segment arrives grouped)

so the reference's headline property — mapper CPU does nothing per fetch —
becomes "host does nothing per block": no per-block round-trips exist at
all, only one compiled program launch (SURVEY.md §7 hard part (c)).

Transport format: rows are fused int32 columns — ``[key_lo, key_hi,
value_words...]`` — produced by bit-exact views on the host (never dtype
casts: jnp would silently truncate int64 with x64 off). Routing uses the
low 32 key bits, which is exactly what the 32-bit mixing hash consumes, so
host-published size rows and device routing agree for 64-bit keys. One
fused stream also means ONE exchange per shuffle instead of one per
column family.

Overflow handling: the data plane flags capacity overflow mesh-wide; the
reader retries with a doubled plan (one recompile) rather than
provisioning worst-case HBM up front.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from sparkucx_tpu.ops.partition import (
    blocked_partition_map, destination_sort, hash_partition)
from sparkucx_tpu.shuffle.alltoall import (ragged_shuffle, wire_pack_rows,
                                           wire_unpack_rows)
from sparkucx_tpu.shuffle.plan import (ShufflePlan, plan_takes_seed,
                                       wire_row_words)
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.metrics import C_D2H, GLOBAL_METRICS
from sparkucx_tpu.utils.trace import GLOBAL_TRACER

log = get_logger("shuffle.reader")

KEY_WORDS = 2  # int64 key as two int32 columns [lo, hi]


def pull_to_host(arr, what: str, res=None, nbytes: Optional[int] = None,
                 pull=np.asarray) -> np.ndarray:
    """``pull(arr)`` — one device-to-host copy of a read's output — as a
    ``shuffle.d2h`` span with attrs ``bytes`` and ``what``: ``rows`` for
    payload, ``seg`` for the delivered-count matrix. ``nbytes`` defaults
    to ``arr.nbytes``; a pull that copies only part of ``arr`` (this
    process's shards of a global array) passes the bytes it copies.

    Payload pulls are accounted to the reader result ``res``: the
    cumulative ``shuffle.read.d2h.bytes`` counter (the figure the device
    sink drives to ZERO) plus the
    owning read's ExchangeReport when the manager armed the callback
    (``_d2h_cb``, set at on_done). Pulls that happen BEFORE arming (the
    distributed force-materialize runs inside result()) park in
    ``_d2h_early`` for the manager to flush. Seg matrices are metadata
    and deliberately not counted."""
    n = int(arr.nbytes if nbytes is None else nbytes)
    with GLOBAL_TRACER.span("shuffle.d2h", bytes=n, what=what):
        host = pull(arr)
    if what == "rows" and n > 0:
        GLOBAL_METRICS.inc(C_D2H, float(n))
        cb = getattr(res, "_d2h_cb", None)
        if cb is not None:
            cb(n)
        else:
            res._d2h_early = getattr(res, "_d2h_early", 0) + n
    return host


@functools.lru_cache(maxsize=32)
def _blocked_map(num_partitions: int, num_devices: int):
    return blocked_partition_map(num_partitions, num_devices)


def _concat_blocks(blocks) -> np.ndarray:
    """Dense concatenation of row blocks via ONE preallocated destination
    + sliced copies (no temp-list np.concatenate) — the multi-run
    partition-block builder, shared by the run-index path and the waved
    result's plain-mode merge."""
    total = sum(b.shape[0] for b in blocks)
    out = np.empty((total, blocks[0].shape[1]), blocks[0].dtype)
    off = 0
    for b in blocks:
        out[off:off + b.shape[0]] = b
        off += b.shape[0]
    return out


def _device_bounds(num_partitions: int, num_devices: int) -> np.ndarray:
    """Static [P+1] partition-range boundaries of the blocked map: device d
    owns partitions [bounds[d], bounds[d+1])."""
    p2d = np.asarray(_blocked_map(num_partitions, num_devices))
    return np.searchsorted(p2d, np.arange(num_devices + 1)).astype(np.int32)


def _make_part_fn(plan: ShufflePlan, R: int):
    """The pluggable partitioner (Spark's Partitioner SPI analog),
    shared by the flat, hierarchical, and pallas step bodies."""
    def part_fn(rows):
        if plan.partitioner == "direct":
            return jnp.clip(rows[:, 0], 0, R - 1)
        if plan.partitioner == "range":
            from sparkucx_tpu.ops.partition import range_partition_words
            return range_partition_words(rows[:, 0], rows[:, 1],
                                         plan.bounds)
        return hash_partition(rows[:, 0], R)
    return part_fn


def seeded_nvalid(plan: ShufflePlan, nvalid: np.ndarray, base_seed: int,
                  shard_ids=None) -> np.ndarray:
    """The host half of the seeded-step contract: a plan on the int8
    wire (plan_takes_seed) widens its per-shard nvalid input from
    ``[count]`` to ``[count, seed]`` — the noise seed rides the SAME
    staged, P(axis)-sharded lane as the count, so the step signature
    never grows a separately-sharded argument (one compiled program per
    shape family, wire mode included). Seeds are derived per GLOBAL
    shard (``base*P + shard_id``, int32 ring), so every shard draws a
    distinct stream and the arithmetic is identical on every process of
    a collective read by construction. Raw/lossless plans pass through
    untouched."""
    nv = np.asarray(nvalid, dtype=np.int32).reshape(-1)
    if not plan_takes_seed(plan):
        return nv
    ids = np.arange(nv.shape[0], dtype=np.int64) if shard_ids is None \
        else np.asarray(shard_ids, dtype=np.int64)
    seeds = (np.int64(base_seed) * plan.num_shards + ids) & 0x7FFFFFFF
    return np.stack([nv.astype(np.int64), seeds],
                    axis=1).reshape(-1).astype(np.int32)


def _wire_ragged_shuffle(plan: ShufflePlan, send, sizes, axis, seed,
                         unpack: bool = True):
    """One collective on the plan's wire tier: int8 narrows the value
    lanes around ragged_shuffle (quantize on send, dequantize on
    receive — the key lanes and the [P] size row stay exact), every
    other tier is ragged_shuffle verbatim. The delivered rows are
    full-width by default, so everything downstream of the collective
    (receive-side combine/keysort, run arithmetic, unpack) is
    wire-oblivious. ``unpack=False`` hands the caller the received
    rows STILL in wire format (key lanes exact, value lanes packed) —
    the fused dequant segment-reduce's input, which dequantizes inside
    the consuming kernel instead of running a separate program."""
    if seed is None:
        return ragged_shuffle(send, sizes, axis,
                              out_capacity=plan.cap_out, impl=plan.impl)
    width = send.shape[1]
    packed = wire_pack_rows(send, plan.wire_words, seed)
    r = ragged_shuffle(packed, sizes, axis, out_capacity=plan.cap_out,
                       impl=plan.impl)
    if not unpack:
        return r
    data = wire_unpack_rows(r.data, width, plan.wire_words)
    from sparkucx_tpu.shuffle.alltoall import ShuffleResult
    return ShuffleResult(data, r.recv_sizes, r.total, r.overflow)


def combine_status(overflow, rows_recv, rows_out):
    """A combining step's last output, [3] int32 per shard: the overflow
    flag first (where the other steps put their [1] bool flag), then the
    rows this shard received (what the map-side combines put on the wire
    to it) and its rows after the receive-side reduce. The host reads it
    where it reads the flag, so the counts take no transfer of their own
    (``overflow_flags``)."""
    return jnp.concatenate([jnp.reshape(x, (1,)).astype(jnp.int32)
                            for x in (overflow, rows_recv, rows_out)])


def reduce_rows_scanned(plan: ShufflePlan, width: int) -> int:
    """Rows of a shard's receive buffer that its combining step hands the
    Pallas segment-reduce (the kernel's grid covers them in 128-row
    tiles), 0 where the step runs no such kernel: a plain or ordered
    read, the jnp reduce, or one shard, whose map-side combine is the
    whole reduce."""
    if not plan.combine or plan.kernel_impl != "pallas":
        return 0
    if plan.impl == "pallas":
        return _pallas_recv_rows(plan, width)
    return plan.cap_out if plan.num_shards > 1 else 0


def _pallas_recv_rows(plan: ShufflePlan, width: int) -> int:
    """The first-party transport's receive capacity a shard: ``cap_out``
    aligned to its DMA chunk, plus a chunk of pad a sender."""
    from sparkucx_tpu.ops.pallas.ragged_a2a import align_rows, chunk_rows_for
    chunk = chunk_rows_for(wire_row_words(plan, width))
    return int(align_rows(plan.cap_out, chunk)) + plan.num_shards * chunk


def overflow_flags(last, num_shards: int) -> np.ndarray:
    """[num_shards] bool overflow verdicts from a step's last output on
    the host: [1] bool a shard, or a combining step's ``combine_status``,
    whose first word is the flag."""
    return np.asarray(last).reshape(num_shards, -1)[:, 0] != 0


def step_body(plan: ShufflePlan, axis: str):
    """The per-shard exchange step (call under shard_map over ``axis``).

    Exposed separately from :func:`_build_step` so the AOT chip
    compiles (shuffle/aot.py) and the tests lower the EXACT production
    pipeline rather than a re-implementation that could drift.

    PARTITION-MAJOR design: the send side sorts by GLOBAL reduce-partition
    id. The blocked partition->device map is monotone, so one sort groups
    rows by destination device (the all-to-all invariant) AND leaves each
    delivered segment internally partition-sorted — the receive side needs
    NO regrouping at all (the old design re-sorted the cap_out-sized
    receive buffer, the single largest op in the step). ``partition(r)``
    is then served as one contiguous slice per sender, with offsets
    computed from the [P, R] per-sender partition-count matrix that each
    shard already produced for its own rows (all_gathered: tiny, rides the
    same program)."""
    R = plan.num_partitions
    Pn = plan.num_shards
    if plan.impl == "pallas":
        # the first-party remote-DMA transport — its chunk-aligned layout
        # needs its own sort and run arithmetic (plain), or a receive-side
        # densify pass (combine/ordered)
        return _pallas_step_body(plan, axis)
    # numpy, NOT jnp: a closed-over concrete jnp array becomes a lifted
    # executable parameter, which jax's C++ fastpath fails to re-supply on
    # repeat calls when the step is traced inside a caller's scan;
    # a numpy constant inlines as a literal at trace time
    bounds = _device_bounds(R, Pn)
    part_fn = _make_part_fn(plan, R)
    seeded = plan_takes_seed(plan)

    def dev_counts(rcounts):
        # per-device segment sizes = partition-count sums over each
        # device's (static) partition range
        cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(rcounts).astype(jnp.int32)])
        return jnp.take(cum, bounds[1:]) - jnp.take(cum, bounds[:-1])

    def step(payload, nvalid):
        # payload [cap_in, width] int32, col 0 = key_lo; nvalid [1] — or
        # [count, seed] on the int8 wire (seeded_nvalid: the noise seed
        # rides the same staged lane as the count)
        seed = nvalid[1] if seeded else None
        nvalid = nvalid[:1]
        part = part_fn(payload)
        if plan.strips_active():
            # single shard, plain: no wire move is needed (the send
            # buffer IS the delivered buffer), so the whole step is the
            # sort — and S independent strip sorts batch into ONE
            # shallower sort network (~log^2(cap/S) depth vs ~log^2(cap);
            # ops/partition.destination_sort_strips). The reader serves
            # each partition as S runs via the same multi-sender run
            # index the flat exchange uses (_RunIndex with
            # align_chunk=plan.strip_rows()); no overflow is possible
            # (rows never leave their strip region).
            from sparkucx_tpu.ops.partition import destination_sort_strips
            if payload.shape[0] != plan.cap_in:
                # static trace-time guard: plan.strip_rows() (the resolve
                # side's align_chunk) derives M from cap_in; the sort
                # derives it from this cap — they must be the same number
                raise ValueError(
                    f"strip path: payload cap {payload.shape[0]} != "
                    f"plan.cap_in {plan.cap_in}")
            send, seg, _m = destination_sort_strips(
                payload, part, nvalid[0], R, plan.sort_strips,
                key_impl=plan.sort_impl)
            return (send, seg, nvalid.astype(jnp.int32),
                    jnp.zeros((1,), jnp.bool_))
        if plan.combine:
            # map-side combine: one row per distinct (partition, key)
            # enters the wire. Its grouping sort is (partition, key) —
            # strictly finer than the partition sort it replaces, so the
            # send-buffer invariants (device-grouped, partition-sorted
            # segments) still hold.
            from sparkucx_tpu.ops.aggregate import combine_rows
            send, rcounts, _ = combine_rows(
                payload, part, nvalid[0], R, plan.combine_words,
                np.dtype(plan.combine_dtype), plan.combine,
                sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction)
        elif plan.ordered and Pn == 1:
            # single shard: ONE sender means delivered rows keep send
            # order, so doing the (partition, key) sort on the send side
            # (cap_in rows) replaces the receive-side re-sort of the
            # capacityFactor-larger receive buffer
            from sparkucx_tpu.ops.aggregate import keysort_rows
            _, send, rcounts = keysort_rows(payload, part, nvalid[0], R)
        else:
            # ordered needs no key order on the SEND side: the receive
            # stage fully re-sorts by (partition, key). Tie order among
            # EQUAL keys is unspecified either way (keysort_rows is
            # unstable), so the plain (cheaper) partition sort here loses
            # nothing — the ordered contract is key order, not tie order.
            send, rcounts = destination_sort(payload, part, nvalid[0], R,
                                             method=plan.sort_impl)

        # int8 + blocked kernels + multi-sender combine: keep the
        # received rows in WIRE format — the fused dequant segment-
        # reduce consumes them directly (EQuARX: no separate dequant
        # program). Key lanes are exact in wire rows, so the grouping
        # keysort below needs no unpack either.
        width = payload.shape[1]
        fused = (plan.combine and Pn > 1 and seeded
                 and plan.kernel_impl == "pallas"
                 and width == 2 + plan.wire_words)
        r = _wire_ragged_shuffle(plan, send, dev_counts(rcounts), axis,
                                 seed, unpack=not fused)

        if plan.combine:
            if Pn == 1:
                # single shard: there is exactly one sender, so the
                # map-side combine above already produced ONE row per
                # (partition, key), key-sorted — a receive-side merge
                # would re-sort the (1.5x larger) receive buffer to merge
                # nothing. rcounts IS the per-partition output counts.
                return r.data, rcounts.reshape(1, R), r.total, \
                    combine_status(r.overflow, r.total, r.total)
            # reduce-side combine: merge the per-sender segments' rows by
            # key before D2H — one run per partition, so the seg matrix is
            # this shard's OWN combined counts ([1, R] per shard)
            if fused:
                from sparkucx_tpu.ops.aggregate import keysort_rows
                from sparkucx_tpu.ops.pallas.segmented import \
                    segment_reduce_wire_rows
                spart, swire, _ = keysort_rows(
                    r.data, part_fn(r.data), r.total[0], R)
                rows_out, pcounts, n_out = segment_reduce_wire_rows(
                    swire, spart, R, width, plan.wire_words,
                    sum_words=plan.combine_sum_words, impl="pallas",
                    interpret=plan.pallas_interpret)
            elif plan.kernel_impl == "pallas":
                # blocked tiled segment-reduce over the grouped rows —
                # the keysort replaces combine_rows' internal grouping
                # sort, the reduce replaces its cumsum + flag compaction
                from sparkucx_tpu.ops.aggregate import keysort_rows
                from sparkucx_tpu.ops.pallas.segmented import \
                    segment_reduce_rows
                spart, srows, _ = keysort_rows(
                    r.data, part_fn(r.data), r.total[0], R)
                rows_out, pcounts, n_out = segment_reduce_rows(
                    srows, spart, R, plan.combine_words,
                    np.dtype(plan.combine_dtype), plan.combine,
                    sum_words=plan.combine_sum_words,
                    compaction=plan.combine_compaction, impl="pallas",
                    interpret=plan.pallas_interpret)
            else:
                from sparkucx_tpu.ops.aggregate import combine_rows
                rows_out, pcounts, n_out = combine_rows(
                    r.data, part_fn(r.data), r.total[0], R,
                    plan.combine_words, np.dtype(plan.combine_dtype),
                    plan.combine, sum_words=plan.combine_sum_words,
                    compaction=plan.combine_compaction)
            n_out = n_out.astype(r.total.dtype)
            return rows_out, pcounts.reshape(1, R), n_out, \
                combine_status(r.overflow, r.total, n_out)
        if plan.ordered:
            if Pn == 1:
                # already (partition, key)-sorted on the send side above
                return r.data, rcounts.reshape(1, R), r.total, r.overflow
            # one (partition, key) sort over the received rows yields
            # fully key-sorted partitions — one run each ([1, R] seg)
            from sparkucx_tpu.ops.aggregate import keysort_rows
            _, rows_out, pcounts = keysort_rows(
                r.data, part_fn(r.data), r.total[0], R)
            return rows_out, pcounts.reshape(1, R), r.total, r.overflow
        # every receiver needs every sender's per-partition counts to
        # locate its runs; [P, R] int32 — negligible next to the payload
        seg = jax.lax.all_gather(rcounts, axis)
        return r.data, seg, r.total, r.overflow

    return step


def _pallas_step_body(plan: ShufflePlan, axis: str):
    """Exchange over the first-party Pallas remote-DMA collective
    (ops/pallas/ragged_a2a.py) — the UCX-analog data plane end to end,
    serving every read shape the native transport serves (the reference's
    data plane is shape-agnostic: blocks are opaque byte ranges,
    ref: compat/spark_3_0/UcxShuffleClient.java:95-127).

    Plain: partition-major with DEVICE segments padded to chunk multiples
    (ops/partition.partition_major_sort_aligned), so delivered segments
    are still internally partition-sorted and readers locate runs by
    prefix sums — just with ALIGNED segment starts
    (_RunIndex(align_chunk=...)).

    Combine/ordered: the aligned receive buffer's pad rows are masked to
    a SENTINEL partition id (derived from recv_off/real_recv — pure plan
    arithmetic, no extra collective), then one receive-side
    combine/keysort densifies: sentinel rows sort past every real
    partition, pcounts count only real partitions, and the output is the
    native path's dense [1, R]-seg contract (align_chunk=0 downstream).
    Map-side combine still runs BEFORE the wire, so the traffic-cut
    property survives; its combined rows are re-laid-out by the aligned
    sort (one extra sort of the combined buffer).

    On the CPU backend the kernel runs in interpret mode automatically
    (tests); on TPU it compiles (see plan.pallas_interpret to pin)."""
    R = plan.num_partitions
    Pn = plan.num_shards
    bounds = _device_bounds(R, Pn)
    part_fn = _make_part_fn(plan, R)

    from sparkucx_tpu.ops.pallas.ragged_a2a import (
        chunk_rows_for, pallas_ragged_all_to_all)
    from sparkucx_tpu.ops.partition import partition_major_sort_aligned

    seeded = plan_takes_seed(plan)

    def step(payload, nvalid):
        seed = nvalid[1] if seeded else None
        nvalid = nvalid[:1]
        width = payload.shape[1]
        # chunk alignment follows the WIRE row width: the kernel moves
        # packed (narrower) rows on the int8 tier, and the run-index
        # align_chunk downstream derives from the same wire_row_words
        # seam — one formula, no desync
        chunk = chunk_rows_for(wire_row_words(plan, width))
        part = part_fn(payload)
        if plan.combine:
            # map-side combine first — one row per distinct (partition,
            # key) enters the wire, same as the native path — then the
            # aligned re-layout of the (smaller) combined buffer
            from sparkucx_tpu.ops.aggregate import combine_rows
            comb, _, n_c = combine_rows(
                payload, part, nvalid[0], R, plan.combine_words,
                np.dtype(plan.combine_dtype), plan.combine,
                sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction)
            srows, rcounts, dev_counts = partition_major_sort_aligned(
                comb, part_fn(comb), n_c[0], R, bounds, chunk)
        else:
            srows, rcounts, dev_counts = partition_major_sort_aligned(
                payload, part, nvalid[0], R, bounds, chunk)
        # the kernel requires chunk-multiple buffer capacities; the
        # trailing pad rows are never read (aligned send regions are
        # bounded by align(cap_in) + P*chunk)
        pad = (-srows.shape[0]) % chunk
        if pad:
            srows = jnp.concatenate(
                [srows, jnp.zeros((pad, width), srows.dtype)])
        if seeded:
            # int8 wire: the remote DMA moves packed rows; alignment pad
            # rows quantize to zeros and decode back to zeros
            srows = wire_pack_rows(srows, plan.wire_words, seed)
        cap_eff = _pallas_recv_rows(plan, width)
        # interpret resolves at trace time from the backend UNLESS the
        # plan pins it (plan.pallas_interpret) — an AOT compile from a
        # CPU host against a TPU topology must pin False or the
        # interpreter gets baked into the chip's program
        interpret = (jax.default_backend() == "cpu"
                     if plan.pallas_interpret is None
                     else plan.pallas_interpret)
        out, recv_real, recv_off, total_al = pallas_ragged_all_to_all(
            srows, dev_counts, axis, out_capacity=cap_eff,
            num_devices=Pn, interpret=interpret)
        # int8 + blocked kernels + combine: keep the DMA'd rows in wire
        # format — the fused dequant segment-reduce consumes them as-is
        # (key lanes exact, so the densify keysort needs no unpack)
        fused = (plan.combine and seeded
                 and plan.kernel_impl == "pallas"
                 and width == 2 + plan.wire_words)
        if seeded and not fused:
            # dequantize right off the DMA: everything downstream (the
            # densify combine/keysort, the run index) sees full rows
            out = wire_unpack_rows(out, width, plan.wire_words)
        ovf = (total_al < 0)
        if not (plan.combine or plan.ordered):
            seg = jax.lax.all_gather(rcounts, axis)      # [P, R] real
            total = recv_real.sum().astype(jnp.int32).reshape(1)
            return out, seg, total, ovf

        # combine/ordered: mask the aligned layout's pad rows to the
        # sentinel partition R, then densify on the receive side. Row k
        # belongs to the segment whose aligned start precedes it; it is
        # real iff it sits inside that segment's REAL prefix.
        idx = jnp.arange(cap_eff, dtype=jnp.int32)
        seg_i = jnp.clip(
            jnp.searchsorted(recv_off, idx, side="right") - 1, 0, Pn - 1)
        valid = (idx - jnp.take(recv_off, seg_i)) \
            < jnp.take(recv_real, seg_i)
        pkey = jnp.where(valid, part_fn(out), jnp.int32(R))
        if fused:
            # grouping keysort over the WIRE rows (key/partition lanes
            # exact), then the fused dequant reduce — dequantization
            # happens inside the consuming kernel, no separate program
            from sparkucx_tpu.ops.aggregate import keysort_rows
            from sparkucx_tpu.ops.pallas.segmented import \
                segment_reduce_wire_rows
            spart, swire, _ = keysort_rows(
                out, pkey, jnp.int32(cap_eff), R)
            rows_out, pcounts, _ = segment_reduce_wire_rows(
                swire, spart, R, width, plan.wire_words,
                sum_words=plan.combine_sum_words, impl="pallas",
                interpret=plan.pallas_interpret)
        elif plan.combine and plan.kernel_impl == "pallas":
            from sparkucx_tpu.ops.aggregate import keysort_rows
            from sparkucx_tpu.ops.pallas.segmented import \
                segment_reduce_rows
            spart, srows_g, _ = keysort_rows(
                out, pkey, jnp.int32(cap_eff), R)
            rows_out, pcounts, _ = segment_reduce_rows(
                srows_g, spart, R, plan.combine_words,
                np.dtype(plan.combine_dtype), plan.combine,
                sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction, impl="pallas",
                interpret=plan.pallas_interpret)
        elif plan.combine:
            from sparkucx_tpu.ops.aggregate import combine_rows
            rows_out, pcounts, _ = combine_rows(
                out, pkey, jnp.int32(cap_eff), R, plan.combine_words,
                np.dtype(plan.combine_dtype), plan.combine,
                sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction)
        else:
            from sparkucx_tpu.ops.aggregate import keysort_rows
            _, rows_out, pcounts = keysort_rows(
                out, pkey, jnp.int32(cap_eff), R)
        # total from pcounts, not the sort's group count: the sentinel
        # partition's groups must not inflate the reported row count
        total = pcounts.sum().astype(jnp.int32).reshape(1)
        if plan.combine:
            ovf = combine_status(ovf, recv_real.sum(), total)
        return rows_out, pcounts.reshape(1, R), total, ovf

    return step


def _build_step(mesh: Mesh, axis: str, plan: ShufflePlan, width: int):
    """The exchange step for one (mesh, plan, row width), served from the
    shared keyed step cache (shuffle/stepcache.py) — the jit-cache
    discipline that keeps one compiled program per shape family, now
    observable (compile.step.* counters) and shared with the hierarchical
    builder and manager.warmup. The pipeline itself is
    :func:`step_body`."""
    from sparkucx_tpu.shuffle.stepcache import (GLOBAL_STEP_CACHE,
                                                combine_attrs, program_name)
    # the plan rides the key whole, so the wire tier (plan.wire — part
    # of plan.family too) names its own compiled program per shape
    # family: raw and int8 runs of one shape never collide on a step
    name = program_name("exchange", plan)
    return GLOBAL_STEP_CACHE.get(
        ("flat", mesh, axis, plan, width),
        lambda: _build_step_uncached(mesh, axis, plan, width),
        {"kind": "flat", "program": name, "cap_in": plan.cap_in,
         "cap_out": plan.cap_out, "width": width, "impl": plan.impl,
         "wire": plan.wire, **combine_attrs(plan)})


def _build_step_uncached(mesh: Mesh, axis: str, plan: ShufflePlan,
                         width: int):
    from sparkucx_tpu.shuffle.stepcache import jit_named, program_name
    step = step_body(plan, axis)
    seg_spec = P(axis) if (plan.combine or plan.ordered) else P()

    # check_vma=False: the seg output is an all_gather result — genuinely
    # replicated, but the static varying-axes check cannot prove it
    sm = jax.shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=(P(axis), seg_spec, P(axis), P(axis)),
                       check_vma=False)
    return jit_named(sm, program_name("exchange", plan))


def pack_rows(keys: np.ndarray, values: Optional[np.ndarray],
              width: int, out: Optional[np.ndarray] = None,
              nthreads: Optional[int] = None) -> np.ndarray:
    """Host-side fuse: int64 keys + arbitrary fixed-width values into an
    int32 row matrix via bit views (never value casts).

    ``out`` — optional [n, width] int32 destination (e.g. a pinned-arena
    view): rows are written IN PLACE, skipping the temp allocation and the
    second copy — the pack stage is host-memcpy-bound at spill scale.

    Fast path: the native ``sxt_pack_rows`` (C++, row-wise sequential
    writes, threaded) when the library is available and the inputs are
    contiguous — the numpy formulation's two big strided plane-stores run
    at ~2.9 GB/s on the build host vs a ~14.5 GB/s flat-copy ceiling.
    Bit-identical output either way (pinned by test)."""
    n = keys.shape[0]
    if out is None:
        out = np.zeros((n, width), dtype=np.int32)
        fresh = True
    else:
        assert out.shape == (n, width) and out.dtype == np.int32
        fresh = False
    if n and _native_pack(keys, values, width, out, nthreads):
        return out
    out[:, :KEY_WORDS] = np.ascontiguousarray(
        keys.astype(np.int64, copy=False)).view(np.int32).reshape(n, 2)
    filled = KEY_WORDS
    if values is not None and n:
        vb = np.ascontiguousarray(values).view(np.uint8).reshape(n, -1)
        pad = (-vb.shape[1]) % 4
        if pad:
            vb = np.concatenate(
                [vb, np.zeros((n, pad), np.uint8)], axis=1)
        vw = vb.shape[1] // 4
        out[:, KEY_WORDS:KEY_WORDS + vw] = vb.view(np.int32).reshape(n, vw)
        filled += vw
    if not fresh and filled < width:
        out[:, filled:] = 0   # recycled destination: clear slack columns
    return out


def _native_pack(keys: np.ndarray, values: Optional[np.ndarray],
                 width: int, out: np.ndarray,
                 nthreads: Optional[int] = None) -> bool:
    """Try the C++ row-wise pack; False -> caller runs the numpy path.

    The native kernel writes the WHOLE row (key, payload, zero pad), so
    recycled-destination slack is covered; it requires contiguous int64
    keys, contiguous values, and the value bytes to fit the row.
    ``nthreads`` overrides the one-thread-per-8MiB heuristic — callers
    already running inside their OWN thread fan-out (manager._pack_shards)
    pass 1 so a big spill doesn't oversubscribe workers x native threads
    on a memory-bound copy."""
    if os.environ.get("SPARKUCX_TPU_NO_NATIVE") == "1":
        return False
    from sparkucx_tpu import native
    lib = native.load()
    if lib is None or not out.flags.c_contiguous:
        return False
    n = keys.shape[0]
    if keys.dtype != np.int64 or not keys.flags.c_contiguous:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
    if values is not None:
        # malformed values (row count mismatch, indivisible byte total)
        # must fall through to the numpy path's LOUD reshape error — a
        # floor-divided val_bytes here would silently mis-pack
        if values.shape[0] != n or values.nbytes % n:
            return False
        if not values.flags.c_contiguous:
            values = np.ascontiguousarray(values)
        val_bytes = values.nbytes // n
        vptr = values.ctypes.data
    else:
        val_bytes = 0
        vptr = None
    if width * 4 < 8 + val_bytes:
        return False
    if nthreads is None:
        nthreads = min(os.cpu_count() or 1, max(1, out.nbytes >> 23))
    rc = lib.sxt_pack_rows(keys.ctypes.data, vptr, out.ctypes.data,
                           n, width, val_bytes, nthreads)
    return rc == 0


def value_words(val_shape: Tuple[int, ...], val_dtype) -> int:
    nbytes = int(np.prod(val_shape, dtype=np.int64)) * np.dtype(val_dtype).itemsize
    return (nbytes + 3) // 4


def unpack_rows(rows: np.ndarray, val_shape: Optional[Tuple[int, ...]],
                val_dtype) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Inverse of pack_rows for a [n, width] int32 block."""
    n = rows.shape[0]
    if n == 0:
        keys = np.zeros(0, dtype=np.int64)
        values = (np.zeros((0,) + tuple(val_shape), dtype=val_dtype)
                  if val_shape is not None else None)
        return keys, values
    keys = np.ascontiguousarray(
        rows[:, :KEY_WORDS]).view(np.int64).reshape(n)
    if val_shape is None:
        return keys, None
    vw = value_words(val_shape, val_dtype)
    nbytes = int(np.prod(val_shape, dtype=np.int64)) * np.dtype(val_dtype).itemsize
    vb = np.ascontiguousarray(
        rows[:, KEY_WORDS:KEY_WORDS + vw]).view(np.uint8).reshape(n, -1)
    values = vb[:, :nbytes].copy().view(val_dtype).reshape((n,) + tuple(val_shape))
    return keys, values


class _RunIndex:
    """Per-shard run arithmetic for the partition-major receive layout.

    A shard's receive buffer is the concatenation of one segment per
    sender, each internally sorted by partition id. Given the per-sender
    per-partition count matrix M [NS, R] (NS = senders: P for the flat
    exchange, S relays for the hierarchical one) and the shard's owned
    partition range [r_lo, r_hi), partition r's rows are NS contiguous
    runs at
        run_start[s] = seg_start[s] + within[s, r - r_lo]
    — pure prefix sums, no receive-side sort ever happened."""

    def __init__(self, M: np.ndarray, r_lo: int, r_hi: int,
                 align_chunk: int = 0):
        C = np.asarray(M[:, r_lo:r_hi], dtype=np.int64)
        self.lens = C                                     # [NS, k]
        self.within = np.zeros_like(C)
        np.cumsum(C[:, :-1], axis=1, out=self.within[:, 1:])
        seg_sizes = C.sum(axis=1)
        if align_chunk:
            # pallas transport: segments land at CHUNK-aligned starts
            # (dummy-row tails travel with them); runs inside a segment
            # are still dense prefix sums
            seg_sizes = -(-seg_sizes // align_chunk) * align_chunk
        self.seg_start = np.zeros_like(seg_sizes)
        np.cumsum(seg_sizes[:-1], out=self.seg_start[1:])
        self.r_lo = r_lo

    def runs(self, r: int):
        k = r - self.r_lo
        starts = self.seg_start + self.within[:, k]
        lens = self.lens[:, k]
        return [(int(s), int(n)) for s, n in zip(starts, lens) if n]


def max_recv_rows(seg: np.ndarray, part_to_shard: np.ndarray,
                  num_shards: int) -> int:
    """Max over shards of delivered rows, from the seg-count matrix —
    the receive capacity the exchange actually consumed. ``seg`` is the
    replicated [NS, R] matrix (flat exchange) or [P, NS, R] per-shard."""
    best = 0
    for s in range(num_shards):
        r_lo = int(np.searchsorted(part_to_shard, s, "left"))
        r_hi = int(np.searchsorted(part_to_shard, s, "right"))
        m = seg if seg.ndim == 2 else seg[s]
        best = max(best, int(m[:, r_lo:r_hi].sum()))
    return best


class ShuffleReaderResult:
    """Host-side view of one completed exchange (partition-major layout —
    see :class:`_RunIndex` and ``_build_step``)."""

    def __init__(self, num_partitions: int, part_to_shard: np.ndarray,
                 rows: np.ndarray, seg_counts: np.ndarray,
                 val_shape: Optional[Tuple[int, ...]], val_dtype,
                 align_chunk: int = 0):
        # rows: [P, cap_out, width] int32
        # seg_counts: [NS, R] (shared by all shards — flat exchange) or
        #             [P, NS, R] (per shard — hierarchical exchange)
        # align_chunk: >0 for the pallas transport's chunk-aligned
        #             segment layout (see _RunIndex)
        self.num_partitions = num_partitions
        self._part_to_shard = part_to_shard
        self._rows = rows
        self._seg = seg_counts
        self._val_shape = val_shape
        self._val_dtype = val_dtype
        self._align_chunk = align_chunk
        self._runidx: dict = {}
        # dense multi-run partition blocks, built once per partition:
        # repeated partition(r) calls used to re-concatenate the same
        # runs every time (the copy IS the cost — run lookup is prefix
        # sums). Single-run partitions stay uncached views.
        self._block_cache: dict = {}
        # receive capacity the exchange actually ran with (after any
        # overflow retries) — the manager feeds it back as the next plan's
        # starting capacity for this shuffle shape
        self.cap_out_used: Optional[int] = None
        # max per-shard DELIVERED rows (set by the pending handle when
        # observable): what the exchange actually NEEDED, as opposed to
        # what it was provisioned — the manager's learned-cap hint decays
        # toward this, so a one-off skew spike stops inflating every
        # later same-shape plan (round-3 verdict weak #5)
        self.recv_rows_needed: Optional[int] = None

    def _seg_matrix(self, shard: int) -> np.ndarray:
        return self._seg if self._seg.ndim == 2 else self._seg[shard]

    def _runs(self, shard: int) -> _RunIndex:
        ri = self._runidx.get(shard)
        if ri is None:
            r_lo = int(np.searchsorted(self._part_to_shard, shard, "left"))
            r_hi = int(np.searchsorted(self._part_to_shard, shard, "right"))
            ri = _RunIndex(self._seg_matrix(shard), r_lo, r_hi,
                           self._align_chunk)
            self._runidx[shard] = ri
        return ri

    def _shard_rows(self, shard: int) -> np.ndarray:
        return self._rows[shard]

    def is_local(self, r: int) -> bool:
        """True when partition r is readable from this process (always, in
        single-process mode; the distributed subclass restricts it)."""
        return True

    def _partition_block(self, r: int, shard: int) -> np.ndarray:
        """Dense [n, width] rows of partition r (host array).

        Multi-run blocks are built ONCE (one preallocated destination,
        sliced copies — no temp-list concatenate) and cached: every
        repeat ``partition(r)`` used to re-copy the same runs. Single-run
        partitions return a zero-copy view, which needs no cache."""
        rows = self._shard_rows(shard)
        runs = self._runs(shard).runs(r)
        if not runs:
            return rows[:0]
        if len(runs) == 1:
            s, n = runs[0]
            return rows[s:s + n]
        got = self._block_cache.get(r)
        if got is not None:
            return got
        out = _concat_blocks([rows[s:s + n] for s, n in runs])
        self._block_cache[r] = out
        return out

    def partition(self, r: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """(keys, values) of reduce partition r, densely packed.

        Traced as a ``shuffle.fetch`` span (bytes + partition id): the
        per-block-fetch latency record the reference logs on every
        completion (ref: reducer/OnBlocksFetchCallback.java:55-56) — the
        tracer's summary() aggregates it to the p50/p99 BASELINE.md asks
        for. For the lazy subclass the first fetch of a shard carries its
        D2H wait, later fetches are host slicing — exactly the
        block-arrival distribution the reference measures."""
        with GLOBAL_TRACER.span("shuffle.fetch", partition=r) as sp:
            shard = int(self._part_to_shard[r])
            block = self._partition_block(r, shard)
            sp.set(bytes=int(block.nbytes))
            return unpack_rows(block, self._val_shape, self._val_dtype)

    def partitions(self):
        for r in range(self.num_partitions):
            yield r, self.partition(r)

    def partitions_ready(self, poll_s: float = 0.002):
        """Yield every (r, (keys, values)) exactly once, in ARRIVAL
        order where the layout supports it — the reference's
        deliver-blocks-as-they-arrive iterator (reducers consume
        whichever block completes first,
        ref: compat/spark_3_0/UcxShuffleReader.scala:56-98,
        reducer/OnBlocksFetchCallback.java:45-53). On a host-resident
        result everything is already 'arrived': index order."""
        yield from self.partitions()

    def release_partition(self, r: int) -> None:
        """Drop partition r's cached dense block (and, on a waved
        result, its cached cross-wave merge) — the STREAMING-EMIT seam:
        an external-memory consumer that walks partitions in order and
        releases each behind itself keeps its copied-block footprint at
        one partition instead of accumulating the whole dataset in the
        cache (the workloads' join/terasort emit discipline). Safe to
        call for never-fetched or single-run partitions (no-op); a
        later ``partition(r)`` simply rebuilds the block."""
        self._block_cache.pop(r, None)


class LazyShuffleReaderResult(ShuffleReaderResult):
    """Result view over ON-DEVICE arrays with per-shard streaming D2H.

    ``partition(r)`` transfers only the shard holding partition r (cached),
    so partition 0 is readable as soon as its shard's transfer completes —
    the reference's deliver-blocks-as-they-arrive iterator
    (ref: compat/spark_3_0/UcxShuffleReader.scala:56-98,
    reducer/OnBlocksFetchCallback.java:45-53), with XLA's async transfer
    engine playing the progress thread.

    ``fetch_granularity`` — "shard" (default): first touch of a shard
    pulls its whole receive buffer D2H, later partitions are host
    slicing. "partition": each fetch device-slices ONLY the requested
    partition's runs and transfers those bytes — the reference's
    per-BLOCK fetch granularity (conf ``io.fetchGranularity``). Right
    when the D2H link is slow or the consumer reads a sparse partition
    subset; the whole-shard pull amortizes better when every partition
    gets read over a fast link. Fetched blocks are cached host-side
    (re-reads never re-transfer), and once EVERY partition has been
    fetched the device buffers are dropped so the HBM is free for the
    next shuffle — the same release discipline as shard mode. A shard
    already host-materialized keeps the host path."""

    def __init__(self, num_partitions: int, part_to_shard: np.ndarray,
                 rows_dev, seg_dev, num_shards: int, cap_out: int,
                 val_shape, val_dtype, per_shard_segs: bool = False,
                 align_chunk: int = 0):
        self.num_partitions = num_partitions
        self._align_chunk = align_chunk
        self._part_to_shard = part_to_shard
        self._rows_dev = rows_dev          # jax.Array [P*cap_out, width]
        # seg_dev: replicated [NS, R] (flat) or P(axis)-sharded [P*NS, R]
        # (hierarchical, per_shard_segs=True)
        self._seg_dev = seg_dev
        self._per_shard_segs = per_shard_segs
        self._num_shards = num_shards
        self._cap_out = cap_out
        self._val_shape = val_shape
        self._val_dtype = val_dtype
        self._seg = None
        self._runidx: dict = {}
        self._block_cache: dict = {}       # r -> dense multi-run block
        self._shards: dict = {}            # shard -> np [cap_out, width]
        self.cap_out_used: Optional[int] = cap_out
        self.recv_rows_needed: Optional[int] = None
        self.fetch_granularity: str = "shard"
        # per-shard delivered totals, ON DEVICE (the step's [P] output):
        # the device sink's consumer-side valid-row count — attached by
        # the pending handle so the device view never pulls the seg
        # matrix host-side just to learn occupancy
        self._totals_dev = None
        # fired exactly once when the device row buffers are DROPPED
        # (every shard host-cached / every partition fetched) — the
        # device sink's host_view() escape hatch hangs its HBM-residency
        # admission release here, so a fully drained view stops charging
        # a2a.maxBytesInFlight for memory that is already free
        self._on_device_free = None
        self._part_cache: dict = {}        # r -> np [n, width] block
        # ONE result may be shared by concurrent readers (compat/v2
        # caches it per shuffle): the lazy fetch paths flip _seg_dev /
        # _rows_dev to None after materializing, and an unsynchronized
        # second thread between the None-check and the dereference would
        # crash. RLock: _partition_block -> _shard_rows nests.
        self._fetch_lock = threading.RLock()

    def _seg_matrix(self, shard: int) -> np.ndarray:
        with self._fetch_lock:
            if self._seg is None:
                if self._per_shard_segs:
                    self._seg = pull_to_host(self._seg_dev, "seg").reshape(
                        self._num_shards, -1, self.num_partitions)
                else:
                    # replicated output: any addressable copy is the
                    # whole matrix (np.asarray would reject a
                    # multi-process array)
                    self._seg = pull_to_host(
                        self._seg_dev.addressable_shards[0].data, "seg")
                self._seg_dev = None
        return super()._seg_matrix(shard)

    def _shard_dev(self, shard: int):
        """This shard's single-device [cap_out, width] array, or None
        once the device buffers were dropped."""
        if self._rows_dev is None:
            return None
        for s in self._rows_dev.addressable_shards:
            start = s.index[0].start or 0
            if start // self._cap_out == shard:
                return s.data
        return None

    def _shard_rows(self, shard: int) -> np.ndarray:
        with self._fetch_lock:
            got = self._shards.get(shard)
            if got is not None and not isinstance(got, np.ndarray):
                # a2a.wire=lossless parked this shard as a compressed
                # block (compress_host_blocks); first consumer touch
                # restores the exact bytes and keeps them — the codec's
                # win is the UNTOUCHED waves waiting in the pipeline
                from sparkucx_tpu.shuffle.wire import decode_block
                got = decode_block(got)
                self._shards[shard] = got
            if got is None:
                dev = self._shard_dev(shard)
                if dev is None:
                    raise KeyError(f"shard {shard} not addressable here")
                got = pull_to_host(dev, "rows", self)
                self._shards[shard] = got
                if len(self._shards) == self._num_shards:
                    # every shard is host-side; drop the device buffers
                    # so the HBM is free for the next shuffle's exchange
                    self._rows_dev = None
                    self._fire_device_free()
            return got

    def _fire_device_free(self) -> None:
        cb, self._on_device_free = self._on_device_free, None
        if cb is not None:
            try:
                cb()
            except Exception:
                pass

    def with_rows(self, rows_dev) -> "LazyShuffleReaderResult":
        """A fresh lazy view over REPLACEMENT device rows sharing this
        view's seg/layout metadata — the after-consume verification seam
        of the device sink: a consumer step that passes the rows through
        (donation notwithstanding) hands its output here, and reading it
        back through the same run arithmetic proves the handoff moved
        bits, not garbage (test_fuzz_e2e's device-sink leg)."""
        with self._fetch_lock:
            out = LazyShuffleReaderResult(
                self.num_partitions, self._part_to_shard, rows_dev,
                self._seg_dev, self._num_shards,
                rows_dev.shape[0] // self._num_shards,
                self._val_shape, self._val_dtype,
                per_shard_segs=self._per_shard_segs,
                align_chunk=self._align_chunk)
            if self._seg_dev is None:
                # seg already host-materialized here: share the matrix
                out._seg = self._seg
        return out

    def compress_host_blocks(self, executor=None):
        """``a2a.wire=lossless``: re-encode every host-materialized
        shard block as byte-plane + deflate (shuffle/wire.py) — called
        by the wave pipeline right after a wave drains, optionally
        fanned out over the manager's pack executor (the codec rides
        the same thread pool as the pack stage, per the tier's
        host-side contract). Blocks decompress transparently on first
        consumer touch (:meth:`_shard_rows`). Returns
        ``(raw_bytes, compressed_bytes)`` — ACHIEVED figures for the
        report's lossless accounting; (0, 0) when nothing was
        host-resident to encode."""
        from sparkucx_tpu.shuffle.wire import encode_block
        with self._fetch_lock:
            todo = [(s, a) for s, a in self._shards.items()
                    if isinstance(a, np.ndarray)]
        if not todo:
            return (0, 0)

        def enc(item):
            s, a = item
            return s, encode_block(a)

        done = list(executor.map(enc, todo)) if executor is not None \
            else [enc(t) for t in todo]
        raw = comp = 0
        with self._fetch_lock:
            for s, blk in done:
                # swapping under a concurrent reader is safe: any view a
                # consumer already holds keeps its base array alive, and
                # the block restores bit-identical bytes on next touch
                if self._shards.get(s) is not None:
                    self._shards[s] = blk
                raw += blk.raw_bytes
                comp += blk.nbytes
        return raw, comp

    def partitions_ready(self, poll_s: float = 0.002):
        """Arrival-order iteration: shards whose transfer already
        completed yield their partitions first, so a slow shard never
        head-of-line blocks the consumer — the reference's reducers
        likewise consume whichever remote's blocks complete first
        (ref: reducer/OnBlocksFetchCallback.java:45-53).

        EVENT-driven, not a spin: each still-pending shard gets a waiter
        thread parked in the runtime's own completion wait
        (``block_until_ready`` — the WAKEUP-event discipline of the
        reference's progress loop, ref: UcxNode.java:63-66,
        UcxListenerThread.java:44-52), posting to a queue the consumer
        blocks on. ``poll_s`` only matters on the degenerate backend
        shape that exposes ``is_ready`` but no blocking wait — there the
        waiter polls at this interval. Partition granularity transfers
        on demand (arrival order has no meaning there): index order."""
        if self._rows_dev is None or self.fetch_granularity == "partition":
            yield from self.partitions()
            return
        import queue as _queue
        ready_q: "_queue.Queue" = _queue.Queue()
        n_pending = 0
        for s in range(self._num_shards):
            # already-host shards are trivially ready (yield first, in
            # index order); a shard NEITHER host-cached nor
            # device-addressable must fail up front with the descriptive
            # error, not a KeyError mid-iteration (ADVICE r4). The
            # cached/device snapshot rides _fetch_lock: a concurrent
            # reader of the SHARED result (compat/v2) may materialize
            # the final shard — flipping _rows_dev to None — between an
            # unlocked membership check and _shard_dev's dereference.
            with self._fetch_lock:
                cached = s in self._shards
                dev = None if cached else self._shard_dev(s)
            if cached:
                ready_q.put(s)
                n_pending += 1
                continue
            if dev is None:
                raise KeyError(f"shard {s} not addressable here")
            # non-blocking pre-pass: a transfer that already completed
            # (the common case once the exchange quiesced) costs no
            # thread — only genuinely in-flight shards get a waiter
            try:
                already = bool(dev.is_ready())
            except AttributeError:
                already = True       # no readiness API: don't stall
            if already:
                ready_q.put(s)
                n_pending += 1
                continue

            def wait(shard=s, d=dev):
                try:
                    d.block_until_ready()
                except AttributeError:
                    # readiness API without a blocking wait (the pre-pass
                    # just saw is_ready() False): poll INSIDE the waiter —
                    # posting immediately would hand the consumer a
                    # knowingly in-flight transfer
                    pause = threading.Event()
                    try:
                        while not d.is_ready():
                            pause.wait(poll_s)
                    except Exception:
                        pass    # surface errors on the fetch itself
                except Exception:
                    pass        # surface errors on the fetch itself
                ready_q.put(shard)
            t = threading.Thread(target=wait, daemon=True,
                                 name=f"sxt-shard-wait-{s}")
            t.start()
            n_pending += 1
        for _ in range(n_pending):
            s = ready_q.get()       # true event wait, no spin
            # blocked map is sorted (same invariant _runs uses)
            r_lo = int(np.searchsorted(self._part_to_shard, s, "left"))
            r_hi = int(np.searchsorted(self._part_to_shard, s, "right"))
            for r in range(r_lo, r_hi):
                yield r, self.partition(r)

    def _partition_block(self, r: int, shard: int) -> np.ndarray:
        with self._fetch_lock:
            return self._partition_block_locked(r, shard)

    def _partition_block_locked(self, r: int, shard: int) -> np.ndarray:
        if self.fetch_granularity != "partition" \
                or shard in self._shards:
            return super()._partition_block(r, shard)
        got = self._part_cache.get(r)
        if got is not None:
            return got
        dev = self._shard_dev(shard)
        if dev is None:
            return super()._partition_block(r, shard)
        runs = self._runs(shard).runs(r)
        if not runs:
            block = np.zeros((0, dev.shape[1]), np.int32)
        else:
            # Device-slice ONLY this partition's runs and transfer those
            # bytes — the reference's per-BLOCK fetch. Run lengths are
            # bucketed to powers of two so at most log2(cap_out) slice
            # programs ever compile (a per-exact-shape slice would pay
            # one compile per distinct run length).
            import jax as _jax
            cap = dev.shape[0]
            blocks = []
            for s, n in runs:
                bucket = min(cap, 1 << max(0, (n - 1).bit_length()))
                start = min(s, cap - bucket)
                sl = _jax.lax.dynamic_slice_in_dim(dev, start, bucket,
                                                   axis=0)
                host = pull_to_host(sl, "rows", self)
                blocks.append(host[s - start:s - start + n])
            block = blocks[0] if len(blocks) == 1 \
                else np.concatenate(blocks)
        self._part_cache[r] = block
        if len(self._part_cache) == self.num_partitions:
            # every partition is host-side (cached blocks) — drop the
            # device buffers, same HBM-release point as shard mode
            self._rows_dev = None
            self._fire_device_free()
        return block


def merge_sorted_rows(blocks) -> np.ndarray:
    """Merge per-wave key-sorted packed row blocks into one key-sorted
    block (host). Each block is already sorted by signed int64 key (the
    device keysort's order), so one argsort over the concatenation
    restores the ``ordered`` contract across waves — key order only; tie
    order among equal keys is unspecified, exactly like the device sort."""
    rows = np.concatenate(blocks)
    keys = np.ascontiguousarray(
        rows[:, :KEY_WORDS]).view(np.int64).ravel()
    return rows[np.argsort(keys, kind="stable")]


def combine_packed_rows(blocks, val_words_n: int, val_dtype,
                        sum_words: int = 0) -> np.ndarray:
    """Merge per-wave COMBINED packed row blocks by key (host) — the
    cross-wave half of combine-by-key. Each wave's block already holds
    one key-sorted row per distinct key (the device combine ran map- and
    reduce-side within the wave); a key that appeared in several waves
    has one row per wave here, and this pass sums them.

    Numerics match the device combiner's store semantics: integer sums
    are exact modulo the declared dtype's width (accumulating in any
    wider integer then casting is the same ring arithmetic as the
    device's int32 lanes), floats accumulate in float32 and store back
    to the declared dtype. ``sum_words`` transport words are summed, the
    rest of the value row is CARRIED (per-key-constant payload — any
    representative is THE value, same contract as ops/aggregate)."""
    rows = blocks[0] if len(blocks) == 1 else np.concatenate(blocks)
    n = rows.shape[0]
    if n == 0:
        return rows
    keys = np.ascontiguousarray(
        rows[:, :KEY_WORDS]).view(np.int64).ravel()
    order = np.argsort(keys, kind="stable")
    rows = rows[order]
    keys = keys[order]
    starts_mask = np.empty(n, dtype=bool)
    starts_mask[0] = True
    np.not_equal(keys[1:], keys[:-1], out=starts_mask[1:])
    starts = np.flatnonzero(starts_mask)
    # representative row per key carries the key words AND the carried
    # payload lanes; only the summed lanes are overwritten below
    out = rows[starts].copy()
    vdt = np.dtype(val_dtype)
    sw = sum_words if sum_words > 0 else val_words_n
    if sw:
        vals = np.ascontiguousarray(
            rows[:, KEY_WORDS:KEY_WORDS + sw]).view(vdt)
        acc_dt = np.float32 if np.issubdtype(vdt, np.floating) \
            else np.int64
        acc = np.add.reduceat(vals.astype(acc_dt), starts,
                              axis=0).astype(vdt)
        out[:, KEY_WORDS:KEY_WORDS + sw] = \
            np.ascontiguousarray(acc).view(np.int32)
    return out


# -- device-native cross-wave merge (read.sink=device, ordered/combine) ----

def merge_step_body(plan: ShufflePlan, acc_cap: int, wave_cap: int,
                    merge_impl: str):
    """One fold step of the DEVICE cross-wave merge (call under
    shard_map): merge the accumulator's rows with one wave's delivered
    rows — key-sorted merge for ``ordered``, merge + segment-reduce for
    ``combine`` (ops/pallas/segmented.py holds both formulations; the
    numerics mirror :func:`combine_packed_rows` by construction —
    float32 accumulation, integer ring arithmetic, carried lanes).

    Validity is sentinel-encoded (partition id R on invalid rows)
    because neither input's valid rows form a joint prefix after
    concatenation. Output rows are sliced back to ``acc_cap`` — the
    accumulator capacity is derived from the REAL per-shard delivered
    totals across all waves (device_merge_fold), so every surviving row
    fits by construction and the step needs no overflow plumbing."""
    R = plan.num_partitions
    part_fn = _make_part_fn(plan, R)

    from sparkucx_tpu.ops.pallas.segmented import (merge_reduce_rows,
                                                   merge_rows)

    def step(acc_rows, acc_n, wave_rows, wave_n):
        # acc_rows [acc_cap, W]; acc_n [1]; wave_rows [wave_cap, W];
        # wave_n [1] — all per shard
        pa = jnp.where(
            jnp.arange(acc_cap, dtype=jnp.int32) < acc_n[0],
            part_fn(acc_rows), jnp.int32(R))
        pw = jnp.where(
            jnp.arange(wave_cap, dtype=jnp.int32) < wave_n[0],
            part_fn(wave_rows), jnp.int32(R))
        if plan.combine:
            rows_out, pcounts, _ = merge_reduce_rows(
                acc_rows, pa, wave_rows, pw, R, plan.combine_words,
                np.dtype(plan.combine_dtype), plan.combine,
                sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction, impl=merge_impl,
                interpret=plan.pallas_interpret)
        else:
            rows_out, _, pcounts = merge_rows(
                acc_rows, pa, wave_rows, pw, R, impl=merge_impl,
                interpret=plan.pallas_interpret)
        # real rows only: sentinel groups (junk past the valid counts)
        # sort last and must not inflate the carry's valid count — the
        # pallas step body's pcounts-not-group-count discipline
        total = pcounts.sum().astype(jnp.int32).reshape(1)
        return rows_out[:acc_cap], pcounts.reshape(1, R), total

    return step


def _build_merge_step(mesh: Mesh, axis: str, plan: ShufflePlan,
                      acc_cap: int, wave_cap: int, width: int,
                      merge_impl: str):
    """The device merge program for one (merge family) — served from the
    shared step cache so ordered/combine device reads keep the
    one-program-per-family contract (plan.merge_family deliberately
    drops the exchange capacities). The accumulator is DONATED — the
    fold is its last consumer, so XLA may alias the output into its
    HBM; the wave buffer frees through consume()'s dropped references."""
    from sparkucx_tpu.shuffle.plan import merge_family
    from sparkucx_tpu.shuffle.stepcache import (GLOBAL_STEP_CACHE,
                                                program_name)
    fam = merge_family(plan, acc_cap, wave_cap, width, merge_impl)
    name = program_name("wave_merge", plan)
    return GLOBAL_STEP_CACHE.get(
        ("devmerge", mesh, axis) + fam,
        lambda: _build_merge_step_uncached(mesh, axis, plan, acc_cap,
                                           wave_cap, width, merge_impl),
        {"kind": "devmerge", "program": name, "acc_cap": acc_cap,
         "wave_cap": wave_cap, "width": width, "impl": merge_impl,
         "mode": "combine" if plan.combine else "ordered"})


def _build_merge_step_uncached(mesh: Mesh, axis: str, plan: ShufflePlan,
                               acc_cap: int, wave_cap: int, width: int,
                               merge_impl: str):
    from sparkucx_tpu.shuffle.stepcache import jit_named, program_name
    step = merge_step_body(plan, acc_cap, wave_cap, merge_impl)
    sm = jax.shard_map(step, mesh=mesh, in_specs=(P(axis),) * 4,
                       out_specs=(P(axis), P(axis), P(axis)),
                       check_vma=False)
    # donate the ACCUMULATOR only: the wave buffer's last reference is
    # dropped by consume() before the call, so its HBM frees either way,
    # and XLA flags the differently-shaped wave operand as an unusable
    # donation (a per-call warning) when it cannot alias it into the
    # acc-shaped output
    return jit_named(sm, program_name("wave_merge", plan),
                     donate_argnums=(0,))


def _build_seed_acc(mesh: Mesh, axis: str, acc_cap: int, wave_cap: int,
                    width: int, num_parts: int):
    """The fold's FIRST step: seed the accumulator from wave 0 WITHOUT
    a merge — the wave's rows are already partition-major key-sorted
    (the exchange step merged within the wave) and its [1, R] seg row
    is already the accumulator's partition counts, so seeding is a
    pad/truncate to ``acc_cap`` (valid rows are a prefix and fit by the
    acc sizing), not a sort. Saves one full merge program per read —
    on dispatch-bound backends that is a whole launch."""
    from sparkucx_tpu.shuffle.stepcache import (GLOBAL_STEP_CACHE,
                                                jit_named, program_name)
    name = program_name("wave_seed")

    def build():
        def seed(rows, seg, nv):
            if acc_cap <= wave_cap:
                out = rows[:acc_cap]
            else:
                out = jnp.concatenate(
                    [rows, jnp.zeros((acc_cap - wave_cap, width),
                                     jnp.int32)])
            return out, seg, nv
        sm = jax.shard_map(seed, mesh=mesh,
                           in_specs=(P(axis),) * 3,
                           out_specs=(P(axis),) * 3, check_vma=False)
        # no donation: the acc-shaped output cannot alias the wave-
        # shaped input when the caps differ, and XLA warns per call on
        # an unusable donation; the wave buffer frees through the
        # dropped references either way
        return jit_named(sm, name)

    return GLOBAL_STEP_CACHE.get(
        ("devmerge-seed", mesh, axis, acc_cap, wave_cap, width,
         num_parts), build,
        {"kind": "devmerge-seed", "program": name, "acc_cap": acc_cap,
         "wave_cap": wave_cap, "width": width})


def resolve_merge_impl(conf, plan: ShufflePlan) -> str:
    """Resolve ``read.mergeImpl`` against what THIS plan's fold can run
    on THIS backend (the _resolve_wire discipline — pure conf/plan/
    backend facts, delegated to segmented.resolve_kernel_impl so the
    fold and the manager's plan decoration cannot drift): ``auto`` is
    the blocked pallas kernels exactly where they compile natively
    (TPU) and jnp elsewhere; ``pallas`` is honored wherever the
    capability gate clears (TPU native, CPU interpret) and falls back
    to jnp with a log line otherwise — a combine whose value dtype is
    not a 4-byte lane gates either way (the segment-reduce kernel
    accumulates whole transport words)."""
    from sparkucx_tpu.ops.pallas.segmented import resolve_kernel_impl
    impl, reason = resolve_kernel_impl(
        conf.read_merge_impl, jax.default_backend(),
        combine_dtype=plan.combine_dtype or None)
    if reason is not None:
        log.info("read.mergeImpl=%s resolves to jnp for this read: %s "
                 "(segmented.resolve_kernel_impl)",
                 conf.read_merge_impl, reason)
    return impl


def device_merge_fold(res: "DeviceShuffleReaderResult", mesh: Mesh,
                      axis: str, conf, metrics) -> "LazyShuffleReaderResult":
    """Fold a multi-wave ordered/combine DEVICE result into ONE merged
    device view — the on-device replacement for the host cross-wave
    merge (:func:`merge_sorted_rows` / :func:`combine_packed_rows`),
    driven through the result's own ``consume(fn, carry)`` chain so
    every wave's receive buffer is DONATED into the merge program the
    moment the fold reaches it (zero payload D2H by construction).

    The accumulator capacity derives from the REAL per-shard delivered
    totals across waves (one [P]-int pull per wave — metadata-class,
    the seg-matrix exclusion precedent of ``pull_to_host``), quantized on
    the cap-bucket ladder so same-shaped warm reads land on the same
    compiled merge program (0 warm recompiles). The blocked pallas merge
    keeps both runs VMEM-resident, so a fold larger than
    ``segmented.merge_rows_cap`` merges with the jnp path — counted in
    ``metrics`` as a ``vmem_capacity`` kernel fallback."""
    from sparkucx_tpu.shuffle.plan import bucket_cap_conf
    plan = res._plan
    Pn = plan.num_shards
    R = plan.num_partitions
    views = res.wave_views()
    # multi-process device views hold only their local totals shards;
    # local_totals_row sums the full [P] row over the agreement channel
    # (one metadata round per wave) so acc sizing agrees everywhere
    from sparkucx_tpu.shuffle.distributed import local_totals_row
    totals = np.stack([local_totals_row(v._totals_dev, Pn)
                       for v in views])                     # [W, P]
    need = int(totals.sum(axis=0).max()) if totals.size else 0
    acc_cap = bucket_cap_conf(max(8, -(-need // 8) * 8), conf)
    width = views[0]._rows_dev.shape[1]
    merge_impl = resolve_merge_impl(conf, plan)
    from sparkucx_tpu.ops.pallas.segmented import merge_rows_cap
    wave_cap = views[0]._rows_dev.shape[0] // Pn
    if merge_impl == "pallas" and acc_cap + wave_cap > merge_rows_cap(width):
        log.info("device merge of %d + %d rows exceeds the pallas merge's "
                 "VMEM cap (%d rows); merging with jnp", acc_cap, wave_cap,
                 merge_rows_cap(width))
        from sparkucx_tpu.utils.metrics import C_KERNEL_FALLBACK, labeled
        metrics.inc(C_KERNEL_FALLBACK, 1.0)
        metrics.inc(labeled(C_KERNEL_FALLBACK, reason="vmem_capacity"), 1.0)
        merge_impl = "jnp"
    # wave 0 seeds the accumulator sort-free (its rows are already
    # merged within the wave and its seg row IS the acc's counts) —
    # grab its on-device seg BEFORE consume drops the view's buffers
    seg0 = views[0]._seg_dev
    seg_box = {}
    wave_i = [0]

    def fold(carry, rows, tot):
        wave_cap = rows.shape[0] // Pn
        if wave_i[0] == 0:
            sstep = _build_seed_acc(mesh, axis, acc_cap, wave_cap,
                                    width, R)
            out_rows, pcounts, out_n = sstep(rows, seg0, tot)
        else:
            a_rows, a_n = carry
            mstep = _build_merge_step(mesh, axis, plan, acc_cap,
                                      wave_cap, width, merge_impl)
            out_rows, pcounts, out_n = mstep(a_rows, a_n, rows, tot)
        wave_i[0] += 1
        seg_box["seg"] = pcounts
        return (out_rows, out_n)

    with GLOBAL_TRACER.span("shuffle.merge", waves=len(views),
                            impl=merge_impl):
        acc_rows, acc_n = res.consume(fold, None)
    view = LazyShuffleReaderResult(
        R, np.asarray(_blocked_map(R, Pn)), acc_rows, seg_box["seg"],
        Pn, acc_cap, res._val_shape, res._val_dtype,
        per_shard_segs=True)
    view._totals_dev = acc_n
    return view


def drain_wave_result(res) -> None:
    """Drain one completed wave: pull every locally-addressable shard's
    receive buffer (and the seg matrix) host-side NOW — the D2H stage of
    the wave pipeline. LazyShuffleReaderResult drops its device arrays
    once every shard is host-cached, so after this the wave holds no HBM
    and the collectives behind it in the pipeline have the device memory
    to themselves. Host-resident results (the distributed view) are
    already drained — no-op."""
    if isinstance(res, LazyShuffleReaderResult):
        res._seg_matrix(0)
        for s in range(res._num_shards):
            try:
                res._shard_rows(s)
            except KeyError:
                pass        # shard not addressable on this process


class WavedShuffleReaderResult(ShuffleReaderResult):
    """Composed host-side view over the W per-wave results of a
    wave-pipelined exchange (manager.PendingWaveShuffle).

    Each wave was a complete mini-exchange over a fixed-size slice of
    the staged rows, so partition r's rows are the union of its rows in
    every wave — served as W x NS contiguous runs through each wave's
    OWN run index (the existing ``_RunIndex`` arithmetic with the sender
    axis effectively stacked to senders x waves; no receive-side sort
    ever happened, per wave or across them). Cross-wave semantics:

    * plain    — runs concatenate wave-major (row order within a
                 partition is unspecified, as in single-shot);
    * ordered  — per-wave key-sorted runs merge by key on the host
                 (``merge_sorted_rows``);
    * combine  — per-wave combined rows merge-by-key with the summed /
                 carried lane split (``combine_packed_rows``), restoring
                 one row per distinct key.

    Merged partition blocks land in the base class's block cache, so
    repeat ``partition(r)`` calls pay the merge once. Everything is
    host-resident by construction (the pipeline drained every wave
    before assembling this), so ``partitions_ready`` is index order."""

    def __init__(self, wave_results, plan: ShufflePlan, val_shape,
                 val_dtype):
        if not wave_results:
            raise ValueError("waved result needs at least one wave")
        self._waves = list(wave_results)
        self._plan = plan
        self.num_partitions = plan.num_partitions
        self._part_to_shard = wave_results[0]._part_to_shard
        self._val_shape = val_shape
        self._val_dtype = val_dtype
        self._block_cache: dict = {}
        self.waves = len(wave_results)
        # wave capacities live on the per-wave results; the manager's
        # single-shot cap-hint learner must not ratchet on wave shapes
        self.cap_out_used = None
        self.recv_rows_needed = None

    def wave_results(self):
        """The per-wave results, in wave order — each a complete view of
        that wave's slice (streaming consumers can fold partial
        partitions wave by wave)."""
        return list(self._waves)

    def is_local(self, r: int) -> bool:
        return self._waves[0].is_local(r)

    def partition(self, r: int):
        if not self.is_local(r):
            # same reducer contract as the distributed view: non-local
            # partitions fail loudly, never return wrong data
            raise KeyError(
                f"partition {r} lives on shard "
                f"{int(self._part_to_shard[r])}, not on this process")
        return super().partition(r)

    def partitions(self):
        for r in range(self.num_partitions):
            if self.is_local(r):
                yield r, self.partition(r)

    def _partition_block(self, r: int, shard: int) -> np.ndarray:
        got = self._block_cache.get(r)
        if got is not None:
            return got
        blocks = [b for b in (w._partition_block(r, shard)
                              for w in self._waves) if b.shape[0]]
        if not blocks:
            return self._waves[0]._partition_block(r, shard)
        if (self._plan.combine or self._plan.ordered) and len(blocks) > 1:
            with GLOBAL_TRACER.span("shuffle.merge", waves=len(blocks),
                                    impl="host") as sp:
                if self._plan.combine:
                    block = combine_packed_rows(
                        blocks, self._plan.combine_words,
                        np.dtype(self._plan.combine_dtype),
                        self._plan.combine_sum_words)
                    sp.set(rows_in=sum(b.shape[0] for b in blocks),
                           rows_out=int(block.shape[0]))
                else:
                    block = merge_sorted_rows(blocks)
        elif len(blocks) == 1:
            block = blocks[0]
        else:
            block = _concat_blocks(blocks)
        self._block_cache[r] = block
        return block

    def release_partition(self, r: int) -> None:
        """The streaming-emit seam on a waved result must release the
        per-WAVE cached blocks too: the cross-wave merge above pulls
        ``w._partition_block(r, shard)`` from every wave, and each wave
        caches its own multi-run concatenation — dropping only the
        top-level merge would leave W copies of the partition resident
        and the consumer's footprint would grow with the dataset."""
        super().release_partition(r)
        for w in self._waves:
            w.release_partition(r)


class DeviceShuffleReaderResult:
    """Device-resident result of one exchange (``read.sink=device``) —
    the read path with the host round-trip deleted.

    Partitions never leave HBM: each wave's receive buffer stays the
    sharded jax Array the compiled step produced (single-shot reads are
    one wave), and :meth:`consume` chains them into a consumer step —
    this result drops its OWN references to a wave's buffers before the
    handoff, so a consumer jitted with ``donate_argnums`` may alias them
    in place. Zero D2H by construction: ``shuffle.read.d2h.bytes`` does
    not move (tests/test_device_sink.py holds the delta at 0), where the
    host path pays a full drain plus the consumer's re-upload.

    The admission reservation of the exchange (HBM residency — the
    receive buffers live until the consumer takes them, unlike the host
    path whose on_done frees them at drain) is released when the result
    is consumed or closed (``_release_hbm``, armed by the manager).

    ``host_view()`` is the escape hatch back to the numpy partition
    contract: over the live buffers it COUNTS the d2h it forces; over
    consumer-returned row arrays (``wave_rows=...``) it is the
    after-consume verification seam."""

    sink = "device"

    def __init__(self, views, plan: ShufflePlan, val_shape, val_dtype):
        if not views:
            raise ValueError("device result needs at least one wave view")
        self._views: Optional[list] = list(views)
        self._plan = plan
        self._val_shape = val_shape
        self._val_dtype = val_dtype
        self.num_partitions = plan.num_partitions
        self.waves = len(views)
        self.consumed = False
        # manager-armed: admission release (HBM residency accounting)
        self._release_hbm = None
        # capacity-learning contract (manager._learn_cap): the plan
        # capacity, like the lazy result; the true requirement is not
        # observed — reading the seg matrix host-side would be the very
        # metadata pull this sink exists to avoid paying per read
        self.cap_out_used: Optional[int] = plan.cap_out if self.waves == 1 \
            else None
        self.recv_rows_needed: Optional[int] = None

    def is_local(self, r: int) -> bool:
        return self._views[0].is_local(r) if self._views else True

    def wave_views(self):
        """The per-wave device-holding views, wave order (metadata
        handles — the buffers themselves are reachable via
        ``device_rows``/``device_totals`` until consumed)."""
        return list(self._views or [])

    def _live_views(self) -> list:
        if self.consumed or self._views is None:
            raise RuntimeError(
                "device result already consumed/closed: its buffers were "
                "handed to the consumer step (donation) — re-read the "
                "shuffle, or keep the consumer's outputs")
        return self._views

    def device_rows(self, wave: int = 0):
        """Wave ``wave``'s receive buffer: [P*cap_shard, width] int32,
        sharded over the exchange axis. Rows are the packed transport
        format (keys + bit-cast value lanes) — consumers decode on
        device (jax.lax.bitcast_convert_type), see models/moe.py."""
        return self._live_views()[wave]._rows_dev

    def device_totals(self, wave: int = 0):
        """Wave ``wave``'s per-shard delivered row counts: [P] int32,
        sharded like the rows — the consumer-side valid-row bound."""
        return self._live_views()[wave]._totals_dev

    def consume(self, fn, carry=None):
        """Chain the consumer step over the per-wave device buffers:
        ``carry = fn(carry, rows, totals)`` per wave, wave order. Before
        each call this result DROPS its references to that wave's
        buffers, so a consumer jitted with ``donate_argnums`` on the
        rows argument aliases the HBM in place. After the last wave the
        admission reservation is released. Returns the final carry."""
        views = self._live_views()
        try:
            for v in views:
                with v._fetch_lock:
                    rows, totals = v._rows_dev, v._totals_dev
                    v._rows_dev = None
                    v._totals_dev = None
                if rows is None:
                    raise RuntimeError(
                        "device wave buffers already taken — consume() "
                        "ran concurrently or device_rows escaped")
                carry = fn(carry, rows, totals)
                del rows, totals
        except BaseException:
            # a consumer that dies mid-fold must not leave the REMAINING
            # waves' receive buffers pinned while the finally below
            # frees their admission reservation — drop the views so the
            # HBM goes with the budget (the close() discipline)
            self._views = None
            raise
        finally:
            self.consumed = True
            self._fire_release()
        return carry

    def host_view(self, wave_rows=None):
        """A HOST-readable result (the numpy ``partition(r)`` contract).

        Without arguments: over the LIVE device buffers — forces (and
        counts, ``shuffle.read.d2h.bytes``) the drain the device sink
        deferred; invalid after :meth:`consume`. With ``wave_rows`` (one
        array per wave, shaped like ``device_rows``): over
        consumer-returned buffers — the after-consume verification path,
        valid any time."""
        if wave_rows is None:
            views = list(self._live_views())
            # the escape hatch transfers the HBM-residency admission
            # release to the DRAIN itself: once every view's device
            # buffers drop (all shards host-side), the reservation
            # frees — a fully drained device result must not keep
            # charging a2a.maxBytesInFlight for memory that is free
            remaining = [len(views)]
            lock = threading.Lock()

            def one_freed():
                with lock:
                    remaining[0] -= 1
                    done = remaining[0] == 0
                if done:
                    self._fire_release()
            for v in views:
                v._on_device_free = one_freed
        else:
            base = self._views or []
            if len(wave_rows) != len(base):
                raise ValueError(
                    f"wave_rows has {len(wave_rows)} entries for "
                    f"{len(base)} waves")
            views = [v.with_rows(r) for v, r in zip(base, wave_rows)]
        if len(views) == 1:
            return views[0]
        return WavedShuffleReaderResult(views, self._plan,
                                        self._val_shape, self._val_dtype)

    def partition(self, r: int):
        raise RuntimeError(
            "device-sink results hold partitions in HBM — consume() them "
            "into a jitted step, or host_view() for the numpy contract "
            "(which re-pays the D2H this sink deletes); a numpy consumer "
            "under conf read.sink=device should read(sink='host'). This "
            "holds for ALL four read modes now: plain/shard, ordered "
            "(device-merged key order) and combine (device segment-"
            "reduce) all land device-resident — rows are valid up to "
            "device_totals() per shard, key-sorted within partitions "
            "for ordered/combine")

    # the numpy-iteration surface fails CLOSED with the same guidance —
    # a host-contract consumer handed a device result by a conf-level
    # read.sink=device must get the remediation, not an AttributeError
    def partitions(self):
        self.partition(0)

    def partitions_ready(self, poll_s: float = 0.002):
        self.partition(0)

    def close(self) -> None:
        """Drop the device buffers without consuming them (frees the HBM
        and the admission reservation) — the abandon path."""
        self.consumed = True
        self._views = None
        self._fire_release()

    def _fire_release(self) -> None:
        cb, self._release_hbm = self._release_hbm, None
        if cb is not None:
            try:
                cb()
            except Exception:
                pass

    def __del__(self):
        try:
            self._fire_release()
        except Exception:
            pass


class PendingExchangeBase:
    """Shared lifecycle for future-like exchange handles (single- and
    multi-process — shuffle/distributed.py subclasses this).

    Subclass contract: ``__init__`` must set ``_result = None``,
    ``_attempt = 0``, ``_on_done = None``, run the first dispatch via
    ``_initial_dispatch(admit)`` (which sets ``self._out`` — or defers,
    see below), and only THEN arm ``_on_done`` — so a dispatch failure
    inside ``__init__`` leaves cleanup with the caller and this
    half-built object's ``__del__`` cannot fire the callback a second
    time (double pool.put of the pinned pack buffer). Subclasses
    implement ``_dispatch()`` and ``_result_inner()`` (the overflow-retry
    loop returning the reader result).

    Admission control: ``admit`` is None (no cap) or a callable
    ``admit(block: bool) -> bool`` from the manager's maxBytesInFlight
    accounting. When the submit-time non-blocking attempt fails, the
    exchange QUEUES — ``done()`` stays False and the dispatch happens
    inside ``result()`` once earlier exchanges release capacity (the
    deferred-request model of Spark's ShuffleBlockFetcherIterator,
    ref: UcxShuffleReader.scala:56-70 — a blocking submit would deadlock
    a single-threaded caller that resolves handles in order)."""

    def _initial_dispatch(self, admit) -> None:
        self._admit_cb = None
        self._dead = False
        self._out = None
        if admit is None or admit(False):
            self._dispatch()
        else:
            self._admit_cb = admit   # deferred: dispatch in result()

    def _outputs_ready(self) -> bool:
        """Stage-local poll: the CURRENTLY DISPATCHED outputs are
        computed on device. For single-program exchanges this is
        done(); a multi-stage handle (PendingTieredShuffle) overrides
        done() with its whole-exchange view while this stays the
        honest is-the-device-busy probe — the wave pipeline's
        measured-overlap accounting reads THIS (a pack only counts
        hidden when a dispatched program was provably still running,
        never when the device idled between stages)."""
        if self._result is not None or getattr(self, "_dead", False):
            return True
        if getattr(self, "_admit_cb", None) is not None \
                or self._out is None:
            return False             # queued behind maxBytesInFlight
        try:
            return all(bool(x.is_ready()) for x in self._out)
        except AttributeError:  # backend array without is_ready
            return True

    def done(self) -> bool:
        """True once the current attempt's outputs are computed on device
        (local poll; result() then blocks only on D2H / consensus).
        A handle whose result() failed reports done (completed
        exceptionally, the Future convention); retrying raises."""
        return self._outputs_ready()

    def _notify(self, result) -> None:
        """Fire on_done exactly once — with the result, or None on failure
        (so the owner can release the pinned pack buffer either way)."""
        if self._on_done is not None:
            cb, self._on_done = self._on_done, None
            cb(result)

    def __del__(self):
        # A submitted-then-abandoned handle must still return the pinned
        # pack buffer to the pool — but only after the in-flight dispatch
        # has finished consuming it: on_done recycles the buffer, and the
        # async device_put/step may still be reading that host memory
        # (result() is safe because it blocks on the outputs first; this
        # path must do the same or the pool hands the bytes to the next
        # shuffle mid-DMA).
        try:
            if self._result is None and not getattr(self, "_dead", False) \
                    and getattr(self, "_out", None):
                # never block on a DEAD handle's outputs: a failed
                # distributed exchange's collective outputs may never
                # complete (peer gone) — blocking would hang GC/shutdown
                for x in self._out:
                    try:
                        x.block_until_ready()
                    except Exception:
                        break
            self._notify(None)
        except Exception:
            pass

    def result(self):
        if self._result is not None:
            return self._result
        if getattr(self, "_dead", False):
            raise RuntimeError(
                "exchange handle is dead: a previous result() failed and "
                "its buffers were released — re-submit the shuffle")
        try:
            if getattr(self, "_admit_cb", None) is not None:
                # queued submit: wait for capacity, then run the deferred
                # first dispatch (raises TimeoutError if nothing frees)
                admit, self._admit_cb = self._admit_cb, None
                admit(True)
                # anatomy span (pack phase): the deferred first dispatch
                # runs here, outside the manager's dispatch span — the
                # admission wait above is covered, this must be too
                with GLOBAL_TRACER.span("shuffle.dispatch",
                                        deferred=True):
                    self._dispatch()
            res = self._result_inner()
            # post-result hook (manager arms it at integrity.verify=full):
            # the post-collective digest check runs INSIDE result() so
            # async submit()/result() consumers get the same verification
            # as read() — a raise here takes the failure path below like
            # any other exchange error (typed, replay-absorbable)
            hook = getattr(self, "_post_result", None)
            if hook is not None:
                hook(res)
        except Exception:
            # on_done fires exactly once and releases the pinned pack
            # buffer, so the handle cannot be retried — mark it dead for a
            # clear error instead of an AttributeError on stale state.
            # _out is dropped too: __del__ must not find (and block on)
            # outputs of a failed collective.
            self._dead = True
            self._out = None
            self._notify(None)
            raise
        self._result = res
        self._out = None
        self._notify(res)
        return res


class PendingShuffle(PendingExchangeBase):
    """Future-like handle for an in-flight exchange — the submit/poll
    split the reference gets from its non-blocking ``ucp_get`` storm +
    lazy-progress iterator (ref: UcxShuffleClient.java (3.0):95-127,
    UcxWorkerWrapper.scala:109-120). XLA dispatch is already asynchronous;
    this object simply refrains from forcing device-to-host reads, so the
    caller can pack/submit the NEXT shuffle (or run any host work) while
    the collective is on the wire.

    ``done()``   — non-blocking readiness poll.
    ``result()`` — block, run the overflow-retry loop if needed, and
                   return a :class:`LazyShuffleReaderResult` that streams
                   each shard D2H on first touch."""

    def __init__(self, build_step, sharding, plan: ShufflePlan,
                 shard_rows: np.ndarray, shard_nvalid: np.ndarray,
                 val_shape, val_dtype, on_done=None,
                 per_shard_segs: bool = False, admit=None,
                 wire_seed: int = 0):
        self._build_step = build_step
        self._sharding = sharding
        self._plan = plan
        self._per_shard_segs = per_shard_segs
        self._rows_host = shard_rows
        self._nvalid_host = shard_nvalid
        self._val_shape = val_shape
        self._val_dtype = val_dtype
        # int8-wire noise base (the manager threads its exchange seq —
        # identical on every process); each overflow retry offsets it so
        # the re-run draws fresh rounding noise
        self._wire_seed = int(wire_seed)
        self._on_done = None
        self._result: Optional[ShuffleReaderResult] = None
        self._attempt = 0
        self._initial_dispatch(admit)
        self._on_done = on_done

    def _dispatch(self) -> None:
        from sparkucx_tpu.io.dlpack import stage_to_device
        width = self._rows_host.shape[2]
        step = self._build_step(self._plan)
        # the device-plane join point: the manager reads this step's
        # cost_record (stepcache harvest) into ExchangeReport.device_cost
        # at on_done — after a retry regrow this is the FINAL program
        self._step = step
        # one DMA from the pinned pack buffer, already mesh-sharded — no
        # pageable bounce, no resharding copy (round-1 weak #3)
        rows_flat = stage_to_device(
            self._rows_host.reshape(-1, width), self._sharding)
        nvalid = stage_to_device(
            seeded_nvalid(self._plan, self._nvalid_host,
                          self._wire_seed + self._attempt),
            self._sharding)
        self._out = step(rows_flat, nvalid)

    def _result_inner(self) -> ShuffleReaderResult:
        while True:
            rows_out, seg, total, ovf = self._out
            # anatomy span: materializing the overflow flag blocks until
            # the dispatched collective drains — the single-process flat
            # transfer wait (single-slice mesh => the ICI tier;
            # containment-matched, no trace id on this signature)
            with GLOBAL_TRACER.span("shuffle.exchange.wait",
                                    tier="ici") as sp:
                last = np.asarray(ovf)
                overflowed = bool(overflow_flags(
                    last, self._plan.num_shards).any())
                if self._plan.combine and not overflowed:
                    # the combine's row counts rode the flag's transfer
                    status = last.reshape(self._plan.num_shards, -1)
                    sp.set(rows_in=int(self._nvalid_host.sum()),
                           rows_wire=int(status[:, 1].sum()),
                           rows_out=int(status[:, 2].sum()))
                    scanned = reduce_rows_scanned(
                        self._plan, self._rows_host.shape[2])
                    if scanned:
                        # the kernel's grid, and the tiles that hold a
                        # row: it skips the rest
                        from sparkucx_tpu.ops.pallas.segmented import \
                            reduce_tiles
                        sp.set(reduce_tiles=self._plan.num_shards
                               * int(reduce_tiles(scanned)),
                               reduce_tiles_live=int(
                                   reduce_tiles(status[:, 1]).sum()))
            if not overflowed:
                break
            if self._attempt >= self._plan.max_retries:
                raise RuntimeError(
                    f"shuffle still overflowing after "
                    f"{self._plan.max_retries} retries "
                    f"(cap_out={self._plan.cap_out}); extreme skew — "
                    f"repartition the data")
            log.info("shuffle overflow at cap_out=%d (attempt %d); "
                     "growing", self._plan.cap_out, self._attempt)
            self._plan = self._plan.grown()
            self._attempt += 1
            # anatomy span (pack phase): the grown-capacity redispatch
            # re-stages the rows and re-dispatches inside result() —
            # dark on every overflow retry otherwise (containment-
            # matched, no trace id on the pending side)
            with GLOBAL_TRACER.span("shuffle.dispatch",
                                    retry=self._attempt):
                self._dispatch()
        # anatomy span (sink phase): result assembly — the seg-matrix
        # host read and the lazy-result wrapper — is the tail between
        # the collective draining and on_done settling the wall
        with GLOBAL_TRACER.span("shuffle.result",
                                sink=self._plan.sink):
            Pn = self._plan.num_shards
            R = self._plan.num_partitions
            # cap per shard derives from the OUTPUT (the pallas
            # transport rounds cap_out up to its chunk-aligned
            # effective capacity)
            cap_shard = rows_out.shape[0] // Pn
            align_chunk = 0
            if self._plan.impl == "pallas" and not (self._plan.combine
                                                    or self._plan.ordered):
                # plain pallas delivers the chunk-aligned layout;
                # combine/ordered densify on device and use the normal
                # [1, R] contract. Chunk follows the WIRE row width —
                # the same wire_row_words seam the step aligned with
                from sparkucx_tpu.ops.pallas.ragged_a2a import \
                    chunk_rows_for
                align_chunk = chunk_rows_for(
                    wire_row_words(self._plan, self._rows_host.shape[2]))
            elif self._plan.strips_active():
                # strip-sorted single-shard layout: each of the S
                # virtual senders occupies one strip_rows-sized region
                # (step_body's strip fast path); the [S, R] seg matrix
                # indexes it with strip-aligned segment starts
                align_chunk = self._plan.strip_rows()
            res = LazyShuffleReaderResult(
                R, np.asarray(_blocked_map(R, Pn)), rows_out, seg,
                Pn, cap_shard, self._val_shape, self._val_dtype,
                per_shard_segs=self._per_shard_segs,
                align_chunk=align_chunk)
            # report the PLAN capacity, not the chunk-inflated buffer
            # size: cap_out_used feeds the manager's learned-cap hint,
            # and the inflated value would ratchet every same-shape
            # pallas read into a bigger plan (and a recompile) forever
            res.cap_out_used = self._plan.cap_out
            res._totals_dev = total
            if self._plan.sink == "device":
                # device-resident sink: partitions stay the sharded
                # arrays above — no drain, no seg pull (even the
                # metadata read is deferred to an explicit host_view);
                # the manager arms the HBM-residency release on the
                # wrapper
                return DeviceShuffleReaderResult(
                    [res], self._plan, self._val_shape, self._val_dtype)
            if not (self._plan.combine or self._plan.impl == "pallas"):
                # plain/ordered: the seg matrix carries true delivered
                # counts (combine's is post-merge; pallas consumes
                # aligned slack) — observable "needed" capacity for the
                # manager's hint decay. Forcing _seg_matrix here costs
                # one tiny host read the result would do on first
                # partition() anyway.
                res.recv_rows_needed = max_recv_rows(
                    res._seg_matrix(0) if not self._per_shard_segs
                    else pull_to_host(seg, "seg").reshape(Pn, -1, R),
                    np.asarray(_blocked_map(R, Pn)), Pn)
            return res


def submit_shuffle(
    mesh: Mesh,
    axis: str,
    plan: ShufflePlan,
    shard_rows: np.ndarray,
    shard_nvalid: np.ndarray,
    val_shape: Optional[Tuple[int, ...]],
    val_dtype,
    on_done=None,
    admit=None,
    wire_seed: int = 0,
) -> PendingShuffle:
    """Dispatch the exchange without blocking (see :class:`PendingShuffle`).

    shard_rows   — [P, cap_in, width] fused int32 rows per shard
    shard_nvalid — [P] valid row counts
    wire_seed    — int8-wire noise base (ignored on other tiers); the
                   manager threads its exchange sequence through it so
                   every exchange — and every wave of one — draws a
                   fresh stochastic-rounding realization
    """
    from jax.sharding import NamedSharding
    width = shard_rows.shape[2]
    return PendingShuffle(
        lambda p: _build_step(mesh, axis, p, width),
        NamedSharding(mesh, P(axis)), plan, shard_rows, shard_nvalid,
        val_shape, val_dtype, on_done=on_done, admit=admit,
        wire_seed=wire_seed,
        # combined/ordered output is one run per partition: the seg matrix
        # is each shard's own [1, R] counts, sharded like the rows
        per_shard_segs=bool(plan.combine or plan.ordered))


def read_shuffle(
    mesh: Mesh,
    axis: str,
    plan: ShufflePlan,
    shard_rows: np.ndarray,
    shard_nvalid: np.ndarray,
    val_shape: Optional[Tuple[int, ...]],
    val_dtype,
) -> ShuffleReaderResult:
    """Blocking exchange with overflow retry (submit + immediate result)."""
    return submit_shuffle(mesh, axis, plan, shard_rows, shard_nvalid,
                          val_shape, val_dtype).result()
