"""Multi-process (multi-host) read path — the DCN-scale deployment shape.

The reference runs one ``UcxNode`` per Spark executor process and scales to
many hosts through the driver's full-mesh introduction RPC
(ref: UcxNode.java:111-145, rpc/RpcConnectionCallback.java:70-84). The TPU
analog is JAX multi-controller: every process calls
``jax.distributed.initialize`` (the rendezvous), ``jax.devices()`` spans
the cluster, and ONE SPMD program executes the exchange — the same
compiled step as single-process, just over a bigger mesh.

What is genuinely different from the single-process path:

- **Map outputs are process-local.** A mapper's staged rows live in its
  process's host arena and can only be device_put onto that process's
  devices — exactly Spark's "map outputs stay on the executor's local
  disk". So map outputs round-robin over the *local* shards, and the
  global send buffer is assembled with
  ``jax.make_array_from_process_local_data``.
- **The metadata plane needs a real wire.** Size rows / schema / presence
  are per-process facts; they cross processes with
  ``multihost_utils.process_allgather`` (the driver-table fetch analog,
  ref: UcxWorkerWrapper.scala:176-196, as a collective instead of a
  one-sided read of a driver buffer).
- **Results are partial views.** Each process owns the reduce partitions
  that land on its shards (Spark reducers read only their partition);
  ``partition(r)`` raises for non-local partitions instead of silently
  returning wrong data.

Every process MUST call :func:`read_shuffle_distributed` (it is a
collective); mismatched call counts deadlock, like any SPMD program.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.shuffle.plan import ShufflePlan, wire_row_words
from sparkucx_tpu.shuffle.reader import (
    LazyShuffleReaderResult, PendingExchangeBase, ShuffleReaderResult,
    _blocked_map, _build_step, max_recv_rows, overflow_flags,
    seeded_nvalid)
from sparkucx_tpu.shuffle.topology import (PendingTieredShuffle,
                                           TierHooks,
                                           TopologyDescriptor)
from sparkucx_tpu.utils.logging import get_logger

log = get_logger("shuffle.distributed")


def local_shard_ids(mesh: Mesh) -> list:
    """Global flat shard indices owned by this process, in mesh order."""
    me = jax.process_index()
    return [i for i, d in enumerate(mesh.devices.reshape(-1))
            if d.process_index == me]


def allgather_sizes(local_vals: np.ndarray, shard_ids: Sequence[int],
                    num_shards: int) -> np.ndarray:
    """Scatter this process's per-shard values into a [num_shards] row and
    sum-allgather so every process holds the full size row — the
    driver-table fetch (ref: UcxWorkerWrapper.scala:176-196) as a
    collective."""
    row = np.zeros(num_shards, dtype=np.int64)
    row[list(shard_ids)] = np.asarray(local_vals, dtype=np.int64)
    # [nproc, num_shards]; rides the watchdog-fenced channel
    gathered = allgather_blob(row, what="size-row allgather")
    return gathered.sum(axis=0)


def allgather_blob(blob: np.ndarray,
                   what: str = "metadata allgather",
                   timeout_ms: Optional[float] = None) -> np.ndarray:
    """[nproc, ...] stack of one small host array per process (schema
    agreement checks).

    THE metadata-plane wire — size rows, schema agreement, wave
    agreement, completeness barriers, overflow verdicts and the
    telemetry gathers all frame through here — and therefore THE place
    a dead peer parks every survivor. The call is deadline-fenced by
    the process watchdog (``failure.collectiveTimeoutMs``,
    runtime/watchdog.py): on expiry it raises
    :class:`~sparkucx_tpu.runtime.failures.PeerLostError` after a
    liveness probe and a flight postmortem, instead of hanging forever.
    With the watchdog off (the default) this is a direct call.
    ``timeout_ms`` overrides the watchdog's standing deadline for this
    one round (the agreement plane threads per-tier deadlines through
    here).

    Anatomy span: every round records as ``shuffle.barrier`` (the
    barrier_wait phase) — the call is a rendezvous on the slowest
    process by construction. No trace attr (the channel is shared by
    trace-less callers like the clock-anchor gather); the ledger
    attributes it by containment inside the exchange wall."""
    from jax.experimental import multihost_utils

    from sparkucx_tpu.runtime.watchdog import current_watchdog
    from sparkucx_tpu.utils.trace import GLOBAL_TRACER
    with GLOBAL_TRACER.span("shuffle.barrier", kind="allgather",
                            what=what):
        out = current_watchdog().call(
            lambda: np.asarray(multihost_utils.process_allgather(blob)),
            what=what, timeout_ms=timeout_ms)
    # jax's process_allgather skips the leading [nproc] axis at nproc=1
    # (identity); restore the documented [nproc, ...] contract so the
    # degenerate single-process gather — the shape every distributed
    # code path is TESTED under — indexes like the real one
    if out.shape == np.shape(blob):
        import jax
        if jax.process_count() == 1:
            out = out[None]
    return out


def allgather_json(obj) -> list:
    """COLLECTIVE: allgather one JSON-able object per process; returns
    the per-process list (process order). Two allgather rounds — length,
    then max-padded payload — over the same metadata-plane channel the
    schema agreement rides. The telemetry plane's cross-process wire:
    gather_reports, gather_spans and the connect-time clock-anchor
    exchange all speak through here, so the framing cannot drift
    between them. Entries that fail to decode come back as {} (a
    telemetry gather must degrade, not hang the job)."""
    import json as _json
    raw = np.frombuffer(_json.dumps(obj).encode(), dtype=np.uint8)
    lens = allgather_blob(np.array([raw.size], dtype=np.int64))[:, 0]
    cap = max(int(lens.max()), 1)
    buf = np.zeros(cap, dtype=np.uint8)
    buf[:raw.size] = raw
    gathered = allgather_blob(buf)                      # [nproc, cap]
    out = []
    for row, n in zip(gathered, lens):
        try:
            out.append(_json.loads(bytes(row[:int(n)]).decode()))
        except ValueError:
            out.append({})
    return out


def agree_wave_count(local_waves: int) -> int:
    """COLLECTIVE: agree on the wave count of a wave-pipelined exchange
    (``a2a.waveRows``) so every process runs the same number of per-wave
    collectives in lockstep. The proposal is already identical everywhere
    by construction — it derives from the allgathered global size row
    (plan.wave_count) — so this round exists to FAIL FAST on the one way
    it can diverge: a process booted with a different ``a2a.waveRows``
    conf, which would otherwise desync the SPMD group into a hang on
    wave W+1. The manager therefore calls it on EVERY distributed read
    (a waves-off or below-threshold process proposes 1): on/off conf
    divergence is the likeliest drift and must raise too, not just
    nonzero-vs-nonzero. Mismatch raises on every process together (the
    verdict rides the allgather, like the completeness barrier's
    timeout bit).

    The FIRST client of the agreement primitive
    (shuffle/agreement.py): the round is an epoch-scoped unanimous
    ``agree`` frame, so a sequencing split (a process entering a
    different round entirely) is typed too, not just a value split."""
    from sparkucx_tpu.shuffle.agreement import (AgreementDivergenceError,
                                                agree)
    try:
        return int(agree("a2a.waveRows",
                         np.array([local_waves], dtype=np.int64),
                         conf_key="spark.shuffle.tpu.a2a.waveRows")[0])
    except AgreementDivergenceError as e:
        if e.kind != "value":
            raise
        raise AgreementDivergenceError(
            e.topic, e.kind, e.dissenters, e.proposals,
            conf_key=e.conf_key,
            detail="wave-count mismatch across processes (collective "
                   "reads derive waves from the same global size "
                   "row)") from None


def agree_wave_sizes(wave_sizes: np.ndarray) -> np.ndarray:
    """COLLECTIVE: agree on the PER-WAVE real row counts of a ragged
    waved exchange (the [W] vector ``plan.wave_payload_rows`` derives
    from the global size row). Like :func:`agree_wave_count`, the
    proposal is identical everywhere by construction — this round exists
    to FAIL FAST on the one way it can diverge: a process whose view of
    the staged occupancy differs (stale size row after a raced remesh/
    unregister, or a conf divergence that survived the wave-count
    agreement), which would otherwise dispatch per-wave collectives with
    inconsistent size rows and desync — or silently corrupt — the mesh.
    Mismatch raises on every process together (the verdict rides the
    allgather). Returns the agreed vector. The second client of the
    agreement primitive (shuffle/agreement.py)."""
    from sparkucx_tpu.shuffle.agreement import (AgreementDivergenceError,
                                                agree)
    mine = np.asarray(wave_sizes, dtype=np.int64).reshape(-1)
    try:
        return agree("a2a.waveSizes", mine,
                     conf_key="spark.shuffle.tpu.a2a.waveRows")
    except AgreementDivergenceError as e:
        if e.kind != "value":
            raise
        raise AgreementDivergenceError(
            e.topic, e.kind, e.dissenters, e.proposals,
            conf_key=e.conf_key,
            detail="per-wave occupancy mismatch across processes — "
                   "every process must derive the same per-wave real "
                   "row counts from the allgathered size row (stale "
                   "staged outputs or divergent conf)") from None


def gather_clock_anchors(tracer=None) -> list:
    """COLLECTIVE: every process's wall↔perf anchor pair
    (:meth:`Tracer.anchor` + process index), gathered at connect/remesh
    so per-process monotonic span clocks can be aligned into one
    cluster timeline (utils/export.merge_timeline). Every process must
    call it — the usual SPMD discipline."""
    import jax
    from sparkucx_tpu.utils.trace import GLOBAL_TRACER
    a = (tracer or GLOBAL_TRACER).anchor()
    a["process_id"] = jax.process_index()
    return allgather_json(a)


def gather_fleet_registry(entry) -> list:
    """COLLECTIVE — the ONE boot-time round the fleet telemetry plane
    is allowed (utils/collector.py): every process's registry entry
    (its live-telemetry scrape URL + boot anchor), allgathered at
    connect when the whole fleet is alive in lockstep by construction.
    A process whose live server is off publishes ``{}`` — it still
    MUST call (the collective is unconditional) and simply contributes
    no scrape target. After this round the plane never touches a
    collective again: scraping is HTTP, so it keeps working when this
    very channel is parked on a dead peer."""
    return allgather_json(entry if entry is not None else {})


class DistributedReaderResult(ShuffleReaderResult):
    """Partial, process-local view: only partitions on local shards are
    readable (the Spark-reducer contract). Layout is partition-major
    (reader.py ``_RunIndex``): ``seg_counts`` is [NS, R] shared (flat
    exchange) or [L, NS, R] with this process's shards only
    (hierarchical)."""

    def __init__(self, num_partitions: int, part_to_shard: np.ndarray,
                 shard_ids: Sequence[int], local_rows: np.ndarray,
                 seg_counts: np.ndarray, val_shape, val_dtype,
                 align_chunk: int = 0):
        super().__init__(num_partitions, part_to_shard, local_rows,
                         seg_counts, val_shape, val_dtype,
                         align_chunk=align_chunk)
        self._shard_ord = {int(s): i for i, s in enumerate(shard_ids)}

    def is_local(self, r: int) -> bool:
        return int(self._part_to_shard[r]) in self._shard_ord

    def _ordinal(self, shard: int) -> int:
        if shard not in self._shard_ord:
            raise KeyError(
                f"shard {shard} is not on this process (local shards: "
                f"{sorted(self._shard_ord)})")
        return self._shard_ord[shard]

    def _seg_matrix(self, shard: int) -> np.ndarray:
        return self._seg if self._seg.ndim == 2 \
            else self._seg[self._ordinal(shard)]

    def _shard_rows(self, shard: int) -> np.ndarray:
        return self._rows[self._ordinal(shard)]

    def partition(self, r: int):
        if not self.is_local(r):
            raise KeyError(
                f"partition {r} lives on shard "
                f"{int(self._part_to_shard[r])}, not on this process "
                f"(local shards: {sorted(self._shard_ord)})")
        return super().partition(r)

    def partitions(self):
        for r in range(self.num_partitions):
            if self.is_local(r):
                yield r, self.partition(r)


def _local_shards_of(arr: jax.Array, shard_ids: Sequence[int],
                     rows_per_shard: int) -> np.ndarray:
    """Collect this process's shards of a P(axis)-sharded global array
    into [L, rows_per_shard, ...] in shard_ids order."""
    by_start = {}
    for s in arr.addressable_shards:
        start = s.index[0].start or 0
        by_start[start // rows_per_shard] = np.asarray(s.data)
    return np.stack([by_start[int(i)] for i in shard_ids])


def _pull_local(arr: jax.Array, shard_ids: Sequence[int],
                rows_per_shard: int, what: str, res=None) -> np.ndarray:
    """:func:`_local_shards_of` as one ``shuffle.d2h`` span of a read's
    output (``reader.pull_to_host``: ``rows`` counted against ``res``,
    ``seg`` not)."""
    from sparkucx_tpu.shuffle.reader import pull_to_host
    shard_bytes = arr.nbytes // (arr.shape[0] // rows_per_shard)
    return pull_to_host(
        arr, what, res, nbytes=shard_bytes * len(shard_ids),
        pull=lambda a: _local_shards_of(a, shard_ids, rows_per_shard))


class DistributedLazyReaderResult(LazyShuffleReaderResult):
    """Device-resident PARTIAL view for the multi-process device sink:
    the payload stays sharded across every process's devices (zero
    payload D2H — the whole point of ``read.sink=device`` distributed),
    and only partitions on this process's shards are readable (the
    Spark-reducer contract of :class:`DistributedReaderResult`).

    The base class's device plumbing already speaks global offsets —
    ``_shard_dev`` matches addressable shards by ``start // cap_out``,
    and ``_shard_rows`` raises for a shard another process owns — so the
    overrides here are only the locality guards and a local-shards seg
    materialization (``np.asarray`` rejects a non-fully-addressable
    array; non-local seg rows stay zero and sit unreachable behind the
    ``partition()`` guard)."""

    def __init__(self, *args, shard_ids: Sequence[int] = (), **kw):
        super().__init__(*args, **kw)
        self._shard_ord = {int(s): i for i, s in enumerate(shard_ids)}

    def is_local(self, r: int) -> bool:
        return int(self._part_to_shard[r]) in self._shard_ord

    def partition(self, r: int):
        if not self.is_local(r):
            raise KeyError(
                f"partition {r} lives on shard "
                f"{int(self._part_to_shard[r])}, not on this process "
                f"(local shards: {sorted(self._shard_ord)})")
        return super().partition(r)

    def partitions(self):
        for r in range(self.num_partitions):
            if self.is_local(r):
                yield r, self.partition(r)

    def _seg_matrix(self, shard: int) -> np.ndarray:
        with self._fetch_lock:
            sd = self._seg_dev
            if self._seg is None and sd is not None \
                    and self._per_shard_segs \
                    and not getattr(sd, "is_fully_addressable", True):
                ns = sd.shape[0] // self._num_shards
                full = np.zeros(
                    (self._num_shards, ns, self.num_partitions),
                    dtype=np.asarray(
                        sd.addressable_shards[0].data).dtype)
                for s in sd.addressable_shards:
                    start = s.index[0].start or 0
                    full[start // ns] = np.asarray(s.data)
                self._seg = full
                self._seg_dev = None
            return super()._seg_matrix(shard)


def local_totals_row(totals_dev, num_shards: int) -> np.ndarray:
    """The [P] per-shard delivered-totals row of a device result, with
    non-addressable entries summed in over the agreement channel when
    the array spans processes (the device merge fold's acc sizing must
    agree everywhere or the merge programs desync). Metadata-class:
    one [P] int row, never payload."""
    if getattr(totals_dev, "is_fully_addressable", True):
        return np.asarray(totals_dev).reshape(-1)
    row = np.zeros(int(totals_dev.shape[0]), dtype=np.int64)
    for s in totals_dev.addressable_shards:
        start = s.index[0].start or 0
        d = np.asarray(s.data).reshape(-1)
        row[start:start + d.shape[0]] = d
    return np.asarray(allgather_blob(
        row, what="device-merge totals row")).reshape(-1, row.shape[0]) \
        .sum(axis=0)


def read_shuffle_distributed(
    mesh: Mesh,
    axis: str,
    plan: ShufflePlan,
    local_rows: np.ndarray,
    local_nvalid: np.ndarray,
    shard_ids: Sequence[int],
    val_shape: Optional[Tuple[int, ...]],
    val_dtype,
    hier_mesh: Optional[Mesh] = None,
    dcn_axis: Optional[str] = None,
) -> DistributedReaderResult:
    """Run the exchange across all processes; COLLECTIVE — every process
    must call with the same plan/width.

    local_rows   — [L, cap_in, width] fused rows for this process's shards
    local_nvalid — [L] valid counts
    shard_ids    — global shard indices of this process (mesh order;
                   identical for the flat and 2-D mesh because the
                   hierarchical flattening is row-major over (dcn, ici))
    hier_mesh    — when set (with ``dcn_axis``), run the two-stage
                   ICI-then-DCN exchange over this 2-D mesh instead of the
                   flat single collective, so each row crosses the slow
                   DCN links exactly once (shuffle/hierarchical.py)
    """
    return submit_shuffle_distributed(
        mesh, axis, plan, local_rows, local_nvalid, shard_ids,
        val_shape, val_dtype, hier_mesh=hier_mesh,
        dcn_axis=dcn_axis).result()


class PendingDistributedShuffle(PendingExchangeBase):
    """Future-like handle for an in-flight MULTI-PROCESS exchange.

    Collective contract: every process must call submit (which dispatches
    the SPMD step) and, later, ``result()`` — in the same order relative
    to other collectives. Between the two calls each process is free to
    pack the next shuffle or run any host work: XLA dispatch is already
    asynchronous, so the collective rides the wire meanwhile (the
    per-executor fetch/compute overlap of the reference's non-blocking
    ``ucp_get`` storm, ref: UcxShuffleClient.java (3.0):95-127).

    ``done()`` is a LOCAL, non-collective poll (this process's outputs
    computed); the overflow verdict and any retry live in ``result()``,
    because they require the cross-process allgather. Lifecycle
    (exactly-once on_done, abandonment release, result caching) comes
    from :class:`sparkucx_tpu.shuffle.reader.PendingExchangeBase`."""

    def __init__(self, mesh, axis, plan, local_rows, local_nvalid,
                 shard_ids, val_shape, val_dtype, hier_mesh, dcn_axis,
                 on_done=None, admit=None, wire_seed: int = 0):
        self._mesh, self._axis = mesh, axis
        self._plan = plan
        self._local_rows, self._local_nvalid = local_rows, local_nvalid
        self._shard_ids = list(shard_ids)
        # int8-wire noise base — the manager's exchange seq, identical
        # on every process by the collective-read lockstep; per-shard
        # streams derive from GLOBAL shard ids (seeded_nvalid), so the
        # noise a shard draws never depends on process placement
        self._wire_seed = int(wire_seed)
        self._val_shape, self._val_dtype = val_shape, val_dtype
        self._hier_mesh, self._dcn_axis = hier_mesh, dcn_axis
        L, cap_in, width = local_rows.shape
        self._L, self._cap_in, self._width = L, cap_in, width
        if hier_mesh is not None:
            self._sharding = NamedSharding(hier_mesh, P((dcn_axis, axis)))
        else:
            self._sharding = NamedSharding(mesh, P(axis))
        self._result = None
        self._attempt = 0
        self._on_done = None
        # the defer decision is deterministic across processes (same plan,
        # same footprint arithmetic, same submit/result order), so queued
        # dispatches stay in SPMD lockstep
        self._initial_dispatch(admit)
        self._on_done = on_done

    def _dispatch(self):
        cur = self._plan
        if self._hier_mesh is not None:
            from sparkucx_tpu.shuffle.hierarchical import _build_hier_step
            step = _build_hier_step(self._hier_mesh, self._dcn_axis,
                                    self._axis, cur, self._width)
        else:
            step = _build_step(self._mesh, self._axis, cur, self._width)
        # device-plane join point, same as PendingShuffle._dispatch: the
        # manager reads cost_record off the final dispatched program
        self._step = step
        payload = jax.make_array_from_process_local_data(
            self._sharding,
            self._local_rows.reshape(self._L * self._cap_in, self._width))
        nvalid = jax.make_array_from_process_local_data(
            self._sharding,
            seeded_nvalid(cur, self._local_nvalid,
                          self._wire_seed + self._attempt,
                          shard_ids=self._shard_ids))
        self._out = step(payload, nvalid)

    def _result_inner(self):
        # COLLECTIVE: every process must reach result() — it allgathers
        # the overflow verdict and retries in lockstep.
        R = self._plan.num_partitions
        Pn = self._plan.num_shards
        part_to_shard = np.asarray(_blocked_map(R, Pn))
        while True:
            cur = self._plan
            rows_out, seg, total, ovf = self._out
            # The retry decision must be identical on every process or
            # the SPMD group diverges. The flat exchange's flag is a
            # mesh-wide psum, but the hierarchical flag (r1|r2) is only
            # uniform within a slice — so allgather the local verdicts
            # and OR them globally. Materializing the flag BLOCKS until
            # the dispatched collective completes — the in-flight wait a
            # dead peer parks forever — so it rides the watchdog fence
            # like the metadata allgathers (PeerLostError past the
            # deadline, never a silent hang).
            from sparkucx_tpu.runtime.watchdog import current_watchdog
            from sparkucx_tpu.utils.trace import GLOBAL_TRACER
            # anatomy span: this wait IS the fabric transfer from the
            # host's point of view (the dispatched collective draining);
            # the tier attr routes it to transfer.dcn/ici in the ledger
            # (containment-matched — no trace id on this signature)
            with GLOBAL_TRACER.span(
                    "shuffle.exchange.wait",
                    tier="ici+dcn" if self._hier_mesh is not None
                    else "dcn"):
                mine = current_watchdog().call(
                    lambda: any(bool(overflow_flags(s.data, 1)[0])
                                for s in ovf.addressable_shards),
                    # the fused hierarchical step cannot split its tiers
                    # under separate deadlines (shuffle/topology.py
                    # does, single-process) — but the fence should still
                    # SAY the wait covered both fabrics when it expires
                    what="hierarchical (ici+dcn fused) exchange "
                         "completion wait"
                    if self._hier_mesh is not None
                    else "exchange completion wait")
            ovf_global = bool(allgather_blob(
                np.array([1 if mine else 0], dtype=np.int64),
                what="overflow verdict").any())
            if not ovf_global:
                # anatomy span (sink phase): result assembly — the
                # local-shard drain and seg pull between the collective
                # completing and the wall settling (containment-matched,
                # same as reader.py's single-process tail)
                with GLOBAL_TRACER.span("shuffle.result",
                                        sink=self._plan.sink):
                    # per-shard capacity from the OUTPUT, not the plan:
                    # the pallas transport's buffers are chunk-inflated
                    # (cap_eff = align(cap_out) + P*chunk), so slicing by
                    # cur.cap_out would misattribute shards (reader.py's
                    # single-process _result_inner derives it the same
                    # way)
                    cap_shard = rows_out.shape[0] // Pn
                    align_chunk = 0
                    if cur.impl == "pallas" and not (cur.combine
                                                     or cur.ordered):
                        from sparkucx_tpu.ops.pallas.ragged_a2a import \
                            chunk_rows_for
                        # wire-aware: the step aligned on the WIRE row
                        # width
                        align_chunk = chunk_rows_for(
                            wire_row_words(cur, self._width))
                    elif cur.strips_active():
                        # degenerate 1-shard cluster: step_body takes the
                        # strip fast path (see reader.py resolve)
                        align_chunk = cur.strip_rows()
                    sharded_seg = (cur.combine or cur.ordered
                                   or self._hier_mesh is not None)
                    if cur.sink == "device":
                        # device sink distributed: the payload stays
                        # sharded across every process's devices — ZERO
                        # payload D2H, the single-process device-sink
                        # contract held multi-host (manager gap 2)
                        from sparkucx_tpu.shuffle.reader import \
                            DeviceShuffleReaderResult
                        view = DistributedLazyReaderResult(
                            R, part_to_shard, rows_out, seg, Pn,
                            cap_shard, self._val_shape, self._val_dtype,
                            per_shard_segs=sharded_seg,
                            align_chunk=align_chunk,
                            shard_ids=self._shard_ids)
                        view.cap_out_used = cur.cap_out
                        view._totals_dev = total
                        return DeviceShuffleReaderResult(
                            [view], cur, self._val_shape,
                            self._val_dtype)
                    if sharded_seg:
                        # SHARDED seg output — collect this process's
                        # rows: [1, R] own counts under combine/ordered,
                        # else [S, R] relay counts (hierarchical)
                        ns = 1 if (cur.combine or cur.ordered) \
                            else self._hier_mesh.devices.shape[0]
                        seg_host = _pull_local(seg, self._shard_ids, ns,
                                               "seg")
                    else:
                        # flat uncombined: replicated [P, R] — any
                        # addressable copy is the whole matrix
                        # (np.asarray rejects multi-process arrays)
                        from sparkucx_tpu.shuffle.reader import \
                            pull_to_host
                        seg_host = pull_to_host(
                            seg.addressable_shards[0].data, "seg")
                    res = DistributedReaderResult(
                        R, part_to_shard, self._shard_ids, None,
                        seg_host, self._val_shape, self._val_dtype,
                        align_chunk=align_chunk)
                    # the HOST sink force-materializes its local shards
                    # — honest d2h accounting (``read.sink=device`` is
                    # the zero-D2H path above)
                    res._rows = _pull_local(rows_out, self._shard_ids,
                                            cap_shard, "rows", res)
                    res.cap_out_used = cur.cap_out
                    if not sharded_seg:
                        # flat plain: the replicated [P, R] seg carries
                        # true delivered counts, identical on every
                        # process — the manager's hint decay stays in
                        # SPMD lockstep
                        res.recv_rows_needed = max_recv_rows(
                            seg_host, part_to_shard, Pn)
                    return res
            if self._attempt >= self._plan.max_retries:
                raise RuntimeError(
                    f"shuffle still overflowing after "
                    f"{self._plan.max_retries} retries "
                    f"(cap_out={cur.cap_out}); extreme skew — repartition "
                    f"the data")
            log.info("distributed shuffle overflow at cap_out=%d "
                     "(attempt %d)", cur.cap_out, self._attempt)
            self._plan = cur.grown()
            self._attempt += 1
            # anatomy span (pack phase): the grown-capacity redispatch
            # re-stages and re-dispatches inside result() — dark on
            # every overflow retry otherwise (containment-matched, no
            # trace id on the pending side)
            from sparkucx_tpu.utils.trace import GLOBAL_TRACER
            with GLOBAL_TRACER.span("shuffle.dispatch",
                                    retry=self._attempt):
                self._dispatch()


def submit_shuffle_distributed(
    mesh: Mesh,
    axis: str,
    plan: ShufflePlan,
    local_rows: np.ndarray,
    local_nvalid: np.ndarray,
    shard_ids: Sequence[int],
    val_shape: Optional[Tuple[int, ...]],
    val_dtype,
    hier_mesh: Optional[Mesh] = None,
    dcn_axis: Optional[str] = None,
    on_done=None,
    admit=None,
    wire_seed: int = 0,
) -> PendingDistributedShuffle:
    """Dispatch the multi-process exchange without blocking (collective:
    see :class:`PendingDistributedShuffle`)."""
    return PendingDistributedShuffle(
        mesh, axis, plan, local_rows, local_nvalid, shard_ids,
        val_shape, val_dtype, hier_mesh, dcn_axis, on_done=on_done,
        admit=admit, wire_seed=wire_seed)


# -- split-tier multi-process exchange --------------------------------------
class PendingDistributedTieredShuffle(PendingTieredShuffle):
    """The two-tier (ICI, DCN) exchange over a MULTI-PROCESS mesh as the
    same TWO per-tier compiled programs the single-process path runs
    (shuffle/topology.py), replacing the fused single program the
    distributed path was stuck with — a slow DCN stage no longer stalls
    the ICI stage's pipeline, and each tier joins under its OWN watchdog
    deadline (``failure.ici.timeoutMs`` / ``failure.dcn.timeoutMs``).

    The host join between the stages is what forced the fused shape:
    every process must take the SAME overflow/regrow decision or the
    group recompiles different programs and desyncs the mesh. The
    distributed seams override exactly that — the overflow verdict is an
    ``any``-reduced agreement round, the regrown capacity a unanimous
    one (:func:`sparkucx_tpu.shuffle.agreement.agree`), both riding
    inside the tier's span/wall/deadline, so a dissenting peer raises
    :class:`~sparkucx_tpu.shuffle.agreement.AgreementDivergenceError` on
    every process together and a dead one raises ``PeerLostError``
    naming the tier. Staging is process-local
    (``jax.make_array_from_process_local_data``), and only this
    process's [L] stage-1 totals cross to host between the stages —
    the metadata-exclusion precedent, now per process."""

    def __init__(self, mesh: Mesh, topo: TopologyDescriptor,
                 plan: ShufflePlan, local_rows: np.ndarray,
                 local_nvalid: np.ndarray, shard_ids: Sequence[int],
                 val_shape, val_dtype, on_done=None, admit=None,
                 wire_seed: int = 0, hooks: Optional[TierHooks] = None):
        # set before super().__init__: the deferred-admission first
        # dispatch runs inside it and the seams below read the ids
        self._shard_ids = list(shard_ids)
        super().__init__(mesh, topo, plan, local_rows, local_nvalid,
                         val_shape, val_dtype, on_done=on_done,
                         admit=admit, wire_seed=wire_seed, hooks=hooks)

    # -- the distributed seams (topology.PendingTieredShuffle) -------------
    def _stage_to_device(self, arr):
        return jax.make_array_from_process_local_data(
            self._sharding, np.ascontiguousarray(arr))

    def _seed_nvalid(self, values, stream: int) -> np.ndarray:
        from sparkucx_tpu.shuffle.reader import seeded_nvalid
        # per-shard noise streams derive from GLOBAL shard ids, so the
        # noise a shard draws never depends on process placement
        return seeded_nvalid(
            self._plan, values,
            (self._wire_seed + self._attempt) * 2 + stream,
            shard_ids=self._shard_ids)

    def _local_overflow(self, ovf) -> bool:
        return any(bool(np.asarray(s.data).any())
                   for s in ovf.addressable_shards)

    def _agree_timeout(self, tier: str) -> Optional[float]:
        limit = float(self._hooks.timeouts.get(tier, 0.0))
        return limit if limit > 0 else None

    def _agree_overflow(self, tier: str, mine: bool) -> bool:
        from sparkucx_tpu.shuffle.agreement import agree
        verdict = agree(f"hier.{tier}.overflow",
                        np.array([1 if mine else 0], dtype=np.int64),
                        reduce="any",
                        conf_key="spark.shuffle.tpu.a2a.capacityFactor",
                        timeout_ms=self._agree_timeout(tier))
        return bool(verdict[0])

    def _agree_regrow(self, tier: str, cap: int) -> int:
        from sparkucx_tpu.shuffle.agreement import agree
        # unanimity round: a peer proposing a DIFFERENT capacity (a
        # divergent a2a.capacityFactor / bucket ladder) raises typed on
        # every process instead of recompiling a mismatched program
        agreed = agree(f"hier.{tier}.regrow",
                       np.array([int(cap)], dtype=np.int64),
                       conf_key="spark.shuffle.tpu.a2a.capacityFactor",
                       timeout_ms=self._agree_timeout(tier))
        return int(agreed[0])

    def _totals_host(self, tot1) -> np.ndarray:
        # only this process's [L] totals cross to host — stage-2 seeding
        # is per-LOCAL-shard (make_array_from_process_local_data re-
        # assembles the global lane), the per-process metadata exclusion
        return _local_shards_of(tot1, self._shard_ids, 1) \
            .reshape(-1).astype(np.int64)

    def _assemble(self, rows_out, seg, total):
        plan = self._plan
        Pn = plan.num_shards
        R = plan.num_partitions
        part_to_shard = np.asarray(_blocked_map(R, Pn))
        cap_shard = rows_out.shape[0] // Pn
        if plan.sink == "device":
            # device sink distributed: payload stays sharded in HBM
            # across every process (zero payload D2H); the view guards
            # non-local partitions like every distributed result
            from sparkucx_tpu.shuffle.reader import \
                DeviceShuffleReaderResult
            view = DistributedLazyReaderResult(
                R, part_to_shard, rows_out, seg, Pn, cap_shard,
                self._val_shape, self._val_dtype, per_shard_segs=True,
                shard_ids=self._shard_ids)
            view.cap_out_used = plan.cap_out
            view._totals_dev = total
            return DeviceShuffleReaderResult(
                [view], plan, self._val_shape, self._val_dtype)
        # host sink: drain ONLY this process's shards — the partial-view
        # contract of the fused distributed path, now per tier
        ns = seg.shape[0] // Pn
        seg_host = _pull_local(seg, self._shard_ids, ns, "seg")
        res = DistributedReaderResult(
            R, part_to_shard, self._shard_ids, None, seg_host,
            self._val_shape, self._val_dtype)
        res._rows = _pull_local(rows_out, self._shard_ids, cap_shard,
                                "rows", res)
        res.cap_out_used = plan.cap_out
        return res


def submit_shuffle_tiered_distributed(
    mesh: Mesh,
    topo: TopologyDescriptor,
    plan: ShufflePlan,
    local_rows: np.ndarray,
    local_nvalid: np.ndarray,
    shard_ids: Sequence[int],
    val_shape,
    val_dtype,
    on_done=None,
    admit=None,
    wire_seed: int = 0,
    hooks: Optional[TierHooks] = None,
) -> PendingDistributedTieredShuffle:
    """Dispatch the multi-process two-tier exchange without blocking —
    COLLECTIVE (every process submits and joins in lockstep; the
    overflow/regrow decisions ride agreement rounds)."""
    return PendingDistributedTieredShuffle(
        mesh, topo, plan, local_rows, local_nvalid, shard_ids,
        val_shape, val_dtype, on_done=on_done, admit=admit,
        wire_seed=wire_seed, hooks=hooks)
