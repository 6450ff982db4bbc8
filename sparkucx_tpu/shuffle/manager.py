"""TpuShuffleManager — the framework API layer (L4).

The Spark SPI surface of the reference, capability for capability
(ref: compat/spark_3_0/UcxShuffleManager.scala:25-60,
CommonUcxShuffleManager.scala:39-91):

  reference SPI                       here
  -------------                       ----
  registerShuffle(id, deps)        -> register_shuffle(id, num_maps, R)
  getWriter(handle, mapId)         -> get_writer(handle, map_id)
  getReader(handle, partitions)    -> read(handle) / read_partitions(h, s, e)
  unregisterShuffle(id)            -> unregister_shuffle(id)
  stop()                           -> stop()

The handle embeds the metadata-plane reference the way UcxShuffleHandle
embeds the driver table's {address, rkey}
(ref: CommonUcxShuffleManager.scala:49-52, rpc/UcxRemoteMemory.java:13-17).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import ClassVar, Dict, List, Optional

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.meta.registry import ShuffleEntry
from sparkucx_tpu.meta.segments import validate_row_sizes
from sparkucx_tpu.runtime.node import TpuNode
from sparkucx_tpu.shuffle.plan import (ShufflePlan, make_plan,
                                       ragged_layout, wave_count,
                                       wave_payload_rows, wave_step_plan)
from sparkucx_tpu.shuffle.reader import (
    KEY_WORDS,
    ShuffleReaderResult,
    WavedShuffleReaderResult,
    drain_wave_result,
    pack_rows,
    submit_shuffle,
    value_words,
)
from sparkucx_tpu.shuffle.writer import MapOutputWriter
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.runtime.failures import (BlockCorruptionError,
                                           PeerLostError, StaleEpochError,
                                           TransientError)
from sparkucx_tpu.shuffle.tenancy import (FairShareQueue, FifoAdmitQueue,
                                          TenantRegistry)
from sparkucx_tpu.utils.metrics import (C_ADMIT_BYTES,
                                        C_INTEGRITY_CORRUPT,
                                        C_INTEGRITY_CORRUPT_BLOCKS,
                                        C_INTEGRITY_QUARANTINED,
                                        C_INTEGRITY_RECOVERED,
                                        C_INTEGRITY_VERIFIED,
                                        C_REPLAY_MS, C_REPLAYS,
                                        C_KERNEL_FALLBACK,
                                        C_SINK_FALLBACK, C_TIER_BYTES,
                                        COMPILE_HITS, COMPILE_PROGRAMS,
                                        G_TENANT_INFLIGHT,
                                        GLOBAL_METRICS, H_ADMIT_CROSS,
                                        H_ADMIT_WAIT, H_BW,
                                        H_FETCH_FIRST, H_FETCH_WAIT,
                                        H_PEER_BYTES, H_PEER_ROWS,
                                        H_WAVE_GAP, labeled)
from sparkucx_tpu.utils.trace import format_trace_id

log = get_logger("shuffle.manager")

# Most-recent ExchangeReports the manager retains (keyed by shuffle id,
# LRU-evicted) — bounded like every other telemetry ring. The DEFAULT of
# the ``metrics.reportCapacity`` conf key; eviction is tenant-aware (see
# _new_report): the ring is shared across all tenants, and a chatty
# tenant must evict its OWN oldest reports, not flush another tenant's
# out from under gather_reports/doctor before they are read.
REPORT_CAPACITY = 64


@dataclass
class ExchangeReport:
    """Structured postmortem of one shuffle read — the per-exchange unit
    of the telemetry plane. Accumulated by the manager during
    ``_submit_local`` / ``_submit_distributed`` (phases timed directly —
    a report must exist even when the tracer is off), completed by the
    read's exactly-once ``on_done``, and retrievable after the fact via
    ``manager.report(shuffle_id)`` — the "explain this exchange without
    a rerun" answer the reference's four log lines approximate.

    ``group_ms`` spans dispatch-start to completion (the collective +
    receive-side grouping); ``skew_ratio`` is max/mean partition rows
    from the metadata table (per-peer rows in distributed mode, where no
    single process holds the [M, R] table)."""

    shuffle_id: int
    num_maps: int
    num_partitions: int
    partitioner: str
    # cluster-correlation key s<sid>.e<epoch>.x<seq> (trace.format_trace_id):
    # the same id stamps this report, the read's spans, and any flight
    # events recorded while the exchange was in flight — one grep joins a
    # crash dump to its row in gather_reports and its timeline track
    trace_id: str = ""
    process_id: int = 0
    distributed: bool = False
    hierarchical: bool = False
    impl: str = ""
    plan_ms: float = 0.0
    pack_ms: float = 0.0
    dispatch_ms: float = 0.0
    group_ms: float = 0.0
    rows_global: int = 0
    rows_local: int = 0
    bytes_local: int = 0
    # Real-bytes accounting (plan.RaggedLayout — the ragged data plane's
    # wire contract): ``payload_bytes`` is the REAL global payload,
    # ``wire_bytes`` what the resolved transport moved over the fabric
    # for it, ``pad_ratio`` their quotient (1.0 = every wire byte was a
    # real byte — the ragged-native contract; dense pays ~P x
    # capacityFactor). ``impl`` above is the RESOLVED transport (never
    # 'auto'), so the figures always name the path that ran. Overflow
    # retries refresh wire_bytes/pad_ratio from the final (regrown) plan.
    payload_bytes: int = 0
    wire_bytes: int = 0
    pad_ratio: float = 0.0
    # Wire-compression tier (a2a.wire) accounting: ``wire`` is the
    # RESOLVED tier this exchange rode (never the conf ask — an int8
    # request on an int-valued schema resolves to 'raw' and says so
    # here, the resolved-impl discipline). On int8, ``wire_bytes`` above
    # already reports the ACHIEVED (narrowed) wire cost — pad_ratio can
    # sit below 1.0 — and ``wire_dequant_error`` carries the sampled
    # relative-RMS estimate of the rounding loss (shuffle/wire.py; 0.0
    # when sampling is off). ``effective_bw_gbps`` is the EQuARX figure:
    # the link rate a RAW exchange would have needed to match this wall
    # (= bw_gbps x raw/wire row-width gain; equals bw_gbps off-tier).
    # ``lossless_*``: measured byte-plane+deflate size of the
    # host-staged blocks on the lossless drain path vs the real payload.
    wire: str = "raw"
    wire_dequant_error: float = 0.0
    effective_bw_gbps: float = 0.0
    lossless_bytes: int = 0
    lossless_ratio: float = 0.0
    peer_rows: List[int] = field(default_factory=list)
    peer_bytes: List[int] = field(default_factory=list)
    skew_ratio: float = 0.0
    retries: int = 0
    stepcache_hits: int = 0
    stepcache_programs: int = 0
    plan_bucket: List[int] = field(default_factory=list)
    # Compiled-program family of the dispatched plan (plan.family(),
    # stringified) — the replay-stability contract: a replayed exchange
    # whose learned caps carried over reports the SAME family as the
    # pre-fault run, i.e. the replay re-packed and re-dispatched but did
    # not recompile. The chaos drill diffs this across the fault matrix.
    plan_family: str = ""
    # Waved reads: [W] REAL global rows each wave moved (the occupancy
    # the pipeline shipped, vs cap_in rows provisioned per wave) — the
    # per-wave view of the payload/wire split above. Empty = single-shot.
    wave_payload_rows: List[int] = field(default_factory=list)
    # Wave-pipelined exchange (a2a.waveRows): wave split plus the
    # per-wave timeline — one entry per wave, {wave, rows, pack_start_ms,
    # pack_ms, dispatch_ms, hidden, forced_ms, wait_ms, retries}, times
    # relative to read start. ``hidden`` is MEASURED, not structural: it
    # marks a pack that finished while an earlier wave's collective was
    # provably still running (done() polled false after the pack), so
    # its cost is off the critical path; the overlap-proof test and the
    # doctor's pipeline_stall rule both read this record. 0/empty =
    # single-shot.
    waves: int = 0
    wave_rows: int = 0
    wave_pack_hidden_ms: float = 0.0
    wave_timeline: List[Dict] = field(default_factory=list)
    # Device-plane join (shuffle/stepcache.py harvest): the XLA cost/
    # memory record of the compiled program this exchange dispatched —
    # flops, bytes accessed, argument/output/temp HBM footprint — fields
    # null on backends without the analyses, the record itself present
    # for every warm-compiled program. ``model_bytes_gbps`` (when byte
    # counts exist) is the cost-model byte-movement rate the dispatch
    # achieved — the roofline comparison the array-redistribution model
    # (arxiv 2112.01075) supports.
    device_cost: Optional[Dict] = None
    # Achieved collective bandwidth: global payload bytes over group_ms
    # (dispatch-start -> completion). Always filled on completion;
    # observed into shuffle.collective.bw_gbps only for steady-state
    # (non-compile-bearing) reads — the same split as fetch-wait.
    bw_gbps: float = 0.0
    # Failure-domain accounting (failure.policy=replay): how many times
    # this read transparently re-planned + re-ran the exchange (stale-
    # handle re-pins through the recovery ledger plus transient-failure
    # re-runs) and the wall the FAILED attempts burned. 0/0.0 on the
    # failfast policy and on clean reads — the doctor's replay_storm
    # rule grades these against failure.replayBudget.
    replays: int = 0
    replay_ms: float = 0.0
    # Integrity plane (shuffle/integrity.py): the verify level this read
    # actually ran — "staged" = the staged/spill bytes were re-checked
    # against the commit checksums before entering the exchange, "full"
    # = additionally the host-drained post-collective rows verified per
    # partition against the published digest sums (key lanes only under
    # the int8 wire — dequantized values are legitimately lossy).
    # ``integrity_bytes`` counts what was verified. "" = off / no
    # records published.
    integrity: str = ""
    integrity_bytes: int = 0
    # Read-sink plane (read.sink, shuffle/reader.py): ``sink`` is the
    # RESOLVED landing tier this read ran — "device" = partitions stayed
    # sharded jax Arrays handed to the consumer (zero payload D2H by
    # construction), "host" = the historical drain (the resolved-impl
    # discipline: never the conf ask). ``d2h_bytes`` counts the PAYLOAD
    # bytes this read's result actually pulled device-to-host — filled
    # as the consumer touches partitions (a lazy result drains after
    # completion, so the figure keeps accruing on the live report); 0 on
    # the device path is the deleted-round-trip evidence the doctor's
    # host_roundtrip rule grades.
    sink: str = "host"
    d2h_bytes: int = 0
    # Device-kernel tier the combine/ordered fold path RAN
    # (plan.kernel_impl — segmented.resolve_kernel_impl's verdict, the
    # resolved-impl discipline): "pallas" = the blocked merge-path /
    # tiled segment-reduce kernels, "jnp" = the XLA sort-network
    # formulation (plain reads always say jnp — no fold runs). A conf
    # ask of pallas that reports jnp here is the kernel_fallback
    # evidence (C_KERNEL_FALLBACK carries the gate reason).
    kernel: str = "jnp"
    # Device-native ordered/combine (read.sink=device): wall the
    # cross-wave DEVICE merge fold spent (reader.device_merge_fold —
    # compiled merge programs over the completed waves, blocked for an
    # honest figure). 0.0 on host sinks, single-shot device reads (the
    # exchange step already merged) and plain device reads.
    merge_ms: float = 0.0
    # Multi-tenant plane (shuffle/tenancy.py): the tenant this shuffle
    # was registered under (conf tenant.id, or the register_shuffle
    # override) — the join key between this report, the per-tenant
    # labeled metrics (admit wait, payload/wire counters) and the
    # doctor's quota_starvation rule. ``admit_wait_ms`` is the wall this
    # read's reservation spent DEFERRED in the admission queue (0 for an
    # immediate grant) — group_ms includes it when dispatch was
    # deferred, so consumers wanting the pure exchange wall subtract it.
    tenant: str = ""
    admit_wait_ms: float = 0.0
    # Async plane width (shuffle/tenancy.py AsyncShuffleExecutor): the
    # EFFECTIVE worker count of the facade's async executor when this
    # read ran — 0 when no async plane is attached. A distributed
    # facade that asked for K workers but reports 1 here was clamped
    # (tenant.asyncAgreedOrder=false) — the unrequested-serialization
    # evidence the doctor reads.
    async_workers: int = 0
    # Topology plane (shuffle/topology.py): per-tier accounting of a
    # hierarchical exchange — one entry per fabric tier ("ici", "dcn"),
    # each a separate payload/wire pair (stage-1 ICI bytes vs stage-2
    # DCN bytes) with its own pad_ratio, measured wall (``ms``, from
    # the tiered pending's per-tier joins) and effective_bw_gbps; the
    # DCN entry's ``payload_rows`` with ``cross_exact=true`` is the
    # each-row-crosses-the-slow-tier-exactly-once evidence (derived
    # from the metadata table's device matrix). Empty on flat reads.
    # When present, the headline ``wire_bytes``/``pad_ratio`` above are
    # the TWO-HOP SUM (the real fabric cost), not the flat
    # single-collective lower bound the pre-topology reports carried.
    tiers: List[Dict] = field(default_factory=list)
    # Exchange anatomy (utils/anatomy.py, folded at settlement when the
    # tracer is on): the conservation-audited phase ledger — swept
    # non-overlapping wall milliseconds per canonical phase, whose sum
    # plus ``dark_ms`` equals ``anatomy_wall_ms`` exactly.
    # ``dark_intervals`` are the uncovered [start, end] pairs (ms into
    # the wall) — the dark_time doctor rule's evidence. Empty/0 when
    # the tracer is off (the direct-timed plan/pack/dispatch fields
    # above stay authoritative either way).
    phases: Dict[str, float] = field(default_factory=dict)
    dark_ms: float = 0.0
    anatomy_wall_ms: float = 0.0
    dark_intervals: List[List[float]] = field(default_factory=list)
    # Decision-plane summary (shuffle/decisions.py, stamped at
    # settlement): the agreement rounds THIS process closed during the
    # read's wall — {"rounds", "agree_ms", "slowest_topic"} — diffed
    # from the ledger's monotonic append index, so it is ring-wrap safe
    # and free when the plane is off (the NULL ledger yields {}).
    # Per-process activity during the wall, not a per-read causal join:
    # a concurrent async read's rounds land in whichever report's
    # window they close in.
    agreement: Dict = field(default_factory=dict)
    completed: bool = False
    error: Optional[str] = None
    # bookkeeping, excluded from to_dict()
    # exchange wall start (perf_counter, set by _new_report) — closed
    # into the shuffle.exchange wall span at settlement
    _t_start: float = field(default=0.0, repr=False)
    _full_done: bool = field(default=False, repr=False)
    _t_dispatched: float = field(default=0.0, repr=False)
    _hits0: float = field(default=0.0, repr=False)
    _prog0: float = field(default=0.0, repr=False)
    # raw/wire row-width gain of the int8 tier (1.0 elsewhere) — feeds
    # effective_bw_gbps at settlement
    _wire_gain: float = field(default=1.0, repr=False)
    # exchange sequence (the x<seq> of the trace id) — the int8 noise
    # base every dispatch of this read derives its streams from
    _seq: int = field(default=0, repr=False)
    # decision-ledger monotonic index at read start (-1 = plane off) —
    # settlement diffs it into the public ``agreement`` summary
    _agree_mark: int = field(default=-1, repr=False)

    # public field names, resolved once: to_dict runs per report per
    # doctor/stats/dump pass, and dataclasses.asdict's recursive deepcopy
    # made it the single hottest piece of a doctor pass
    _PUBLIC_FIELDS: ClassVar[tuple] = ()

    def to_dict(self) -> Dict:
        cls = type(self)
        if not cls._PUBLIC_FIELDS:
            cls._PUBLIC_FIELDS = tuple(
                f.name for f in dataclasses.fields(cls)
                if not f.name.startswith("_"))
        out = {}
        for name in cls._PUBLIC_FIELDS:
            v = getattr(self, name)
            if isinstance(v, list):
                v = list(v)
            elif isinstance(v, dict):
                v = dict(v)
            out[name] = v
        return out


@dataclass
class ShuffleHandle:
    """Broadcastable shuffle descriptor (UcxShuffleHandle analog).

    ``epoch`` pins the handle to the mesh membership it was registered
    under; a remesh invalidates it fail-fast (runtime/failures.py
    EpochManager) instead of letting a collective hang."""

    shuffle_id: int
    num_maps: int
    num_partitions: int
    entry: ShuffleEntry = field(repr=False)
    partitioner: str = "hash"
    epoch: int = 0
    # sorted int64 split points for partitioner="range" (Spark's
    # RangePartitioner analog — the caller samples them, like Spark's
    # reservoir sampling, and every process must pass the same tuple)
    bounds: Optional[tuple] = None
    # tenancy: the tenant id the shuffle was registered under — every
    # read of this handle is accounted, admitted and policy-resolved
    # (replay budget, integrity level, wave depth) as this tenant
    tenant: str = "default"

    def __post_init__(self):
        if self.num_maps <= 0 or self.num_partitions <= 0:
            raise ValueError("num_maps and num_partitions must be positive")
        if self.partitioner not in ("hash", "direct", "range"):
            raise ValueError(f"unknown partitioner {self.partitioner!r}")
        if (self.partitioner == "range") != (self.bounds is not None):
            raise ValueError(
                "partitioner='range' requires bounds (and only it)")


class TpuShuffleManager:
    """Per-process shuffle service bound to a TpuNode."""

    def __init__(self, node: Optional[TpuNode] = None,
                 conf: Optional[TpuShuffleConf] = None):
        self.node = node or TpuNode.start(conf)
        self.conf = conf or self.node.conf
        self._writers: Dict[int, Dict[int, MapOutputWriter]] = {}
        # Learned receive capacities keyed by shuffle shape: a skewed
        # workload pays the overflow-retry recompile once, then every later
        # shuffle of the same shape starts at the capacity that worked.
        self._cap_hints: Dict[tuple, int] = {}
        # same idea for WAVE plans ((cap_key, wave cap_in) -> settled
        # cap_out): a wave that overflowed grows once, then every later
        # wave — this exchange's AND later same-shape exchanges' — starts
        # at the capacity that worked (no per-exchange re-overflow)
        self._wave_cap_hints: Dict[tuple, int] = {}
        # Persistent pack executor (a2a.packThreads), built lazily by
        # _pack_executor() and shut down in stop(): _pack_shards used to
        # spawn/tear down a ThreadPoolExecutor PER READ, whose cost
        # forced a 16 MiB amortization guard — and the wave pipeline
        # packs N-waves times per read, multiplying that spawn cost.
        self._pack_pool = None
        # writers dropped by an epoch bump, kept alive until no read that
        # could still touch their buffers remains (see _on_epoch_bump)
        self._graveyard: list = []          # [(dropped_at_gen, writers)]
        # -- recovery ledger (failure.policy=replay) ----------------------
        # Registration shapes by shuffle id — what re-registration under
        # a new epoch needs (the registry entry may be gone: remesh
        # clears it BEFORE bump listeners run).
        self._shapes: Dict[int, Dict] = {}
        # Shuffles the last epoch bump carried over: sid -> {entry,
        # epoch}. A stale handle re-pins through this instead of
        # StaleEpochError when the policy allows.
        self._replayed: Dict[int, Dict] = {}
        # Cumulative replays spent per shuffle (re-pins + re-runs);
        # past failure.replayBudget the shuffle falls back to failfast.
        self._replay_counts: Dict[int, int] = {}
        self._policy = self.conf.failure_policy
        self._replay_budget = self.conf.replay_budget
        # -- integrity plane (shuffle/integrity.py) -----------------------
        self._integrity_level = self.conf.integrity_verify
        # distributed full-verify: per-shuffle expected digest tables
        # allgathered at submit, consumed by the post-collective check
        self._full_expect: Dict[int, Dict] = {}
        self._warned_integrity: set = set()     # warn-once latches
        # -- durable ledger (failure.ledgerDir, shuffle/durable.py) -------
        # Disk-backed twin of the replay ledger: commits seal to disk,
        # and THIS constructor — a restarted process — scans the
        # directory, validates manifests + checksums, re-registers
        # intact shuffles and keeps them adoptable by register_shuffle
        # with zero recompute (quarantined blocks excepted).
        self._ledger = None
        self._recovered: Dict[int, Dict] = {}
        if self.conf.ledger_dir:
            from sparkucx_tpu.shuffle.durable import ShuffleLedger
            self._ledger = ShuffleLedger(self.conf.ledger_dir)
            self._ledger.epoch = self.node.epochs.current
            self._recover_from_ledger()
        # In-flight reads by the manager GENERATION they registered under.
        # The generation (not the node epoch) keys the guard because it is
        # mutated under the same lock that clears _writers — the node
        # epoch increments before the bump listener runs, so epoch-keyed
        # tracking would let a read register "post-bump" yet still
        # snapshot pre-bump writers.
        self._gen = 0
        self._active_reads: Dict[int, int] = {}
        # monotone exchange counter — the seq component of trace ids
        # (reads are collective, so it advances in lockstep cluster-wide)
        self._exchange_seq = 0
        # warn-once latch: a2a.wire=lossless on a single-shot read is an
        # inert codec (it rides the wave drain path only)
        self._warned_inert_lossless = False
        # warn-once latches for read-sink resolution (read.sink=device
        # falling back to host, lossless-under-device-sink inertness)
        self._warned_sink: set = set()
        self._lock = threading.Lock()
        # -- multi-tenant service plane (shuffle/tenancy.py) --------------
        # Per-tenant policy (priority weights, quotas, replay/integrity
        # overrides) resolved purely from conf; every shuffle carries its
        # tenant on the handle from register_shuffle on.
        self._tenants = TenantRegistry(self.conf)
        # Admission control (a2a.maxBytesInFlight): combined footprint of
        # in-flight submitted exchanges; submit() blocks past the cap
        # (ref: UcxShuffleReader.scala:56-70 — Spark's
        # ShuffleBlockFetcherIterator throttles inflight bytes the same
        # way). Deferred exchanges queue in a WEIGHTED FAIR-SHARE queue
        # (deficit round-robin across tenants, priority classes as weight
        # multipliers) instead of the historical FIFO, so a whale shuffle
        # parked at the head cannot starve every minnow behind it;
        # tenant.fairShare=false restores strict FIFO.
        self._inflight_bytes = 0
        self._inflight_by_tenant: Dict[str, int] = {}
        # admission grant sequencing (the cross-grants discriminator):
        # total grants ever, and grants per tenant — both monotone,
        # mutated under the cv lock only
        self._grant_seq = 0
        self._grant_count_by_tenant: Dict[str, int] = {}
        self._inflight_cv = threading.Condition(self._lock)
        self._admit_queue = FairShareQueue(self._tenants) \
            if self._tenants.fair_share else FifoAdmitQueue()
        self._admit_ticket = 0
        # concurrently-packing tenants (pack-executor fair share):
        # tenant -> live pack count, guarded by _lock
        self._packing: Dict[str, int] = {}
        # Telemetry plane: latest ExchangeReport per shuffle id (ring of
        # metrics.reportCapacity, survives unregister so a postmortem can
        # still explain a shuffle that was torn down; eviction is
        # tenant-aware — see _evict_reports_locked). The flight recorder
        # pulls them at dump time through the exchange_reports provider.
        self._report_capacity = max(
            1, self.conf.get_int("metrics.reportCapacity",
                                 REPORT_CAPACITY))
        self._reports: "OrderedDict[int, ExchangeReport]" = OrderedDict()
        self.node.flight.add_context_provider(self.exchange_reports)
        self._bind_mesh()
        # Elastic membership: a remesh (node.remesh) bumps the epoch; this
        # manager rebinds to the new mesh and drops writer state for the
        # cleared shuffles — handles from the old epoch fail fast in read()
        self.node.epochs.on_bump(self._on_epoch_bump)

    def _bind_mesh(self) -> None:
        """Derive the exchange topology from the node's current mesh —
        resolved through the topology plane (``a2a.topology``, slice
        detection under ``auto``), so a replay remesh re-resolves on
        the SURVIVING mesh: a world that is still 2-D multi-slice keeps
        the two-tier exchange, one that collapsed to a single slice
        falls back to flat."""
        from sparkucx_tpu.shuffle.topology import resolve_topology
        mesh = self.node.mesh
        self.topology = resolve_topology(mesh, self.conf)
        self.axis = self.topology.ici_axis
        self.hierarchical = self.topology.hierarchical
        if len(mesh.axis_names) > 1:
            from jax.sharding import Mesh as _Mesh
            self.exchange_mesh = _Mesh(
                mesh.devices.reshape(-1), (self.axis,))
        else:
            self.exchange_mesh = mesh

    def _on_epoch_bump(self, epoch: int) -> None:
        self._bind_mesh()
        if self._ledger is not None:
            # manifests written from now on record the new epoch
            self._ledger.epoch = epoch
            # a remesh cleared the registry BEFORE this listener ran:
            # ledger-recovered shuffles still awaiting adoption would
            # otherwise hand out orphaned entries — re-register them
            # under the new epoch (their sealed files are disk state a
            # membership change did not touch)
            self._refresh_recovered_registrations()
        # Recovery ledger (failure.policy=replay): an epoch bump no
        # longer unconditionally drops every shuffle. The staged writer
        # blocks on THIS process are host memory — a membership change
        # did nothing to them (Spark's map outputs survive executor loss
        # the same way: durable local files) — so shuffles whose local
        # staged state is fully intact re-register under the new epoch
        # and stale handles re-pin through _resolve_handle instead of
        # dying on StaleEpochError. Anything partial drops as before.
        survivors = self._ledger_candidates() \
            if self._policy == "replay" else {}
        with self._lock:
            dropped = [ws for sid, ws in self._writers.items()
                       if sid not in survivors]
            self._writers = {sid: ws for sid, ws in self._writers.items()
                             if sid in survivors}
            # DEFERRED release: a read that passed epoch validation just
            # before this bump may still be copying staged arena arrays /
            # spill mmap views — releasing now would hand its buffers to
            # the next shuffle mid-copy (use-after-free). Such a read is
            # doomed (its mesh is gone) but must fail, not corrupt. Each
            # dropped batch is tagged with the generation of the clear and
            # released only when NO read registered before the clear
            # remains in flight (round-2 advisor: a fixed one-epoch
            # deferral still raced a slow read under two quick remeshes).
            self._gen += 1
            if dropped:
                self._graveyard.append((self._gen, dropped))
            to_free = self._collect_free_graveyard_locked()
        self._release_writer_batches(to_free)
        carried = [sid for sid in sorted(survivors)
                   if self._reregister_shuffle(sid, epoch)]
        mesh_desc = dict(zip(self.node.mesh.axis_names,
                             self.node.mesh.devices.shape))
        if carried:
            log.warning(
                "manager rebound to epoch %d: mesh %s; %d shuffle(s) "
                "re-registered from the recovery ledger (%s) — stale "
                "handles replay transparently; %d dropped", epoch,
                mesh_desc, len(carried), carried, len(dropped))
        else:
            log.warning(
                "manager rebound to epoch %d: mesh %s, shuffle state "
                "dropped — re-register and re-run live shuffles", epoch,
                mesh_desc)

    # -- recovery ledger (failure.policy=replay) ---------------------------
    def _ledger_candidates(self) -> Dict[int, Dict]:
        """Shuffles whose LOCAL staged writer blocks are intact — every
        map committed, none released — the re-registration precondition.
        A partially-staged shuffle drops as before: an uncommitted map's
        rows are unrecoverable without re-running its task, which is the
        host framework's job."""
        with self._lock:
            snap = {sid: dict(ws) for sid, ws in self._writers.items()}
        out: Dict[int, Dict] = {}
        for sid, ws in snap.items():
            shape = self._shapes.get(sid)
            if not shape or not ws:
                continue
            committed = {m for m, w in ws.items()
                        if w.committed and not w.released}
            if committed == set(range(shape["num_maps"])):
                out[sid] = ws
        return out

    def _reregister_shuffle(self, sid: int, epoch: int) -> bool:
        """Re-register one ledger survivor under the new epoch: fresh
        registry entry (the remesh cleared the old one), the committed
        size rows copied over from the old entry the writers still hold,
        writers re-pointed. On ANY failure the shuffle is dropped the
        pre-ledger way (graveyard + release) — a half-re-registered
        shuffle must not serve reads."""
        try:
            shape = self._shapes[sid]
            with self._lock:
                ws = dict(self._writers.get(sid, {}))
            old_entry = next(iter(ws.values())).entry
            reg = self.node.registry
            reg.unregister(sid)     # no-op when remesh already cleared it
            entry = reg.register(sid, shape["num_maps"],
                                 shape["num_partitions"],
                                 shape["partitioner"], shape["bounds"])
            for m in sorted(ws):
                # the integrity record rides the re-registration beside
                # the size row — a replayed read must still verify
                entry.publish(m, old_entry.fetch_record(m),
                              integrity=old_entry.fetch_integrity(m))
                ws[m].entry = entry
            with self._lock:
                self._replayed[sid] = {"entry": entry, "epoch": epoch}
            return True
        except Exception as e:
            log.error("recovery ledger could not re-register shuffle %d "
                      "(%s) — dropping it", sid, e)
            with self._lock:
                ws = self._writers.pop(sid, None)
                to_free = []
                if ws:
                    self._gen += 1
                    self._graveyard.append((self._gen, [ws]))
                    to_free = self._collect_free_graveyard_locked()
            self._release_writer_batches(to_free)
            return False

    def _tenant_of(self, sid: int) -> str:
        """The tenant a shuffle was registered under (the conf default
        for shuffles that predate the registration record)."""
        with self._lock:
            shape = self._shapes.get(sid)
        return (shape or {}).get("tenant") or self._tenants.default_id

    def _integrity_for(self, tenant: Optional[str]) -> str:
        """The integrity verify level for one tenant's shuffles: the
        per-tenant ``tenant.<id>.integrity.verify`` override when set,
        else the global ``integrity.verify``. Commit and read resolve
        from the same tenant of the same shuffle, so records and checks
        cannot disagree."""
        spec = self._tenants.spec(tenant)
        return spec.integrity_verify or self._integrity_level

    def _replay_budget_for(self, sid: int):
        """(budget, conf_key) for one shuffle: the tenant's
        ``replayBudget`` override when set, else the global."""
        tid = self._tenant_of(sid)
        spec = self._tenants.spec(tid)
        if spec.replay_budget is not None:
            return spec.replay_budget, \
                f"spark.shuffle.tpu.tenant.{tid}.replayBudget"
        return self._replay_budget, \
            "spark.shuffle.tpu.failure.replayBudget"

    def _spend_replay(self, sid: int) -> bool:
        """Consume one unit of the shuffle's replay budget (the tenant's
        override when set); False once exhausted (the caller falls back
        to failfast)."""
        budget, conf_key = self._replay_budget_for(sid)
        with self._lock:
            spent = self._replay_counts.get(sid, 0)
            if spent >= budget:
                log.error("shuffle %d replay budget exhausted (%d/%d, "
                          "%s) — failing fast", sid, spent, budget,
                          conf_key)
                return False
            self._replay_counts[sid] = spent + 1
        return True

    def _resolve_handle(self, handle: ShuffleHandle) -> int:
        """Pin a handle to the current epoch. Returns 1 when it was
        transparently re-pinned through the recovery ledger (counts as a
        replay), 0 when already current; raises StaleEpochError when the
        policy / ledger / budget cannot save it — the failfast default
        is exactly the old validate."""
        cur = self.node.epochs.current
        if handle.epoch == cur:
            return 0
        sid = handle.shuffle_id
        with self._lock:
            rec = self._replayed.get(sid)
        if self._policy != "replay" or rec is None \
                or rec["epoch"] != cur:
            self.node.epochs.validate(handle.epoch, f"shuffle {sid}")
            return 0              # unreachable: validate raises on stale
        if not self._spend_replay(sid):
            budget, conf_key = self._replay_budget_for(sid)
            raise StaleEpochError(
                f"shuffle {sid} pinned to epoch {handle.epoch}, mesh is "
                f"at {cur}, and its replay budget "
                f"({budget}) is spent — re-register and "
                f"re-run, or raise {conf_key}")
        handle.entry = rec["entry"]
        handle.epoch = cur
        log.warning("shuffle %d re-pinned to epoch %d through the "
                    "recovery ledger (staged state intact) — replaying "
                    "on the surviving mesh", sid, cur)
        return 1

    def _replay_after_failure(self, handle: ShuffleHandle, err) -> bool:
        """Whether read() may transparently re-run the exchange after a
        transient failure. Multi-process, the decision itself is a
        COLLECTIVE: every process proposes (shuffle_id, remaining
        budget) over the agreement channel and the group re-enters the
        exchange together, spending exactly one budget unit group-wide
        (each process decrements its own counter once, in lockstep) —
        a replay verdict taken on one process alone would desync the
        SPMD group. A distributed PeerLostError never replays in-place:
        the collective channel itself is dead, so the typed error
        surfaces to the recovery controller (buildlib/run_cluster.py),
        which re-bootstraps an agreed world; the ledger then serves the
        re-pin in the fresh manager. Single-process, a PeerLostError
        additionally remeshes over the probe's survivors first (the
        bump routes this shuffle through the ledger)."""
        if self._policy != "replay":
            return False
        if self.node.is_distributed:
            if isinstance(err, PeerLostError):
                return False      # allgather channel is gone — failfast
            budget, _ = self._replay_budget_for(handle.shuffle_id)
            with self._lock:
                left = budget - self._replay_counts.get(
                    handle.shuffle_id, 0)
            from sparkucx_tpu.shuffle.agreement import (
                AgreementDivergenceError, agree)
            # Dedicated (shorter) deadline for the entry round: when
            # the failure is NOT group-wide — a peer's read succeeded,
            # or failed with a different error class — that peer never
            # enters replay.enter, and without this bound the replaying
            # survivors would stall the FULL failure.collectiveTimeoutMs
            # before converting to failfast.
            enter_ms = self.conf.replay_agree_timeout_ms
            try:
                agree("replay.enter",
                      np.array([handle.shuffle_id, left],
                               dtype=np.int64),
                      conf_key="spark.shuffle.tpu.failure.replayBudget",
                      timeout_ms=enter_ms if enter_ms > 0 else None)
            except AgreementDivergenceError as e:
                # divergent budget (or a peer not replaying this
                # shuffle at all): no process may re-enter — the
                # collective would hang half the group
                log.error("distributed replay vetoed: %s", e)
                return False
            except Exception as e:
                log.error("distributed replay agreement failed (%s); "
                          "failing fast", e)
                return False
            if not self._spend_replay(handle.shuffle_id):
                return False
            self.node.flight.record("replay",
                                    shuffle_id=handle.shuffle_id,
                                    error=repr(err)[:200],
                                    distributed=True)
            log.warning("replaying shuffle %d group-wide after "
                        "transient failure: %r", handle.shuffle_id,
                        err)
            return True
        if not self._spend_replay(handle.shuffle_id):
            return False
        if isinstance(err, PeerLostError):
            try:
                self.node.remesh(
                    reason=f"replay shuffle {handle.shuffle_id} after "
                           f"{type(err).__name__}")
            except Exception as e:
                log.error("replay remesh failed (%s); failing fast", e)
                return False
            # The unit spent above covers this replay END TO END: re-pin
            # the handle through the ledger here, or the retry loop's
            # _resolve_handle would charge (and count) a SECOND unit for
            # the same fault — replayBudget=1 could then never absorb a
            # single peer loss, and one blip would read as a storm.
            cur = self.node.epochs.current
            with self._lock:
                rec = self._replayed.get(handle.shuffle_id)
            if rec is None or rec["epoch"] != cur:
                log.error("staged state for shuffle %d did not survive "
                          "the replay remesh; failing fast",
                          handle.shuffle_id)
                return False
            handle.entry = rec["entry"]
            handle.epoch = cur
        self.node.flight.record("replay", shuffle_id=handle.shuffle_id,
                                error=repr(err)[:200])
        log.warning("replaying shuffle %d after transient failure: %r",
                    handle.shuffle_id, err)
        return True

    def _account_replays(self, handle: ShuffleHandle, replays: int,
                         replay_ms: float) -> None:
        rep = self.report(handle.shuffle_id)
        if rep is not None:
            rep.replays = int(replays)
            rep.replay_ms = round(replay_ms, 3)
        self.node.metrics.inc(C_REPLAYS, float(replays))
        self.node.metrics.inc(labeled(C_REPLAYS, tenant=handle.tenant),
                              float(replays))
        if replay_ms:
            self.node.metrics.inc(C_REPLAY_MS, float(replay_ms))
            self.node.metrics.inc(
                labeled(C_REPLAY_MS, tenant=handle.tenant),
                float(replay_ms))

    # -- restart recovery (failure.ledgerDir, shuffle/durable.py) ----------
    def _recover_from_ledger(self) -> None:
        """Scan the durable ledger at construction: each CRC-validated
        manifest whose sealed files pass their checksums re-registers in
        the registry under the CURRENT epoch — intact size rows (and
        integrity records) published, corrupt blocks quarantined by the
        scan. ``register_shuffle`` with a matching shape then ADOPTS the
        recovered state instead of raising 'already registered', and
        reads serve the sealed mmap views with zero recompute."""
        reg = self.node.registry
        for rs in self._ledger.scan():
            sid = rs.shuffle_id
            try:
                reg.get(sid)
                continue       # a live manager in this process owns it
            except KeyError:
                pass
            try:
                entry = reg.register(sid, rs.num_maps, rs.num_partitions,
                                     rs.partitioner, rs.bounds)
                for mid in sorted(rs.intact):
                    rec, sizes = rs.intact[mid]
                    entry.publish(mid, sizes, integrity=rec)
            except Exception as e:
                log.error("restart recovery: shuffle %d could not "
                          "re-register (%s) — it will recompute", sid, e)
                reg.unregister(sid)
                continue
            self._recovered[sid] = {"rs": rs, "entry": entry}
            self.node.metrics.inc(C_INTEGRITY_RECOVERED,
                                  float(len(rs.intact)))
            if rs.quarantined:
                self.node.metrics.inc(C_INTEGRITY_QUARANTINED,
                                      float(len(rs.quarantined)))
                self.node.flight.record(
                    "block_quarantine", shuffle_id=sid,
                    maps=list(rs.quarantined))
            log.warning(
                "restart recovery: shuffle %d re-registered from the "
                "ledger (%d/%d maps intact served without recompute%s)",
                sid, len(rs.intact), rs.num_maps,
                f"; maps {rs.quarantined} quarantined — re-stage only "
                f"those" if rs.quarantined else "")

    def _refresh_recovered_registrations(self) -> None:
        """Re-register recovered-but-unadopted shuffles whose registry
        entries a remesh cleared (registry.clear runs before bump
        listeners). Failure drops the recovery — the shuffle simply
        recomputes, the no-ledger behavior."""
        reg = self.node.registry
        with self._lock:
            pending = list(self._recovered.items())
        for sid, rec in pending:
            try:
                reg.get(sid)
                continue                      # entry survived
            except KeyError:
                pass
            rs = rec["rs"]
            try:
                entry = reg.register(sid, rs.num_maps, rs.num_partitions,
                                     rs.partitioner, rs.bounds)
                for mid in sorted(rs.intact):
                    irec, sizes = rs.intact[mid]
                    entry.publish(mid, sizes, integrity=irec)
                rec["entry"] = entry
            except Exception as e:
                log.error("recovered shuffle %d could not re-register "
                          "after the remesh (%s) — it will recompute",
                          sid, e)
                with self._lock:
                    self._recovered.pop(sid, None)

    def recovered_shuffles(self) -> Dict[int, Dict]:
        """{shuffle_id: {"intact": [...], "quarantined": [...]}} still
        awaiting adoption by :meth:`register_shuffle` — the restart
        drill's zero-recompute evidence."""
        with self._lock:
            return {sid: {"intact": sorted(rec["rs"].intact),
                          "quarantined": list(rec["rs"].quarantined)}
                    for sid, rec in self._recovered.items()}

    def _adopt_recovered(self, rec: Dict, shuffle_id: int, num_maps: int,
                         num_partitions: int, partitioner: str,
                         bounds,
                         tenant: Optional[str] = None
                         ) -> Optional[ShuffleHandle]:
        """Install a ledger-recovered shuffle as live state: committed
        writers over the sealed file sets for every intact map (reads
        consume their mmap views — zero recompute), nothing for
        quarantined maps (``entry.present(m)`` is False there; the app
        re-stages only those). Returns None on a shape mismatch — the
        recovery is dropped and the caller registers fresh (a shuffle id
        reused with a different shape is a different shuffle)."""
        rs = rec["rs"]
        want_bounds = tuple(int(x) for x in bounds) \
            if bounds is not None else None
        if (num_maps, num_partitions, partitioner, want_bounds) != \
                (rs.num_maps, rs.num_partitions, rs.partitioner,
                 rs.bounds):
            log.warning(
                "register_shuffle(%d): shape differs from the ledger's "
                "(%dx%d %s vs %dx%d %s) — dropping the recovered state "
                "and registering fresh", shuffle_id, num_maps,
                num_partitions, partitioner, rs.num_maps,
                rs.num_partitions, rs.partitioner)
            self.node.registry.unregister(shuffle_id)
            if self._ledger is not None:
                self._ledger.forget(shuffle_id)
            return None
        tid = self._tenants.resolve(tenant)
        entry = rec["entry"]
        ws = {
            mid: MapOutputWriter.recovered(
                entry, mid, self.node.pool, rs.directory, irec,
                partitioner=partitioner, bounds=want_bounds,
                integrity_level=self._integrity_for(tid))
            for mid, (irec, _sizes) in rs.intact.items()}
        with self._lock:
            self._writers[shuffle_id] = ws
            self._shapes[shuffle_id] = {
                "num_maps": num_maps, "num_partitions": num_partitions,
                "partitioner": partitioner, "bounds": want_bounds,
                "tenant": tid}
            self._replayed.pop(shuffle_id, None)
            self._replay_counts.pop(shuffle_id, None)
        log.info(
            "shuffle %d adopted from the recovery ledger: %d/%d maps "
            "served from sealed spill files, %d to re-stage",
            shuffle_id, len(ws), num_maps, num_maps - len(ws))
        return ShuffleHandle(shuffle_id, num_maps, num_partitions, entry,
                             partitioner, self.node.epochs.current,
                             want_bounds, tenant=tid)

    # -- integrity verification (shuffle/integrity.py) ---------------------
    def _warn_integrity_once(self, key: str, msg: str) -> None:
        if key not in self._warned_integrity:
            self._warned_integrity.add(key)
            log.warning(msg)

    def _note_corruption(self, shuffle_id: int, block: str, nbytes: int,
                         detail: str) -> str:
        """Account one detected corruption (counters + a flight-ring
        event naming the corrupt block — the postmortem evidence) and
        return the error message for the typed raise."""
        self.node.metrics.inc(C_INTEGRITY_CORRUPT_BLOCKS, 1.0)
        self.node.metrics.inc(C_INTEGRITY_CORRUPT, float(max(nbytes, 0)))
        self.node.flight.record("block_corruption", shuffle_id=shuffle_id,
                                block=block, bytes=int(nbytes),
                                detail=detail[:200])
        msg = (f"shuffle {shuffle_id}: block corruption detected in "
               f"{block}: {detail} — staged bytes no longer match the "
               f"checksums published at commit "
               f"(spark.shuffle.tpu.integrity.verify="
               f"{self._integrity_level}); failure.policy=replay spends "
               f"one budget unit re-verifying and re-running")
        log.error(msg)
        return msg

    def _verified_materialize(self, entry, map_id: int, w):
        """Materialize one committed map output and re-verify its bytes
        against the integrity record published at commit — the
        pack-time staged verify (bytes are checked BEFORE they enter
        the exchange). Home of the FaultInjector ``corrupt`` sites:
        an armed ``corrupt.staged``/``corrupt.spill`` flips one bit
        into the staged arena bytes / sealed spill file for exactly the
        duration of this verification read (transient in-flight
        corruption — detection always fires; the replay's re-verify
        finds the bytes intact and recovers to oracle-exact output)."""
        from sparkucx_tpu.shuffle import integrity as integ
        faults = self.node.faults
        token = None
        try:
            spilled = w._spill is not None
            if not spilled and w._keys:
                # only consult the injector when a flippable target
                # exists: an empty map output must not CONSUME the
                # armed firing while applying no flip — the cell's
                # detection-always gate would read fault_fired=true
                # with nothing to detect
                off = faults.fire("corrupt.staged")
                if off is not None:
                    # pre-materialize: the arena batches are about to be
                    # concatenated, and the flip must ride the copy
                    token = integ.flip_array_byte(w._keys[0], off)
            keys, values = w.materialize()
            if spilled:
                off = faults.fire("corrupt.spill")
                if off is not None:
                    # post-mmap: MAP_SHARED views observe the file flip
                    # through the page cache
                    token = integ.flip_file_byte(w._spill.keys_path, off)
            rec = entry.fetch_integrity(map_id)
            if rec is None:
                # pre-integrity publisher (direct registry users,
                # integrity.verify=off at commit time): nothing to
                # check — -1 tells the caller this map does NOT count
                # as verified (the report must not claim it was)
                return keys, values, -1
            try:
                nbytes = integ.verify_staged(keys, values, rec)
            except integ._StagedMismatch as e:
                block = (os.path.basename(w._spill.keys_path)
                         if spilled else f"map {map_id} staged arena")
                raise BlockCorruptionError(self._note_corruption(
                    entry.shuffle_id, f"map {map_id} ({block})",
                    int(keys.nbytes)
                    + (int(values.nbytes) if values is not None else 0),
                    str(e))) from None
            return keys, values, nbytes
        finally:
            if token is not None:
                token.restore()

    def _verify_full_result(self, handle: ShuffleHandle, res,
                            combine: Optional[str] = None) -> None:
        """The ``integrity.verify=full`` post-collective check: every
        LOCAL reduce partition of the drained result re-digests
        (order-independent row-digest sums, shuffle/integrity.py) and
        must match the senders' published per-partition digest rows.
        Raw/lossless wires verify the full rows; the int8 tier verifies
        the exact key lanes (dequantized values are legitimately
        lossy). Entirely host-side — the compiled program is untouched
        at every level. Runs once per read (``_full_done``); combined
        reads skip (the device merge legitimately rewrites rows).

        Distributed note: a mismatch verdict is PROCESS-LOCAL (each
        process drains only its partitions) and runs AFTER the
        collective completed everywhere, so no peer is left mid-
        rendezvous; the raise surfaces typed to the caller because
        ``_replay_after_failure`` refuses distributed replays — the
        recovery controller owns the coordinated re-run, the same
        posture as every other distributed failure."""
        if self._integrity_for(handle.tenant) != "full":
            return
        rep = self.report(handle.shuffle_id)
        if rep is None or rep._full_done:
            return
        rep._full_done = True
        # the verify wall as an anatomy span — recorded on BOTH verdicts
        # (a corruption raise still burned the wall it burned), covering
        # the device-sampled variant through the delegation below
        _t0_verify = time.perf_counter()
        try:
            self._verify_full_inner(handle, res, rep, combine)
        finally:
            self.node.tracer.record_span(
                "shuffle.verify", _t0_verify, level="full",
                shuffle_id=handle.shuffle_id, trace=rep.trace_id)

    def _verify_full_inner(self, handle: ShuffleHandle, res,
                           rep, combine: Optional[str] = None) -> None:
        if getattr(res, "sink", "host") == "device":
            # device sink: the full digest check is host-side by design
            # and forcing the whole drain would re-pay the round-trip
            # the sink deletes — but silently downgrading to staged was
            # dishonest. Instead verify the EXACT lanes the wire
            # contract guarantees (keys + partition routing) on ONE
            # SAMPLED wave through a host-side COPY (device buffers
            # stay live for the consumer), counting the sampled D2H
            # bytes honestly in shuffle.read.d2h.bytes / the report.
            self._verify_full_device(handle, res, rep)
            return
        if combine:
            self._warn_integrity_once(
                "full_combine",
                "integrity.verify=full: combined reads verify at the "
                "staged level only — combine-by-key legitimately "
                "rewrites rows on device, so per-row digests cannot "
                "survive it")
            return
        from sparkucx_tpu.shuffle.integrity import (aggregate_digests,
                                                    digest_sum)
        key_only = rep.wire == "int8"
        if self.node.is_distributed:
            st = self._full_expect.pop(handle.shuffle_id, None)
            if st is None:
                self._warn_integrity_once(
                    "full_dist", "integrity.verify=full: no agreed "
                    "digest table for this distributed read (a peer "
                    "committed below the full level?) — staged verify "
                    "only")
                return
            expected = st["key" if key_only else "full"]
        else:
            expected = aggregate_digests(handle.entry, handle.num_maps,
                                         key_only)
            if expected is None:
                self._warn_integrity_once(
                    "full_missing",
                    "integrity.verify=full: commit published no digest "
                    "rows (maps committed below the full level) — "
                    "staged verify only for this shuffle")
                return
        verified = 0
        for r in range(handle.num_partitions):
            if not res.is_local(r):
                continue
            k, v = res.partition(r)
            got = digest_sum(k, None if key_only else v)
            if got != int(expected[r]):
                raise BlockCorruptionError(self._note_corruption(
                    handle.shuffle_id,
                    f"reduce partition {r} (post-collective"
                    f"{', key lanes' if key_only else ''})",
                    int(k.nbytes) + (int(v.nbytes) if v is not None
                                     and not key_only else 0),
                    f"drained digest {got:#x} != published "
                    f"{int(expected[r]):#x}"))
            verified += int(k.nbytes) + (int(v.nbytes)
                                         if v is not None
                                         and not key_only else 0)
        self.node.metrics.inc(C_INTEGRITY_VERIFIED, float(verified))
        rep.integrity = "full"
        rep.integrity_bytes += verified

    def _verify_full_device(self, handle: ShuffleHandle, res,
                            rep) -> None:
        """``integrity.verify=full`` over a DEVICE-sink result: sample
        the FIRST wave's KEY LANES (single-shot reads are one wave;
        waved ordered/combine reads land one MERGED view, so the sample
        covers the whole fold; a waved PLAIN device read is sampled at
        wave 0 only — the ISSUE-12 sampled-wave contract, with
        ``integrity_bytes`` recording exactly what was checked) as a
        host-side copy and re-derive every key's partition through the
        host twin of the device routing (integrity.verify_key_routing).
        Works for ALL modes — combine included, where per-row digests
        cannot survive the rewrite — and under every wire tier (key
        lanes are exact). Only the two key-lane columns transfer (the
        check reads nothing else), and the sampled pull is REAL D2H,
        charged to the read (``shuffle.read.d2h.bytes`` +
        ``ExchangeReport.d2h_bytes``) — the honest cost of
        verification, never smuggled. The pallas transport's
        chunk-ALIGNED plain layout (pad rows INSIDE segments — valid
        rows are not a prefix) cannot ride the prefix-based check and
        keeps the staged-only posture, warn-once."""
        from sparkucx_tpu.shuffle import integrity as integ
        from sparkucx_tpu.shuffle.reader import pull_to_host
        views = res.wave_views()
        if not views:
            return
        v = views[0]
        if getattr(v, "_align_chunk", 0):
            # chunk-aligned receive layout (pallas plain / strip sort):
            # per-segment pad rows sit between valid runs, so the
            # prefix slice would "verify" junk — or falsely flag it
            self._warn_integrity_once(
                "full_device_aligned",
                "integrity.verify=full: device-sink reads on a "
                "chunk-aligned receive layout (pallas plain / strip "
                "sort) verify at the staged level only — valid rows "
                "are not a dense prefix there")
            return
        with v._fetch_lock:
            rows_dev = v._rows_dev
            totals_dev = v._totals_dev
        if rows_dev is None or totals_dev is None:
            return        # already drained/consumed: nothing to sample
        # key lanes only: the check reads cols 0..1 of the valid prefix
        # (a COPY — the buffers stay live)
        rows = pull_to_host(rows_dev[:, :2], "rows", v)
        totals = np.asarray(totals_dev).reshape(-1)
        try:
            verified = integ.verify_key_routing(
                rows, totals, handle.num_partitions,
                self.node.num_devices, partitioner=handle.partitioner,
                bounds=handle.bounds)
        except integ._StagedMismatch as e:
            raise BlockCorruptionError(self._note_corruption(
                handle.shuffle_id,
                "device receive buffer (post-collective, key lanes, "
                "sampled wave 0)",
                int(rows.nbytes), str(e))) from None
        self.node.metrics.inc(C_INTEGRITY_VERIFIED, float(verified))
        rep.integrity = "full"
        rep.integrity_bytes += verified

    def _stash_full_expect(self, handle: ShuffleHandle, writers) -> None:
        """Distributed full verify: allgather every process's local
        digest-row sums so each receiver holds the GLOBAL expected
        table for its partitions. uint64 digests travel as four 16-bit
        lanes — the blob channel rides jnp int32 arithmetic, which
        silently truncates wider lanes (the e2e harness's established
        caveat). One extra metadata-plane allgather per read, only at
        the full level; any process lacking digest rows makes every
        process skip together (SPMD-uniform verdict)."""
        import numpy as _np
        from sparkucx_tpu.shuffle.distributed import allgather_blob
        R = handle.num_partitions
        full = _np.zeros(R, dtype=_np.uint64)
        key = _np.zeros(R, dtype=_np.uint64)
        have = 1
        for mid in writers:
            rec = handle.entry.fetch_integrity(mid)
            if rec is None or rec.digests is None:
                have = 0
                break
            full += _np.asarray(rec.digests, dtype=_np.uint64)
            key += _np.asarray(rec.key_digests, dtype=_np.uint64)

        def lanes(u64):
            out = _np.zeros(4 * R, dtype=_np.int64)
            for i in range(4):
                out[i::4] = ((u64 >> _np.uint64(16 * i))
                             & _np.uint64(0xFFFF)).astype(_np.int64)
            return out

        blob = _np.concatenate([_np.array([have], dtype=_np.int64),
                                lanes(full), lanes(key)])
        gathered = allgather_blob(blob)              # [nproc, 1+8R]
        if not int(gathered[:, 0].min()):
            return                                    # someone lacks rows

        def unlanes(rows):
            acc = _np.zeros(R, dtype=_np.uint64)
            for p in range(rows.shape[0]):
                u = _np.zeros(R, dtype=_np.uint64)
                for i in range(4):
                    u |= rows[p, i::4].astype(_np.uint64) \
                        << _np.uint64(16 * i)
                acc += u
            return acc

        self._full_expect[handle.shuffle_id] = {
            "full": unlanes(gathered[:, 1:1 + 4 * R]),
            "key": unlanes(gathered[:, 1 + 4 * R:]),
        }

    # -- in-flight read tracking (graveyard release condition) -------------
    def _collect_free_graveyard_locked(self) -> list:
        """Split off graveyard batches no in-flight read can reach. A read
        registered at generation G snapshotted _writers at G or later, so
        a batch cleared out at generation g_drop <= G was already gone
        before the read looked — only reads with G < g_drop can hold
        views into it. Caller holds the lock."""
        oldest = min(self._active_reads, default=None)
        free, keep = [], []
        for dropped_at, ws in self._graveyard:
            if oldest is None or oldest >= dropped_at:
                free.append(ws)
            else:
                keep.append((dropped_at, ws))
        self._graveyard = keep
        return free

    @staticmethod
    def _release_writer_batches(batches: list) -> None:
        """Each batch is one bump's drop: a list of per-shuffle writer
        dicts ({map_id: writer})."""
        for batch in batches:
            for ws in batch:
                for w in ws.values():
                    w.release()

    def _read_started(self) -> int:
        with self._lock:
            g = self._gen
            self._active_reads[g] = self._active_reads.get(g, 0) + 1
        return g

    def _read_finished(self, start_gen: int) -> None:
        with self._lock:
            n = self._active_reads.get(start_gen, 0) - 1
            if n > 0:
                self._active_reads[start_gen] = n
            else:
                self._active_reads.pop(start_gen, None)
            to_free = self._collect_free_graveyard_locked()
            # same underlying lock as the admission cv — wake stop()'s
            # read-drain wait too
            self._inflight_cv.notify_all()
        self._release_writer_batches(to_free)

    # -- exchange reports (telemetry plane) --------------------------------
    def _new_report(self, handle: ShuffleHandle,
                    distributed: bool) -> ExchangeReport:
        rep = ExchangeReport(
            shuffle_id=handle.shuffle_id, num_maps=handle.num_maps,
            num_partitions=handle.num_partitions,
            partitioner=handle.partitioner,
            process_id=self.node.process_id, distributed=distributed,
            hierarchical=self.hierarchical,
            tenant=handle.tenant,
            # effective async-plane width: the facade stamps
            # _async_workers when it builds its executor (0 = none)
            async_workers=int(getattr(self, "_async_workers", 0)))
        # the exchange WALL starts here: a report exists from read start
        # (postmortem discipline), and the anatomy plane conserves
        # against this instant at settlement
        rep._t_start = time.perf_counter()
        # step-cache counters are process-global; the delta between read
        # start and completion attributes compiles to this exchange
        # (approximate under concurrent reads, exact in the common case)
        rep._hits0 = GLOBAL_METRICS.get(COMPILE_HITS)
        rep._prog0 = GLOBAL_METRICS.get(COMPILE_PROGRAMS)
        # decision-ledger position at read start: settlement diffs the
        # monotonic append index into report.agreement (same
        # window-delta discipline as the compile counters above)
        try:
            from sparkucx_tpu.shuffle.decisions import current_ledger
            rep._agree_mark = int(current_ledger().total)
        except Exception:
            rep._agree_mark = -1
        with self._lock:
            # Exchange sequence: reads are collective and execute in the
            # same order on every process, so this per-process counter
            # agrees cluster-wide — the seq third of the trace id.
            self._exchange_seq += 1
            rep._seq = self._exchange_seq
            rep.trace_id = format_trace_id(
                handle.shuffle_id, self.node.epochs.current,
                self._exchange_seq)
            self._reports[handle.shuffle_id] = rep
            self._reports.move_to_end(handle.shuffle_id)
            while len(self._reports) > self._report_capacity:
                self._evict_report_locked()
        # ring events recorded while this exchange is in flight carry its
        # trace id (ended by on_done, or the submit failure paths)
        self.node.flight.begin_trace(rep.trace_id)
        return rep

    def _evict_report_locked(self) -> None:
        """Evict ONE report, tenant-aware: the victim is the OLDEST
        report of the tenant holding the most ring entries, so a chatty
        tenant churns its own history instead of flushing another
        tenant's reports out from under gather_reports/doctor before
        they are read. One tenant degenerates to the historical LRU
        exactly (its oldest == the global oldest)."""
        counts: Dict[str, int] = {}
        for r in self._reports.values():
            counts[r.tenant] = counts.get(r.tenant, 0) + 1
        if len(counts) <= 1:
            self._reports.popitem(last=False)
            return
        # max count wins; ties resolve to whichever tenant owns the
        # globally oldest entry (insertion order scan — deterministic)
        top = max(counts.values())
        for sid, r in self._reports.items():
            if counts[r.tenant] == top:
                self._reports.pop(sid)
                return

    def report(self, shuffle_id: int) -> Optional[ExchangeReport]:
        """Latest ExchangeReport for a shuffle (None if never read or
        evicted from the ring)."""
        with self._lock:
            return self._reports.get(shuffle_id)

    def reports(self) -> List[ExchangeReport]:
        """All retained reports, oldest first."""
        with self._lock:
            return list(self._reports.values())

    def exchange_reports(self) -> List[Dict]:
        """JSON-able view of the retained reports — the flight-recorder
        context provider (its dump key is this method's name)."""
        return [r.to_dict() for r in self.reports()]

    def gather_reports(self, shuffle_id: int) -> List[Dict]:
        """COLLECTIVE (distributed mode): allgather every process's
        report for a shuffle so any process — process 0 for the operator
        — holds the cluster-wide picture. Two allgather rounds (length,
        then max-padded payload) over ``shuffle/distributed
        .allgather_blob``, the same metadata-plane channel the schema
        agreement rides. Single-process: the local report alone.

        Every process must call it (the usual SPMD discipline); entries
        are per-process dicts, empty for a process that never read the
        shuffle."""
        rep = self.report(shuffle_id)
        local = rep.to_dict() if rep is not None else {}
        if not self.node.is_distributed:
            return [local] if local else []
        from sparkucx_tpu.shuffle.distributed import allgather_json
        return allgather_json(local)

    def gather_spans(self) -> List[Dict]:
        """COLLECTIVE (distributed mode): every process's span buffer as
        chrome trace events plus its clock anchor — the input of
        ``utils.export.merge_timeline`` (one Perfetto doc, a track per
        process, clock-aligned through the anchors). Same two-round
        allgather channel as :meth:`gather_reports`. Single-process:
        just the local capture. Every process must call it (SPMD
        discipline); a process with tracing off contributes an empty
        event list but still a valid anchor."""
        tracer = self.node.tracer
        local = {
            "process_id": self.node.process_id,
            "pid": os.getpid(),
            "anchor": tracer.anchor(),
            "events": tracer.chrome_events(),
            "dropped_spans": tracer.dropped,
        }
        if not self.node.is_distributed:
            return [local]
        from sparkucx_tpu.shuffle.distributed import allgather_json
        return allgather_json(local)

    # -- lifecycle --------------------------------------------------------
    def register_shuffle(self, shuffle_id: int, num_maps: int,
                         num_partitions: int,
                         partitioner: str = "hash",
                         bounds=None,
                         tenant: Optional[str] = None) -> ShuffleHandle:
        """Allocate the metadata table for a shuffle
        (ref: CommonUcxShuffleManager.scala:39-56). ``partitioner`` is the
        Spark Partitioner-SPI analog: 'hash' groups by key hash; 'direct'
        treats keys as precomputed partition ids; 'range' routes the full
        int64 key through the sorted split points in ``bounds``
        (device-evaluated — Spark's RangePartitioner).

        ``tenant`` pins the shuffle to a tenant id (default: the conf
        ``tenant.id``) — every read is then admitted, accounted and
        policy-resolved (replay budget, integrity level, wave depth) as
        that tenant (shuffle/tenancy.py). The per-tenant conf overrides
        are VALIDATED here, at registration, not mid-read."""
        if bounds is not None:
            b = np.asarray(bounds, dtype=np.int64)
            # validate HERE, not at read time: a malformed bounds tuple
            # would otherwise publish silently-wrong size rows through the
            # whole map phase before make_plan finally rejects it
            if b.shape != (num_partitions - 1,) or (np.diff(b) < 0).any():
                raise ValueError(
                    f"range bounds must be {num_partitions - 1} sorted "
                    f"int64 split points, got shape {b.shape}")
            bounds = tuple(int(x) for x in b)
        # every ShuffleHandle invariant must hold BEFORE touching the
        # registry: a post-registration validation failure would leak a
        # dead entry that blocks the corrected retry ("already registered")
        if (partitioner == "range") != (bounds is not None):
            raise ValueError(
                "partitioner='range' requires bounds (and only it)")
        # tenancy: resolve + VALIDATE the tenant's policy now (a typo'd
        # tenant.<id>.priority must fail registration, not the first
        # read); the spec itself is re-resolved at each use site
        tid = self._tenants.resolve(tenant)
        self._tenants.spec(tid)
        # Restart recovery (failure.ledgerDir): a shuffle the ledger scan
        # validated is ADOPTED — committed writers over its sealed files,
        # zero recompute of intact maps — instead of colliding with its
        # own re-registration. Shape mismatch drops the recovery and
        # registers fresh.
        with self._lock:
            rec = self._recovered.pop(shuffle_id, None)
        if rec is not None:
            h = self._adopt_recovered(rec, shuffle_id, num_maps,
                                      num_partitions, partitioner,
                                      bounds, tenant=tid)
            if h is not None:
                return h
        entry = self.node.registry.register(shuffle_id, num_maps,
                                            num_partitions, partitioner,
                                            bounds)
        with self._lock:
            self._writers[shuffle_id] = {}
            # recovery-ledger shape record: re-registration after a
            # remesh needs it (the registry entry dies with the epoch);
            # a fresh registration resets the replay bookkeeping
            self._shapes[shuffle_id] = {
                "num_maps": num_maps, "num_partitions": num_partitions,
                "partitioner": partitioner, "bounds": bounds,
                "tenant": tid}
            self._replayed.pop(shuffle_id, None)
            self._replay_counts.pop(shuffle_id, None)
        log.info("registered shuffle %d: %d maps x %d partitions "
                 "(table %d B, tenant %s)", shuffle_id, num_maps,
                 num_partitions, len(entry.table), tid)
        return ShuffleHandle(shuffle_id, num_maps, num_partitions, entry,
                             partitioner, self.node.epochs.current,
                             bounds, tenant=tid)

    def get_writer(self, handle: ShuffleHandle,
                   map_id: int) -> MapOutputWriter:
        """Writer for one map task (ref: compat/spark_3_0/
        UcxShuffleManager.scala:32-51)."""
        if not (0 <= map_id < handle.num_maps):
            raise IndexError(
                f"mapId {map_id} out of range [0,{handle.num_maps})")
        # durable staging: with the ledger on, spills land in the
        # shuffle's ledger dir and commit() seals + manifests them there
        spill_dir = self._ledger.shuffle_dir(handle.shuffle_id) \
            if self._ledger is not None else self.conf.spill_dir
        w = MapOutputWriter(handle.entry, map_id, self.node.pool,
                            partitioner=handle.partitioner,
                            faults=self.node.faults,
                            spill_dir=spill_dir,
                            spill_threshold=self.conf.spill_threshold,
                            bounds=handle.bounds,
                            integrity_level=self._integrity_for(
                                handle.tenant),
                            ledger=self._ledger)
        with self._lock:
            # First-commit-wins: a committed map output is immutable. A
            # speculative or retried map task may run again, but replacing
            # the committed writer would discard its staged rows while the
            # metadata table still claims them — read() would then silently
            # return an incomplete result. (Spark resolves the same race by
            # keeping the first committed index/data file pair.)
            prev = self._writers[handle.shuffle_id].get(map_id)
            if prev is not None and prev.committed:
                raise RuntimeError(
                    f"shuffle {handle.shuffle_id} map {map_id} is already "
                    f"committed; its output is immutable (first commit "
                    f"wins). unregister_shuffle() to restart the shuffle.")
            if prev is not None:
                # failed-task retry: the half-written writer is dead —
                # return its staged arena blocks before dropping it
                prev.release()
            self._writers[handle.shuffle_id][map_id] = w
            live = sum(1 for ws in self._writers.values()
                       for x in ws.values() if not x.committed)
        cores = self.conf.cores_per_process
        if live > cores:
            log.warning(
                "%d uncommitted writers live > coresPerProcess=%d; map "
                "tasks are oversubscribing this process (ref: "
                "UcxNode.java:85-95 warns the same way)", live, cores)
        return w

    # -- admission control -------------------------------------------------
    @staticmethod
    def _exchange_footprint(plan: ShufflePlan, width: int,
                            stage_bytes: int) -> int:
        """Approximate bytes a pending exchange holds until result(): the
        pinned pack buffer plus the device send+receive row matrices.
        Deliberately an estimate — the cap is backpressure, not a ledger."""
        device = (plan.cap_in + plan.cap_out) * width * 4 * plan.num_shards
        return int(stage_bytes) + int(device)

    def _tenant_fits_locked(self, tenant: str, nbytes: int) -> bool:
        """Capacity predicate for ONE tenant's next reservation under the
        lock: global room (the admitted-alone rule keeps a bigger-than-
        cap exchange from deadlocking itself) AND the tenant's own quota
        room (``tenant.<id>.maxBytesInFlight``; same alone rule per
        tenant, so a quota smaller than one exchange still admits it
        when the tenant has nothing else in flight)."""
        cap = self.conf.max_bytes_in_flight
        if self._inflight_bytes and self._inflight_bytes + nbytes > cap:
            return False
        quota = self._tenants.spec(tenant).max_bytes_in_flight
        if quota > 0:
            held = self._inflight_by_tenant.get(tenant, 0)
            if held and held + nbytes > quota:
                return False
        return True

    def _tenant_quota_blocked_locked(self, tenant: str,
                                     nbytes: int) -> bool:
        """True when GLOBAL room exists for this reservation but the
        tenant's OWN quota refuses it — the one case the fair-share
        queue may bypass the head for (a globally-blocked head must
        keep the floor until in-flight bytes drain, or a big exchange
        starves behind a stream of small ones)."""
        cap = self.conf.max_bytes_in_flight
        if self._inflight_bytes and self._inflight_bytes + nbytes > cap:
            return False
        quota = self._tenants.spec(tenant).max_bytes_in_flight
        if quota <= 0:
            return False
        held = self._inflight_by_tenant.get(tenant, 0)
        return bool(held) and held + nbytes > quota

    def _fits_inflight_locked(self, nbytes: int, ticket=None,
                              tenant: Optional[str] = None) -> bool:
        """Admission check under the lock. A submit-time attempt
        (ticket=None) must yield to any already-deferred exchange, or a
        later submit would steal capacity freed for a queued one and
        starve it (Spark's fetch iterator defers requests the same way);
        a QUEUED ticket is admitted only when the fair-share queue's
        deficit-round-robin scan selects it — across tenants the grant
        order is weighted by priority class, within a tenant it stays
        FIFO (submit order is the collective order)."""
        tid = self._tenants.resolve(tenant)
        if ticket is None:
            if self._admit_queue:
                return False
            return self._tenant_fits_locked(tid, nbytes)
        return self._admit_queue.grantable(
            self._tenant_fits_locked,
            self._tenant_quota_blocked_locked) == ticket

    def _grant_inflight_locked(self, tenant: str, nbytes: int) -> None:
        """Account one granted reservation (under the lock): global and
        per-tenant in-flight bytes, the cumulative per-tenant grant
        counter/sequence, and the point-in-time inflight gauge the
        doctor's quota_starvation rule reads for the hog's held share."""
        self._inflight_bytes += nbytes
        held = self._inflight_by_tenant.get(tenant, 0) + nbytes
        self._inflight_by_tenant[tenant] = held
        # grant sequence numbers feed the cross-grants starvation
        # discriminator: a deferred ticket snapshots them at enqueue and
        # differences them at grant (see _make_admitter)
        self._grant_seq += 1
        self._grant_count_by_tenant[tenant] = \
            self._grant_count_by_tenant.get(tenant, 0) + 1
        metrics = self.node.metrics
        metrics.inc(labeled(C_ADMIT_BYTES, tenant=tenant), float(nbytes))
        metrics.set_gauge(labeled(G_TENANT_INFLIGHT, tenant=tenant),
                          held)

    def _release_inflight(self, nbytes: int,
                          tenant: Optional[str] = None) -> None:
        if nbytes <= 0:
            return
        tid = self._tenants.resolve(tenant)
        with self._inflight_cv:
            self._inflight_bytes -= nbytes
            held = self._inflight_by_tenant.get(tid, 0) - nbytes
            if held > 0:
                self._inflight_by_tenant[tid] = held
            else:
                self._inflight_by_tenant.pop(tid, None)
            self.node.metrics.set_gauge(
                labeled(G_TENANT_INFLIGHT, tenant=tid), max(0, held))
            self._inflight_cv.notify_all()

    def _make_admitter(self, plan: ShufflePlan, width: int,
                       stage_bytes: int, timeout: Optional[float],
                       tenant: Optional[str] = None,
                       report: Optional[ExchangeReport] = None):
        """(admit, release) pair for one exchange; ``admit(block)`` is
        handed to the pending handle (None when the cap is off), and
        ``release()`` is idempotent — safe from the exactly-once on_done
        AND the not-yet-armed failure path.

        Tenancy: the reservation is accounted to ``tenant`` (the
        handle's registration tenant), checked against the tenant's own
        quota on top of the global cap, and — when deferred — granted in
        the fair-share queue's deficit-round-robin order instead of
        FIFO. Every grant observes its deferral wall into the labeled
        ``shuffle.admit.wait_ms{tenant=...}`` histogram (0 for an
        immediate grant), the distribution the doctor's quota_starvation
        rule grades.

        ``timeout=None`` — wait without a deadline (the distributed path:
        a local wall-clock TimeoutError could fire on one process while a
        peer proceeds into the collective, diverging the SPMD group; with
        the documented resolve-in-order discipline capacity is guaranteed
        to free, so indefinite blocking is the collective-safe choice —
        the same contract as result() itself)."""
        if self.conf.max_bytes_in_flight <= 0:
            return None, lambda: None
        tid = self._tenants.resolve(tenant)
        nbytes = self._exchange_footprint(plan, width, stage_bytes)
        state = {"reserved": 0, "ticket": None, "queued_at": 0.0}
        metrics = self.node.metrics

        def admit(block: bool) -> bool:
            import time as _time
            with self._inflight_cv:
                if not block:
                    if self._fits_inflight_locked(nbytes, tenant=tid):
                        self._grant_inflight_locked(tid, nbytes)
                        state["reserved"] = nbytes
                        metrics.observe(
                            labeled(H_ADMIT_WAIT, tenant=tid), 0.0)
                        return True
                    # defer into the fair-share queue; dispatch happens
                    # in result() once the DRR scan grants the ticket
                    ticket = self._admit_ticket
                    self._admit_ticket += 1
                    self._admit_queue.enqueue(ticket, tid, nbytes)
                    state["ticket"] = ticket
                    state["queued_at"] = _time.perf_counter()
                    # cross-grants snapshot (see H_ADMIT_CROSS)
                    state["seq0"] = self._grant_seq
                    state["own0"] = \
                        self._grant_count_by_tenant.get(tid, 0)
                    log.info("submit deferred by maxBytesInFlight=%d "
                             "(tenant %s, in flight %d B, requesting "
                             "%d B, queue depth %d)",
                             self.conf.max_bytes_in_flight, tid,
                             self._inflight_bytes, nbytes,
                             self._admit_queue.depth())
                    return False
                ticket = state["ticket"]
                deadline = None if timeout is None \
                    else _time.monotonic() + timeout
                while not self._fits_inflight_locked(nbytes, ticket,
                                                     tenant=tid):
                    if deadline is not None:
                        remaining = deadline - _time.monotonic()
                        if remaining <= 0:
                            raise TimeoutError(
                                f"deferred exchange (tenant {tid}) "
                                f"waited {timeout}s: "
                                f"{self._inflight_bytes} B in flight "
                                f"exceeds a2a.maxBytesInFlight="
                                f"{self.conf.max_bytes_in_flight} and no "
                                f"exchange completed — resolve earlier "
                                f"submits or raise the cap")
                        self._inflight_cv.wait(min(remaining, 1.0))
                    else:
                        self._inflight_cv.wait(1.0)
                self._admit_queue.pop(ticket, nbytes)
                state["ticket"] = None
                # cross-grants BEFORE this grant lands in the counters:
                # grants to OTHER tenants while this ticket waited
                cross = (self._grant_seq - state.get("seq0", 0)) - (
                    self._grant_count_by_tenant.get(tid, 0)
                    - state.get("own0", 0))
                self._grant_inflight_locked(tid, nbytes)
                state["reserved"] = nbytes
                t_grant = _time.perf_counter()
                waited = (t_grant - state["queued_at"]) * 1e3
                if report is not None:
                    # the deferred-admission wall as an anatomy span:
                    # enqueue -> grant, trace-tagged so the ledger's
                    # admission_wait phase is this exact interval
                    self.node.tracer.record_span(
                        "shuffle.admit.wait", state["queued_at"],
                        t_grant, trace=report.trace_id, tenant=tid)
                metrics.observe(labeled(H_ADMIT_WAIT, tenant=tid),
                                waited)
                metrics.observe(labeled(H_ADMIT_CROSS, tenant=tid),
                                float(max(0, cross)))
                if report is not None:
                    report.admit_wait_ms += waited
                self._inflight_cv.notify_all()
                return True

        def release() -> None:
            with self._inflight_cv:
                if state["ticket"] is not None:
                    # abandoned while queued: unblock those behind it
                    self._admit_queue.discard(state["ticket"])
                    state["ticket"] = None
                    self._inflight_cv.notify_all()
            n, state["reserved"] = state["reserved"], 0
            self._release_inflight(n, tenant=tid)

        return admit, release

    # -- warmup (the preconnect analog) -----------------------------------
    def warmup(self, handle: ShuffleHandle,
               rows_per_map=None, rows_per_shard=None,
               val_shape=None, val_dtype=None,
               combine: Optional[str] = None,
               ordered: bool = False,
               sink: Optional[str] = None) -> ShufflePlan:
        """Pre-trace + compile (and once-execute on empty inputs) the
        exchange step a later ``read()``/``submit()`` of this handle will
        dispatch — while map tasks are still running. The reference
        overlaps connection setup with the map publish the same way
        (``preconnect()`` dials every peer while the metadata put is in
        flight, ref: UcxWorkerWrapper.scala:125-127,
        CommonUcxShuffleBlockResolver.scala:100); here the cost being
        hidden is XLA trace+compile, which otherwise lands in-band on the
        first read of each (mesh, plan, width) family.

        ``rows_per_map``   — expected rows per map output (int or
                             [num_maps]); grouped onto shards exactly like
                             the single-process read (map_id % P).
        ``rows_per_shard`` — alternative: expected staged rows per shard
                             directly ([P]); required in distributed mode,
                             where map→shard placement is process-local.
        ``val_shape``/``val_dtype`` — the value schema the writers will
        stage (None = keys-only), ``combine``/``ordered`` — the read
        options; together these determine the compiled program.

        The warmed program is reused iff the read-time plan matches —
        same expected row distribution, schema and options. A mismatch is
        harmless: the read compiles its own program (correctness never
        depends on warmup). Multi-process: warmup executes a collective,
        so EVERY process must call it with the same arguments (the same
        SPMD discipline as read()). Returns the warmed plan."""
        self.node.epochs.validate(handle.epoch,
                                  f"warmup shuffle {handle.shuffle_id}")
        Pn = self.node.num_devices
        if (rows_per_map is None) == (rows_per_shard is None):
            raise ValueError(
                "pass exactly one of rows_per_map / rows_per_shard")
        if rows_per_map is not None:
            if self.node.is_distributed:
                raise ValueError(
                    "distributed warmup needs rows_per_shard: map->shard "
                    "placement is process-local (ordinal over local "
                    "shards), so per-map counts do not determine the "
                    "global plan")
            per_map = np.broadcast_to(
                np.asarray(rows_per_map, dtype=np.int64),
                (handle.num_maps,))
            nvalid = np.zeros(Pn, dtype=np.int64)
            for map_id in range(handle.num_maps):
                nvalid[map_id % Pn] += per_map[map_id]
        else:
            nvalid = np.asarray(rows_per_shard, dtype=np.int64)
            if nvalid.shape != (Pn,):
                raise ValueError(
                    f"rows_per_shard must be [{Pn}], got {nvalid.shape}")

        has_vals = val_dtype is not None
        val_tail = tuple(val_shape) if val_shape is not None else ()
        plan = make_plan(nvalid, Pn, handle.num_partitions, self.conf,
                         partitioner=handle.partitioner,
                         bounds=handle.bounds)
        plan = self._apply_cap_hint(plan, handle, int(nvalid.sum()))
        plan = self._decorated_plan(
            plan, combine, ordered, has_vals,
            val_tail if has_vals else None, val_dtype,
            # warm the program family the read will dispatch: sink keys
            # the family (plan.family), so a device read must warm its
            # own entry
            sink=self._resolve_sink(sink, combine, ordered,
                                    distributed=self.node.is_distributed))
        width = KEY_WORDS + (value_words(val_tail, val_dtype)
                             if has_vals else 0)
        with self.node.tracer.span("shuffle.warmup",
                                   shuffle_id=handle.shuffle_id,
                                   cap_in=plan.cap_in,
                                   cap_out=plan.cap_out, width=width):
            self._warm_step(plan, width)
        return plan

    def _warm_step(self, plan: ShufflePlan, width: int) -> None:
        """Compile + once-execute the step for (plan, width) on EMPTY
        inputs (nvalid=0 moves nothing), populating the jit cache the
        first real dispatch will hit. Executing (not just lowering) is
        deliberate: AOT ``lower().compile()`` results do not seed the jit
        call cache, so the first call would compile again."""
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as PSpec
        from sparkucx_tpu.io.dlpack import stage_to_device

        if self.node.is_distributed and plan.impl == "pallas":
            raise NotImplementedError(
                "impl='pallas' is single-process for now — warmup "
                "follows read()'s restriction")
        hier = self.hierarchical and plan.impl != "pallas"
        if hier and not self.node.is_distributed:
            # the local path dispatches the TIERED two-step exchange:
            # warm BOTH tier programs — stage 1 on empty inputs, then
            # stage 2 fed the (zero) relay it produced, so each warmed
            # program's signature matches its real dispatch exactly
            from sparkucx_tpu.shuffle.plan import plan_takes_seed \
                as _takes_seed
            from sparkucx_tpu.shuffle.topology import (
                _build_stage1_step, _build_stage2_step)
            s1 = _build_stage1_step(self.node.mesh, self.topology, plan,
                                    width, plan.cap_out)
            s2 = _build_stage2_step(self.node.mesh, self.topology, plan,
                                    width, plan.cap_out, plan.cap_out)
            sharding = NamedSharding(
                self.node.mesh,
                PSpec((self.conf.mesh_dcn_axis, self.axis)))
            Pn = plan.num_shards
            lanes = 2 if _takes_seed(plan) else 1
            from sparkucx_tpu.io.dlpack import stage_to_device as _std
            payload = _std(np.zeros((Pn * plan.cap_in, width), np.int32),
                           sharding)
            nvalid = _std(np.zeros(Pn * lanes, np.int32), sharding)
            relay, _tot, _ovf = s1(payload, nvalid)
            out = s2(relay, nvalid)
            _jax.block_until_ready(out)
            return
        if hier:
            from sparkucx_tpu.shuffle.hierarchical import _build_hier_step
            step = _build_hier_step(self.node.mesh,
                                    self.conf.mesh_dcn_axis, self.axis,
                                    plan, width)
            sharding = NamedSharding(
                self.node.mesh,
                PSpec((self.conf.mesh_dcn_axis, self.axis)))
        else:
            # pallas on a multi-slice mesh warms the FLAT step — the one
            # read() actually dispatches via its flat fallback
            from sparkucx_tpu.shuffle.reader import _build_step
            step = _build_step(self.exchange_mesh, self.axis, plan, width)
            sharding = NamedSharding(self.exchange_mesh, PSpec(self.axis))
        # seeded (int8-wire) steps take [count, seed] per shard — warm
        # with the widened zero row so the warmed program's signature
        # matches the real dispatch exactly (reader.seeded_nvalid)
        from sparkucx_tpu.shuffle.plan import plan_takes_seed
        lanes = 2 if plan_takes_seed(plan) else 1
        if self.node.is_distributed:
            # only local shards are addressable: assemble the global array
            # from process-local zero blocks, like the real dispatch
            L = len(self.node.local_shard_ids)
            payload = _jax.make_array_from_process_local_data(
                sharding, np.zeros((L * plan.cap_in, width), np.int32))
            nvalid = _jax.make_array_from_process_local_data(
                sharding, np.zeros(L * lanes, np.int32))
        else:
            Pn = plan.num_shards
            payload = stage_to_device(
                np.zeros((Pn * plan.cap_in, width), np.int32), sharding)
            nvalid = stage_to_device(np.zeros(Pn * lanes, np.int32),
                                     sharding)
        out = step(payload, nvalid)
        _jax.block_until_ready(out)

    # -- the read path ----------------------------------------------------
    def read(self, handle: ShuffleHandle,
             timeout: Optional[float] = None,
             combine: Optional[str] = None,
             ordered: bool = False,
             combine_sum_words: int = 0,
             sink: Optional[str] = None) -> ShuffleReaderResult:
        """Execute the full exchange for a shuffle and return partitioned
        results (the getReader + fetch-everything path, SURVEY.md §3.4).

        Blocks until all map outputs are published, mirroring the metadata
        wait (ref: UcxWorkerWrapper.scala:134-143).

        ``combine="sum"`` turns on device combine-by-key (ops/aggregate.py)
        on both sides of the wire: the result holds ONE row per distinct
        key, key-sorted within each partition — the reference reduce
        pipeline's stock aggregate+sort (ref: compat/spark_2_4/
        UcxShuffleReader.scala:80-144) executed on the accelerator, with
        proportionally less ICI traffic and D2H volume. Needs a numeric
        value schema.

        Under ``failure.policy=replay`` a transient failure (injected
        fault, PeerLostError from the watchdog) or a stale-epoch handle
        whose staged state survived the remesh is absorbed HERE: the
        whole exchange re-plans and re-runs on the surviving mesh —
        waved reads restart as a whole exchange, per-wave learned caps
        carry over (``_wave_cap_hints`` outlive the attempt) — up to
        ``failure.replayBudget`` times, with the replay count and the
        failed attempts' wall on the final ExchangeReport. The failfast
        default keeps the old typed-error contract exactly.

        ``sink="device"`` (or conf ``read.sink=device``) returns a
        :class:`~sparkucx_tpu.shuffle.reader.DeviceShuffleReaderResult`:
        partitions stay sharded jax Arrays handed — donation-safe, zero
        D2H — to a jitted consumer step (``result.consume``); waved
        reads land as per-wave device views chained through the same
        fold. See ``_resolve_sink`` for the host fallbacks."""
        timeout = timeout if timeout is not None \
            else self.conf.connection_timeout_ms / 1e3
        # Fetch-wait DISTRIBUTION per read — what Spark's incFetchWaitTime
        # flattens into a sum. Compile-bearing reads (fresh step-cache
        # programs in this read's report) observe into H_FETCH_FIRST
        # instead: the first exchange of a plan shape pays XLA compile
        # in-band, and one 3000 ms warmup read in the wait histogram
        # poisons every outlier rule keyed on it.
        # The split happens HERE, after result(), because the report's
        # step-cache delta is only final once on_done ran.
        metrics = self.node.metrics
        # Pin the handle BEFORE the metrics window opens: a failfast
        # StaleEpochError here keeps the pre-replay contract exactly —
        # no read.count / read.ms / near-zero wait sample for a read
        # that never started (the loop's resolve is a no-op on the
        # first pass; it only re-pins when an external bump races a
        # replayed attempt).
        replays = self._resolve_handle(handle)
        t0 = time.perf_counter()
        replay_ms = 0.0
        try:
            while True:
                t_attempt = time.perf_counter()
                try:
                    replays += self._resolve_handle(handle)
                    if self.node.is_distributed:
                        # collective: every process must pass the same
                        # combine/ordered/sink values (same SPMD
                        # discipline as calling read() at all)
                        res = self._submit_distributed(
                            handle, timeout, combine=combine,
                            ordered=ordered,
                            combine_sum_words=combine_sum_words,
                            sink=sink).result()
                    else:
                        res = self._submit_local(
                            handle, timeout, combine=combine,
                            ordered=ordered,
                            combine_sum_words=combine_sum_words,
                            sink=sink).result()
                    # integrity.verify=full: the post-collective check
                    # runs INSIDE the retry window — a corrupt drained
                    # block is a TransientError the replay policy may
                    # absorb (waved reads already verified in their
                    # finalize; _full_done makes this a no-op there)
                    self._verify_full_result(handle, res, combine)
                    break
                except TransientError as e:
                    replay_ms += (time.perf_counter() - t_attempt) * 1e3
                    if not self._replay_after_failure(handle, e):
                        raise
                    replays += 1
            if replays:
                self._account_replays(handle, replays, replay_ms)
            return res
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            metrics.inc("shuffle.read.ms", ms)
            metrics.inc("shuffle.read.count", 1)
            # failure included: a read that compiled and THEN died still
            # carried the compile in its wall time — it must not land in
            # the steady-state wait distribution either (on_done has
            # already finalized the report's step-cache delta; a read
            # that died before its report exists observes as wait)
            rep = self.report(handle.shuffle_id)
            compiled = rep is not None and rep.stepcache_programs > 0
            hist = H_FETCH_FIRST if compiled else H_FETCH_WAIT
            metrics.observe(hist, ms)
            # per-tenant face of the same pair: the labeled wait
            # distribution is the isolation evidence (a starved minnow's
            # p99 diverges from its solo baseline HERE first) and the
            # per-tenant read counter its signal floor
            metrics.observe(labeled(hist, tenant=handle.tenant), ms)
            metrics.inc(labeled("shuffle.read.count",
                                tenant=handle.tenant), 1)

    def read_partitions(self, handle: ShuffleHandle, start: int, end: int,
                        timeout: Optional[float] = None,
                        combine: Optional[str] = None,
                        ordered: bool = False):
        """Iterator of (r, (keys, values)) for reduce partitions
        [start, end) — the reference SPI's partition-range getReader
        (ref: compat/spark_3_0/UcxShuffleManager.scala:53-60 passes
        startPartition/endPartition through to the reader). The exchange
        itself is still ONE collective (the whole reduce side is one
        batch); the range selects which host-side views to materialize —
        in distributed mode, non-local partitions in the range are
        skipped (the reducer contract)."""
        # validate + run the collective EAGERLY, then hand out a generator
        # over the result: a generator body would defer both to first
        # next(), so bad ranges would escape try/except and a distributed
        # caller that never iterates would leave peers hung in the
        # all-to-all
        if not (0 <= start <= end <= handle.num_partitions):
            raise IndexError(
                f"partition range [{start}, {end}) out of "
                f"[0, {handle.num_partitions}]")
        # range reads ARE host materialization (the caller iterates
        # numpy views) — pin the host sink so read.sink=device conf
        # cannot hand this iterator a device-resident result
        res = self.read(handle, timeout=timeout, combine=combine,
                        ordered=ordered, sink="host")
        return ((r, res.partition(r)) for r in range(start, end)
                if res.is_local(r))

    def submit(self, handle: ShuffleHandle,
               timeout: Optional[float] = None,
               combine: Optional[str] = None,
               ordered: bool = False,
               combine_sum_words: int = 0,
               sink: Optional[str] = None):
        """Asynchronous read: plan + pack on the host, DISPATCH the
        exchange, and return a :class:`shuffle.reader.PendingShuffle`
        without blocking — so the caller overlaps this shuffle's collective
        with the next shuffle's pack or any downstream host work (the
        fetch/compute overlap of the reference's lazy-progress iterator,
        ref: compat/spark_3_0/UcxShuffleReader.scala:54-98).

        Multi-process: submit() is COLLECTIVE, like read() — every
        process must call submit() and later result() in the same order.
        done() stays a local poll; the overflow consensus (and any retry)
        runs inside result(), where all processes are present.

        Under ``failure.policy=replay`` a stale handle whose staged state
        survived the remesh transparently re-pins to the new epoch here
        (like read()); mid-flight transient failures surface to the
        caller — the async contract has no place to loop."""
        replayed = self._resolve_handle(handle)
        timeout = timeout if timeout is not None \
            else self.conf.connection_timeout_ms / 1e3
        if self.node.is_distributed:
            pending = self._submit_distributed(
                handle, timeout, combine=combine, ordered=ordered,
                combine_sum_words=combine_sum_words, sink=sink)
        else:
            pending = self._submit_local(
                handle, timeout, combine=combine, ordered=ordered,
                combine_sum_words=combine_sum_words, sink=sink)
        if replayed:
            # after _submit_*: the fresh report now exists in the ring
            self._account_replays(handle, replayed, 0.0)
        return pending

    def _submit_local(self, handle: ShuffleHandle, timeout: float,
                      combine: Optional[str] = None,
                      ordered: bool = False,
                      combine_sum_words: int = 0,
                      sink: Optional[str] = None):
        # the report exists from read START: a read that dies in the
        # metadata fetch must still be explainable from the postmortem
        rep = self._new_report(handle, distributed=False)
        try:
            # anatomy envelope (plan phase, lowest priority): absorbs
            # the submit-side slivers BETWEEN the precise spans — report
            # setup, plan decoration, admitter arming — so the ledger
            # conserves; the barrier/pack/dispatch/compile spans inside
            # all outrank it in the sweep
            with self.node.tracer.span("shuffle.submit",
                                       shuffle_id=handle.shuffle_id,
                                       trace=rep.trace_id):
                return self._submit_local_staged(
                    handle, timeout, combine, ordered, combine_sum_words,
                    rep, sink=sink)
        except BaseException as e:
            rep.error = rep.error or repr(e)[:300]
            # a read that dies before arming never reaches on_done — the
            # exchange is no longer in flight, close its flight trace
            self.node.flight.end_trace(rep.trace_id)
            raise

    def _submit_local_staged(self, handle: ShuffleHandle, timeout: float,
                             combine: Optional[str], ordered: bool,
                             combine_sum_words: int, rep: ExchangeReport,
                             sink: Optional[str] = None):
        tracer = self.node.tracer
        sink = self._resolve_sink(sink, combine, ordered,
                                  distributed=False)
        rep.sink = sink
        with tracer.span("shuffle.barrier", kind="map_outputs",
                         shuffle_id=handle.shuffle_id,
                         trace=rep.trace_id):
            complete = handle.entry.wait_complete(timeout)
        if not complete:
            raise TimeoutError(
                f"shuffle {handle.shuffle_id}: only "
                f"{handle.entry.num_present}/{handle.num_maps} map outputs "
                f"published within {timeout}s")
        # Metadata fetch is a retryable control-plane step (the reference
        # leans on Spark task retry here; we carry our own policy).
        table = self.node.retry_policy.run(
            lambda: (self.node.faults.check("fetch"),
                     handle.entry.fetch_table())[1])

        # Collect staged outputs, grouped round-robin onto mesh shards the
        # way multiple map tasks colocate on one executor. Keys and values
        # travel as aligned pairs per map output.
        #
        # In-flight-read guard: from the writers snapshot through the end
        # of pack, this read walks writer-owned memory (spill mmap views,
        # arena-staged batches); a concurrent remesh must park those
        # writers in the graveyard until this window closes, no matter how
        # many bumps arrive meanwhile. Registration precedes the snapshot
        # (same lock as the bump's clear), so any batch dropped after
        # registration is provably held. After pack, the read holds only
        # the pinned stage_buf (owned by on_done) and device arrays.
        Pn = self.node.num_devices
        read_gen = self._read_started()
        try:
            with self._lock:
                if handle.shuffle_id not in self._writers:
                    raise RuntimeError(
                        f"shuffle {handle.shuffle_id} is not registered "
                        f"with this manager (already unregistered?)")
                writers = dict(self._writers[handle.shuffle_id])
            # completeness is tracked by distinct map id in the metadata
            # table; an extra uncommitted (half-written) writer must not
            # inject rows — and a map whose committed rows are gone must
            # fail loudly, not shrink the result (the distributed path's
            # bitmap does the same)
            writers = {m: w for m, w in writers.items() if w.committed}
            missing = sorted(set(range(handle.num_maps)) - set(writers))
            if missing:
                raise RuntimeError(
                    f"shuffle {handle.shuffle_id}: metadata table is "
                    f"complete but maps {missing[:8]} have no committed "
                    f"staged rows in this manager — map output lost "
                    f"(writer replaced or released?)")
            # anatomy span (pack phase): every committed map output
            # gathered into its shard's list
            with tracer.span("shuffle.stage", maps=len(writers),
                             trace=rep.trace_id):
                shard_outputs, has_vals, val_tail, val_dtype = \
                    self._materialize_outputs(
                        writers, Pn, lambda ordinal, map_id: map_id % Pn,
                        entry=handle.entry, rep=rep)

            # int32-range guard on what actually feeds the plan arithmetic:
            # the per-DEVICE aggregated transfer matrix, not the raw [M, R]
            from sparkucx_tpu.ops.partition import blocked_partition_map
            map_to_dev = np.arange(handle.num_maps) % Pn
            red_to_dev = np.asarray(
                blocked_partition_map(handle.num_partitions, Pn))
            dev_matrix = table.device_matrix(map_to_dev, red_to_dev, Pn)
            validate_row_sizes(dev_matrix)

            nvalid = np.array(
                [sum(k.shape[0] for k, _ in outs) for outs in shard_outputs],
                dtype=np.int64)
            t_plan = time.perf_counter()
            with tracer.span("shuffle.plan", shuffle_id=handle.shuffle_id,
                             trace=rep.trace_id):
                plan = make_plan(nvalid, Pn, handle.num_partitions,
                                 self.conf, partitioner=handle.partitioner,
                                 bounds=handle.bounds)
                plan = self._apply_cap_hint(plan, handle, int(nvalid.sum()))
            rep.plan_ms = (time.perf_counter() - t_plan) * 1e3
            # the decoration validates dtypes against the mode (ordered/
            # combine) and can pay a one-time compile-adjacent cost on
            # the first decorated read — plan phase, its own span so the
            # ledger sees it (rep.plan_ms keeps its original meaning)
            with tracer.span("shuffle.plan", shuffle_id=handle.shuffle_id,
                             decorate=True, trace=rep.trace_id):
                plan = self._decorated_plan(plan, combine, ordered,
                                            has_vals, val_tail, val_dtype,
                                            combine_sum_words, sink=sink)

            # fuse key+value bytes into one int32 row matrix (bit views, no
            # value casts — jnp would silently truncate int64 with x64 off)
            width = KEY_WORDS + (value_words(val_tail, val_dtype)
                                 if has_vals else 0)
            self._report_volume(rep, plan, nvalid, width,
                                part_rows=table.sizes.sum(axis=0))
            self._estimate_wire_error(rep, plan, shard_outputs)
            hier = self.hierarchical and plan.impl != "pallas"
            if hier:
                # per-tier accounting: stage-1 ICI vs stage-2 DCN as
                # separate payload/wire pairs, cross-fabric rows EXACT
                # from the metadata table's device matrix (the
                # crosses-DCN-exactly-once evidence)
                self._stamp_tiers(rep, plan, nvalid, width,
                                  dev_matrix=dev_matrix)
            # Wave-pipelined mode (a2a.waveRows): instead of one giant
            # pack + one monolithic program, split the staged rows into
            # fixed-shape waves and run a software pipeline inside the
            # pending handle's result() — pack wave i+1 while wave i's
            # collective is in flight and wave i-1 drains D2H.
            if self.conf.wave_rows > 0 and self._waves_eligible(plan):
                W = wave_count(nvalid, self.conf.wave_rows)
                if W > 1:
                    if hier and plan.sink == "device":
                        # waved hierarchical reads drain host-side (the
                        # per-wave tier fold has no device merge over
                        # the 2-D mesh yet) — counted, the single-shot
                        # hier path keeps the device sink
                        mode = "combine" if combine else (
                            "ordered" if ordered else "plain")
                        self._warn_sink_once(
                            "hier_waved",
                            "read.sink=device on a WAVED hierarchical "
                            "read resolves to host (single-shot "
                            "hierarchical reads keep the device sink)")
                        self._note_sink_fallback(mode,
                                                 "hierarchical_waved")
                        plan = dataclasses.replace(plan, sink="host")
                        rep.sink = "host"
                    return self._submit_waved(
                        handle, shard_outputs, nvalid, plan, width,
                        has_vals, val_tail if has_vals else None,
                        val_dtype, rep, timeout, W, distributed=False)
            self._note_inert_lossless(plan)
            t_pack = time.perf_counter()
            with tracer.span("shuffle.pack", rows=int(nvalid.sum()),
                             trace=rep.trace_id):
                shard_rows, stage_buf = self._pack_shards(
                    shard_outputs, plan.cap_in, width, has_vals,
                    tenant=handle.tenant)
            rep.pack_ms = (time.perf_counter() - t_pack) * 1e3
        finally:
            self._read_finished(read_gen)

        # Admission control: a non-blocking reservation happens inside the
        # pending handle's first dispatch; over the cap, the exchange
        # queues and dispatches in result() once capacity frees
        admit, release_admitted = self._make_admitter(
            plan, width, stage_buf.requested, timeout,
            tenant=handle.tenant, report=rep)

        on_done, arm = self._arm_read_callbacks(
            stage_buf, release_admitted, handle,
            int(nvalid.sum()), int(nvalid.sum()), width, report=rep,
            combine=combine)

        # Buffer ownership: until a pending handle exists, failures here
        # (the fault site, compile errors inside the first dispatch) must
        # release the pinned pack buffer; once the handle is armed it is
        # the SOLE owner (its exactly-once on_done releases), so a late
        # exception — e.g. out of the span __exit__ — must NOT also put,
        # or two shuffles would end up sharing one arena block.
        pending = None
        try:
            self.node.faults.check("exchange")
            # span covers DISPATCH only — the exchange itself completes
            # asynchronously inside result() (read() wraps that wait in
            # metrics "shuffle.read")
            rep._t_dispatched = time.perf_counter()
            with tracer.span("shuffle.dispatch",
                             shuffle_id=handle.shuffle_id,
                             rows=int(nvalid.sum()), width=width,
                             hierarchical=self.hierarchical,
                             trace=rep.trace_id):
                vt = val_tail if has_vals else None
                if self.hierarchical and plan.impl == "pallas":
                    # the pallas transport is flat-only: run it over the
                    # flattened alias mesh (correct on a single process;
                    # the two-stage DCN-once optimization is native/dense
                    # territory) — the report must say what RAN
                    log.info("a2a.impl=pallas on a multi-slice mesh: "
                             "using the flat exchange over %d devices",
                             self.exchange_mesh.devices.size)
                    rep.hierarchical = False
                    pending = submit_shuffle(
                        self.exchange_mesh, self.axis, plan,
                        shard_rows, nvalid, vt, val_dtype,
                        on_done=on_done, admit=admit,
                        wire_seed=rep._seq)
                elif self.hierarchical:
                    # the tiered two-step path (shuffle/topology.py):
                    # stage-1 ICI and stage-2 DCN as separate compiled
                    # programs with per-tier deadlines/walls/faults —
                    # same admission, on_done and wire-seed contract as
                    # the flat pending
                    from sparkucx_tpu.shuffle.topology import \
                        submit_shuffle_tiered
                    pending = submit_shuffle_tiered(
                        self.node.mesh, self.topology, plan,
                        shard_rows, nvalid, vt, val_dtype,
                        on_done=on_done, admit=admit,
                        wire_seed=rep._seq,
                        hooks=self._tier_hooks(rep.trace_id))
                else:
                    pending = submit_shuffle(
                        self.exchange_mesh, self.axis, plan,
                        shard_rows, nvalid, vt, val_dtype,
                        on_done=on_done, admit=admit,
                        wire_seed=rep._seq)
            rep.dispatch_ms = (time.perf_counter()
                               - rep._t_dispatched) * 1e3
            arm(pending)
            return pending
        except BaseException:
            if pending is None:
                self.node.pool.put(stage_buf)
                release_admitted()
            raise

    def _report_volume(self, rep: ExchangeReport, plan: ShufflePlan,
                       nvalid: np.ndarray, width: int,
                       part_rows: Optional[np.ndarray] = None,
                       local_rows: Optional[int] = None) -> None:
        """Fill the report's volume/skew/plan fields and feed the
        per-peer distribution histograms — one observation per peer per
        exchange, the per-endpoint bytes log of the reference
        (OnBlocksFetchCallback.java:55-56) as a live distribution.

        The real-bytes accounting (payload/wire/pad_ratio) and the
        RESOLVED transport come from the plan's ragged layout descriptor
        — one contract shared with the data plane itself, so the report
        can never claim a wire cost the transport didn't pay. Initial
        figures; an overflow retry (regrown cap) refreshes them at
        on_done, and the waved path re-derives them per wave."""
        layout = ragged_layout(plan, nvalid, width)
        rep.impl = layout.impl
        rep.payload_bytes = layout.payload_bytes
        rep.wire_bytes = layout.wire_bytes
        rep.pad_ratio = layout.pad_ratio
        rep.wire = layout.wire
        rep.kernel = plan.kernel_impl
        # raw/wire row-width gain — the effective-bandwidth multiplier
        # the int8 tier earns (1.0 on raw/lossless; the lossless codec
        # is host-side and must not claim link bandwidth)
        rep._wire_gain = (width * 4 / layout.wire_row_bytes) \
            if layout.wire == "int8" and layout.wire_row_bytes else 1.0
        rep.plan_bucket = [int(plan.cap_in), int(plan.cap_out)]
        rep.plan_family = str(plan.family())
        # plain-python arithmetic over the (tiny, per-peer) lists: numpy
        # reductions on 8-element arrays cost more in dispatch than the
        # math, and this runs on every read
        rep.peer_rows = [int(x) for x in nvalid]
        rep.peer_bytes = [r * width * 4 for r in rep.peer_rows]
        rep.rows_global = sum(rep.peer_rows)
        rep.rows_local = rep.rows_global if local_rows is None \
            else int(local_rows)
        rep.bytes_local = rep.rows_local * width * 4
        if part_rows is not None:
            skew_src = [int(x) for x in part_rows]
        else:
            skew_src = rep.peer_rows
        mean = sum(skew_src) / len(skew_src) if skew_src else 0.0
        rep.skew_ratio = max(skew_src) / mean if mean > 0 else 0.0
        metrics = self.node.metrics
        for r, b in zip(rep.peer_rows, rep.peer_bytes):
            metrics.observe(H_PEER_ROWS, float(r))
            metrics.observe(H_PEER_BYTES, float(b))

    def _estimate_wire_error(self, rep: ExchangeReport,
                             plan: ShufflePlan, slot_outputs) -> None:
        """Sample the staged float values of an int8-wire read and stamp
        the dequantization-error estimate (relative RMS of a
        round-to-nearest int8 pass, shuffle/wire.py) on the report — the
        evidence the doctor's ``wire_dequant_error`` rule grades.
        Bounded by ``a2a.wireErrorSampleRows`` (0 = off); never raises
        into the read path."""
        from sparkucx_tpu.shuffle.plan import plan_takes_seed
        limit = self.conf.wire_error_sample_rows
        if not plan_takes_seed(plan) or limit <= 0:
            return
        try:
            from sparkucx_tpu.shuffle.wire import estimate_dequant_error
            sample, left = [], limit
            for outs in slot_outputs:
                for _keys, vals in outs:
                    if vals is None or not vals.shape[0]:
                        continue
                    take = min(left, vals.shape[0])
                    sample.append(np.asarray(
                        vals[:take], dtype=np.float32).reshape(take, -1))
                    left -= take
                    if left <= 0:
                        break
                if left <= 0:
                    break
            if sample:
                rep.wire_dequant_error = round(
                    estimate_dequant_error(np.concatenate(sample),
                                           sample_rows=limit), 6)
        except Exception:
            log.debug("wire dequant-error sampling failed", exc_info=True)

    @staticmethod
    def _set_wave_wire(rep: ExchangeReport, wplan: ShufflePlan,
                       wave_sizes, width: int) -> None:
        """Waved wire accounting: sum the per-wave layout costs under the
        (current) wave plan. rep.payload_bytes was set by _report_volume
        from the full size row and is the denominator either way."""
        wire = sum(
            ragged_layout(wplan, np.asarray([int(s)]), width).wire_bytes
            for s in wave_sizes)
        rep.wire_bytes = int(wire)
        rep.pad_ratio = round(wire / rep.payload_bytes, 6) \
            if rep.payload_bytes else 0.0

    # -- topology plane (shuffle/topology.py) ------------------------------
    def _tier_hooks(self, trace_id: str):
        """Per-read plumbing for the tiered two-step exchange: fault
        sites (tier.ici/tier.dcn), tracer tier spans, flight events and
        the per-tier watchdog deadlines (failure.ici/dcn.timeoutMs,
        defaulting from collectiveTimeoutMs)."""
        from sparkucx_tpu.shuffle.topology import TierHooks, tier_timeouts
        return TierHooks(faults=self.node.faults, tracer=self.node.tracer,
                         flight=self.node.flight, trace_id=trace_id,
                         timeouts=tier_timeouts(self.conf))

    def _stamp_tiers(self, rep: ExchangeReport, plan: ShufflePlan,
                     nvalid, width: int, dev_matrix=None,
                     relay_cap=None) -> None:
        """Fill ``rep.tiers`` (per-tier payload/wire pairs) and make the
        headline wire accounting the TWO-HOP SUM — the real fabric cost
        of a hierarchical exchange, replacing the flat single-collective
        lower bound _report_volume stamped. ``dev_matrix`` ([P, P]
        source x dest rows, the metadata table's device matrix) makes
        the cross-fabric row counts exact — the local read path holds
        it; distributed reads stamp the every-row upper bound."""
        from sparkucx_tpu.shuffle.topology import tier_layouts
        rep.tiers = tier_layouts(plan, self.topology, nvalid, width,
                                 dev_matrix=dev_matrix,
                                 relay_cap=relay_cap)
        rep._tier_matrix = None if dev_matrix is None \
            else np.asarray(dev_matrix)
        wire = sum(t["wire_bytes"] for t in rep.tiers)
        rep.wire_bytes = int(wire)
        rep.pad_ratio = round(wire / rep.payload_bytes, 6) \
            if rep.payload_bytes else 0.0

    def _agreed_dev_matrix(self, handle, writers, L, Pn, shard_ids):
        """Exact [P, P] source x dest row matrix for a DISTRIBUTED
        hierarchical read. Each process holds only its local maps'
        registry rows (Spark: sizes live with the writing executor), so
        every process builds the partial matrix for the maps it staged
        — source shard = shard_ids[ordinal % L], the
        _materialize_outputs slot rule over sorted map ids — and the
        cluster matrix is the agreed SUM. Rides the agreement channel
        so a divergent topology conf fails typed instead of stamping
        mismatched tier accounting across processes."""
        from sparkucx_tpu.ops.partition import blocked_partition_map
        from sparkucx_tpu.shuffle.agreement import agree
        local = np.zeros((Pn, Pn), dtype=np.int64)
        red_to_dev = np.asarray(
            blocked_partition_map(handle.num_partitions, Pn))
        for ordinal, mid in enumerate(sorted(writers)):
            sizes = np.asarray(handle.entry.fetch_record(mid),
                               dtype=np.int64)
            src = int(shard_ids[ordinal % L])
            np.add.at(local[src], red_to_dev, sizes)
        return agree("tier.crossRows", local.reshape(-1), reduce="sum",
                     conf_key="spark.shuffle.tpu.a2a.topology"
                     ).reshape(Pn, Pn)

    def _stamp_wave_tiers(self, rep: ExchangeReport, wplan: ShufflePlan,
                          wave_sizes, width: int) -> None:
        """Waved hierarchical tier accounting: the pipeline dispatches W
        tiered exchanges of the wave plan's shape — per-tier wire cost
        is per wave (padded transports pay their caps every wave), the
        per-tier payload the summed real rows. Cross-fabric counts are
        not derivable per wave (the device matrix is whole-exchange),
        so the entries carry the every-row upper bound
        (cross_exact=false)."""
        from sparkucx_tpu.shuffle.topology import tier_layouts
        tiers = None
        for s in wave_sizes:
            lays = tier_layouts(wplan, self.topology,
                                np.asarray([int(s)]), width)
            if tiers is None:
                tiers = lays
            else:
                for acc, lay in zip(tiers, lays):
                    for k in ("payload_rows", "payload_bytes",
                              "wire_rows", "wire_bytes"):
                        acc[k] += lay[k]
        for t in tiers or []:
            t["pad_ratio"] = round(
                t["wire_bytes"] / t["payload_bytes"], 6) \
                if t["payload_bytes"] else 0.0
            t["rows_in"] = int(sum(int(s) for s in wave_sizes))
        rep.tiers = tiers or []
        wire = sum(t["wire_bytes"] for t in rep.tiers)
        rep.wire_bytes = int(wire)
        rep.pad_ratio = round(wire / rep.payload_bytes, 6) \
            if rep.payload_bytes else 0.0

    def _settle_tiers(self, rep: ExchangeReport, tier_walls,
                      width: int, completed: bool = True) -> None:
        """Stamp measured per-tier walls/rates onto ``rep.tiers`` and
        account the per-tier wire counters
        (``shuffle.tier.bytes{tier,tenant}``) — called exactly once per
        hierarchical read (single-shot on_done, waved finalize). A
        FAILED read keeps its measured walls (postmortem evidence: the
        tier that burned the wall is the tier that hung) but counts no
        wire — the bytes never fully moved."""
        if not rep.tiers:
            return
        from sparkucx_tpu.shuffle.topology import settle_tier_walls
        if tier_walls:
            settle_tier_walls(rep.tiers, tier_walls, width)
        if not completed:
            return
        metrics = self.node.metrics
        tid = rep.tenant or self._tenants.default_id
        frac = len(self.node.local_shard_ids) \
            / max(self.node.num_devices, 1)
        for t in rep.tiers:
            # LOCAL share, the _inc_volume discipline: counters sum
            # across processes in doctor.build_view, and the cluster
            # sum must reconstruct each tier's global wire exactly once
            metrics.inc(labeled(C_TIER_BYTES, tier=t["tier"],
                                tenant=tid),
                        float(t["wire_bytes"]) * frac)

    def _finish_device_plane(self, rep: ExchangeReport, step, width: int,
                             completed: bool) -> None:
        """Complete a report's device-plane fields at read settlement:
        ``device_cost`` from the dispatched step's stepcache harvest (a
        record exists for every warm-compiled program; its fields may be
        null on backends without the XLA analyses) and ``bw_gbps`` =
        REAL global payload bytes / group wall — always the ragged
        layout's payload figure, never a padded-cap product, so the rate
        is comparable across transports (a dense exchange that moved 16x
        the payload in padding still reports the payload rate — the
        padding shows up in pad_ratio, not as phantom bandwidth).
        Steady-state reads observe the
        figure into ``shuffle.collective.bw_gbps``; compile-bearing reads
        keep the field but stay out of the distribution — an in-band XLA
        compile inside group_ms says nothing about the link (the
        H_FETCH_WAIT/H_FETCH_FIRST discipline). Never raises."""
        try:
            rec = getattr(step, "cost_record", None)
            if rec is not None:
                dc = dict(rec)
                if completed and rep.group_ms > 0 \
                        and dc.get("bytes_accessed"):
                    # the cost-model byte-movement rate this dispatch
                    # achieved — the roofline the compile-time model
                    # supports (bytes / (group_ms*1e-3 s) / 1e9)
                    dc["model_bytes_gbps"] = round(
                        dc["bytes_accessed"] / (rep.group_ms * 1e6), 6)
                rep.device_cost = dc
            if completed and rep.group_ms > 0:
                payload = rep.payload_bytes or rep.rows_global * width * 4
                gbps = payload / (rep.group_ms * 1e6)
                rep.bw_gbps = round(gbps, 6)
                # EQuARX's effective-bandwidth figure: the payload rate
                # scaled by the raw/wire row-width gain — what a RAW
                # exchange would have needed from the link to match this
                # wall. Equals bw_gbps off the int8 tier.
                rep.effective_bw_gbps = round(gbps * rep._wire_gain, 6)
                if not rep.stepcache_programs:
                    self.node.metrics.observe(H_BW, gbps)
        except Exception:
            log.debug("device-plane report completion failed",
                      exc_info=True)

    def _arm_read_callbacks(self, stage_buf, release_admitted, handle,
                            global_rows: int, local_rows: int, width: int,
                            report: Optional[ExchangeReport] = None,
                            combine: Optional[str] = None):
        """(on_done, arm) pair shared by the local and distributed submit
        paths: exactly-once pinned-buffer + admission release, capacity
        learning, the reporter counters (rows/bytes local to this
        process; retries read from the pending handle), and
        ExchangeReport completion. ``arm(pending)``
        records a WEAK reference — a strong one would cycle through
        on_done back to the pending and defer the __del__-based
        abandoned-handle release from refcounting to cyclic GC."""
        handle_box = {}

        def on_done(result):
            _t_settle = time.perf_counter()
            self.node.pool.put(stage_buf)
            if result is not None and \
                    getattr(result, "sink", "host") == "device":
                # HBM-residency admission: a device-sink result's
                # receive buffers stay resident until the consumer takes
                # them, so the reservation releases at consume()/close()
                # — not here, where the host path's drain frees them
                result._release_hbm = release_admitted
            else:
                release_admitted()
            if result is not None:
                if hasattr(result, "fetch_granularity"):
                    # lazy results honor io.fetchGranularity (per-block
                    # device-sliced D2H vs whole-shard pulls)
                    result.fetch_granularity = self.conf.fetch_granularity
                if report is not None:
                    self._arm_d2h(result, report)
                self._learn_cap(handle, result, global_rows)
                self.node.metrics.inc("shuffle.rows", float(local_rows))
                self.node.metrics.inc("shuffle.bytes",
                                      float(local_rows) * width * 4)
            ref = handle_box.get("pending")
            pend = ref() if ref is not None else None
            retries = getattr(pend, "_attempt", 0) if pend is not None \
                else 0
            if retries:
                # overflow retries this read paid (capacity growth) — the
                # reporter-visible retry counter
                self.node.metrics.inc("shuffle.retries", float(retries))
            if report is not None:
                if report._t_dispatched:
                    report.group_ms = (time.perf_counter()
                                       - report._t_dispatched) * 1e3
                report.retries = int(retries)
                if retries and pend is not None \
                        and getattr(pend, "_plan", None) is not None:
                    # the overflow retry regrew the plan: wire accounting
                    # must reflect the capacities the FINAL dispatch
                    # padded to, not the ones the first attempt overflowed
                    if report.tiers:
                        # tiered: re-derive BOTH hops under the final
                        # capacities (stage-2 regrow + relay regrow)
                        self._stamp_tiers(
                            report, pend._plan,
                            np.asarray(report.peer_rows), width,
                            dev_matrix=getattr(report, "_tier_matrix",
                                               None),
                            relay_cap=getattr(pend, "_relay_cap", None))
                    else:
                        lay = ragged_layout(pend._plan,
                                            np.asarray(report.peer_rows),
                                            width)
                        report.wire_bytes = lay.wire_bytes
                        report.pad_ratio = lay.pad_ratio
                if report.tiers:
                    # per-tier walls/rates + shuffle.tier.bytes{tier,
                    # tenant} — the single-shot settle (waved reads
                    # settle in their finalize)
                    self._settle_tiers(
                        report, getattr(pend, "tier_walls", None),
                        width, completed=result is not None)
                if result is not None and report.payload_bytes:
                    # cumulative real-vs-wire volume counters — the
                    # Prometheus view of the per-report pad_ratio. The
                    # report fields are GLOBAL figures; counters sum
                    # across processes in doctor.build_view (the
                    # shuffle.rows/bytes discipline above), so each
                    # process accounts its LOCAL share — its own staged
                    # payload and its own shards' wire segments — and
                    # the cluster sum reconstructs the global exactly.
                    frac = len(self.node.local_shard_ids) \
                        / max(self.node.num_devices, 1)
                    self._inc_volume(report.tenant,
                                     float(report.rows_local) * width * 4,
                                     float(report.wire_bytes) * frac)
                report.stepcache_hits = int(
                    GLOBAL_METRICS.get(COMPILE_HITS) - report._hits0)
                report.stepcache_programs = int(
                    GLOBAL_METRICS.get(COMPILE_PROGRAMS) - report._prog0)
                # device-plane join: the dispatched program's cost record
                # (stepcache harvest; final program after any retry
                # regrow) plus the achieved-bandwidth figure
                self._finish_device_plane(
                    report, getattr(pend, "_step", None), width,
                    completed=result is not None)
                if result is not None:
                    report.completed = True
                else:
                    report.error = report.error or "exchange failed"
                self._settle_agreement(report)
                # exchange anatomy: close the wall span, fold the phase
                # ledger, publish phase counters (utils/anatomy.py);
                # two cheap guards when the tracer is off. The settle
                # span first: on_done's own accounting (cap learning,
                # tier settle, device-plane harvest) is the tail
                # between the result landing and the wall closing, and
                # it must not read as dark time
                if self.node.tracer.enabled:
                    self.node.tracer.record_span(
                        "shuffle.settle", _t_settle,
                        trace=report.trace_id)
                self._settle_anatomy(report,
                                     completed=result is not None)
                # the exchange is settled either way — flight-ring events
                # from here on belong to the next exchange
                self.node.flight.end_trace(report.trace_id)

        def arm(pending):
            handle_box["pending"] = weakref.ref(pending)
            if self._integrity_for(handle.tenant) == "full":
                # the post-collective digest check rides result() itself
                # (reader.PendingExchangeBase), so async submit()/result()
                # consumers verify exactly like read() — which then skips
                # via the report's _full_done guard
                pending._post_result = lambda res: \
                    self._verify_full_result(handle, res, combine)

        return on_done, arm

    def _settle_agreement(self, report: ExchangeReport) -> None:
        """Decision-plane settlement: diff the ledger's monotonic index
        against the read-start mark into the public ``agreement``
        summary — rounds closed, wall ms spent agreeing, and the
        slowest topic (by total ms). Plane off (NULL ledger) or no
        rounds = the summary stays ``{}``; never raises (telemetry must
        never fail a shuffle)."""
        if report._agree_mark < 0:
            return
        try:
            from sparkucx_tpu.shuffle.decisions import current_ledger
            recs = current_ledger().since(report._agree_mark)
            if not recs:
                return
            by_topic: Dict[str, float] = {}
            for r in recs:
                t = r.get("topic", "?")
                by_topic[t] = by_topic.get(t, 0.0) \
                    + float(r.get("round_ms", 0.0))
            slowest = max(by_topic.items(), key=lambda kv: kv[1])[0]
            report.agreement = {
                "rounds": len(recs),
                "agree_ms": round(sum(by_topic.values()), 3),
                "slowest_topic": slowest,
                "divergent": sum(1 for r in recs
                                 if not r.get("ok", True)),
            }
        except Exception:
            pass

    def _settle_anatomy(self, report: ExchangeReport,
                        completed: bool) -> None:
        """Exchange-anatomy settlement (utils/anatomy.py): record the
        ``shuffle.exchange`` WALL span (report start → now, trace-tagged
        — the interval the conservation audit holds against), fold the
        ring's spans into the phase ledger, stamp it onto the report,
        and publish the ``shuffle.phase.ms{phase=...}`` counters that
        ride TelemetryHistory frames into the phase_regression rule.
        Tracer off = one enabled check + one no-op record_span per
        exchange. Fold failures degrade to an un-annotated
        report — anatomy must never take down a read's settlement."""
        tracer = self.node.tracer
        if not tracer.enabled or not report._t_start:
            return
        try:
            tracer.record_span(
                "shuffle.exchange", report._t_start,
                shuffle_id=report.shuffle_id, trace=report.trace_id,
                tenant=report.tenant or self._tenants.default_id,
                completed=completed)
            from sparkucx_tpu.utils.anatomy import DARK, fold_tracer
            from sparkucx_tpu.utils.metrics import (C_PHASE_MS,
                                                    C_TRACE_DROPPED)
            led = fold_tracer(tracer, report.trace_id)
            if led is None:
                return
            report.phases = {k: round(v, 3)
                             for k, v in led.phases_ms.items()}
            report.dark_ms = round(led.dark_ms, 3)
            report.anatomy_wall_ms = round(led.wall_ms, 3)
            report.dark_intervals = [[round(a, 3), round(b, 3)]
                                     for a, b in led.dark_intervals[:16]]
            if completed:
                # the single-shot on_done discipline: a failed exchange
                # keeps its ledger as postmortem evidence but counts no
                # phase milliseconds into the trend counters
                metrics = self.node.metrics
                for ph, ms in led.phases_ms.items():
                    metrics.inc(labeled(C_PHASE_MS, phase=ph), ms)
                if led.dark_ms > 0.0:
                    metrics.inc(labeled(C_PHASE_MS, phase=DARK),
                                led.dark_ms)
            tracer.publish_dropped(self.node.metrics)
        except Exception:
            log.debug("anatomy settlement failed for %s",
                      report.trace_id, exc_info=True)

    def _inc_volume(self, tenant: str, payload: float,
                    wire: float) -> None:
        """Cumulative payload/wire byte counters, global AND labeled per
        tenant — one helper so the single-shot and waved completion
        paths cannot drift on the per-tenant accounting."""
        metrics = self.node.metrics
        metrics.inc("shuffle.payload.bytes", payload)
        metrics.inc("shuffle.wire.bytes", wire)
        tid = tenant or self._tenants.default_id
        metrics.inc(labeled("shuffle.payload.bytes", tenant=tid), payload)
        metrics.inc(labeled("shuffle.wire.bytes", tenant=tid), wire)

    def _arm_d2h(self, result, rep: ExchangeReport) -> None:
        """Join a result's device-to-host payload pulls onto its report:
        lazy results drain AFTER completion (on consumer touch), so
        ``d2h_bytes`` keeps accruing on the live report — the per-read
        face of the cumulative ``shuffle.read.d2h.bytes`` counter. Pulls
        that happened before arming (the distributed force-materialize)
        flush from ``_d2h_early``. A device-sink result arms its inner
        wave views too, so an explicit ``host_view()`` drain is charged
        to the read that produced it."""
        def cb(n, _rep=rep):
            _rep.d2h_bytes += int(n)
        early = getattr(result, "_d2h_early", 0)
        if early:
            result._d2h_early = 0
            cb(early)
        result._d2h_cb = cb
        wv = getattr(result, "wave_views", None)
        if wv is not None:
            for v in wv():
                # pre-arming pulls parked on the VIEW flush too: the
                # full-level device sampling runs inside result() via
                # _post_result, BEFORE on_done arms this callback
                early = getattr(v, "_d2h_early", 0)
                if early:
                    v._d2h_early = 0
                    cb(early)
                v._d2h_cb = cb

    # -- capacity learning -------------------------------------------------
    def _resolve_wire(self, plan: ShufflePlan, has_vals: bool, val_tail,
                      val_dtype) -> tuple:
        """Resolve the conf's ``a2a.wire`` ask against what THIS read can
        actually compress — the (wire, wire_words) pair the plan is
        stamped with. ``int8`` demands float32 value lanes (keys and int
        payloads stay exact by the contract) and a real wire move: a
        1-shard axis (the local move) and the strip-sorted fast path
        (no collective at all) resolve to raw — the report's ``wire``
        field says which tier ran, never which was asked for. The
        hierarchical two-stage exchange is int8-ELIGIBLE: each hop
        quantizes around its own collective (topology._tier_wire_
        shuffle — the DCN hop, the slow fabric, is exactly where the
        narrowing pays most; two hops means two rounding steps, still
        unbiased per step). ``lossless`` is dtype-agnostic (bit-exact
        host codec). Resolution is pure conf/plan/schema facts —
        identical on every process, SPMD-safe without a collective
        (the _waves_eligible discipline)."""
        wire = self.conf.a2a_wire
        if wire == "raw":
            return "raw", 0
        if wire == "lossless":
            return "lossless", 0
        reason = None
        if plan.num_shards == 1 or plan.strips_active():
            reason = "no wire move exists on this path (1-shard/strips)"
        elif not has_vals:
            reason = "keys-only payload (key lanes stay exact)"
        elif np.dtype(val_dtype) != np.float32:
            reason = (f"value dtype {np.dtype(val_dtype).str} is not "
                      f"float32 (int lanes stay exact)")
        if reason is not None:
            log.info("a2a.wire=int8 resolves to raw for this read: %s",
                     reason)
            return "raw", 0
        return "int8", value_words(val_tail, val_dtype)

    def _warn_sink_once(self, key: str, msg: str) -> None:
        if key not in self._warned_sink:
            self._warned_sink.add(key)
            log.warning(msg)

    def _note_sink_fallback(self, mode: str, reason_key: str) -> None:
        """A read that ASKED for the device sink landed on host: the
        graded evidence behind the doctor's ``sink_fallback`` rule —
        the cumulative counter plus a labeled twin naming the read mode
        (plain/ordered/combine) and the fallback reason, so the finding
        can say WHICH aggregation-shaped reads are still paying the
        round-trip and why."""
        m = self.node.metrics
        m.inc(C_SINK_FALLBACK, 1.0)
        m.inc(labeled(C_SINK_FALLBACK, mode=mode, reason=reason_key),
              1.0)

    def _resolve_sink(self, requested: Optional[str],
                      combine: Optional[str] = None, ordered: bool = False,
                      distributed: bool = False) -> str:
        """Resolve the read's landing tier from the per-read ask and the
        ``read.sink`` conf — the _resolve_wire discipline: the report's
        ``sink`` field names the tier that RAN, never the ask. Pure
        conf/argument facts, identical on every process (collective
        reads pass the same arguments by the SPMD contract), so the
        branch decision needs no collective.

        ``auto`` (conf default) = host unless the consumer declared a
        device sink for this read; ``device`` makes device the default
        ask; ``host`` pins the historical drain. The device sink is
        legal for ALL FOUR read modes on the single-process flat
        exchange AND the single-shot hierarchical two-tier exchange
        (the stage-2 output is already partition-sorted on device —
        ordered/combine land fully merged, shuffle/topology.py), AND
        distributed reads (the partial device view keeps the payload
        sharded in HBM across processes — zero payload D2H,
        shuffle/distributed.py DistributedLazyReaderResult): the
        restrictions the earlier resolvers enforced were pure policy.
        A device ask still falls back to host — warn-once AND counted
        (``shuffle.sink.fallback.count``, the doctor's sink_fallback
        evidence) — where the result cannot stay resident: WAVED
        hierarchical reads (the per-wave fold is demoted at the wave
        branch, reason ``hierarchical_waved``)."""
        from sparkucx_tpu.shuffle.alltoall import validate_sink
        if requested is not None:
            validate_sink(requested, conf_key="read(sink=...)")
            if requested == "auto":
                requested = None
        mode = "combine" if combine else ("ordered" if ordered
                                          else "plain")
        conf = self.conf.read_sink
        want = requested
        if want is None:
            want = "device" if conf == "device" else "host"
        elif want == "device" and conf == "host":
            self._warn_sink_once(
                "conf_pins_host",
                "read(sink='device') under spark.shuffle.tpu.read.sink="
                "host — the conf pins the host drain; set read.sink=auto "
                "(or device) to honor per-read device sinks")
            self._note_sink_fallback(mode, "conf_pins_host")
            want = "host"
        if want != "device":
            return "host"
        return "device"

    def _decorated_plan(self, plan: ShufflePlan, combine, ordered: bool,
                        has_vals: bool, val_tail, val_dtype,
                        combine_sum_words: int = 0,
                        sink: str = "host") -> ShufflePlan:
        """Validate and stamp the combine/ordered read options AND the
        resolved wire tier onto a plan (shared by the single- and
        multi-process read paths, and warmup — so a warmed program and
        the read that follows agree on the full compiled-step family,
        wire mode included). combine implies ordered output, so it takes
        precedence. ``combine_sum_words`` > 0 sums only that many
        leading transport words of the value row and CARRIES the rest
        per key (varlen payloads — io/varlen.py)."""
        import dataclasses
        wire, wire_words = self._resolve_wire(plan, has_vals, val_tail,
                                              val_dtype)
        plan = dataclasses.replace(plan, wire=wire,
                                   wire_words=wire_words, sink=sink)
        if sink == "device" and wire == "lossless":
            # the lossless codec is a host-drain-path tier by contract;
            # a device sink never drains, so it cannot engage — the
            # plan keeps the stamp (program family) but the report will
            # show lossless_bytes=0
            self._warn_sink_once(
                "lossless_device",
                "a2a.wire=lossless with a device sink: the codec is "
                "host-only (it rides the drain path) and will not run — "
                "device-sink reads report lossless_bytes=0")
        if combine:
            from sparkucx_tpu.ops.aggregate import check_combinable
            check_combinable(val_tail if has_vals else None,
                             val_dtype if has_vals else None, combine)
            vw = value_words(val_tail, val_dtype)
            if combine_sum_words < 0 or combine_sum_words > vw:
                raise ValueError(
                    f"combine_sum_words={combine_sum_words} out of "
                    f"[0, {vw}] for this value schema")
            return self._stamp_kernel(dataclasses.replace(
                plan, combine=combine,
                combine_words=vw,
                combine_dtype=np.dtype(val_dtype).str,
                combine_sum_words=combine_sum_words))
        if ordered:
            return self._stamp_kernel(
                dataclasses.replace(plan, ordered=True))
        return plan

    def _stamp_kernel(self, plan: ShufflePlan) -> ShufflePlan:
        """Resolve the device-kernel tier for a combine/ordered plan
        (read.mergeImpl through segmented.resolve_kernel_impl — the
        _resolve_wire discipline applied to the kernel plane) and stamp
        it: the step bodies and the cross-wave merge fold branch on
        ``plan.kernel_impl``, the report names it, and family() keys
        it. A pallas ask that degrades to jnp counts into
        C_KERNEL_FALLBACK with the gate reason — the doctor's
        kernel_fallback evidence."""
        import dataclasses
        from sparkucx_tpu.ops.pallas.segmented import resolve_kernel_impl
        import jax as _jax
        impl, reason = resolve_kernel_impl(
            self.conf.read_merge_impl, _jax.default_backend(),
            combine_dtype=plan.combine_dtype or None)
        if reason is not None:
            m = self.node.metrics
            m.inc(C_KERNEL_FALLBACK, 1.0)
            m.inc(labeled(C_KERNEL_FALLBACK, reason=reason), 1.0)
            self._warn_sink_once(
                f"kernel_{reason}",
                f"read.mergeImpl={self.conf.read_merge_impl} resolves "
                f"to jnp on this read: {reason} "
                f"(segmented.resolve_kernel_impl; the report's "
                f"'kernel' field names what ran)")
        return dataclasses.replace(plan, kernel_impl=impl)

    @staticmethod
    def _cap_key(handle: ShuffleHandle) -> tuple:
        return (handle.num_maps, handle.num_partitions, handle.partitioner)

    def _apply_cap_hint(self, plan: ShufflePlan, handle: ShuffleHandle,
                        total_rows: int) -> ShufflePlan:
        """Seed cap_out with the SKEW FACTOR a previous same-shape shuffle
        settled at (round-1 weak #6: stop paying an overflow-retry
        recompile per run). The hint is stored volume-normalized — learned
        cap over the balanced share — so one huge skewed shuffle doesn't
        permanently inflate every later small shuffle of the same shape."""
        import dataclasses

        from sparkucx_tpu.shuffle.plan import bucket_cap_conf
        with self._lock:
            factor = self._cap_hints.get(self._cap_key(handle))
        if not factor or plan.num_shards == 1:
            # one receiver gets exactly its staged rows (make_plan)
            return plan
        balanced = max(1.0, total_rows / max(plan.num_shards, 1))
        # the hint-derived capacity is quantized by the SAME bucket
        # ladder as make_plan's, or learned hints would mint one fresh
        # compiled-step signature per observed skew factor — exactly the
        # shape churn a2a.capBuckets exists to collapse. The epsilon
        # matters: a ratchet factor stored as used/balanced reproduces
        # `used` with float noise (448 * (448/200)/448 = 448.000...06),
        # and a bare ceil would climb one rung — and compile one fresh
        # program — per same-shape read forever
        hint = bucket_cap_conf(
            int(np.ceil(balanced * factor / 8.0 - 1e-6)) * 8, self.conf)
        if hint > plan.cap_out:
            log.debug("seeding cap_out=%d from learned skew factor %.2f "
                      "(plan computed %d)", hint, factor, plan.cap_out)
            return dataclasses.replace(plan, cap_out=hint)
        return plan

    def _learn_cap(self, handle: ShuffleHandle, result,
                   total_rows: int) -> None:
        """Update the volume-normalized skew-factor hint for this shape.

        When the result exposes the exchange's true requirement
        (``recv_rows_needed`` — max per-shard delivered rows), the hint
        tracks THAT with 15% headroom, and DECAYS toward it when it
        shrinks: a ratchet keyed on provisioned capacity self-perpetuates
        (a hinted plan reports the hint back as "used"), so one
        pathological skewed run would inflate every later same-shape
        plan's HBM footprint forever (round-3 verdict weak #5). EWMA with
        alpha=0.5 forgets a one-off spike in a few runs while a genuinely
        skewed workload keeps its headroom. Results that cannot observe
        the requirement (combine: post-merge counts; pallas: aligned
        slack) keep the up-only provisioned-capacity ratchet."""
        used = getattr(result, "cap_out_used", None)
        if not (used and total_rows):
            return
        balanced = max(1.0, total_rows / max(self.node.num_devices, 1))
        needed = getattr(result, "recv_rows_needed", None)
        key = self._cap_key(handle)
        with self._lock:
            cur = self._cap_hints.get(key, 0.0)
            if needed:
                observed = needed * 1.15 / balanced
                self._cap_hints[key] = (observed if observed >= cur
                                        else 0.5 * (cur + observed))
            elif used / balanced > cur:
                self._cap_hints[key] = used / balanced

    # -- shared staging helpers -------------------------------------------
    def _materialize_outputs(self, writers, num_slots, slot_of,
                             entry=None, rep=None):
        """Materialize committed map outputs into per-slot lists and agree
        on one value schema. ``slot_of(ordinal, map_id)`` places each map
        output (slots = shards single-process, local shards distributed).

        With ``entry`` and ``integrity.verify != off``, every output is
        RE-VERIFIED against the integrity record its commit published —
        the pack-time staged check: bytes that no longer match raise
        typed :class:`BlockCorruptionError` before they can enter the
        exchange (``rep`` records the verified level + bytes).

        Returns (slot_outputs, has_vals, val_tail, val_dtype); raises on a
        mixed schema — bit-reinterpreting one writer's rows under another's
        schema would silently corrupt."""
        level = self._integrity_for(rep.tenant if rep is not None
                                    else None)
        verify = entry is not None and level != "off"
        verified_bytes = 0
        verified_maps = 0
        slot_outputs = [[] for _ in range(num_slots)]
        has_vals = False
        val_tail, val_dtype = None, None
        for ordinal, (map_id, w) in enumerate(sorted(writers.items())):
            if verify:
                keys, values, nb = self._verified_materialize(
                    entry, map_id, w)
                if nb >= 0:
                    verified_bytes += nb
                    verified_maps += 1
            else:
                keys, values = w.materialize()
            if values is not None and keys.shape[0]:
                has_vals = True
                if val_dtype is None:
                    val_tail, val_dtype = values.shape[1:], values.dtype
                elif (values.shape[1:], values.dtype) != (val_tail,
                                                          val_dtype):
                    raise ValueError(
                        f"mixed value schema across map outputs: mapId "
                        f"{map_id} wrote {values.dtype}{values.shape[1:]}, "
                        f"earlier outputs wrote {val_dtype}{val_tail}")
            slot_outputs[slot_of(ordinal, map_id)].append((keys, values))
        if has_vals:
            for outs in slot_outputs:
                for keys, values in outs:
                    if keys.shape[0] and values is None:
                        raise ValueError(
                            "mixed schema: some map outputs have values, "
                            "others have keys only")
        if verify:
            if verified_bytes:
                self.node.metrics.inc(C_INTEGRITY_VERIFIED,
                                      float(verified_bytes))
            if rep is not None and verified_maps:
                # only maps that PUBLISHED records count as verified —
                # a shuffle whose commits carried no integrity records
                # (direct registry publishers, pre-integrity state)
                # keeps integrity="" per the report contract rather
                # than claiming a check that never ran
                rep.integrity = level
                rep.integrity_bytes += verified_bytes
        return slot_outputs, has_vals, val_tail, val_dtype

    def _pack_share(self, tenant: str) -> int:
        """Fair share of the pack executor for one tenant's fill
        fan-out: with a single packing tenant, every worker; under
        contention, workers split by priority weight (a batch whale
        packing beside a high minnow gets ~1/5 of the slots instead of
        all of them — the pack-slot half of the no-starvation
        contract). Floor 1: a share of zero would serialize the tenant
        entirely, which is a starvation of its own."""
        workers = max(1, int(self.conf.pack_threads
                             or self.conf.cores_per_process))
        with self._lock:
            contending = [t for t, n in self._packing.items() if n > 0]
        if len(contending) <= 1:
            return workers
        weights = {t: self._tenants.spec(t).weight for t in contending}
        total = sum(weights.values()) or 1
        return max(1, (workers * weights.get(tenant, 1)) // total)

    def _pack_shards(self, slot_outputs, cap_in, width, has_vals,
                     tenant: Optional[str] = None):
        """Fuse key+value bytes into one [slots, cap_in, width] int32 row
        matrix (bit views, no value casts — jnp would silently truncate
        int64 with x64 off).

        The matrix is packed DIRECTLY into a pinned arena block — the one
        host copy on the read path — and the reader device_puts from that
        view, so host bytes DMA into HBM without a pageable bounce (the
        register-once-serve-zero-copy property,
        ref: CommonUcxShuffleBlockResolver.scala:45-57). Returns
        (rows_view, arena_buf); the caller releases arena_buf when the
        exchange is done.

        ``tenant`` joins the pack-slot fair share: concurrent packs of
        different tenants split the persistent executor's workers by
        priority weight (``_pack_share``), so a whale's giant fill
        cannot occupy every pack slot while a minnow's pack waits."""
        tid = self._tenants.resolve(tenant)
        shape = (len(slot_outputs), cap_in, width)
        buf = self.node.pool.get(max(int(np.prod(shape)) * 4, 1))
        rows = buf.view().view(np.int32).reshape(shape)

        def fill(p, pack_threads=None):
            # slots write disjoint rows[p] planes, so this parallelizes
            # cleanly; numpy copies release the GIL (measured ~1.5 GB/s
            # single-threaded — the host-side bottleneck at spill scale).
            # pack_threads=1 when THIS loop is already fanned out, so the
            # native pack doesn't oversubscribe workers x its own threads
            # on a memory-bound copy
            off = 0
            for keys, values in slot_outputs[p]:
                n = keys.shape[0]
                if n:
                    pack_rows(keys, values if has_vals else None, width,
                              out=rows[p, off:off + n],
                              nthreads=pack_threads)
                off += n
            # zero only the padding tail: pool blocks are recycled and
            # stale bytes must not leak rows, but re-zeroing the filled
            # prefix would cost a wasted full pass
            rows[p, off:] = 0

        with self._lock:
            self._packing[tid] = self._packing.get(tid, 0) + 1
        try:
            # the persistent executor makes fan-out dispatch ~µs, so the
            # old 16 MiB spawn-amortization guard shrinks to a modest
            # floor that only filters shapes where the copy itself is
            # cheaper than waking the workers (tiny test shuffles).
            ex = self._pack_executor_if_parallel() \
                if len(slot_outputs) > 1 and rows.nbytes >= (1 << 20) \
                else None
            if ex is not None:
                share = self._pack_share(tid)
                workers = max(1, int(self.conf.pack_threads
                                     or self.conf.cores_per_process))
                if share >= workers:
                    # uncontended (the common case): the executor's own
                    # worker count is the only bound — one continuous
                    # fan-out, no added synchronization on the wave
                    # pipeline's critical path
                    list(ex.map(lambda p: fill(p, pack_threads=1),
                                range(len(slot_outputs))))
                else:
                    # contending tenants: bound THIS pack's concurrent
                    # fills to its fair share with a sliding window
                    # (top-up on completion — a chunk barrier would
                    # stall on each chunk's straggler)
                    from concurrent.futures import (FIRST_COMPLETED,
                                                    wait as _fwait)
                    live = set()
                    for p in range(len(slot_outputs)):
                        live.add(ex.submit(fill, p, 1))
                        if len(live) >= share:
                            done, live = _fwait(
                                live, return_when=FIRST_COMPLETED)
                            for f in done:
                                f.result()
                    for f in live:
                        f.result()
            else:
                for p in range(len(slot_outputs)):
                    fill(p)
        except BaseException:
            # the caller's cleanup only guards AFTER we return; a failure
            # mid-pack must not strand the pinned block
            self.node.pool.put(buf)
            raise
        finally:
            with self._lock:
                n = self._packing.get(tid, 1) - 1
                if n > 0:
                    self._packing[tid] = n
                else:
                    self._packing.pop(tid, None)
        return rows, buf

    def _pack_executor(self):
        """The manager's persistent pack thread pool (lazily built, shut
        down in stop()). Sized by ``a2a.packThreads`` (0 = coresPerProcess)
        — the knob the doctor's pipeline_stall rule points at when wave
        packs run slower than the collective they should hide behind."""
        with self._lock:
            if self._pack_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                workers = self.conf.pack_threads \
                    or self.conf.cores_per_process
                self._pack_pool = ThreadPoolExecutor(
                    max_workers=max(1, int(workers)),
                    thread_name_prefix="sxt-pack")
            return self._pack_pool

    def _note_inert_lossless(self, plan: ShufflePlan) -> None:
        """``a2a.wire=lossless`` on a read that runs single-shot: the
        codec engages on the wave drain path only, so nothing will be
        compressed and the report will show ``lossless_bytes=0``. Warn
        ONCE (not per read) so the inert conf is visible — the int8
        tier's ineligible-read log discipline, without re-stamping the
        plan (wavedness depends on per-read row counts, and flip-
        flopping the wire family per read size would churn programs)."""
        if plan.wire == "lossless" and not self._warned_inert_lossless:
            self._warned_inert_lossless = True
            log.warning(
                "a2a.wire=lossless configured but this read runs "
                "single-shot — the codec rides the wave drain path only "
                "(set spark.shuffle.tpu.a2a.waveRows); such reads "
                "report lossless_bytes=0")

    def _pack_executor_if_parallel(self):
        """The pack fan-out policy in ONE place (staged pack fill, the
        lossless drain codec): the shared executor when conf sizes it
        above one worker (``a2a.packThreads``, 0 = coresPerProcess),
        else None — callers serialize inline and a single-core process
        never builds the pool."""
        workers = self.conf.pack_threads or self.conf.cores_per_process
        return self._pack_executor() if workers > 1 else None

    # -- wave-pipelined exchange (a2a.waveRows) ----------------------------
    def _waves_eligible(self, plan: ShufflePlan) -> bool:
        """Whether a2a.waveRows applies to this read. Pure conf/plan/
        node facts — identical on every process, so the distributed
        branch decision stays in SPMD lockstep without a collective.
        Hierarchical reads wave through the tiered two-step path
        (PendingWaveShuffle dispatches a PendingTieredShuffle per wave
        — or a PendingDistributedTieredShuffle multi-process, whose
        per-stage overflow/regrow decisions ride agreement rounds), so
        waves are legal on every topology."""
        if plan.impl == "pallas":
            log.info("a2a.waveRows set with impl='pallas' — single-shot "
                     "read (the remote-DMA transport owns its own "
                     "chunk-aligned flow control)")
            return False
        return True

    def _submit_waved(self, handle: ShuffleHandle, slot_outputs,
                      nvalid: np.ndarray, plan: ShufflePlan, width: int,
                      has_vals: bool, val_tail, val_dtype,
                      rep: ExchangeReport, timeout: Optional[float],
                      num_waves: int, distributed: bool,
                      shard_ids=None) -> "PendingWaveShuffle":
        """Build the pending handle for a wave-pipelined read. Packs are
        DEFERRED into result() (where the pipeline drives them overlapped
        with the collectives), so this path re-registers its own
        in-flight-read guard — the caller's guard window closes when this
        returns, but writer-owned memory is walked until the LAST wave's
        pack."""
        wave_rows = self.conf.wave_rows
        outer = dataclasses.replace(plan, wave_rows=wave_rows,
                                    num_waves=num_waves)
        wplan = wave_step_plan(outer, self.conf)
        with self._lock:
            hint = self._wave_cap_hints.get(
                (self._cap_key(handle), wplan.cap_in), 0)
        if hint > wplan.cap_out:
            # a same-shape exchange already settled its wave capacity —
            # start there instead of re-paying the overflow recompile
            wplan = dataclasses.replace(wplan, cap_out=hint)
        # Ragged wave contract: the [W] REAL per-wave row counts derive
        # from the global size row (identical on every process). In
        # distributed mode they are AGREED collectively, agree_wave_count
        # style — a process with a divergent occupancy view (stale staged
        # outputs, raced unregister) fails fast on every process together
        # instead of desyncing the per-wave collectives mid-pipeline.
        wave_sizes = wave_payload_rows(nvalid, wave_rows, num_waves)
        if distributed:
            from sparkucx_tpu.shuffle.distributed import agree_wave_sizes
            wave_sizes = agree_wave_sizes(wave_sizes)
        rep.waves = num_waves
        rep.wave_rows = wave_rows
        rep.wave_payload_rows = [int(x) for x in wave_sizes]
        rep.plan_bucket = [int(wplan.cap_in), int(wplan.cap_out)]
        rep.plan_family = str(wplan.family())
        # wave wire accounting: the pipeline dispatches W exchanges of the
        # wave plan's shape — wire cost is per wave (a padded transport
        # pays its caps every wave, occupancy notwithstanding; the ragged
        # native collective pays each wave's real rows). Refreshed in
        # _finalize once any overflow regrow settles the final wave plan.
        self._set_wave_wire(rep, wplan, wave_sizes, width)
        if self.hierarchical and wplan.impl != "pallas":
            # hierarchical waves: per-tier accounting summed over the
            # wave plan's W tiered exchanges (re-settled in finalize) —
            # distributed included, now that waved multi-process reads
            # dispatch the same per-tier split programs per wave
            self._stamp_wave_tiers(rep, wplan, wave_sizes, width)
        # pipeline depth: the tenant's waveDepth override wins (a batch
        # tenant can be held to a shallower — cheaper-footprint —
        # pipeline while a high tenant keeps the conf depth). Conf-
        # derived per tenant, so it is identical on every process.
        spec_depth = self._tenants.spec(handle.tenant).wave_depth
        depth = max(1, min(spec_depth or self.conf.wave_depth,
                           num_waves))
        # Admission: the pipeline's whole point is a bounded footprint —
        # `depth` pinned wave blocks plus up to `depth` waves' device
        # buffers, NOT the full shuffle (same estimate discipline as
        # _exchange_footprint; identical on every process by
        # construction, like the single-shot distributed path).
        # _make_admitter adds ONE wave's device term itself, so the
        # stage argument carries the other depth-1 — an undrained wave
        # pins its send+recv matrices until drain_wave_result, and the
        # reservation must say so or the backpressure cap silently
        # loosens by a factor of depth.
        block_bytes = len(slot_outputs) * wplan.cap_in * width * 4
        device_wave = (wplan.cap_in + wplan.cap_out) * width * 4 \
            * wplan.num_shards
        # Device sink: waves are NOT drained — every wave's receive
        # buffer stays HBM-resident until the consumer folds it, so the
        # reservation accounts ALL waves' device buffers (HBM residency),
        # not the depth-bounded pipeline window the host drain earns.
        # _make_admitter adds one wave's device term itself.
        hbm_waves = num_waves if wplan.sink == "device" else depth
        admit, release_admitted = self._make_admitter(
            wplan, width,
            depth * block_bytes + (hbm_waves - 1) * device_wave,
            None if distributed else timeout, tenant=handle.tenant,
            report=rep)
        local_rows = sum(int(k.shape[0])
                         for outs in slot_outputs for k, _ in outs)
        read_gen = self._read_started()
        try:
            # same injection site as the single-shot dispatch: the waved
            # branch returns before _submit_*_staged's check, so without
            # this the chaos matrix's waved x exchange cell never fires
            self.node.faults.check("exchange")
            log.info("wave-pipelined read: shuffle %d, %d waves x %d "
                     "rows/shard (depth %d, wave plan cap_in=%d "
                     "cap_out=%d)", handle.shuffle_id, num_waves,
                     wave_rows, depth, wplan.cap_in, wplan.cap_out)
            return PendingWaveShuffle(
                self, handle, outer, wplan, depth, slot_outputs, nvalid,
                width, has_vals, val_tail, val_dtype, rep, read_gen,
                admit, release_admitted, local_rows, distributed,
                shard_ids, wave_sizes=wave_sizes)
        except BaseException:
            self._read_finished(read_gen)
            release_admitted()
            raise

    # -- the multi-process read path --------------------------------------
    def _submit_distributed(self, handle: ShuffleHandle, timeout: float,
                            combine: Optional[str] = None,
                            ordered: bool = False,
                            combine_sum_words: int = 0,
                            sink: Optional[str] = None):
        # resolve the landing tier HERE, identically on every process —
        # pure argument/conf facts, no collective needed; the device
        # sink is legal distributed (DistributedLazyReaderResult keeps
        # the payload sharded in HBM, zero payload D2H)
        sink = self._resolve_sink(sink, combine, ordered,
                                  distributed=True)
        rep = self._new_report(handle, distributed=True)
        rep.sink = sink
        try:
            # same anatomy envelope as _submit_local (plan phase,
            # lowest sweep priority — the precise spans inside win)
            with self.node.tracer.span("shuffle.submit",
                                       shuffle_id=handle.shuffle_id,
                                       trace=rep.trace_id):
                return self._submit_distributed_impl(
                    handle, timeout, combine, ordered,
                    combine_sum_words, rep, sink)
        except BaseException as e:
            rep.error = rep.error or repr(e)[:300]
            self.node.flight.end_trace(rep.trace_id)
            raise

    def _submit_distributed_impl(self, handle: ShuffleHandle,
                                 timeout: float, combine: Optional[str],
                                 ordered: bool, combine_sum_words: int,
                                 rep: ExchangeReport,
                                 sink: str = "host"):
        """COLLECTIVE multi-process submit (shuffle/distributed.py);
        returns a PendingDistributedShuffle — result() is the other half
        of the collective. Map
        outputs stay on this process's shards (Spark: outputs live on the
        writing executor's local disk); metadata crosses processes via
        allgather; the exchange is the same jitted SPMD step over the
        global mesh. Hierarchical ICI/DCN applies unchanged when the mesh
        is 2-D, since the exchange mesh flattening is identical on every
        process."""
        import time as _time

        from sparkucx_tpu.shuffle.distributed import (
            allgather_blob, allgather_sizes, submit_shuffle_distributed)

        import jax
        if self.conf.a2a_impl == "pallas" and \
                jax.default_backend() != "tpu":
            # The kernel itself is process-agnostic — remote DMA targets
            # mesh-logical device ids, and the n=8 AOT proof lowers the
            # multi-peer program (shuffle/aot.py). What
            # cannot span processes is the CPU INTERPRET validation path
            # (python-simulated DMA inside one process), so multi-process
            # pallas is gated to real TPU backends rather than forbidden.
            raise NotImplementedError(
                "impl='pallas' multi-process requires a TPU backend: the "
                "CPU interpret path cannot simulate cross-process DMA; "
                "use native/dense for multi-process CPU reads")
        tracer = self.node.tracer
        shard_ids = self.node.local_shard_ids
        L = len(shard_ids)
        Pn = self.node.num_devices

        with self._lock:
            writers = dict(self._writers.get(handle.shuffle_id, {}))

        # Completeness barrier: poll the global DISTINCT-map-id presence
        # bitmap (the wait_complete analog, ref:
        # UcxWorkerWrapper.scala:134-143) — a count would let a duplicate
        # commit mask a missing map. Both the success exit AND the timeout
        # exit ride the allgathered values — one process's expired clock
        # makes every process raise together, never leaving a peer blocked
        # in the next collective.
        limit = self.conf.meta_buffer_size
        if (handle.num_maps + 1) * 8 > limit:
            raise ValueError(
                f"shuffle {handle.shuffle_id}: presence bitmap "
                f"({(handle.num_maps + 1) * 8} B for {handle.num_maps} "
                f"maps) exceeds meta.bufferSize={limit}; raise "
                f"spark.shuffle.tpu.meta.bufferSize")
        deadline = _time.monotonic() + timeout
        with tracer.span("shuffle.barrier", kind="map_outputs",
                         shuffle_id=handle.shuffle_id,
                         trace=rep.trace_id):
            while True:
                bitmap = np.zeros(handle.num_maps + 1, dtype=np.int64)
                for map_id, w in writers.items():
                    if w.committed:
                        bitmap[map_id] = 1
                bitmap[-1] = 1 if _time.monotonic() > deadline else 0
                gathered = allgather_blob(bitmap)      # [nproc, M+1]
                owners = gathered[:, :-1].sum(axis=0)
                if (owners > 1).any():
                    dups = np.nonzero(owners > 1)[0].tolist()
                    raise RuntimeError(
                        f"shuffle {handle.shuffle_id}: map ids {dups} "
                        f"committed by multiple processes — ambiguous "
                        f"ownership (maps must be partitioned over "
                        f"processes)")
                total = int((owners > 0).sum())
                if total >= handle.num_maps:
                    break
                if gathered[:, -1].any():
                    raise TimeoutError(
                        f"shuffle {handle.shuffle_id}: only {total}/"
                        f"{handle.num_maps} map outputs published within "
                        f"{timeout}s")
                _time.sleep(0.05)
                with self._lock:
                    writers = dict(
                        self._writers.get(handle.shuffle_id, {}))

        committed_ids = sorted(m for m, w in writers.items() if w.committed)

        # Local materialize + schema summary (maps round-robin over LOCAL
        # shards: outputs stay on the writing process, like Spark's
        # executor-local shuffle files). Same in-flight-read guard as the
        # local path: writer-owned memory is only touched through the end
        # of pack. The snapshot is retaken UNDER the guard — the barrier
        # loop's snapshot predates registration, so a remesh in between
        # could otherwise hand us already-released writers.
        read_gen = self._read_started()
        try:
            with self._lock:
                writers = {
                    m: w for m, w in
                    self._writers.get(handle.shuffle_id, {}).items()
                    if w.committed}
            # The stale-snapshot verdict must ride a collective: raising
            # on one process while peers proceed into the schema
            # allgather would hang them (the barrier loop above rides its
            # timeout bit through the allgather for exactly this reason)
            changed = int(sorted(writers) != committed_ids)
            from sparkucx_tpu.shuffle.distributed import allgather_blob
            if allgather_blob(np.array([changed], dtype=np.int64)).any():
                raise RuntimeError(
                    f"shuffle {handle.shuffle_id}: committed map outputs "
                    f"changed between the completeness barrier and "
                    f"staging on at least one process (remesh or "
                    f"unregister raced this read)")
            return self._submit_distributed_staged(
                handle, writers, L, Pn, shard_ids, combine, ordered,
                tracer, combine_sum_words, rep, sink)
        finally:
            self._read_finished(read_gen)

    def _submit_distributed_staged(self, handle, writers, L, Pn, shard_ids,
                                   combine, ordered, tracer,
                                   combine_sum_words: int = 0,
                                   rep: Optional[ExchangeReport] = None,
                                   sink: str = "host"):
        from sparkucx_tpu.shuffle.distributed import (
            allgather_blob, allgather_sizes, submit_shuffle_distributed)

        with tracer.span("shuffle.stage", maps=len(writers),
                         trace=rep.trace_id):
            shard_outputs, has_vals, val_tail, val_dtype = \
                self._materialize_outputs(
                    writers, L, lambda ordinal, map_id: ordinal % L,
                    entry=handle.entry, rep=rep)
        local_rows_n = sum(k.shape[0]
                           for outs in shard_outputs for k, _ in outs)

        # Schema agreement across processes. Wildcard (-1) = this process
        # wrote no valued rows and adopts the cluster schema.
        blob = np.full(8, -1, dtype=np.int64)
        if local_rows_n:
            blob[0] = 1 if has_vals else 0
        if has_vals:
            if len(val_tail) > 5:
                raise ValueError(
                    f"value rank {len(val_tail)} > 5 unsupported in "
                    f"multi-process mode; flatten the trailing dims")
            dt = np.dtype(val_dtype).str.encode()[:6]
            blob[1] = int.from_bytes(dt, "little")
            blob[2] = len(val_tail)
            blob[3:3 + len(val_tail)] = val_tail
        schemas = allgather_blob(blob)                 # [nproc, 8]
        known = schemas[schemas[:, 0] >= 0]
        if known.size:
            if not (known == known[0]).all():
                # covers keys-only vs valued processes too (blob[0] differs)
                raise ValueError(
                    f"mixed value schema across processes: {schemas}")
            ref = known[0]
            if ref[0] == 1 and not has_vals:
                val_dtype = np.dtype(
                    int(ref[1]).to_bytes(6, "little").rstrip(b"\0").decode())
                val_tail = tuple(int(x) for x in ref[3:3 + int(ref[2])])
            has_vals = bool(ref[0])

        nvalid_local = np.array(
            [sum(k.shape[0] for k, _ in outs) for outs in shard_outputs],
            dtype=np.int64)
        nvalid = allgather_sizes(nvalid_local, shard_ids, Pn)
        validate_row_sizes(nvalid.reshape(1, -1))
        if self._integrity_for(handle.tenant) == "full" and not combine:
            # one more metadata-plane collective, full level only: the
            # receivers need the GLOBAL per-partition digest table
            self._stash_full_expect(handle, writers)
        t_plan = time.perf_counter()
        with tracer.span("shuffle.plan", shuffle_id=handle.shuffle_id,
                         trace=rep.trace_id if rep is not None else ""):
            plan = make_plan(nvalid, Pn, handle.num_partitions, self.conf,
                             partitioner=handle.partitioner,
                             bounds=handle.bounds)
            # safe cross-process: every process runs the same collective
            # read sequence, so learned hints advance in lockstep
            plan = self._apply_cap_hint(plan, handle, int(nvalid.sum()))
        if rep is not None:
            rep.plan_ms = (time.perf_counter() - t_plan) * 1e3
        with tracer.span("shuffle.plan", shuffle_id=handle.shuffle_id,
                         decorate=True,
                         trace=rep.trace_id if rep is not None else ""):
            plan = self._decorated_plan(plan, combine, ordered, has_vals,
                                        val_tail, val_dtype,
                                        combine_sum_words, sink=sink)

        width = KEY_WORDS + (value_words(val_tail, val_dtype)
                             if has_vals else 0)
        if rep is not None:
            # no process holds the [M, R] table here: skew comes from the
            # allgathered per-peer rows (the cluster-wide view every
            # process shares by construction)
            self._report_volume(rep, plan, nvalid, width,
                                local_rows=int(nvalid_local.sum()))
            self._estimate_wire_error(rep, plan, shard_outputs)
            if self.hierarchical and plan.impl != "pallas":
                # exact cross-fabric rows, distributed: no single
                # process holds the [M, R] table, but each holds its
                # LOCAL maps' size rows — sum the per-process partial
                # [P, P] device matrices over the agreement channel
                self._stamp_tiers(
                    rep, plan, nvalid, width,
                    dev_matrix=self._agreed_dev_matrix(
                        handle, writers, L, Pn, shard_ids))
        # Wave-pipelined mode, multi-process: the wave count derives from
        # the ALLGATHERED global size row (identical math everywhere), and
        # agree_wave_count allgathers the verdict so a divergent
        # a2a.waveRows conf fails fast on every process together instead
        # of desyncing the SPMD group mid-pipeline. The agreement runs on
        # EVERY distributed read — a waves-off process proposes 1 — or a
        # process booted with waveRows=0 would skip straight into the
        # single-shot collective while its peers enter the wave loop
        # (exactly the desync the guard exists to prevent; one tiny
        # allgather rides the same metadata plane as the barriers above).
        from sparkucx_tpu.shuffle.distributed import agree_wave_count
        eligible = self.conf.wave_rows > 0 and self._waves_eligible(plan)
        W = wave_count(nvalid, self.conf.wave_rows) if eligible else 1
        W = agree_wave_count(W if eligible and W > 1 else 1)
        if W > 1:
            return self._submit_waved(
                handle, shard_outputs, nvalid, plan, width, has_vals,
                val_tail if has_vals else None, val_dtype, rep, None,
                W, distributed=True, shard_ids=shard_ids)
        self._note_inert_lossless(plan)
        t_pack = time.perf_counter()
        with tracer.span("shuffle.pack", rows=int(nvalid_local.sum()),
                         trace=rep.trace_id if rep is not None else ""):
            local_rows, stage_buf = self._pack_shards(
                shard_outputs, plan.cap_in, width, has_vals,
                tenant=handle.tenant)
        if rep is not None:
            rep.pack_ms = (time.perf_counter() - t_pack) * 1e3

        # Admission control — the footprint must be identical on every
        # process or defer decisions diverge and (timeout=None) the group
        # hangs. stage_buf.requested is process-LOCAL (local shard count x
        # pool size-class rounding can differ), so the staging term is
        # derived purely from (plan, width, num_shards) globals: the
        # worst-case per-process pinned buffer, ceil(P/nproc) shard
        # planes. Every process computes the same number by construction
        # (round-3 advisor finding). timeout=None: a local-clock
        # TimeoutError on one process while a peer proceeds into the
        # collective would diverge the SPMD group (see _make_admitter).
        nproc = max(1, self.conf.num_processes)
        stage_global = -(-Pn // nproc) * plan.cap_in * width * 4
        admit, release_admitted = self._make_admitter(
            plan, width, stage_global, None, tenant=handle.tenant,
            report=rep)

        on_done, arm = self._arm_read_callbacks(
            stage_buf, release_admitted, handle,
            int(nvalid.sum()), int(nvalid_local.sum()), width, report=rep,
            combine=combine)

        # same ownership rule as the local path: the armed handle is the
        # sole releaser of the pack buffer
        pending = None
        try:
            self.node.faults.check("exchange")
            if rep is not None:
                rep._t_dispatched = time.perf_counter()
            with tracer.span("shuffle.dispatch",
                             shuffle_id=handle.shuffle_id,
                             rows=int(nvalid.sum()), width=width,
                             hierarchical=self.hierarchical,
                             distributed=True,
                             trace=rep.trace_id if rep is not None
                             else ""):
                vt = val_tail if has_vals else None
                # flat-only transport: pallas on a multi-slice mesh rides
                # the flattened alias mesh, same as the local path
                # (manager.py _submit_local); the two-stage exchange is
                # native/dense territory
                hier = self.hierarchical and plan.impl != "pallas"
                if self.hierarchical and not hier:
                    log.info("a2a.impl=pallas on a multi-slice mesh "
                             "(distributed): using the flat exchange "
                             "over %d devices",
                             self.exchange_mesh.devices.size)
                if hier:
                    # split per-tier programs: each tier runs under its
                    # OWN watchdog deadline, overflow/regrow verdicts
                    # ride agreement rounds (a wedged DCN expires the
                    # dcn deadline without stalling the ici stage)
                    from sparkucx_tpu.shuffle.distributed import (
                        submit_shuffle_tiered_distributed)
                    pending = submit_shuffle_tiered_distributed(
                        self.node.mesh, self.topology, plan,
                        local_rows, nvalid_local, shard_ids, vt,
                        val_dtype, on_done=on_done, admit=admit,
                        wire_seed=rep._seq if rep is not None else 0,
                        hooks=self._tier_hooks(
                            rep.trace_id if rep is not None else ""))
                else:
                    pending = submit_shuffle_distributed(
                        self.exchange_mesh, self.axis, plan, local_rows,
                        nvalid_local, shard_ids, vt, val_dtype,
                        on_done=on_done, admit=admit,
                        wire_seed=rep._seq if rep is not None else 0)
            if rep is not None:
                rep.dispatch_ms = (time.perf_counter()
                                   - rep._t_dispatched) * 1e3
            arm(pending)
            return pending
        except BaseException:
            if pending is None:
                self.node.pool.put(stage_buf)
                release_admitted()
            raise

    def has_live_writer(self, shuffle_id: int, map_id: int) -> bool:
        """True when (shuffle_id, map_id) currently holds an UNCOMMITTED
        writer — the live-lease query facades use to reject an equal-id
        re-lease (compat/v2.writer) without reaching into this manager's
        writer table themselves."""
        with self._lock:
            w = self._writers.get(shuffle_id, {}).get(map_id)
        return w is not None and not w.committed

    # -- checkpoint support ----------------------------------------------
    def live_shuffles(self):
        """Registered shuffle ids (snapshot enumeration)."""
        with self._lock:
            return sorted(self._writers.keys())

    def export_shuffle(self, shuffle_id: int):
        """{map_id: (keys, values, committed)} staged state for
        runtime.checkpoint.snapshot_shuffles (shape + partitioner come
        from the registry entry — the single source of truth)."""
        # snapshot walks writer-owned memory (spill mmap views) — hold the
        # in-flight-read guard so a concurrent remesh defers their release
        # (registered BEFORE the snapshot, like the read paths)
        read_gen = self._read_started()
        try:
            with self._lock:
                if shuffle_id not in self._writers:
                    raise KeyError(f"shuffle {shuffle_id} not registered")
                writers = dict(self._writers[shuffle_id])
            staged = {}
            for map_id, w in writers.items():
                keys, values = w.materialize()
                # spill materialize returns mmap VIEWS that die with the
                # writer; copy so the snapshot owns its bytes
                staged[map_id] = (np.array(keys, copy=True),
                                  None if values is None
                                  else np.array(values, copy=True),
                                  w.committed)
            return staged
        finally:
            self._read_finished(read_gen)

    # -- teardown ---------------------------------------------------------
    def unregister_shuffle(self, shuffle_id: int,
                           keep_durable: bool = False) -> None:
        """Release table + staged buffers
        (ref: CommonUcxShuffleManager.scala:73-77).

        The dropped writers go through the same in-flight-read guard as a
        remesh drop: a read between its writers snapshot and the end of
        pack may still be walking these buffers, and an inline release
        here would be the exact use-after-free the graveyard exists to
        prevent. With no read in flight they free immediately.

        ``keep_durable`` (stop()'s path) leaves the shuffle's ledger
        state on disk: process shutdown must NOT destroy what the
        ledger exists to carry across restarts. The default — explicit
        application teardown — forgets it."""
        with self._lock:
            writers = self._writers.pop(shuffle_id, {})
            self._shapes.pop(shuffle_id, None)
            self._replayed.pop(shuffle_id, None)
            self._replay_counts.pop(shuffle_id, None)
            self._recovered.pop(shuffle_id, None)
            self._full_expect.pop(shuffle_id, None)
            self._gen += 1
            if writers:
                self._graveyard.append((self._gen, [writers]))
            to_free = self._collect_free_graveyard_locked()
        self._release_writer_batches(to_free)
        self.node.registry.unregister(shuffle_id)
        if self._ledger is not None and not keep_durable:
            self._ledger.forget(shuffle_id)

    def stop(self, drain_timeout: float = 10.0) -> None:
        """Tear everything down (ref: CommonUcxShuffleManager.scala:82-91).

        Parked graveyard batches may still be walked by an in-flight
        read's materialize→pack window — drain those reads (bounded) so
        shutdown does not re-create the use-after-free the graveyard
        prevents. A read that outlives the drain window gets a warning
        and its buffers are released anyway (shutdown must terminate)."""
        import time as _time
        self.node.epochs.remove_listener(self._on_epoch_bump)
        self.node.flight.remove_context_provider(self.exchange_reports)
        deadline = _time.monotonic() + drain_timeout
        with self._inflight_cv:
            while self._active_reads:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    log.warning(
                        "stop(): %d reads still in flight after %.0fs "
                        "drain; releasing their buffers anyway",
                        sum(self._active_reads.values()), drain_timeout)
                    break
                self._inflight_cv.wait(min(remaining, 1.0))
            ids = list(self._writers.keys())
            graveyard, self._graveyard = self._graveyard, []
        self._release_writer_batches([ws for _, ws in graveyard])
        with self._lock:
            pack_pool, self._pack_pool = self._pack_pool, None
        if pack_pool is not None:
            pack_pool.shutdown(wait=True)
        for sid in ids:
            # shutdown keeps durable ledger state — surviving process
            # death is the ledger's whole point
            self.unregister_shuffle(sid, keep_durable=True)
        # recovered-but-never-adopted shuffles hold registry entries the
        # scan created; drop those too (their files stay on disk)
        with self._lock:
            leftover = list(self._recovered.keys())
        for sid in leftover:
            self.unregister_shuffle(sid, keep_durable=True)
        # A drain that timed out leaves reads active: the unregister loop
        # just RE-parked those writers in the graveyard keyed against the
        # still-live generations, where they would sit until process exit
        # (round-3 advisor: the "releasing anyway" warning above was a
        # promise the code didn't keep). Shutdown must terminate — force
        # the remaining batches out regardless of generation.
        with self._lock:
            leftover, self._graveyard = self._graveyard, []
        self._release_writer_batches([ws for _, ws in leftover])


def _slice_slot_outputs(slot_outputs, lo: int, hi: int):
    """Row range [lo, hi) of each slot's concatenated staged sequence, as
    ZERO-COPY views into the writer-owned arrays — one wave's share of the
    staged map outputs. Returns (sliced_slot_outputs, per_slot_counts);
    callers must hold the manager's in-flight-read guard for as long as
    the views are live (they alias arena/mmap memory)."""
    out, counts = [], []
    for outs in slot_outputs:
        sliced = []
        off = 0
        for keys, values in outs:
            n = keys.shape[0]
            s, e = max(lo - off, 0), min(hi - off, n)
            if s < e:
                sliced.append((keys[s:e],
                               None if values is None else values[s:e]))
            off += n
        out.append(sliced)
        counts.append(sum(int(k.shape[0]) for k, _ in sliced))
    return out, np.asarray(counts, dtype=np.int64)


class PendingWaveShuffle:
    """Future-like handle for a WAVE-PIPELINED exchange (a2a.waveRows).

    ``result()`` drives a depth-D software pipeline over the staged map
    outputs: wave *i+1* is packed on the host (persistent pack executor,
    recycled HostMemoryPool blocks) while wave *i*'s collective is in
    flight and wave *i-1* drains D2H — the streaming fetch window of the
    reference's reader (maxBlocksInFlight over a lazy request queue,
    ref: UcxShuffleReader.scala:56-70 / compat/spark_3_0 fetch iterator),
    rebuilt over compiled-program launches instead of block requests.

    Invariants the pipeline keeps:

    * every wave dispatches the SAME compiled program (wave_step_plan —
      fixed shape, one step-cache entry per shape family);
    * an overflow retry regrows and re-runs ONLY the offending wave
      (PendingShuffle's own retry loop), and later waves start at the
      grown capacity;
    * pinned staging never exceeds ``depth`` wave blocks — the pool
      recycles the block a drained wave released into the next pack;
    * multi-process: every process drives the identical wave sequence in
      lockstep (wave count agreed collectively at submit), so the
      per-wave collectives — including retry consensus — stay SPMD-safe.

    ``done()`` is a local poll (False until result() ran: packs are
    deferred into the drive so they can overlap the collectives)."""

    def __init__(self, mgr: TpuShuffleManager, handle: ShuffleHandle,
                 outer_plan: ShufflePlan, wave_plan: ShufflePlan,
                 depth: int, slot_outputs, nvalid: np.ndarray, width: int,
                 has_vals: bool, val_tail, val_dtype, rep: ExchangeReport,
                 read_gen: int, admit, release_admitted, local_rows: int,
                 distributed: bool, shard_ids=None, wave_sizes=None):
        self._mgr = mgr
        self._handle = handle
        self._outer_plan = outer_plan
        self._wave_plan = wave_plan
        self._depth = depth
        self._slot_outputs = slot_outputs
        self._nvalid = nvalid
        self._width = width
        self._has_vals = has_vals
        self._val_tail = val_tail
        self._val_dtype = val_dtype
        self._rep = rep
        self._read_gen = read_gen
        self._guard_open = True
        self._admit = admit
        self._release_admitted = release_admitted
        self._local_rows = local_rows
        self._distributed = distributed
        self._shard_ids = list(shard_ids) if shard_ids is not None else None
        self._num_waves = outer_plan.num_waves
        self._wave_rows = outer_plan.wave_rows
        # [W] agreed REAL rows per wave (ragged wave contract) — drives
        # the report's wire accounting; None only from legacy callers
        self._wave_sizes = None if wave_sizes is None \
            else np.asarray(wave_sizes, dtype=np.int64)
        self._result = None
        self._dead = False
        # last drained wave's compiled step — every wave shares ONE
        # program by construction, so its cost record speaks for the
        # whole exchange (device-plane join in _finalize)
        self._last_step = None
        # a2a.wire=lossless drain accounting: [raw_bytes, compressed]
        # summed over every drained wave's host blocks
        self._lossless = [0, 0]
        # hierarchical waves: per-tier walls summed over the drained
        # waves' tiered pendings (the per-wave tier timeline's total)
        self._tier_walls: Dict[str, float] = {}

    # -- lifecycle ---------------------------------------------------------
    def done(self) -> bool:
        return self._result is not None or self._dead

    def _finish_guard(self) -> None:
        if self._guard_open:
            self._guard_open = False
            # drop the staged-output views FIRST: they alias writer
            # memory the guard is about to stop protecting
            self._slot_outputs = None
            self._mgr._read_finished(self._read_gen)

    def __del__(self):
        # abandoned handle: nothing was dispatched (packs defer into
        # result()), but the read guard and any queued admission must not
        # leak — a parked graveyard batch would otherwise never free
        try:
            if self._result is None and not self._dead:
                self._finish_guard()
                self._release_admitted()
                self._mgr.node.flight.end_trace(self._rep.trace_id)
        except Exception:
            pass

    def result(self) -> ShuffleReaderResult:
        if self._result is not None:
            return self._result
        if self._dead:
            raise RuntimeError(
                "wave exchange handle is dead: a previous result() failed "
                "and its buffers were released — re-submit the shuffle")
        rep = self._rep
        try:
            res = self._drive()
        except BaseException as e:
            self._dead = True
            self._release_admitted()
            rep.error = rep.error or repr(e)[:300]
            rep.stepcache_hits = int(
                GLOBAL_METRICS.get(COMPILE_HITS) - rep._hits0)
            rep.stepcache_programs = int(
                GLOBAL_METRICS.get(COMPILE_PROGRAMS) - rep._prog0)
            if rep.tiers:
                # a FAILED hierarchical read keeps the tier walls its
                # drained waves measured (partial by construction) —
                # the which-tier-burned-the-wall postmortem evidence;
                # completed=False counts no wire (the single-shot
                # on_done discipline)
                self._mgr._settle_tiers(rep, self._tier_walls,
                                        self._width, completed=False)
            self._mgr.node.flight.end_trace(rep.trace_id)
            raise
        self._result = res
        return res

    # -- the pipeline ------------------------------------------------------
    def _drive(self) -> ShuffleReaderResult:
        from collections import deque
        mgr = self._mgr
        rep = self._rep
        tracer = mgr.node.tracer
        t_read0 = time.perf_counter()
        inflight: "deque" = deque()       # (wave_idx, pending)
        timeline: List[Dict] = []
        wave_results: List = [None] * self._num_waves
        retries_total = 0
        pack_total = dispatch_total = pack_hidden = 0.0
        if self._admit is not None and not self._admit(False):
            self._admit(True)             # blocks until capacity frees
        try:
            for i in range(self._num_waves):
                # per-wave injection site: a fault mid-pipeline settles
                # the in-flight waves (the except path below), then the
                # replay policy restarts the WHOLE exchange — per-wave
                # learned caps carry over through mgr._wave_cap_hints
                mgr.node.faults.check("wave")
                while len(inflight) >= self._depth:
                    retries_total += self._drain_oldest(
                        inflight, wave_results, timeline, t_read0)
                oldest = inflight[0][1] if inflight else None
                t0 = time.perf_counter()
                with tracer.span("shuffle.wave",
                                 shuffle_id=self._handle.shuffle_id,
                                 wave=i, trace=rep.trace_id):
                    sliced, wnv = _slice_slot_outputs(
                        self._slot_outputs, i * self._wave_rows,
                        (i + 1) * self._wave_rows)
                    shard_rows, buf = mgr._pack_shards(
                        sliced, self._wave_plan.cap_in, self._width,
                        self._has_vals, tenant=self._handle.tenant)
                    t1 = time.perf_counter()
                    if i == self._num_waves - 1:
                        # last pack done: writer memory is no longer
                        # walked — close the guard window before the
                        # drains so a concurrent remesh need not park
                        # the writers for the pipeline tail
                        self._finish_guard()
                    if not rep._t_dispatched:
                        rep._t_dispatched = t1
                    try:
                        pending = self._dispatch_wave(shard_rows, wnv,
                                                      buf, i)
                    except BaseException:
                        # no pending exists: the pinned block has no
                        # owner yet (same rule as the single-shot path)
                        mgr.node.pool.put(buf)
                        raise
                t2 = time.perf_counter()
                # MEASURED overlap, not structural: a pack counts as
                # hidden only when the oldest in-flight collective is
                # provably still running AFTER the pack finished — a
                # pack-bound pipeline whose collectives finish mid-pack
                # must not report itself hidden (that is the
                # pipeline_stall condition). Partial overlap counts as
                # not hidden, so the hidden fraction is a lower bound.
                # The STAGE-LOCAL poll, not done(): a tiered pending's
                # done() is deliberately False until its DCN hop runs
                # (dispatched inside result()), and the device idling
                # between its stages must not read as overlap.
                hidden = oldest is not None \
                    and not oldest._outputs_ready()
                pack_ms = (t1 - t0) * 1e3
                pack_total += pack_ms
                if hidden:
                    pack_hidden += pack_ms
                dispatch_total += (t2 - t1) * 1e3
                timeline.append({
                    "wave": i, "rows": int(wnv.sum()),
                    "pack_start_ms": round((t0 - t_read0) * 1e3, 3),
                    "pack_ms": round(pack_ms, 3),
                    "dispatch_ms": round((t2 - t1) * 1e3, 3),
                    "hidden": hidden,
                    "forced_ms": 0.0, "wait_ms": 0.0, "retries": 0})
                inflight.append((i, pending))
            while inflight:
                retries_total += self._drain_oldest(
                    inflight, wave_results, timeline, t_read0)
        except BaseException:
            # settle every in-flight wave before propagating: their
            # exactly-once on_done returns the pinned blocks, and a
            # distributed peer must not be left mid-collective with
            # this process gone quiet
            while inflight:
                _, p = inflight.popleft()
                try:
                    p.result()
                except Exception:
                    pass
                tw = getattr(p, "tier_walls", None)
                if tw:
                    # partial walls are postmortem evidence: the tier
                    # that burned the wall is the tier that hung
                    for tier, ms in tw.items():
                        self._tier_walls[tier] = \
                            self._tier_walls.get(tier, 0.0) + ms
            raise
        finally:
            self._finish_guard()
        if self._outer_plan.sink == "device":
            # per-wave device views chained into the consumer: unwrap
            # each wave's single-view device result into ONE outer
            # device result whose consume() folds wave order. The
            # admission reservation (HBM residency: every undrained
            # wave's receive buffer) rides the outer result and releases
            # at consume()/close().
            from sparkucx_tpu.shuffle.reader import (
                DeviceShuffleReaderResult, device_merge_fold)
            views = [w.wave_views()[0] for w in wave_results]
            res = DeviceShuffleReaderResult(
                views, self._outer_plan, self._val_tail, self._val_dtype)
            if (self._outer_plan.combine or self._outer_plan.ordered) \
                    and len(views) > 1:
                # ordered/combine: the W per-wave key-sorted/combined
                # runs fold through the compiled device merge (the
                # inner result's own consume chain — every wave buffer
                # donated into the merge program), landing the consumer
                # ONE fully merged device view. Zero payload D2H; the
                # merge programs count into this read's step-cache
                # delta (finalized below), so the warm-recompile gate
                # covers them too.
                import jax as _jax
                t_merge = time.perf_counter()
                merged = device_merge_fold(res, mgr.exchange_mesh,
                                           mgr.axis, mgr.conf,
                                           metrics=mgr.node.metrics)
                # block for an honest merge wall: the wave collectives
                # already completed (each wave's overflow verdict forced
                # them), so this window is the merge programs alone
                _jax.block_until_ready(merged._rows_dev)
                rep.merge_ms = (time.perf_counter() - t_merge) * 1e3
                res = DeviceShuffleReaderResult(
                    [merged], self._outer_plan, self._val_tail,
                    self._val_dtype)
                mgr._arm_d2h(res, rep)
            res._release_hbm = self._release_admitted
        else:
            self._release_admitted()
            res = WavedShuffleReaderResult(wave_results, self._outer_plan,
                                           self._val_tail, self._val_dtype)
        self._finalize(res, timeline, retries_total, pack_total,
                       pack_hidden, dispatch_total)
        # integrity.verify=full: the host-drained wave blocks verify
        # AFTER the collective completes, against the senders' published
        # per-partition digest sums (accumulated across all waves — the
        # digests are order- and wave-split-invariant by construction).
        # Raises typed through result(), where the replay policy can
        # absorb it; async waved consumers get the same check.
        mgr._verify_full_result(self._handle, res,
                                self._outer_plan.combine)
        return res

    def _dispatch_wave(self, shard_rows: np.ndarray, wnv: np.ndarray,
                       buf, wave_i: int):
        mgr = self._mgr
        pool = mgr.node.pool
        # per-wave int8 noise base: the exchange seq spaces reads, the
        # wave index spaces waves within one — every wave of every read
        # draws a distinct stream, identically on every process
        wseed = (self._rep._seq * 100_003 + wave_i) & 0x7FFFFFFF

        def on_done(result, _b=buf):
            # per-wave exactly-once release: the pool's free list hands
            # this block to the NEXT wave's pack — the recycled-block
            # discipline that bounds pinned staging at `depth` blocks
            pool.put(_b)

        if self._distributed:
            if mgr.hierarchical and self._wave_plan.impl != "pallas":
                # distributed hierarchical waves dispatch the SAME
                # per-tier split programs as single-shot multi-process
                # reads — per-wave (ICI, DCN) deadlines and walls, with
                # overflow/regrow verdicts agreed per wave
                from sparkucx_tpu.shuffle.distributed import \
                    submit_shuffle_tiered_distributed
                return submit_shuffle_tiered_distributed(
                    mgr.node.mesh, mgr.topology, self._wave_plan,
                    shard_rows, wnv, self._shard_ids, self._val_tail,
                    self._val_dtype, on_done=on_done, wire_seed=wseed,
                    hooks=mgr._tier_hooks(self._rep.trace_id))
            from sparkucx_tpu.shuffle.distributed import \
                submit_shuffle_distributed
            return submit_shuffle_distributed(
                mgr.exchange_mesh, mgr.axis, self._wave_plan, shard_rows,
                wnv, self._shard_ids, self._val_tail, self._val_dtype,
                on_done=on_done, wire_seed=wseed)
        if mgr.hierarchical and self._wave_plan.impl != "pallas":
            # hierarchical waves ride the tiered two-step path: every
            # wave is its own (ICI, DCN) pair with per-tier deadlines
            # and walls — _drain_oldest folds them into the per-wave
            # tier timeline
            from sparkucx_tpu.shuffle.topology import \
                submit_shuffle_tiered
            return submit_shuffle_tiered(
                mgr.node.mesh, mgr.topology, self._wave_plan,
                shard_rows, wnv, self._val_tail, self._val_dtype,
                on_done=on_done, wire_seed=wseed,
                hooks=mgr._tier_hooks(self._rep.trace_id))
        return submit_shuffle(
            mgr.exchange_mesh, mgr.axis, self._wave_plan, shard_rows,
            wnv, self._val_tail, self._val_dtype, on_done=on_done,
            wire_seed=wseed)

    def _drain_oldest(self, inflight, wave_results, timeline,
                      t_read0: float) -> int:
        """Force the oldest in-flight wave: block on its result (the
        per-wave overflow retry loop lives inside), pull its receive
        buffers host-side NOW (freeing HBM for the waves behind it), and
        record the wait. Returns the wave's retry count."""
        i, pending = inflight.popleft()
        t0 = time.perf_counter()
        res = pending.result()
        wait_ms = (time.perf_counter() - t0) * 1e3
        self._last_step = getattr(pending, "_step", None)
        # charge this wave's d2h to the read's report (zero on the
        # device sink unless host_view later forces a drain)
        self._mgr._arm_d2h(res, self._rep)
        if self._outer_plan.sink != "device":
            drain_wave_result(res)
        # device sink: the wave stays HBM-resident — no D2H drain; the
        # consumer folds the per-wave views after result()
        if self._outer_plan.sink != "device" \
                and self._wave_plan.wire == "lossless" \
                and hasattr(res, "compress_host_blocks"):
            # the lossless tier's home: the wave is host-bound NOW and
            # may wait behind depth-1 others — re-encode its blocks
            # (byte-plane + deflate) through the pack executor, and
            # record ACHIEVED bytes for the report. Distributed wave
            # results are already host-resident partial views with no
            # block store — they pass through untouched.
            try:
                ex = self._mgr._pack_executor_if_parallel()
                raw_b, comp_b = res.compress_host_blocks(ex)
                self._lossless[0] += raw_b
                self._lossless[1] += comp_b
            except Exception:
                log.debug("lossless drain codec failed; wave kept raw",
                          exc_info=True)
        entry = timeline[i]
        entry["forced_ms"] = round((t0 - t_read0) * 1e3, 3)
        entry["wait_ms"] = round(wait_ms, 3)
        retries = int(getattr(pending, "_attempt", 0))
        entry["retries"] = retries
        tw = getattr(pending, "tier_walls", None)
        if tw:
            # per-wave tier timeline (hierarchical waves): this wave's
            # measured ICI vs DCN walls, plus the exchange-level sums
            # the finalize settles onto ExchangeReport.tiers
            for tier, ms in tw.items():
                entry[f"{tier}_ms"] = round(ms, 3)
                self._tier_walls[tier] = \
                    self._tier_walls.get(tier, 0.0) + ms
        wave_results[i] = res
        used = getattr(res, "cap_out_used", None)
        if used and int(used) > self._wave_plan.cap_out:
            # this wave overflowed and grew: later waves start at the
            # capacity that worked — ONE regrow per exchange, not one
            # per wave (and only the offending wave ever re-ran)
            self._wave_plan = dataclasses.replace(
                self._wave_plan, cap_out=int(used))
        return retries

    def _finalize(self, res, timeline, retries_total: int,
                  pack_total: float, pack_hidden: float,
                  dispatch_total: float) -> None:
        mgr = self._mgr
        rep = self._rep
        rep.pack_ms = pack_total
        rep.dispatch_ms = dispatch_total
        rep.wave_pack_hidden_ms = round(pack_hidden, 3)
        rep.wave_timeline = timeline
        rep.retries = retries_total
        if rep._t_dispatched:
            rep.group_ms = (time.perf_counter()
                            - rep._t_dispatched) * 1e3
        rep.stepcache_hits = int(
            GLOBAL_METRICS.get(COMPILE_HITS) - rep._hits0)
        rep.stepcache_programs = int(
            GLOBAL_METRICS.get(COMPILE_PROGRAMS) - rep._prog0)
        if self._wave_sizes is not None:
            # settle the wire accounting under the FINAL wave plan (an
            # overflow regrow mid-pipeline raised cap_out for the waves
            # behind it; charging every wave the settled capacity is the
            # steady-state cost later same-shape exchanges pay)
            mgr._set_wave_wire(rep, self._wave_plan, self._wave_sizes,
                               self._width)
            if rep.tiers:
                # hierarchical waves: re-derive the per-tier pairs under
                # the final wave plan, then settle the summed per-wave
                # tier walls + the tier byte counters
                mgr._stamp_wave_tiers(rep, self._wave_plan,
                                      self._wave_sizes, self._width)
        if rep.tiers:
            mgr._settle_tiers(rep, self._tier_walls, self._width)
        if self._lossless[1]:
            # measured (achieved) host-plane compression of the drained
            # waves, vs the REAL payload — the lossless tier's figure
            rep.lossless_bytes = int(self._lossless[1])
            rep.lossless_ratio = round(
                self._lossless[1] / rep.payload_bytes, 6) \
                if rep.payload_bytes else 0.0
        mgr._finish_device_plane(rep, self._last_step, self._width,
                                 completed=True)
        rep.completed = True
        mgr._settle_anatomy(rep, completed=True)
        mgr.node.flight.end_trace(rep.trace_id)
        metrics = mgr.node.metrics
        metrics.inc("shuffle.rows", float(self._local_rows))
        metrics.inc("shuffle.bytes",
                    float(self._local_rows) * self._width * 4)
        if rep.payload_bytes:
            # LOCAL shares, like shuffle.rows/bytes above: counters sum
            # across processes in build_view, so the cluster total must
            # reconstruct the global payload/wire exactly once
            frac = len(mgr.node.local_shard_ids) \
                / max(mgr.node.num_devices, 1)
            mgr._inc_volume(rep.tenant,
                            float(self._local_rows) * self._width * 4,
                            float(rep.wire_bytes) * frac)
        if retries_total:
            metrics.inc("shuffle.retries", float(retries_total))
        # wave wait-gap distribution: pack time NOT covered by the
        # previous wave's collective — sustained positive gaps mean the
        # device idles on the host pack (doctor: pipeline_stall)
        for k in range(1, len(timeline)):
            metrics.observe(H_WAVE_GAP, max(
                0.0, timeline[k]["pack_ms"] - timeline[k - 1]["wait_ms"]))
        with mgr._lock:
            key = (mgr._cap_key(self._handle), self._wave_plan.cap_in)
            if self._wave_plan.cap_out > mgr._wave_cap_hints.get(key, 0):
                mgr._wave_cap_hints[key] = self._wave_plan.cap_out
