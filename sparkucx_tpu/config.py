"""Config system — the ``spark.shuffle.tpu.*`` key surface.

TPU-native analog of the reference's ``UcxShuffleConf``
(ref: src/main/scala/org/apache/spark/shuffle/UcxShuffleConf.scala:17-90),
which extends SparkConf with the ``spark.shuffle.ucx.*`` namespace. We keep
the same *shape* of surface — a typed view over a flat string key/value map,
byte-size parsing, warm-up maps — but the keys describe TPU resources
(host staging arenas, mesh axes, collective implementation) instead of UCX
registration parameters.

Key table (reference key -> ours):

    spark.shuffle.ucx.driver.host/port      -> spark.shuffle.tpu.coordinator.address
                                               (jax.distributed rendezvous)
    spark.shuffle.ucx.rkeySize (x2 = 300B)  -> (no key: the segment-table slot
                                               size is derived, meta/segments.py
                                               record_size(num_partitions))
    spark.shuffle.ucx.rpc.metadata.bufferSize -> spark.shuffle.tpu.meta.bufferSize
    spark.shuffle.ucx.memory.preAllocateBuffers -> spark.shuffle.tpu.memory.preAllocateBuffers
    spark.shuffle.ucx.memory.minBufferSize  -> spark.shuffle.tpu.memory.minBufferSize
    spark.shuffle.ucx.memory.minAllocationSize -> spark.shuffle.tpu.memory.minAllocationSize
    spark.shuffle.ucx.memory.useOdp         -> spark.shuffle.tpu.memory.pinned
    (new, TPU-only)                            spark.shuffle.tpu.mesh.*, .a2a.impl,
                                               .a2a.capacityFactor, .dcn.*
"""

from __future__ import annotations

import os
import re
from typing import Dict, Iterator, Mapping, Optional, Tuple

_SIZE_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*([kKmMgGtT]?)i?[bB]?\s*$")
_SIZE_MULT = {"": 1, "k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def parse_bytes(text: str | int) -> int:
    """Parse '4m', '1k', '300', '2GiB' into a byte count.

    Mirrors SparkConf.getSizeAsBytes used throughout the reference conf
    (ref: UcxShuffleConf.scala:36-49)."""
    if isinstance(text, int):
        return text
    m = _SIZE_RE.match(str(text))
    if not m:
        raise ValueError(f"cannot parse byte size: {text!r}")
    value, unit = m.groups()
    return int(float(value) * _SIZE_MULT[unit.lower()])


PREFIX = "spark.shuffle.tpu."


def _norm(key: str) -> str:
    """Case/punctuation-insensitive key form, so SPARKUCX_TPU_MIN_BUFFER_SIZE,
    'memory.minBufferSize' and 'memory.minbuffersize' all collide."""
    return key.lower().replace(".", "").replace("_", "")


class TpuShuffleConf:
    """Typed view over a flat ``spark.shuffle.tpu.*`` key/value map.

    Construction accepts any mapping (e.g. a SparkConf dump, a dict of CLI
    overrides) plus ``SPARKUCX_TPU_*`` environment variables
    (``SPARKUCX_TPU_A2A_IMPL=dense`` -> ``spark.shuffle.tpu.a2a.impl=dense``).
    """

    def __init__(self, conf: Optional[Mapping[str, str]] = None, use_env: bool = True):
        self._conf: Dict[str, str] = {}
        self._index: Dict[str, str] = {}  # _norm(key) -> key, explicit conf wins
        if conf:
            for k, v in conf.items():
                self._conf[str(k)] = str(v)
                self._index[_norm(str(k))] = str(k)
        if use_env:
            for k, v in os.environ.items():
                if k.startswith("SPARKUCX_TPU_"):
                    key = PREFIX + k[len("SPARKUCX_TPU_"):].lower().replace("_", ".")
                    if _norm(key) not in self._index:
                        self._conf[key] = v
                        self._index[_norm(key)] = key
        self.validate()

    # All typed properties below, by name — validate() touches each so a
    # malformed VALUE fails at construction, not deep inside a shuffle.
    _TYPED_PROPS = (
        "coordinator_address", "meta_buffer_size", "min_buffer_size",
        "min_allocation_size", "pre_allocate_buffers", "pinned_memory",
        "spill_threshold", "spill_dir", "a2a_impl", "a2a_wire",
        "a2a_topology",
        "read_sink", "read_merge_impl", "wire_error_sample_rows",
        "sort_impl",
        "sort_strips", "combine_compaction", "fetch_granularity",
        "capacity_factor", "cap_buckets", "cap_bucket_growth",
        "wave_rows", "wave_depth", "pack_threads",
        "max_bytes_in_flight", "compile_cache_enabled",
        "compile_min_compile_time_secs",
        "mesh_ici_axis", "mesh_dcn_axis", "num_slices", "num_processes",
        "cores_per_process", "connection_timeout_ms",
        "collective_timeout_ms", "ici_timeout_ms", "dcn_timeout_ms",
        "replay_agree_timeout_ms",
        "failure_policy", "replay_budget",
        "max_backoff_ms", "integrity_verify", "ledger_dir")
    # Namespace keys consumed OUTSIDE config.py (grep-verified), plus the
    # prefix families. A spark.shuffle.tpu.* key matching none of these is
    # a probable typo and gets a warning (not an error: a host engine may
    # legitimately pass a newer/older key surface through — the reference
    # rides inside SparkConf, which never rejects keys).
    # ONE hand-maintained structure: keys (with their short descriptions)
    # consumed outside config.py; their full docs live at the use sites.
    # _EXTERNAL_KEYS and _KEY_FAMILIES derive from it, so adding a key
    # here both silences the unknown-key warning AND lists it in the
    # self-describing table — no second copy to drift.
    _EXTERNAL_KEY_DOCS = {
        "a2a.hierarchical": "LEGACY boolean: false forces the flat "
                            "exchange under a2a.topology=auto (prefer "
                            "a2a.topology; shuffle/topology.py)",
        "io.format": "shuffle payload codec: raw | arrow | varlen "
                     "(service.py connect)",
        "io.keyColumn": "arrow format: which column is the shuffle key "
                        "(io/arrow.py)",
        "io.stringMaxBytes": "varlen format: per-string byte cap "
                             "(io/varlen.py)",
        "compat.version": "host-adapter contract: v1 | v2 "
                          "(compat/__init__.resolve_adapter)",
        "trace.enabled": "turn on the span tracer's ring (utils/trace.py)",
        "trace.capacity": "tracer ring-buffer size",
        "metrics.reportCapacity": "ExchangeReport ring size per manager "
                                  "(default 64; eviction is tenant-"
                                  "aware — shuffle/manager.py)",
        "tenant.*": "multi-tenant service plane (shuffle/tenancy.py): "
                    "tenant.id (this process's default tenant), "
                    "tenant.priority (high|normal|batch), "
                    "tenant.fairShare (DRR admission on/off), "
                    "tenant.asyncWorkers, tenant.asyncAgreedOrder "
                    "(distributed K-worker async rides the agreement "
                    "channel; false clamps to 1 worker), and per-tenant "
                    "overrides "
                    "tenant.<id>.priority/.maxBytesInFlight/"
                    ".maxInflightReads/.replayBudget/.integrity.verify/"
                    ".waveDepth",
        "metrics.dumpDir": "periodic JSON metrics-snapshot dumps land "
                           "here (off when unset; utils/export.py)",
        "metrics.dumpIntervalSecs": "seconds between periodic metrics "
                                    "dumps (default 60)",
        "metrics.httpPort": "live telemetry server (utils/live.py): "
                            "unset = off, 0 = auto-assign, else that "
                            "port — serves /metrics /snapshot /doctor "
                            "/healthz",
        "metrics.httpHost": "live telemetry server bind host (default "
                            "127.0.0.1 — loopback unless opted out)",
        "metrics.httpAdvertiseHost": "host the fleet registry PUBLISHES "
                                     "for peers to scrape (utils/"
                                     "collector.py; default: the bind "
                                     "host — warn-once when that is "
                                     "loopback in a multi-process "
                                     "world)",
        "fleet.scrapeTimeoutMs": "per-peer deadline of a fleet "
                                 "telemetry scrape (utils/collector.py "
                                 "ClusterCollector; default 2000) — a "
                                 "dead peer costs one bounded timeout, "
                                 "never a hang",
        "devmon.enabled": "device memory sampler (runtime/devmon.py): "
                          "HBM + pool watermark gauges on a cadence "
                          "(default off, null-object)",
        "devmon.intervalMs": "devmon sampling interval in ms (default "
                             "1000)",
        "doctor.watchIntervalSecs": "anomaly watcher: run the doctor "
                                    "over live telemetry every N secs; "
                                    "first critical finding triggers a "
                                    "deep capture (default 0 = off)",
        "doctor.captureMs": "profiler window length of a watcher deep "
                            "capture (default 200 ms)",
        "doctor.captureDir": "where watcher captures land (default: "
                             "the flight recorder dir)",
        "doctor.rearmHealthyPasses": "watcher re-arm: a captured "
                                     "finding key absent for N "
                                     "consecutive passes captures "
                                     "again on recurrence (default 3)",
        "history.dir": "windowed telemetry history JSONL directory "
                       "(utils/history.py; unset = in-memory ring "
                       "only) — restart-durable, bounded to "
                       "retainWindows lines",
        "history.windowSecs": "history window length in seconds "
                              "(default 60); rolled on the periodic-"
                              "dumper cadence, no extra thread",
        "history.retainWindows": "history retention, in windows, for "
                                 "both the ring and the on-disk log "
                                 "(default 120)",
        "decisions.enabled": "decision ledger (shuffle/decisions.py): "
                             "append every agree() round — winner/"
                             "proposal digests, round wall ms, per-"
                             "peer header lag — to a bounded ring "
                             "plus (when history.dir is set) a rank-"
                             "keyed decisions_p<rank>.jsonl (default "
                             "on; off = null-object, zero overhead)",
        "decisions.retain": "decision-ledger retention, in records, "
                            "for both the ring and the on-disk log "
                            "(default 256)",
        "slo.*": "service-level objectives (utils/slo.py): "
                 "slo.read.p99Ms (latency bound, ms), slo.read.target "
                 "(good fraction, default 0.99), slo.availability, "
                 "slo.fastWindowSecs/slowWindowSecs (default 300/3600), "
                 "slo.fastBurn/slowBurn (default 14.4/6), "
                 "slo.minEvents; per-tenant overrides ride "
                 "tenant.<id>.slo.* — evaluated over the retained "
                 "history windows into error budgets + burn rates, "
                 "surfaced via service.slo(), /slo, the slo CLI, "
                 "doctor rule slo_burn, and a fast burn degrades "
                 "/healthz",
        "compile.costCapture": "harvest XLA cost/memory analysis per "
                               "compiled exchange program "
                               "(shuffle/stepcache.py; default on)",
        "flightRecorder.enabled": "crash flight recorder: ring of recent "
                                  "telemetry events + postmortem JSON on "
                                  "retry exhaustion / DeviceUnhealthy / "
                                  "abort (runtime/failures.py; implies "
                                  "trace.enabled)",
        "flightRecorder.dir": "where flight-recorder postmortems are "
                              "written (default: per-pid temp dir)",
        "flightRecorder.capacity": "flight-recorder event-ring size "
                                   "(default 512)",
        "failure.maxAttempts": "read-retry budget after device loss "
                               "(runtime/failures.py)",
        "failure.backoffMs": "backoff between failure-recovery attempts",
        "fault.*": "deterministic fault injection: fault.seed + per-site "
                   "arming keys (runtime/failures.FaultInjector)",
        "workload.*": "analytics workload plane (workloads/ registry, "
                      "`python -m sparkucx_tpu workload <name>`): "
                      "workload.budgetMb (pinned-pool memory budget; "
                      "the dataset is 10 x budget x scale bytes), "
                      "workload.scale — consumed by "
                      "workloads.run_workload, which derives "
                      "spill.threshold + a2a.waveRows from the budget",
    }
    _EXTERNAL_KEYS = tuple(k for k in _EXTERNAL_KEY_DOCS
                           if not k.endswith("*"))
    _KEY_FAMILIES = tuple(k[:-1] for k in _EXTERNAL_KEY_DOCS
                          if k.endswith("*"))  # "fault.*" -> "fault."

    def validate(self) -> None:
        """Fail fast on malformed values; warn on unknown namespace keys.

        The reference defers every parse to first use (UcxShuffleConf is
        lazy SparkConf sugar), which surfaces a typo'd size string only
        mid-shuffle; here construction is the checkpoint."""
        # touching every typed property both validates its value and, via
        # the _seen_shorts hook in _get, collects the property-owned key
        # names — no hand-maintained duplicate of the key surface
        self._seen_shorts: set = set()
        for name in self._TYPED_PROPS:
            try:
                getattr(self, name)
            except ValueError as e:
                raise ValueError(f"conf key for {name!r}: {e}") from e
        known = {_norm(PREFIX + s)
                 for s in set(self._EXTERNAL_KEYS) | self._seen_shorts}
        self._seen_shorts = None
        for key in self._conf:
            if not key.startswith(PREFIX):
                continue
            short = key[len(PREFIX):]
            if any(short.startswith(f) for f in self._KEY_FAMILIES):
                continue
            if _norm(key) not in known:
                from sparkucx_tpu.utils.logging import get_logger
                get_logger("config").warning(
                    "unknown conf key %s (typo? known short keys: see "
                    "TpuShuffleConf docstring)", key)

    @classmethod
    def describe_keys(cls):
        """One row per conf key — {key, default, property, doc} —
        generated from the LIVE property surface (the same _get hook
        validate() uses), so the table cannot drift from the code. The
        reference self-describes its key surface the same way, through
        ConfigBuilder doc strings (ref: UcxShuffleConf.scala:25-89)."""
        conf = cls({}, use_env=False)
        rows = []
        for name in cls._TYPED_PROPS:
            captured = []
            real_get = conf._get

            def capture(short, default, _c=captured, _g=real_get):
                _c.append((short, default))
                return _g(short, default)

            conf.__dict__["_get"] = capture
            try:
                getattr(conf, name)
            except Exception:
                pass
            finally:
                del conf.__dict__["_get"]
            doc = (getattr(cls, name).__doc__ or "").strip()
            doc = " ".join(doc.split("\n\n")[0].split())
            for short, default in captured:
                rows.append({"key": PREFIX + short,
                             "default": str(default),
                             "property": name,
                             "doc": doc})
        for short, doc in cls._EXTERNAL_KEY_DOCS.items():
            rows.append({"key": PREFIX + short, "default": "",
                         "property": "", "doc": doc})
        return rows

    # -- raw access -------------------------------------------------------
    def _lookup(self, key: str):
        """Exact spelling first; else the case/punctuation-insensitive
        index — ONE equivalence rule shared by get(), __contains__ and
        the typed _get(), so full-key and short-key reads cannot
        disagree on what counts as the same key. Returns the value or
        None."""
        if key in self._conf:
            return self._conf[key]
        hit = self._index.get(_norm(key))
        if hit is not None:
            return self._conf[hit]
        return None

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        # spelling-insensitive: a conf written under an alternate
        # spelling must still be FOUND by canonical-key readers (set()
        # already writes through the index; e.g. 'compat.Version: v2'
        # must not silently select the default adapter)
        got = self._lookup(key)
        return default if got is None else got

    def set(self, key: str, value) -> "TpuShuffleConf":
        # Case/punctuation-insensitive: writing through any spelling updates
        # the canonical entry rather than shadowing it.
        canonical = self._index.get(_norm(key), key)
        self._conf[canonical] = str(value)
        self._index[_norm(key)] = canonical
        return self

    def __contains__(self, key: str) -> bool:
        return self._lookup(key) is not None

    def items(self) -> Iterator[Tuple[str, str]]:
        return iter(sorted(self._conf.items()))

    # -- typed getters ----------------------------------------------------
    def _get(self, short: str, default) -> str:
        if getattr(self, "_seen_shorts", None) is not None:
            self._seen_shorts.add(short)   # validate() key-surface census
        got = self._lookup(PREFIX + short)
        return str(default) if got is None else got

    def get_int(self, short: str, default: int) -> int:
        return int(self._get(short, default))

    def get_float(self, short: str, default: float) -> float:
        return float(self._get(short, default))

    def get_bool(self, short: str, default: bool) -> bool:
        v = str(self._get(short, default)).strip().lower()
        if v in ("1", "true", "yes", "on"):
            return True
        if v in ("0", "false", "no", "off"):
            return False
        # 'ture' silently meaning False would disable e.g. pinned arenas
        # with no trace — exactly the mid-run surprise validate() exists
        # to prevent
        raise ValueError(
            f"conf key {PREFIX}{short}={v!r} is not a boolean "
            f"(want true/false/1/0/yes/no/on/off)")

    def get_bytes(self, short: str, default) -> int:
        return parse_bytes(self._get(short, default))

    # -- the key surface --------------------------------------------------
    @property
    def coordinator_address(self) -> str:
        """Rendezvous address for jax.distributed / multi-host bootstrap.

        Analog of the driver sockaddr the reference listens on
        (ref: UcxShuffleConf.scala:25-28, UcxNode.java:98-104)."""
        return self._get("coordinator.address", "localhost:55443")

    @property
    def meta_buffer_size(self) -> int:
        """Upper bound on one metadata-plane message (the presence bitmap /
        schema blob a process allgathers in distributed mode). Oversized
        messages fail loudly before the collective instead of stalling it —
        the role the fixed 4 KB bootstrap buffer plays in the reference
        (ref: UcxShuffleConf.scala:42-49, UcxListenerThread.java:34-39).
        Enforced by TpuShuffleManager._submit_distributed; default 64k allows
        ~8000 map outputs per shuffle."""
        return self.get_bytes("meta.bufferSize", "64k")

    @property
    def min_buffer_size(self) -> int:
        """Size-class floor for the host arena
        (ref: UcxShuffleConf.scala:66-72, default 1k)."""
        return self.get_bytes("memory.minBufferSize", "1k")

    @property
    def min_allocation_size(self) -> int:
        """Minimum slab carved from the OS, shared by small size classes
        (ref: UcxShuffleConf.scala:74-81, default 4m)."""
        return self.get_bytes("memory.minAllocationSize", "4m")

    @property
    def pre_allocate_buffers(self) -> Dict[int, int]:
        """Warm-up map 'size:count,size:count' parsed to {bytes: count}
        (ref: UcxShuffleConf.scala:52-64, MemoryPool.java:170-177)."""
        spec = self._get("memory.preAllocateBuffers", "")
        out: Dict[int, int] = {}
        if spec:
            for part in spec.split(","):
                try:
                    size, count = part.split(":")
                    out[parse_bytes(size)] = int(count)
                except ValueError as e:
                    raise ValueError(
                        f"preAllocateBuffers entry {part!r} is not 'size:count'"
                    ) from e
        return out

    @property
    def pinned_memory(self) -> bool:
        """Whether host staging arenas should request pinned pages.

        Plays the role the ODP toggle plays for registration strategy
        (ref: UcxShuffleConf.scala:89)."""
        return self.get_bool("memory.pinned", True)

    @property
    def spill_threshold(self) -> int:
        """Staged bytes per map writer before batches spill to disk files
        (0 disables). The disk story of the reference — map outputs living
        in sort-shuffle ``data``+``index`` files served from page cache
        (ref: CommonUcxShuffleBlockResolver.scala:33-57) — becomes an
        overflow valve here: hot outputs stay in the pinned arena, big ones
        append to per-writer files and are mmapped back at read time, so
        staging RSS stays bounded by this threshold instead of the dataset
        size."""
        return self.get_bytes("spill.threshold", "256m")

    @property
    def spill_dir(self) -> str:
        """Directory for spilled map-output files (the executor local-dir
        analog). Default: a per-process dir under the system temp dir."""
        import tempfile
        return self._get(
            "spill.dir",
            os.path.join(tempfile.gettempdir(),
                         f"sparkucx_tpu_spill_{os.getpid()}"))

    # -- TPU-only keys ----------------------------------------------------
    @property
    def a2a_impl(self) -> str:
        """Collective implementation: auto | native | dense | gather |
        pallas. ``auto`` is ragged-first: it resolves to ``native``
        (jax.lax.ragged_all_to_all — true per-peer row counts on the
        wire) wherever the backend carries the op, with automatic dense
        fallback elsewhere (alltoall.backend_supports_ragged is the
        capability gate). dense = padded all_to_all (portable); gather =
        all_gather oracle (tests/tiny tables); pallas = the first-party
        remote-DMA transport (ops/pallas/ragged_a2a.py, dispatched by
        shuffle/reader._pallas_step_body). The allowed set lives in ONE
        place — shuffle/alltoall.ALLOWED_IMPLS — shared with
        select_impl, so conf validation and the dispatch can't drift."""
        from sparkucx_tpu.shuffle.alltoall import validate_impl
        return validate_impl(self._get("a2a.impl", "auto"),
                             conf_key=PREFIX + "a2a.impl")

    @property
    def a2a_wire(self) -> str:
        """Wire-compression tier, ORTHOGONAL to ``a2a.impl``: raw (exact
        int32 lanes — the default), int8 (float32 value lanes ride as
        stochastically-rounded int8 + one f32 scale per row inside the
        compiled step; keys/partition/size lanes stay exact; ~0.3x the
        raw wire bytes at wide value rows), or lossless (bit-exact
        byte-plane+deflate re-encoding of host-staged blocks on the wave
        drain path). int8 needs a float32 value schema and a real wire
        move — ineligible reads fall back to raw and the ExchangeReport
        says so. The allowed set lives in ONE place —
        shuffle/alltoall.ALLOWED_WIRES — like the impl set."""
        from sparkucx_tpu.shuffle.alltoall import validate_wire
        return validate_wire(self._get("a2a.wire", "raw"),
                             conf_key=PREFIX + "a2a.wire")

    @property
    def a2a_topology(self) -> str:
        """Exchange topology: ``flat`` (one collective over every
        device — the single-slice contract), ``hier`` (the two-stage
        ICI-then-DCN decomposition, shuffle/topology.py — each row
        crosses the slow inter-slice fabric exactly once; requires a
        2-D ``(dcn, ici)`` mesh with >1 slice), or ``auto`` (default —
        slice detection from the mesh: hier exactly when the mesh is
        2-D with more than one slice). The legacy boolean
        ``a2a.hierarchical=false`` still forces flat under ``auto``
        (shuffle/topology.resolve_topology honors it); the allowed set
        lives in ONE place — shuffle/alltoall.ALLOWED_TOPOLOGIES."""
        from sparkucx_tpu.shuffle.alltoall import validate_topology
        return validate_topology(self._get("a2a.topology", "auto"),
                                 conf_key=PREFIX + "a2a.topology")

    @property
    def read_sink(self) -> str:
        """Where a completed exchange LANDS: ``host`` (drain receive
        buffers D2H and serve numpy partition views — the historical
        contract, required by the arrow/varlen egress and the lossless
        drain codec), ``device`` (partitions stay sharded jax Arrays and
        the result hands them — donation-safe, zero D2H — straight to a
        jitted consumer step: reader.DeviceShuffleReaderResult.consume;
        the MoE expert-dispatch and groupby-aggregate paths), or
        ``auto`` (default — host unless the consumer declares a device
        sink per read, ``manager.read(..., sink="device")``). Legal for
        ALL FOUR read modes on the single-process flat exchange —
        ordered/combine land fully merged on device (the exchange
        step's in-step merge single-shot; reader.device_merge_fold for
        waved reads). The manager resolves the tier per read:
        distributed / hierarchical reads still need host-side
        materialization and fall back to host with a warn-once log AND
        a counted ``shuffle.sink.fallback.count`` (the doctor's
        sink_fallback evidence); the report's ``sink`` field names the
        tier that actually ran (the resolved-impl discipline). The
        allowed set lives in ONE place — shuffle/alltoall
        .ALLOWED_SINKS."""
        from sparkucx_tpu.shuffle.alltoall import validate_sink
        return validate_sink(self._get("read.sink", "auto"),
                             conf_key=PREFIX + "read.sink")

    @property
    def read_merge_impl(self) -> str:
        """How the ordered/combine fold path runs on device — the
        receive-side reduce in the exchange step and the cross-wave
        device merge (reader.device_merge_fold): ``auto`` (default —
        the blocked pallas kernels exactly where they compile natively,
        i.e. on a TPU backend, jnp everywhere else), ``jnp`` (the XLA
        sort-network formulation — the bit-exact oracle), or ``pallas``
        (the ops/pallas/segmented.py blocked merge-path merge / tiled
        segment-reduce kernels; a combine whose value dtype the kernel
        cannot accumulate, or a backend with no native-or-interpret
        path, falls back to jnp with a log line and a
        C_KERNEL_FALLBACK count — the doctor's kernel_fallback
        evidence). Resolution is segmented.resolve_kernel_impl; the
        allowed set lives in ONE place —
        shuffle/alltoall.ALLOWED_MERGE_IMPLS."""
        from sparkucx_tpu.shuffle.alltoall import validate_merge_impl
        return validate_merge_impl(self._get("read.mergeImpl", "auto"),
                                   conf_key=PREFIX + "read.mergeImpl")

    @property
    def wire_error_sample_rows(self) -> int:
        """Rows the manager samples per int8-wire exchange to estimate
        the dequantization error (relative RMS of a round-to-nearest
        int8 pass over staged float values) — feeds
        ``ExchangeReport.wire_dequant_error`` and the doctor's
        ``wire_dequant_error`` rule. 0 disables the estimate."""
        v = self.get_int("a2a.wireErrorSampleRows", 256)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.wireErrorSampleRows={v}: want "
                f">= 0 (0 = off)")
        return v

    @property
    def sort_impl(self) -> str:
        """Destination-sort formulation for the exchange hot path:
        auto | argsort | multisort | multisort8 | counting
        (ops/partition.py)."""
        v = self._get("a2a.sortImpl", "auto")
        from sparkucx_tpu.ops.partition import SORT_METHODS
        if v not in SORT_METHODS:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.sortImpl={v!r}: want one of "
                f"{SORT_METHODS}")
        return v

    @property
    def sort_strips(self):
        """Single-shard plain exchanges: destination-sort in this many
        independent strips (one batched sort network — depth
        ~log^2(cap/strips) instead of ~log^2(cap)), served as virtual
        senders by the reader's run index. 1 = one flat sort; 'auto' =
        the backend's measured default, resolved at plan time
        (ops/partition.destination_sort_strips,
        shuffle/plan.default_sort_strips)."""
        raw = self._get("a2a.sortStrips", "auto")
        if raw == "auto":
            return "auto"
        from sparkucx_tpu.shuffle.plan import STRIPS_RANGE
        v = int(raw)
        if not STRIPS_RANGE[0] <= v <= STRIPS_RANGE[1]:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.sortStrips={v}: want "
                f"{STRIPS_RANGE[0]}..{STRIPS_RANGE[1]} or 'auto'")
        return v

    @property
    def fetch_granularity(self) -> str:
        """Lazy-result D2H granularity: ``shard`` (default — first touch
        of a shard pulls its whole receive buffer) or ``partition``
        (each fetch device-slices only that partition's runs — the
        reference's per-block fetch; right for slow D2H links or sparse
        partition reads)."""
        v = self._get("io.fetchGranularity", "shard")
        if v not in ("shard", "partition"):
            raise ValueError(
                f"spark.shuffle.tpu.io.fetchGranularity={v!r}: want "
                f"shard|partition")
        return v

    @property
    def combine_compaction(self) -> str:
        """combine_rows end-row compaction formulation: stable | unstable
        (ops/aggregate.py — bit-identical results, different sort cost).
        Only the ``perm`` form reads it: 3-word rows compact in one
        carried sort on explicit keys (ops/aggregate.combine_form)."""
        v = self._get("a2a.combineCompaction", "stable")
        if v not in ("stable", "unstable"):
            raise ValueError(
                f"spark.shuffle.tpu.a2a.combineCompaction={v!r}: want "
                f"stable|unstable")
        return v

    @property
    def capacity_factor(self) -> float:
        """Output-buffer headroom multiplier over perfectly-balanced size.

        The static-shape answer to ragged skew (SURVEY.md §7 hard part (a))."""
        return float(self._get("a2a.capacityFactor", 2.0))

    @property
    def cap_buckets(self) -> bool:
        """Plan-shape bucketing: quantize plan capacities UP onto a
        geometric ladder (shuffle/plan.bucket_cap) so drifting row counts
        across epochs land on a handful of compiled exchange programs
        instead of one per exact shape. Rounding is up-only — overflow
        semantics and results are unchanged (modulo trailing padding)."""
        return self.get_bool("a2a.capBuckets", True)

    @property
    def cap_bucket_growth(self) -> float:
        """Geometric growth factor of the capacity-bucket ladder
        (``a2a.capBuckets``): consecutive rungs differ by ~this ratio, so
        worst-case over-provisioning per buffer is bounded by it.
        Validated at construction like every typed key — a malformed
        value fails fast even while bucketing is off."""
        raw = float(self._get("a2a.capBucketGrowth", 1.25))
        from sparkucx_tpu.shuffle.plan import CAP_BUCKET_GROWTH_RANGE
        if not CAP_BUCKET_GROWTH_RANGE[0] <= raw \
                <= CAP_BUCKET_GROWTH_RANGE[1]:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.capBucketGrowth={raw}: want "
                f"{CAP_BUCKET_GROWTH_RANGE[0]}..{CAP_BUCKET_GROWTH_RANGE[1]}")
        return raw

    @property
    def compile_cache_enabled(self) -> bool:
        """Persistent XLA compile cache (jax_compilation_cache_dir): on
        by default so a fresh process's first exchange reuses programs
        compiled by ANY earlier process instead of re-paying minutes of
        XLA compile (runtime/compile_cache.py, wired in TpuNode init /
        service.connect)."""
        return self.get_bool("compile.cacheEnabled", True)

    @property
    def compile_min_compile_time_secs(self) -> float:
        """Only compiles at least this long are persisted
        (jax_persistent_cache_min_compile_time_secs): keeps trivial
        programs from churning the cache dir while the multi-minute
        exchange steps always qualify."""
        v = float(self._get("compile.minCompileTimeSecs", 1.0))
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.compile.minCompileTimeSecs={v}: "
                f"want >= 0")
        return v

    @property
    def wave_rows(self) -> int:
        """Wave-pipelined exchange: split the read into fixed-size waves
        of at most this many rows PER SHARD and run a software pipeline —
        pack wave i+1 on the host while wave i's collective is in flight
        and wave i-1 drains D2H. 0 (default) = single-shot (the whole
        shuffle is one pack + one program launch). Because wave shape is
        fixed, every wave of a shuffle hits ONE compiled program, pinned
        staging is bounded by ``a2a.waveDepth`` wave blocks instead of
        the full shuffle, and an overflow retry regrows and re-runs only
        the offending wave (shuffle/manager.py PendingWaveShuffle)."""
        v = self.get_int("a2a.waveRows", 0)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.waveRows={v}: want >= 0 (0 = off)")
        return v

    @property
    def wave_depth(self) -> int:
        """Wave pipeline depth: how many waves may be in flight at once
        (and how many recycled pinned pack blocks the pipeline holds).
        2 (default) is the classic depth-2 software pipeline — pack,
        collective, and drain each own a stage; 1 degenerates to
        serial per-wave execution (bounded memory, no overlap)."""
        from sparkucx_tpu.shuffle.plan import WAVE_DEPTH_RANGE
        v = self.get_int("a2a.waveDepth", 2)
        if not WAVE_DEPTH_RANGE[0] <= v <= WAVE_DEPTH_RANGE[1]:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.waveDepth={v}: want "
                f"{WAVE_DEPTH_RANGE[0]}..{WAVE_DEPTH_RANGE[1]}")
        return v

    @property
    def pack_threads(self) -> int:
        """Worker threads of the manager's persistent pack executor
        (``_pack_shards`` fan-out — numpy copies release the GIL, so the
        host-bound fuse parallelizes). 0 (default) = coresPerProcess.
        The doctor's ``pipeline_stall`` rule points here when wave packs
        run slower than the collective they should hide behind."""
        v = self.get_int("a2a.packThreads", 0)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.a2a.packThreads={v}: want >= 0 "
                f"(0 = coresPerProcess)")
        return v

    @property
    def max_bytes_in_flight(self) -> int:
        """Cap on the combined footprint (pinned pack buffers + estimated
        HBM send/receive buffers) of simultaneously in-flight submitted
        exchanges; 0 = unlimited. ``submit()`` blocks until enough earlier
        exchanges complete — the admission-control role Spark's
        ShuffleBlockFetcherIterator plays with maxBytesInFlight
        (ref: UcxShuffleReader.scala:56-70). A single exchange larger than
        the cap is always admitted alone (never deadlocks)."""
        return self.get_bytes("a2a.maxBytesInFlight", 0)

    @property
    def mesh_ici_axis(self) -> str:
        """Mesh axis name for the intra-slice (ICI) shuffle axis."""
        return self._get("mesh.iciAxis", "shuffle")

    @property
    def mesh_dcn_axis(self) -> str:
        """Mesh axis name for the cross-slice (DCN) axis of a
        multi-slice mesh."""
        return self._get("mesh.dcnAxis", "dcn")

    @property
    def num_slices(self) -> int:
        """Number of TPU slices (DCN-connected). 1 = single slice."""
        return self.get_int("mesh.numSlices", 1)

    @property
    def num_processes(self) -> int:
        """Processes in the cluster (ref: UcxShuffleConf.scala:20-21)."""
        return self.get_int("numProcesses", 1)

    @property
    def cores_per_process(self) -> int:
        """Expected concurrent map tasks per process. The manager warns when
        more writers are live at once — the analog of UcxNode warning when
        task threads exceed spark.executor.cores (ref: UcxNode.java:85-95,
        UcxShuffleConf.scala:22-23). Default: the host's CPU count."""
        return self.get_int("coresPerProcess", os.cpu_count() or 1)

    @property
    def connection_timeout_ms(self) -> int:
        """Peer/metadata wait timeout (ref: UcxWorkerWrapper.scala:133-140,
        spark.network.timeout)."""
        return self.get_int("network.timeoutMs", 120_000)

    @property
    def collective_timeout_ms(self) -> float:
        """Deadline on every distributed rendezvous and in-flight
        collective wait (runtime/watchdog.py): past it, the watchdog
        probes device liveness, dumps a flight postmortem and raises
        PeerLostError instead of hanging the survivors on a dead peer —
        the UCP_ERR_HANDLING_MODE_PEER analog (ref: UcxNode.java:134).
        0 (default) = off; also caps the retry plane's total backoff
        budget when set."""
        v = self.get_float("failure.collectiveTimeoutMs", 0.0)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.failure.collectiveTimeoutMs={v}: "
                f"want >= 0 (0 = off)")
        return v

    def _tier_timeout(self, tier: str) -> float:
        v = self.get_float(f"failure.{tier}.timeoutMs",
                           self.collective_timeout_ms)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.failure.{tier}.timeoutMs={v}: "
                f"want >= 0 (0 = off)")
        return v

    @property
    def ici_timeout_ms(self) -> float:
        """Per-tier deadline on the INTRA-slice (ICI) phase of a
        hierarchical exchange (shuffle/topology.py): past it the
        watchdog raises PeerLostError naming the ICI tier, so the
        flight postmortem attributes the hang to the slice fabric
        instead of the whole collective. Defaults to
        ``failure.collectiveTimeoutMs`` (0 = off)."""
        return self._tier_timeout("ici")

    @property
    def dcn_timeout_ms(self) -> float:
        """Per-tier deadline on the CROSS-slice (DCN) phase of a
        hierarchical exchange — the ``failure.ici.timeoutMs`` twin for
        the slow inter-slice fabric. A DCN expiry names the DCN tier in
        the typed error and the postmortem, which is what lets the
        doctor and the operator tell an ICI straggler from a DCN one.
        Defaults to ``failure.collectiveTimeoutMs`` (0 = off)."""
        return self._tier_timeout("dcn")

    @property
    def replay_agree_timeout_ms(self) -> float:
        """Deadline on the collective replay-entry round
        (``agree("replay.enter")``, shuffle/manager.py): survivors of a
        transient fault agree to re-enter the exchange together — but a
        peer whose read SUCCEEDED (or failed with a different error
        class) never enters the round, so the replaying processes would
        otherwise stall the full ``failure.collectiveTimeoutMs`` before
        PeerLostError converts the replay into failfast. Set this lower
        to bound that stall on partial-failure shapes. Defaults to
        ``failure.collectiveTimeoutMs`` (0 = off)."""
        v = self.get_float("failure.replayAgreeTimeoutMs",
                           self.collective_timeout_ms)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.failure.replayAgreeTimeoutMs={v}: "
                f"want >= 0 (0 = off)")
        return v

    @property
    def failure_policy(self) -> str:
        """What read()/submit() do when an exchange dies or a remesh
        invalidates its handle: ``failfast`` (default — typed errors
        surface to the caller; the host framework owns recovery, the
        reference's Spark-delegation posture) or ``replay`` — the
        manager keeps a recovery ledger across epoch bumps (shuffles
        whose local staged writer blocks are intact re-register under
        the new epoch) and transparently re-plans + re-runs the exchange
        on the surviving mesh, up to ``failure.replayBudget`` times (the
        FetchFailed -> stage-retry analog, in-framework)."""
        v = self._get("failure.policy", "failfast")
        if v not in ("failfast", "replay"):
            raise ValueError(
                f"spark.shuffle.tpu.failure.policy={v!r}: want "
                f"failfast|replay")
        return v

    @property
    def replay_budget(self) -> int:
        """Replays a shuffle may spend under ``failure.policy=replay``
        (stale-handle re-pins after a remesh plus transient-failure
        re-runs, cumulative per shuffle). Exhaustion falls back to
        failfast — the bounded-stage-retry analog of
        spark.stage.maxConsecutiveAttempts."""
        v = self.get_int("failure.replayBudget", 2)
        if v < 0:
            raise ValueError(
                f"spark.shuffle.tpu.failure.replayBudget={v}: want >= 0")
        return v

    @property
    def integrity_verify(self) -> str:
        """Block-integrity verification level (shuffle/integrity.py):
        ``off`` — no checksums anywhere (the reference's trust-the-
        transport posture); ``staged`` (default) — commit publishes
        per-map checksum records beside the size rows and the read path
        re-verifies the staged/spill bytes at pack time, before they
        enter the exchange (memory-bandwidth fold64); ``full`` — staged
        plus a post-collective check of the host-drained rows per
        reduce partition against order-independent row-digest sums
        (bit-equivalent for raw/lossless wires; the int8 tier verifies
        the exact key lanes, since dequantized values are legitimately
        lossy). A mismatch raises typed BlockCorruptionError
        (TransientError) — failure.policy=replay spends one budget unit
        re-verifying and re-running instead of returning silent wrong
        answers. Verification is entirely host-side: compiled-program
        count is identical at every level."""
        from sparkucx_tpu.shuffle.integrity import validate_verify_level
        return validate_verify_level(
            self._get("integrity.verify", "staged"),
            conf_key=PREFIX + "integrity.verify")

    @property
    def ledger_dir(self) -> str:
        """Disk-backed recovery ledger (empty = off): with a directory
        set, every map commit seals its staged output to
        ``<dir>/shuffle_<id>/`` (torn-write-proof: temp + fsync +
        atomic rename) and maintains a checksummed per-shuffle
        ``commit.manifest`` — the durable twin of the PR-7 in-memory
        replay ledger. A RESTARTED manager scanning the same directory
        validates manifests + file checksums, re-registers intact
        shuffles under the new epoch and serves their blocks with zero
        recompute (checksum-failing blocks are quarantined and only
        those maps re-stage) — the role Spark's external shuffle
        service plays for a dead executor's files. ``stop()`` keeps the
        ledger (that is the point); explicit unregister_shuffle deletes
        a shuffle's durable state."""
        return self._get("failure.ledgerDir", "")

    @property
    def max_backoff_ms(self) -> float:
        """Ceiling on any single retry backoff sleep (RetryPolicy's
        decorrelated-jitter schedule grows toward it). Keeps a long
        retry budget from degenerating into multi-minute sleeps."""
        v = self.get_float("failure.maxBackoffMs", 10_000.0)
        if v <= 0:
            raise ValueError(
                f"spark.shuffle.tpu.failure.maxBackoffMs={v}: want > 0")
        return v

    def __repr__(self) -> str:  # pragma: no cover
        return f"TpuShuffleConf({dict(self.items())})"


def _print_key_table() -> None:  # pragma: no cover - exercised via CLI
    rows = TpuShuffleConf.describe_keys()
    w = max(len(r["key"]) for r in rows)
    dw = max(len(r["default"]) for r in rows)
    print(f"{'key':<{w}}  {'default':<{dw}}  description")
    print("-" * (w + dw + 60))
    for r in rows:
        print(f"{r['key']:<{w}}  {r['default']:<{dw}}  {r['doc']}")

