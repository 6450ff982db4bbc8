"""Lightweight metrics: counters, histograms, timers.

The reference has targeted latency logging rather than a tracer: map-publish
overhead per mapId (ref: CommonUcxShuffleBlockResolver.scala:105-106),
per-request completion ms (ref: UcxWorkerWrapper.scala:101-103), per-endpoint
fetch bytes+ms (ref: OnBlocksFetchCallback.java:55-56), and fetch-wait time
fed into Spark's ShuffleReadMetricsReporter
(ref: compat/spark_3_0/UcxShuffleReader.scala:84-87). This module provides
the same spirit as in-process counters/timers that the manager/reader report
into, plus fixed log-bucket :class:`Histogram` metrics for the quantities
where a flat counter is lossy (fetch-wait per read, per-peer bytes, retry
latencies, compile seconds) — the p50/p99 half of the reference's per-fetch
latency log becomes a live queryable distribution instead of grep fodder.
"""

from __future__ import annotations

import contextlib
import math
import re
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple


# Compile-cost observability (shuffle/stepcache.py): ONE place for the
# counter names so the cache and the tests cannot drift on spelling.
COMPILE_PROGRAMS = "compile.step.programs"   # distinct step programs built
COMPILE_HITS = "compile.step.hits"           # step-cache lookups served
COMPILE_SECONDS = "compile.step.seconds"     # first-invocation wall secs

# Device-plane cost capture (shuffle/stepcache.py harvest of XLA
# cost_analysis/memory_analysis at compile time): cumulative totals over
# every program whose record captured — the byte-movement model arxiv
# 2112.01075 shows XLA exposes precisely enough to roofline an exchange.
COMPILE_PROG_CAPTURED = "compile.program.captured"   # programs w/ a record
COMPILE_PROG_FLOPS = "compile.program.flops"         # summed model flops
COMPILE_PROG_BYTES = "compile.program.bytes_accessed"
COMPILE_PROG_TEMP = "compile.program.temp_bytes"     # summed HBM scratch

# Histogram names — the telemetry plane's distribution metrics. Declared
# here (not at the observation sites) for the same no-spelling-drift
# reason as the compile counters; every registry pre-creates them so an
# exporter always has the full surface even before the first shuffle.
H_FETCH_WAIT = "shuffle.read.wait_ms"        # per-read fetch-wait (ms)
# Compile-bearing reads land HERE, not in H_FETCH_WAIT: the first read of
# a plan shape pays XLA compile in-band, which would poison any
# straggler/outlier rule keyed on the wait distribution. A read is "first" when its
# ExchangeReport shows fresh step-cache programs (stepcache_programs > 0).
H_FETCH_FIRST = "shuffle.read.first_wait_ms"
H_PEER_ROWS = "shuffle.peer.rows"            # rows per peer per exchange
H_PEER_BYTES = "shuffle.peer.bytes"          # bytes per peer per exchange
H_RETRY_MS = "failure.retry.ms"              # failed-attempt latency (ms)
H_COMPILE_SECS = "compile.step.duration_s"   # per-program compile seconds
# Wave-pipelined exchange (a2a.waveRows): per wave i >= 1, the pack time
# NOT covered by the previous wave's in-flight collective —
# max(0, pack_ms[i] - wait_ms[i-1]). A healthy pipeline observes ~0 (the
# collective outlives the pack, packs are fully hidden); sustained
# positive gaps mean the device idles between waves waiting on the host
# pack — the doctor's pipeline_stall signal (a2a.waveRows/packThreads).
H_WAVE_GAP = "shuffle.wave.gap_ms"
# Achieved collective bandwidth per steady-state exchange: global payload
# bytes / (dispatch-start .. completion). Compile-bearing reads are
# EXCLUDED (same discipline as the H_FETCH_WAIT/H_FETCH_FIRST split —
# in-band XLA compile lands inside group_ms and would crater the
# distribution's tail), so the histogram answers "what does this link
# actually sustain", the number the doctor's bw_underutilization rule
# grades p50 against the best observed exchange with.
H_BW = "shuffle.collective.bw_gbps"

WELL_KNOWN_HISTOGRAMS = (H_FETCH_WAIT, H_FETCH_FIRST, H_PEER_ROWS,
                         H_PEER_BYTES, H_RETRY_MS, H_COMPILE_SECS,
                         H_WAVE_GAP, H_BW)

# Failure-domain counters (runtime/watchdog.py, shuffle/manager.py replay
# policy): ONE place for the names so the watchdog, the replay loop, the
# doctor's peer_timeout/replay_storm rules and the tests cannot drift.
C_PEER_TIMEOUT = "failure.peer_timeout.count"  # watchdog deadline expiries
C_PROBE_DEAD = "failure.probe.dead"            # devices a probe found dead
C_REPLAYS = "shuffle.replay.count"             # exchange replays executed
C_REPLAY_MS = "shuffle.replay.ms"              # wall burned by failed tries

# Agreement plane (shuffle/agreement.py): cross-process agreement rounds
# executed and typed divergence verdicts raised. Divergence carries a
# labeled twin {topic=...} so the doctor's desync rule can name the
# offending round (and map it to the conf key that governs it) without
# parsing error strings. Like C_PEER_TIMEOUT, C_AGREE_DIVERGENCE is
# never noise: a divergence is a real configuration/state split by
# construction (the primitive already filtered transport flakes through
# the watchdog-fenced channel).
C_AGREE_ROUNDS = "shuffle.agreement.rounds.count"
C_AGREE_DIVERGENCE = "shuffle.agreement.divergence.count"
# Decision-plane observability (PR 20, shuffle/decisions.py ledger +
# agreement.py instrumentation). H_AGREE_ROUND times one FULL agree()
# round (header + payload gathers) wall-clock; the labeled twin
# {topic=...} keys the per-topic distribution the slow_proposer /
# decision-stall diagnoses read. Every exit path — unanimous, reduced,
# divergent, peer-lost — lands exactly one observation (and one
# C_AGREE_ROUNDS count, with a labeled {topic=} twin), so per-topic
# divergence RATIOS are computable from the two labeled families alone.
H_AGREE_ROUND = "shuffle.agreement.round_ms"
# Turnstile plane (agreement.CollectiveTurnstile): wait_ms is
# issue→enter latency per ticket (how long an agreed-order section
# queued behind earlier tickets); depth is the point-in-time count of
# issued-but-unreleased tickets (queue depth, set-semantics gauge);
# abandoned counts tickets released without ever entering (dispatch
# failure / executor stop) — legal by design, but a surge means the
# async plane is issuing work it then throws away.
H_TURNSTILE_WAIT = "shuffle.turnstile.wait_ms"
G_TURNSTILE_DEPTH = "shuffle.turnstile.depth"
C_TURNSTILE_ABANDONED = "shuffle.turnstile.abandoned.count"

# Integrity-plane counters (shuffle/integrity.py, shuffle/manager.py
# verify paths, shuffle/durable.py restart scan): ONE place for the
# names so the verifiers, the doctor's block_corruption rule and the
# tests cannot drift.
C_INTEGRITY_VERIFIED = "shuffle.integrity.verified.bytes"
C_INTEGRITY_CORRUPT = "shuffle.integrity.corrupt.bytes"
C_INTEGRITY_CORRUPT_BLOCKS = "shuffle.integrity.corrupt.count"
C_INTEGRITY_QUARANTINED = "shuffle.integrity.quarantined.count"
C_INTEGRITY_RECOVERED = "shuffle.integrity.recovered.count"

# Device-resident read plane (read.sink, shuffle/reader.py): ONE place
# for the names so the reader's drain paths, the MoE host-staged
# consumer and the doctor's host_roundtrip rule
# cannot drift. C_D2H counts PAYLOAD bytes pulled device-to-host by a
# reader result (whole-shard drains, per-partition device slices, the
# distributed force-materialize) — metadata (seg matrices) is excluded;
# the device-sink acceptance gate is C_D2H delta == 0 across the
# consumer loop. C_H2D counts bytes a consumer RE-UPLOADED to device
# after a host drain (models/moe.host_staged_consume) — the round-trip
# half the device sink deletes.
C_D2H = "shuffle.read.d2h.bytes"
C_H2D = "shuffle.consume.h2d.bytes"
# Reads that ASKED for the device sink but landed on host (the manager's
# _resolve_sink fallback: distributed / hierarchical / conf-pinned
# reads). Labeled twins carry {mode="plain|ordered|combine",
# reason=...} — the doctor's sink_fallback rule grades the total and
# names the modes, since PR-12 made the device sink legal for every
# read mode on the single-process flat exchange.
C_SINK_FALLBACK = "shuffle.sink.fallback.count"
# Combine/ordered reads whose device-kernel resolution LANDED on jnp
# while the conf asked for the blocked pallas kernels
# (read.mergeImpl=pallas through segmented.resolve_kernel_impl) —
# the kernel-plane twin of C_SINK_FALLBACK. Labeled twins carry
# {reason="backend_unsupported|subword_dtype"} (the capability-gate
# evidence); the doctor's kernel_fallback rule grades the total.
# 'auto' resolving to jnp on a CPU backend does NOT count — auto never
# advertised the kernels, so nothing silently degraded.
C_KERNEL_FALLBACK = "shuffle.kernel.fallback.count"
# Topology plane (shuffle/topology.py): cumulative WIRE bytes each
# fabric tier of a hierarchical exchange moved, labeled
# {tier="ici|dcn", tenant=...} — the per-tenant face of
# ExchangeReport.tiers (a whale's DCN appetite is visible per tenant,
# the shuffle.payload/wire.bytes discipline applied per fabric).
C_TIER_BYTES = "shuffle.tier.bytes"

# Multi-tenant service plane (shuffle/tenancy.py, shuffle/manager.py
# admission): ONE place for the names so the fair-share queue, the
# facades' async plane, the doctor's quota_starvation rule and the
# tests cannot drift. All three are LABELED per tenant
# (``labeled(name, tenant=...)``): H_ADMIT_WAIT observes every
# admission's deferral wall (0 for an immediate grant — the p99 must
# see the whole distribution, not just the stalls), C_ADMIT_BYTES
# accumulates granted reservation bytes (the fair-share evidence the
# doctor grades a hog against), C_SUBMIT_THROTTLED counts async
# submissions that hit tenant.<id>.maxInflightReads.
H_ADMIT_WAIT = "shuffle.admit.wait_ms"
# per deferred grant: how many grants OTHER tenants received between
# this ticket's enqueue and its grant — the starvation discriminator.
# A tenant queueing behind its own serialized reads observes ~0 here
# no matter how long it waits; a tenant parked behind another tenant's
# whole flood observes the flood's length. Scale-free (counts, not ms),
# which is what lets the quota_starvation rule separate "busy with my
# own work" from "starved by a neighbor" out of aggregates alone.
H_ADMIT_CROSS = "shuffle.admit.cross_grants"
C_ADMIT_BYTES = "shuffle.admit.bytes"
C_SUBMIT_THROTTLED = "shuffle.submit.throttled.count"
# point-in-time admission reservation per tenant (set-semantics gauge)
G_TENANT_INFLIGHT = "shuffle.inflight.bytes"

# External-memory analytics plane (workloads/):
# ONE place for the names so the pipelines, the doctor's spill_bound
# rule and the tests cannot drift. C_SPILL_BYTES accumulates bytes the
# map writers moved from the pinned arena to sealed spill files
# (shuffle/writer.py _flush_to_disk — threshold-triggered AND
# budget-forced spills both land here; the "spill proven" gate is this
# counter's delta > 0 at the scale shape). C_WORKLOAD_ROWS counts rows
# a workload pipeline emitted/verified; C_WORKLOAD_PHASE_MS accumulates
# per-phase walls — both carry labeled twins
# {workload="terasort|groupby|join", phase="ingest|spill|exchange|
# merge|emit"} which are what the spill_bound rule attributes a
# workload's wall with.
C_SPILL_BYTES = "shuffle.spill.bytes"
C_SPILL_COUNT = "shuffle.spill.count"
C_WORKLOAD_ROWS = "workload.rows"
C_WORKLOAD_PHASE_MS = "workload.phase.ms"

# Exchange anatomy plane (utils/anatomy.py folded at exchange
# settlement): C_PHASE_MS accumulates wall milliseconds per canonical
# phase, labeled {phase="plan|compile|pack|admission_wait|barrier_wait|
# transfer.ici|transfer.dcn|merge|sink|spill|verify|dark_time"} — the
# labeled family rides TelemetryHistory counter deltas, which is what
# lets the phase_regression doctor rule put a TREND on a phase without
# any new frame machinery. C_TRACE_DROPPED surfaces the tracer ring's
# drop count as a counter (watermark-delta published by
# Tracer.publish_dropped) so the dark_time rule can cite span loss as
# the explanation for an attribution hole.
C_PHASE_MS = "shuffle.phase.ms"
C_TRACE_DROPPED = "trace.spans.dropped"

# Device-memory gauge families (runtime/devmon.py sampler; per local
# device index, encoded as a label via :func:`labeled`): ONE place for
# the names so the sampler, the doctor's hbm_pressure rule and the
# tests cannot drift on spelling.
G_HBM_IN_USE = "devmon.hbm.in_use"
G_HBM_LIMIT = "devmon.hbm.limit"
G_HBM_PEAK = "devmon.hbm.peak"


# -- labeled metric identities (gauges) -------------------------------------
def escape_label_value(value) -> str:
    """Prometheus exposition label-value escaping: backslash, quote and
    newline. Applied when a label is ENCODED into a metric identity
    (``labeled``), so the canonical key itself is exposition-legal and a
    hostile-looking value (device paths, rule names) can never corrupt a
    scrape. utils/export.py re-exports this as part of its hardening
    surface."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


_LABELED_RE = re.compile(r"^([^{}\n]+)\{(.*)\}$", re.S)
_LABEL_ITEM_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')
_UNESCAPE_RE = re.compile(r"\\(.)")


def labeled(name: str, **labels) -> str:
    """Canonical labeled-metric identity: ``name{k="v",...}`` with sorted
    keys and escaped values — ONE encoding shared by the gauge registry,
    the JSON snapshot (keys must be stable for the doctor's build_view)
    and the Prometheus exporter (which emits the label block verbatim
    after sanitizing the name parts)."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{escape_label_value(v)}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def parse_labeled(name: str):
    """Inverse of :func:`labeled`: ``(base, {k: v})`` with UNescaped
    values, or ``(name, None)`` when the identity carries no parseable
    label block (including hostile brace garbage — the exporter then
    sanitizes the whole string as a plain name)."""
    m = _LABELED_RE.match(name)
    if not m:
        return name, None
    base, inner = m.groups()
    items = _LABEL_ITEM_RE.findall(inner)
    if not items:
        return name, None
    out = {}
    for k, v in items:
        # ONE pass: sequential str.replace would mangle a literal
        # backslash adjacent to 'n' ("\\n" must stay backslash+n)
        out[k] = _UNESCAPE_RE.sub(
            lambda m: "\n" if m.group(1) == "n" else m.group(1), v)
    return base, out


class Histogram:
    """Thread-safe fixed log-bucket histogram with live p50/p99/max.

    Buckets are a fixed geometric ladder ``GROWTH**k`` (8 per octave, so
    consecutive bucket bounds differ by ~9%); an observation lands in the
    smallest bucket whose upper bound covers it. Quantiles interpolate at
    the geometric midpoint of the hit bucket clipped to the observed
    [min, max], bounding relative quantile error by half a bucket (~4.5%)
    — the trade the reference's per-fetch log line can't make (exact
    values, but only in a log file). Memory is O(occupied buckets): a
    sparse dict, ~no cost until observed."""

    GROWTH = 2.0 ** 0.125
    _LOG_G = math.log(GROWTH)

    __slots__ = ("name", "_lock", "_counts", "_nonpos", "count", "sum",
                 "min", "max")

    def __init__(self, name: str = ""):
        self.name = name
        self._lock = threading.Lock()
        self._counts: Dict[int, int] = {}
        self._nonpos = 0          # observations <= 0 (their own bucket)
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def _index(self, value: float) -> int:
        # smallest k with GROWTH**k >= value
        return int(math.ceil(math.log(value) / self._LOG_G - 1e-9))

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self.count += 1
            self.sum += value
            if value < self.min:
                self.min = value
            if value > self.max:
                self.max = value
            if value <= 0.0:
                self._nonpos += 1
            else:
                idx = self._index(value)
                self._counts[idx] = self._counts.get(idx, 0) + 1

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (0 <= q <= 1) of everything observed."""
        with self._lock:
            return self._quantile_locked(q)

    def _quantile_locked(self, q: float) -> float:
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = self._nonpos
        if cum >= target and self._nonpos:
            return min(self.min, 0.0)
        for idx in sorted(self._counts):
            cum += self._counts[idx]
            if cum >= target:
                lo = self.GROWTH ** (idx - 1)
                hi = self.GROWTH ** idx
                est = math.sqrt(lo * hi)    # geometric midpoint
                return min(max(est, self.min), self.max)
        return self.max

    def _percentiles_locked(self) -> Dict[str, float]:
        if self.count == 0:
            return {"count": 0.0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "mean": 0.0, "p50": 0.0, "p99": 0.0}
        return {
            "count": float(self.count),
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "mean": self.sum / self.count,
            "p50": self._quantile_locked(0.50),
            "p99": self._quantile_locked(0.99),
        }

    def percentiles(self) -> Dict[str, float]:
        with self._lock:
            return self._percentiles_locked()

    def _buckets_locked(self) -> List[Tuple[float, int]]:
        out: List[Tuple[float, int]] = []
        cum = self._nonpos
        if self._nonpos:
            out.append((0.0, cum))
        for idx in sorted(self._counts):
            cum += self._counts[idx]
            out.append((self.GROWTH ** idx, cum))
        out.append((math.inf, self.count))
        return out

    def buckets(self) -> List[Tuple[float, int]]:
        """Cumulative ``(upper_bound, count_leq)`` pairs over occupied
        buckets plus the +Inf terminal — the Prometheus histogram series
        shape (utils/export.py renders these as ``_bucket{le=...}``)."""
        with self._lock:
            return self._buckets_locked()

    def snapshot(self) -> Dict:
        """percentiles() plus the bucket series — the JSON-able full
        state an exporter or flight-recorder dump embeds. The bucket
        bounds are exact ladder values (GROWTH**k survives a JSON float
        round-trip bit-for-bit), so :meth:`from_snapshot` reconstructs
        the histogram losslessly — the property the doctor's
        cluster-wide aggregation (merge over per-process dumps) rides.
        ONE lock acquisition for both halves: a concurrent observe
        between percentiles and buckets would otherwise publish a +Inf
        bucket that disagrees with ``count`` — invalid Prometheus
        exposition and a skewed from_snapshot reconstruction."""
        with self._lock:
            snap = self._percentiles_locked()
            snap["buckets"] = [[le, c]
                               for le, c in self._buckets_locked()]
        return snap

    # to_snapshot is the doctor-facing name; snapshot() predates it
    to_snapshot = snapshot

    @classmethod
    def from_snapshot(cls, snap: Dict, name: str = "") -> "Histogram":
        """Rebuild a histogram from :meth:`snapshot` output (a dump
        written by another process, possibly dead). Per-bucket counts
        come from differencing the cumulative series; the bucket index
        from inverting the exact ladder bound."""
        h = cls(name)
        count = int(snap.get("count", 0))
        if count == 0:
            return h
        h.count = count
        h.sum = float(snap.get("sum", 0.0))
        h.min = float(snap.get("min", 0.0))
        h.max = float(snap.get("max", 0.0))
        prev = 0
        for le, cum in snap.get("buckets", []):
            le, cum = float(le), int(cum)
            c, prev = cum - prev, cum
            if c <= 0:
                continue
            if le <= 0.0:
                h._nonpos += c
            elif le == math.inf:
                # terminal diff should be 0 for a well-formed snapshot;
                # a truncated bucket list attributes the tail to max
                idx = h._index(h.max if h.max > 0 else 1.0)
                h._counts[idx] = h._counts.get(idx, 0) + c
            else:
                idx = int(round(math.log(le) / cls._LOG_G))
                h._counts[idx] = h._counts.get(idx, 0) + c
        return h

    @classmethod
    def snapshot_delta(cls, cur: Dict, prev: Optional[Dict],
                       name: str = "") -> Dict:
        """The WINDOW between two snapshots of the SAME cumulative
        histogram, as a snapshot dict: counts and sums subtract, and the
        cumulative bucket series subtracts bucket-wise (same fixed
        ladder, so per-bucket counts diff exactly). This is the time
        axis the telemetry plane lacked — ``utils/history.py`` calls it
        per retained window so the SLO plane can ask "what was p99 in
        the LAST five minutes" instead of since boot.

        ``prev`` of ``None``/empty means the window starts at zero (the
        first frame IS the cumulative state). A shrinking count means
        the source registry restarted mid-window; the honest answer is
        the current cumulative state, not a negative window.

        The window's min/max are NOT recoverable from two cumulative
        snapshots (the cumulative min/max may predate the window), so
        they are estimated from the occupied delta buckets' geometric
        bounds — the same half-bucket error contract quantiles already
        carry."""
        if not prev or not int(prev.get("count", 0)):
            return dict(cur)
        c0, c1 = int(prev.get("count", 0)), int(cur.get("count", 0))
        if c1 < c0:           # source registry restarted: window = cur
            return dict(cur)
        empty = {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                 "mean": 0.0, "p50": 0.0, "p99": 0.0,
                 "buckets": [[math.inf, 0]]}
        if c1 == c0:
            return dict(empty)

        def _per_bucket(snap):
            out, prior = {}, 0
            for le, cum in snap.get("buckets", []):
                le, cum = float(le), int(cum)
                if le == math.inf:
                    continue      # terminal carries no bucket of its own
                out[le] = cum - prior
                prior = cum
            return out

        b_cur, b_prev = _per_bucket(cur), _per_bucket(prev)
        count = c1 - c0
        cum, series = 0, []
        occupied: List[float] = []
        for le in sorted(set(b_cur) | set(b_prev)):
            c = max(0, b_cur.get(le, 0) - b_prev.get(le, 0))
            if c:
                cum += c
                series.append([le, cum])
                occupied.append(le)
        series.append([math.inf, count])
        # min/max estimates from the occupied bounds (lower geometric
        # neighbour for min), clipped to the cumulative envelope
        if occupied:
            lo = occupied[0] / cls.GROWTH if occupied[0] > 0 else \
                min(float(cur.get("min", 0.0)), 0.0)
            hi = occupied[-1]
        else:
            lo = hi = 0.0
        lo = max(lo, float(cur.get("min", lo)))
        hi = min(hi, float(cur.get("max", hi))) if hi else hi
        s = float(cur.get("sum", 0.0)) - float(prev.get("sum", 0.0))
        h = cls.from_snapshot(
            {"count": count, "sum": s, "min": lo, "max": hi,
             "buckets": series}, name)
        return h.snapshot()

    def merge(self, other: "Histogram") -> "Histogram":
        """Fold ``other``'s observations into this histogram (exact —
        same fixed ladder, so bucket counts add). The cluster-wide
        aggregation primitive: N per-process dumps merge into ONE
        distribution the doctor's rules evaluate. Returns self."""
        with other._lock:
            counts = dict(other._counts)
            nonpos, count = other._nonpos, other.count
            osum, omin, omax = other.sum, other.min, other.max
        with self._lock:
            for idx, c in counts.items():
                self._counts[idx] = self._counts.get(idx, 0) + c
            self._nonpos += nonpos
            self.count += count
            self.sum += osum
            if omin < self.min:
                self.min = omin
            if omax > self.max:
                self.max = omax
        return self


class Timer:
    """Context-manager wall timer; `.ms` after exit."""

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        self.ms = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1e3


class Metrics:
    """Thread-safe counter/gauge registry.

    Role of Spark's ShuffleReadMetricsReporter integration
    (ref: UcxShuffleReader.scala:111-116): incFetchWaitTime, incRecordsRead
    become plain named counters here.

    Reporters: a host engine embedding the framework can observe every
    increment live — ``add_reporter(fn)`` with ``fn(name, value)`` — the
    push-style seam Spark's reporter object provides. Reporter failures
    are swallowed (logged once per reporter): observability must never
    fail a shuffle."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = defaultdict(float)
        # Gauges: SET semantics (last write wins), the kind counters
        # cannot fake — a watermark exported as a counter reads as
        # monotonic to Prometheus and every rate()/increase() query over
        # it lies the moment the value goes down. Keys may carry a label
        # block (``labeled(name, device=0)``); utils/export.py renders
        # them with their own ``# TYPE ... gauge`` line. Reporters do NOT
        # see gauge sets: the devmon sampler re-publishes watermarks on a
        # cadence, and pushing every re-set through the flight recorder's
        # ring would evict the actual events the ring exists to keep.
        self._gauges: Dict[str, float] = {}
        self._reporters = []
        self._broken = set()
        # pre-create the declared distribution metrics so exporters and
        # scrapes see the full surface (with zero counts) from process
        # start — a dashboard query must not 404 until the first shuffle
        self._histograms: Dict[str, Histogram] = {
            name: Histogram(name) for name in WELL_KNOWN_HISTOGRAMS}

    def add_reporter(self, fn) -> None:
        """Attach fn(name: str, value: float), called on every inc()."""
        with self._lock:
            self._reporters.append(fn)

    def remove_reporter(self, fn) -> None:
        with self._lock:
            try:
                self._reporters.remove(fn)
            except ValueError:
                pass

    def _report(self, name: str, value: float, reporters) -> None:
        for fn in reporters:
            try:
                fn(name, value)
            except Exception:
                if id(fn) not in self._broken:
                    self._broken.add(id(fn))
                    from sparkucx_tpu.utils.logging import get_logger
                    get_logger("metrics").exception(
                        "metrics reporter %r raised; further failures "
                        "from it are silenced", fn)

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += value
            reporters = list(self._reporters)
        self._report(name, value, reporters)

    def observe(self, name: str, value: float) -> None:
        """Record one observation into the named histogram (created on
        first use). Reporters see it through the same fn(name, value)
        seam as counters — the push-style integration is one channel.

        Fast path: histogram exists and no reporters attached — both
        reads are GIL-atomic (histogram entries are never deleted, only
        added under the lock), so the registry lock is skipped and the
        cost is one dict lookup + the histogram's own update. This is
        the common case on the read hot path."""
        h = self._histograms.get(name)
        if h is not None and not self._reporters:
            h.observe(value)
            return
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name)
            reporters = list(self._reporters)
        h.observe(value)
        self._report(name, value, reporters)

    def set_gauge(self, name: str, value) -> None:
        """Publish a point-in-time value (HBM in use, pool watermark).
        ``value=None`` clears the gauge — an unsampleable source (CPU
        backend without memory_stats) must not leave a stale number
        behind for a scrape to trust."""
        with self._lock:
            if value is None:
                self._gauges.pop(name, None)
            else:
                self._gauges[name] = float(value)

    def get_gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def gauges(self) -> Dict[str, float]:
        """{identity: value} — identities are plain names or the
        ``labeled()`` canonical form; the exporter-facing view."""
        with self._lock:
            return dict(self._gauges)

    def get(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def histogram(self, name: str) -> Optional[Histogram]:
        with self._lock:
            return self._histograms.get(name)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def histograms(self, populated_only: bool = False) -> Dict[str, Dict]:
        """{name: Histogram.snapshot()} — the exporter-facing view.
        ``populated_only`` skips zero-count histograms: exporters want
        the full pre-registered surface (a dashboard query must not
        404), but the history plane's window deltas drop empty series
        anyway and snapshotting them every roll is pure cost on the
        rolling cadence."""
        with self._lock:
            hists = list(self._histograms.items())
        return {name: h.snapshot() for name, h in hists
                if not populated_only or h.count}

    @contextlib.contextmanager
    def timeit(self, name: str, hist: Optional[str] = None):
        """Counter timer; ``hist=<histogram name>`` additionally observes
        the wall ms into that distribution (fetch-wait and friends)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            self.inc(name + ".ms", ms)
            self.inc(name + ".count", 1)
            if hist is not None:
                self.observe(hist, ms)


GLOBAL_METRICS = Metrics()
