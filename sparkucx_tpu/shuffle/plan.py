"""Shuffle plans — static-shape capacity policy.

XLA compiles one program per shape, so the ragged reality of a shuffle
(skewed partition sizes, ref hard-part (a) in SURVEY.md §7) is absorbed
host-side into a small set of padded capacities. This module decides them:

* ``cap_in``  — per-shard send-buffer rows (max staged rows, padded up)
* ``cap_out`` — per-shard receive rows = balanced share x capacityFactor
* retry policy — overflow is detected mesh-wide by the data plane; the
  caller doubles ``cap_out`` and re-runs (geometric, bounded), the moral
  equivalent of the reference's inflight-bytes throttling loop in Spark's
  ShuffleBlockFetcherIterator (ref: UcxShuffleReader.scala:56-70) — except
  here the budget is HBM instead of network credits.

Capacities are rounded to multiples of 8 rows to keep TPU-friendly tiling.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf


def _round_up(x: int, mult: int = 8) -> int:
    return max(mult, ((int(x) + mult - 1) // mult) * mult)


@dataclass(frozen=True)
class ShufflePlan:
    """Static shapes for one exchange step. Hashable: the jit-cache key."""

    num_shards: int
    num_partitions: int
    cap_in: int
    cap_out: int
    impl: str
    partitioner: str = "hash"  # hash | direct (keys ARE partition ids)
    max_retries: int = 4
    sort_impl: str = "auto"    # ops/partition.py destination_sort method
    # single-shard plain exchanges only: destination-sort in this many
    # independent strips (ops/partition.destination_sort_strips — one
    # batched sort network of depth ~log^2(cap_in/strips) instead of
    # ~log^2(cap_in)), served back as `strips` virtual senders by the
    # reader's run index. 1 = one flat sort. Ignored off the single-shard
    # plain path (combine/ordered have their own sort semantics; the
    # multi-shard collective needs device-contiguous send segments).
    sort_strips: int = 1
    # device combine-by-key (ops/aggregate.py): None, or a COMBINERS entry
    # ("sum"). Applied map-side (before the wire) AND reduce-side (before
    # D2H); needs a numeric value schema, carried here so the jit cache
    # keys on it.
    combine: Optional[str] = None
    combine_words: int = 0     # value width in int32 words (combine only)
    combine_dtype: str = ""    # np.dtype.str of the value (combine only)
    # transport words the combiner SUMS; the rest of the value row is
    # CARRIED per key (per-key-constant payload, e.g. varlen record
    # bytes — io/varlen.py). 0 = sum the whole value row.
    combine_sum_words: int = 0
    # combine_rows end-row compaction formulation (stable | unstable) —
    # bit-identical output, different TPU sort cost; conf-selectable
    # (a2a.combineCompaction), read by the combine's ``perm`` form only.
    combine_compaction: str = "stable"
    # device key sort: partitions come back key-sorted (signed int64
    # order) — the "sort" half of the reference reduce pipeline's stock
    # aggregate+sort, without aggregation (TeraSort's shape). Implied by
    # combine (combined output is already key-sorted).
    ordered: bool = False
    # sorted int64 split points for partitioner="range" (the Spark
    # RangePartitioner analog, device-evaluated): static, so they are
    # part of the compiled program and the jit-cache key.
    bounds: Optional[Tuple[int, ...]] = None
    # impl='pallas' only: None resolves interpret mode from the default
    # backend AT TRACE TIME (CPU tests interpret, TPU compiles); pin it
    # explicitly when tracing for a backend other than the host's — the
    # same backend-keyed-trace hazard aot.py pins sort_impl against (an
    # AOT compile from a CPU host would otherwise bake the interpreter
    # into the TPU program).
    pallas_interpret: Optional[bool] = None
    # Wire-compression tier (a2a.wire, alltoall.ALLOWED_WIRES) — the
    # compiled-step contract half: 'int8' makes the step quantize the
    # trailing ``wire_words`` float32 value lanes to int8 + a per-row
    # scale word before the collective and dequantize on receive (keys /
    # partition / size lanes stay exact int lanes); 'lossless' leaves
    # the step untouched (the tier is the host-side byte-plane codec on
    # the drain path) but still keys the program, so the wire mode is
    # part of the compiled-program family by construction. The manager
    # resolves the conf tier per read (_decorated_plan): int8 demands
    # float32 value lanes and a real wire move, else the plan falls back
    # to 'raw' and the report says so.
    wire: str = "raw"
    # float32 value lanes the int8 wire narrows (= value_words for an
    # f32 schema); 0 on every other tier.
    wire_words: int = 0
    # Read-sink tier (read.sink, alltoall.ALLOWED_SINKS minus 'auto' —
    # the manager resolves per read): 'host' drains results D2H, 'device'
    # keeps partitions as sharded jax Arrays handed straight to a
    # consumer step (reader.DeviceShuffleReaderResult). Like 'lossless'
    # on the wire axis, the compiled step body is sink-oblivious — the
    # field still keys the program family so a host and a device read of
    # one shape never collide on a step (the consumer donates the device
    # read's output buffers; sharing the executable across sinks would
    # let a donated-buffer alias bleed into the host path's result).
    sink: str = "host"
    # Device-kernel tier for the combine/ordered fold path
    # (read.mergeImpl through ops/pallas/segmented.resolve_kernel_impl —
    # the backend-conditional resolution): "jnp" = the XLA sort-network
    # formulation (the oracle, runs everywhere), "pallas" = the blocked
    # merge-path merge / tiled segment-reduce kernels (TPU native, or
    # CPU interpret for tests). Stamped RESOLVED by the manager
    # (_decorated_plan), never the conf ask, and rides family(): a jnp
    # and a pallas read of one shape are different compiled programs
    # (the fused int8 reduce consumes wire-format rows — sharing the
    # executable would alias incompatible step bodies).
    kernel_impl: str = "jnp"
    # Wave-pipelined exchange (a2a.waveRows, shuffle/manager.py): the
    # OUTER descriptive plan of a waved read carries the wave split here
    # — rows per shard per wave and the agreed wave count. The plan each
    # wave actually DISPATCHES (wave_step_plan) keeps both at their
    # defaults, so the compiled-program signature never varies with how
    # many waves a particular shuffle happened to split into (one
    # program per wave-shape family, not one per exchange).
    wave_rows: int = 0
    num_waves: int = 1

    def grown(self) -> "ShufflePlan":
        """Next plan after an overflow: double the receive capacity."""
        import dataclasses
        return dataclasses.replace(self, cap_out=self.cap_out * 2)

    def family(self) -> tuple:
        """Compiled-program family key: every field that shapes the
        compiled step EXCEPT the waved read's outer split
        (``wave_rows``/``num_waves`` never reach a dispatched program —
        see ``wave_step_plan``) and ``max_retries`` (a host-loop bound).

        This is the replay-stability contract (failure.policy=replay):
        a re-run exchange whose learned caps carried over lands on the
        SAME family — i.e. replay costs a re-pack and a re-dispatch, not
        a recompile. The manager stamps it on replay flight events and
        the chaos drill asserts it held across the fault matrix."""
        return (self.num_shards, self.num_partitions, self.cap_in,
                self.cap_out, self.impl, self.partitioner, self.sort_impl,
                self.sort_strips, self.combine, self.combine_words,
                self.combine_dtype, self.combine_sum_words,
                self.combine_compaction, self.ordered, self.bounds,
                self.pallas_interpret, self.wire, self.wire_words,
                self.sink, self.kernel_impl)

    def strips_active(self) -> bool:
        """True when the single-shard strip-sorted plain path runs —
        THE activation predicate, shared by the step that writes the
        layout (reader.step_body) and the resolves that index it
        (reader/distributed align_chunk): one source, no desync."""
        return (self.num_shards == 1 and self.sort_strips > 1
                and not (self.combine or self.ordered)
                and self.impl != "pallas")

    def strip_rows(self) -> int:
        """Rows per strip region in the strip-sorted layout (the
        ``align_chunk`` of the result's run index) — the sorted buffer is
        ``sort_strips * strip_rows()`` rows. Meaningful only when
        :meth:`strips_active`. The step statically checks its payload cap
        equals ``cap_in``, so this host-side derivation and the sort's
        runtime one provably agree."""
        s = max(1, min(int(self.sort_strips), self.cap_in))
        return -(-self.cap_in // s)


# Measured-best strip counts for the single-shard plain path, by backend
# (ops/partition.destination_sort_strips; no on-chip sweep of the current
# code exists). Empty entry / unknown backend = 1 (flat sort).
# Kept as data so a new measurement is a one-line change with a citation.
_MEASURED_STRIPS: dict = {}

# Valid a2a.sortStrips bounds, checked by conf validation.
STRIPS_RANGE = (1, 4096)

# Valid a2a.waveDepth bounds (the STRIPS_RANGE discipline: one constant
# shared by conf validation and the pipeline). Depth 1 = serial waves
# (bounded memory, no overlap); past ~8 the pinned-block working set
# grows without hiding any more latency (three pipeline stages exist).
WAVE_DEPTH_RANGE = (1, 8)

# Valid a2a.capBucketGrowth bounds (the STRIPS_RANGE discipline: one
# constant shared by conf validation and the quantizer). Growth close to
# 1.0 degenerates into one bucket per shape (no amortization); growth
# past 4x over-provisions HBM beyond what any skew hint would.
CAP_BUCKET_GROWTH_RANGE = (1.05, 4.0)

# Hard ceiling on any bucketed capacity: row counts must stay addressable
# by the int32 arithmetic the compiled step runs (the same bound
# meta/segments.validate_row_sizes enforces on staged totals).
CAP_BUCKET_CEILING = (1 << 31) - 8


def bucket_cap(cap: int, growth: float) -> int:
    """Round ``cap`` UP to the next rung of the geometric capacity ladder
    ``rung(k) = round_up8(8 * growth**k)`` — the plan-shape quantizer
    behind ``a2a.capBuckets``.

    XLA compiles one program per (cap_in, cap_out, width) shape, so
    row-count drift across epochs otherwise compiles a fresh program per
    exact shape; quantizing capacities onto a small geometric ladder
    lands drifting shapes on a handful of compiled programs. Rounding is
    UP only (never down), so overflow semantics are unchanged — a
    bucketed plan can only overflow less than the exact one. Rungs stay
    multiples of 8 (the TPU tiling rule _round_up keeps), floored at 8
    and clamped to CAP_BUCKET_CEILING."""
    import math
    if not CAP_BUCKET_GROWTH_RANGE[0] <= growth <= CAP_BUCKET_GROWTH_RANGE[1]:
        raise ValueError(
            f"cap bucket growth {growth} out of "
            f"{CAP_BUCKET_GROWTH_RANGE[0]}..{CAP_BUCKET_GROWTH_RANGE[1]}")
    cap = _round_up(int(cap))
    if cap <= 8:
        return 8
    if cap >= CAP_BUCKET_CEILING:
        return CAP_BUCKET_CEILING
    # smallest ladder rung >= cap. The float log only seeds the search;
    # the loops below settle it exactly — round-to-8 can make SEVERAL
    # consecutive k collapse onto one rung (a lower k may already cover
    # cap), and the smallest-rung answer is what makes the quantizer
    # idempotent (a rung maps to itself, so re-quantizing on the
    # cap-hint path is stable).
    def rung(k: int) -> int:
        return _round_up(int(math.ceil(8.0 * growth ** k)))

    k = max(0, math.ceil(math.log(cap / 8.0) / math.log(growth) - 1e-9))
    while k > 0 and rung(k - 1) >= cap:
        k -= 1
    r = rung(k)
    while r < cap:
        k += 1
        r = rung(k)
    return min(r, CAP_BUCKET_CEILING)


def bucket_cap_conf(cap: int, conf: "TpuShuffleConf") -> int:
    """Conf-gated quantizer: ``a2a.capBuckets`` off returns ``cap``
    unchanged. ONE seam shared by make_plan and the manager's cap-hint
    path so every capacity that reaches a compiled-step signature is
    quantized by the same rule."""
    if not conf.cap_buckets:
        return int(cap)
    return bucket_cap(cap, conf.cap_bucket_growth)


def default_sort_strips(backend: str, num_shards: int) -> int:
    """Resolve ``a2a.sortStrips=auto``: the measured-best strip count for
    this backend on a single-shard axis, else 1 (the lever only exists on
    the 1-shard plain path — ShufflePlan.strips_active)."""
    if num_shards != 1:
        return 1
    return int(_MEASURED_STRIPS.get(backend, 1))


def resolve_sort_strips(conf_val, num_shards: int) -> int:
    """'auto' -> backend-measured default; anything else is already an
    int (conf validation). jax imported lazily: plan.py stays importable
    without touching a backend."""
    if conf_val != "auto":
        return int(conf_val)
    import jax
    return default_sort_strips(jax.default_backend(), num_shards)


def make_plan(
    shard_rows: np.ndarray,
    num_shards: int,
    num_partitions: int,
    conf: Optional[TpuShuffleConf] = None,
    partitioner: str = "hash",
    bounds=None,
) -> ShufflePlan:
    """Derive capacities from per-shard staged row counts.

    ``shard_rows`` — [P] rows staged on each shard. cap_out starts at the
    perfectly-balanced share times ``capacityFactor`` (equal to cap_in on
    one shard); skew beyond that is handled by the overflow-retry loop,
    trading one recompile for not provisioning worst-case HBM everywhere."""
    conf = conf or TpuShuffleConf()
    total = int(np.sum(shard_rows))
    cap_in = bucket_cap_conf(
        _round_up(int(np.max(shard_rows, initial=0))), conf)
    balanced = total / max(num_shards, 1)
    # one shard receives exactly what it staged: no skew to absorb, and
    # the headroom would double the device footprint of the result
    cap_out = cap_in if num_shards == 1 else bucket_cap_conf(
        _round_up(int(np.ceil(balanced * conf.capacity_factor))), conf)
    if partitioner not in ("hash", "direct", "range"):
        raise ValueError(f"unknown partitioner {partitioner!r}")
    if (partitioner == "range") != (bounds is not None):
        raise ValueError("partitioner='range' needs bounds (and only it)")
    if bounds is not None:
        b = np.asarray(bounds, dtype=np.int64)
        if b.shape != (num_partitions - 1,) or (np.diff(b) < 0).any():
            raise ValueError(
                f"range bounds must be {num_partitions - 1} sorted int64 "
                f"split points, got shape {b.shape}")
        bounds = tuple(int(x) for x in b)
    return ShufflePlan(
        num_shards=num_shards,
        num_partitions=num_partitions,
        cap_in=cap_in,
        cap_out=cap_out,
        impl=conf.a2a_impl,
        partitioner=partitioner,
        sort_impl=conf.sort_impl,
        sort_strips=resolve_sort_strips(conf.sort_strips, num_shards),
        combine_compaction=conf.combine_compaction,
        bounds=bounds,
    )


def merge_family(plan: ShufflePlan, acc_cap: int, wave_cap: int,
                 width: int, merge_impl: str) -> tuple:
    """Compiled-program family key for the DEVICE MERGE step of an
    ordered/combine device-sink waved read (reader.device_merge_fold) —
    the merge/combine analog of :meth:`ShufflePlan.family`, kept here so
    the family definition has one home. Only the fields that shape the
    merge program ride the key: the exchange capacities (cap_in/cap_out,
    wire) deliberately do NOT — two reads whose exchanges differ but
    whose merge shapes agree share ONE merge program, which is what
    keeps the warm-recompile count at zero across same-shaped reads
    (the acceptance contract: one program per (shape family, sink,
    mode))."""
    return (plan.num_shards, plan.num_partitions, plan.partitioner,
            plan.bounds, plan.combine, plan.combine_words,
            plan.combine_dtype, plan.combine_sum_words,
            plan.combine_compaction, plan.ordered, plan.pallas_interpret,
            int(acc_cap), int(wave_cap), int(width), str(merge_impl))


def plan_takes_seed(plan: ShufflePlan) -> bool:
    """Whether this plan's compiled step consumes a noise seed — i.e.
    the int8 wire tier is active. THE predicate every dispatch site
    shares (PendingShuffle, the distributed pending, warmup): a seeded
    step widens its per-shard nvalid input to [count, seed], and the
    stage side and the trace side must agree on which plans do that."""
    return plan.wire == "int8" and plan.wire_words > 0


def wire_row_words(plan: ShufflePlan, width: int) -> int:
    """int32 lanes ONE row of this plan costs on the wire: ``width``
    verbatim on the raw/lossless tiers; on int8, the exact head lanes
    plus the packed int8 value lanes plus the f32 scale word
    (alltoall.int8_wire_words — one lane formula shared with the packing
    kernel). The accounting (ragged_layout), the pallas chunk alignment
    and the step's transport width all read this."""
    if not plan_takes_seed(plan):
        return int(width)
    from sparkucx_tpu.shuffle.alltoall import int8_wire_words
    return int(width) - plan.wire_words + int8_wire_words(plan.wire_words)


@dataclass(frozen=True)
class RaggedLayout:
    """Wire-contract descriptor of one exchange — the real-bytes half of
    the ragged data plane (ROADMAP item 1). Derived host-side from the
    plan plus the [P] size row (the same row the pack phase publishes and
    ``meta/segments.exchange_plan`` all-gathers on device), so the
    accounting and the transport read one contract:

    * ``payload_*`` — the REAL staged rows/bytes (what the consumer gets);
    * ``wire_*``    — what the resolved transport moves over the fabric:
      the payload itself for the ragged-native collective and the 1-shard
      local move, ``P² x cap`` padded segments for dense/gather, and the
      chunk-aligned upper bound for the pallas remote-DMA transport;
    * ``pad_ratio`` — wire/payload: 1.0 means every byte on the wire was a
      real byte; dense at uniform occupancy pays ~P x capacityFactor, and
      skew (which grows cap_out) only inflates it further — the figure
      the doctor's ``padding_waste`` rule grades.

    Hierarchical (two-stage ICI/DCN) exchanges ride the same formula per
    stage; the descriptor reports the flat single-collective cost (a lower
    bound — each row crosses twice there), with the report's
    ``hierarchical`` flag carrying the context."""

    impl: str          # resolved transport: native|dense|gather|pallas|local
    num_shards: int
    width: int
    payload_rows: int
    wire_rows: int
    payload_bytes: int
    wire_bytes: int
    pad_ratio: float   # wire/payload; 0.0 for an empty exchange
    # Wire-compression tier (plan.wire): ``wire_row_bytes`` is what ONE
    # wire row costs on this tier (= width*4 on raw/lossless; narrower
    # on int8 — packed int8 value lanes + the scale word), so
    # ``wire_bytes`` above already reports ACHIEVED (compressed) wire
    # bytes and int8 pad_ratio can legitimately sit below 1.0.
    # ``scale_bytes`` is the per-row scale/metadata overhead the int8
    # tier ships inside that figure.
    wire: str = "raw"
    wire_row_bytes: int = 0
    scale_bytes: int = 0


def ragged_layout(plan: ShufflePlan, shard_rows, width: int,
                  backend: Optional[str] = None) -> RaggedLayout:
    """Build the :class:`RaggedLayout` for one exchange (or one wave of a
    waved exchange — pass the wave plan and that wave's real rows).
    ``shard_rows`` is any array whose sum is the exchange's real staged
    rows (the [P] size row on the full read path)."""
    from sparkucx_tpu.shuffle.alltoall import resolved_wire_impl
    impl = resolved_wire_impl(plan.impl, plan.num_shards, backend)
    payload = int(np.sum(np.asarray(shard_rows, dtype=np.int64)))
    P = plan.num_shards
    # wire tier narrows the per-row cost BEFORE the transport multiplies
    # it: every impl below ships rows of row_w lanes, not `width`
    row_w = wire_row_words(plan, width)
    if impl in ("native", "local"):
        # true per-peer counts on the wire (the [P] size-row allgather
        # rides along at P² ints — noise next to any real payload)
        wire = payload
    elif impl == "dense":
        # every shard ships P segments padded to peer_capacity (= cap_out
        # on the production path), occupancy notwithstanding
        wire = P * P * plan.cap_out
    elif impl == "gather":
        # each shard's whole cap_in send buffer replicates to all P peers
        wire = P * P * plan.cap_in
    else:  # pallas: segments round up to the 128-lane chunk — upper bound
        from sparkucx_tpu.ops.pallas.ragged_a2a import chunk_rows_for
        wire = payload + P * P * (chunk_rows_for(row_w) - 1)
    payload_bytes = payload * width * 4
    wire_bytes = wire * row_w * 4
    pad = round(wire_bytes / payload_bytes, 6) if payload_bytes else 0.0
    scale = wire * 4 if plan_takes_seed(plan) else 0
    return RaggedLayout(impl=impl, num_shards=P, width=width,
                        payload_rows=payload, wire_rows=wire,
                        payload_bytes=payload_bytes, wire_bytes=wire_bytes,
                        pad_ratio=pad, wire=plan.wire,
                        wire_row_bytes=row_w * 4, scale_bytes=scale)


def wave_payload_rows(shard_rows: np.ndarray, wave_rows: int,
                      num_waves: int) -> np.ndarray:
    """[W] REAL global rows each wave of a waved exchange moves: wave i
    takes rows [i*wave_rows, (i+1)*wave_rows) of every shard's staged
    sequence, so its occupancy is the clipped remainder per shard. Pure
    arithmetic over the global size row — identical on every process by
    construction, which is exactly why ``distributed.agree_wave_sizes``
    can fail fast on any divergent view instead of desyncing the mesh."""
    rows = np.asarray(shard_rows, dtype=np.int64)
    out = np.zeros(num_waves, dtype=np.int64)
    for i in range(num_waves):
        out[i] = int(np.clip(rows - i * int(wave_rows), 0,
                             int(wave_rows)).sum())
    return out


def wave_count(shard_rows: np.ndarray, wave_rows: int) -> int:
    """Waves a staged row distribution splits into at ``wave_rows`` rows
    per shard per wave: ceil(max staged rows / wave_rows). Every shard
    uses the same count (trailing waves of a lighter shard are empty) so
    the pipeline stays in lockstep — the distributed path allgathers this
    number (shuffle/distributed.agree_wave_count) purely to fail fast on
    divergent ``a2a.waveRows`` conf; the arithmetic itself is already
    identical everywhere because ``shard_rows`` is the global size row."""
    if wave_rows <= 0:
        return 1
    mx = int(np.max(shard_rows, initial=0))
    return max(1, -(-mx // int(wave_rows)))


def wave_step_plan(plan: ShufflePlan, conf: Optional[TpuShuffleConf]
                   = None) -> ShufflePlan:
    """The plan ONE wave of a waved exchange dispatches.

    Derived from the outer plan's ``wave_rows``: cap_in is the (bucketed)
    wave size, cap_out the balanced wave share times capacityFactor —
    both independent of this exchange's total rows or wave count, so
    every wave of every same-shaped shuffle lands on ONE compiled program
    (the acceptance contract: compile.step.programs delta = 1 per shape
    family). Wave fields are reset to their defaults: the step signature
    must not vary with ``num_waves``, and a wave plan whose shape happens
    to equal a single-shot plan's SHARES that program."""
    import dataclasses
    conf = conf or TpuShuffleConf()
    if plan.wave_rows <= 0:
        raise ValueError("wave_step_plan needs a plan with wave_rows > 0")
    cap_in = bucket_cap_conf(_round_up(plan.wave_rows), conf)
    cap_out = cap_in if plan.num_shards == 1 else bucket_cap_conf(
        _round_up(int(np.ceil(plan.wave_rows * conf.capacity_factor))),
        conf)
    return dataclasses.replace(plan, cap_in=cap_in, cap_out=cap_out,
                               wave_rows=0, num_waves=1)
