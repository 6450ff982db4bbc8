"""Topology plane — the two-tier ICI/DCN exchange as a production path.

``shuffle/hierarchical.py`` seeds the two-stage algebra (stage 1 within
each slice over ICI grouped by destination DEVICE INDEX, stage 2 across
slices over DCN grouped by destination SLICE — each row crosses the slow
fabric exactly once) as ONE fused compiled program. That shape predates
every plane built since: a fused program cannot deadline its tiers
separately (the watchdog sees one opaque collective), cannot time them
(the doctor cannot tell an ICI straggler from a DCN one), and its
accounting reports the flat single-collective cost as a lower bound.

This module is the production rebuild:

* :func:`resolve_topology` — ``a2a.topology=flat|hier|auto`` resolved
  against the live mesh (auto = slice detection: hier exactly when the
  mesh is 2-D ``(dcn, ici)`` with more than one slice), validated
  through the one ``alltoall.ALLOWED_TOPOLOGIES`` seam.
* :func:`mesh_cache_key` — the structural ``(shape, axis names, device
  ids)`` key every hierarchical step cache entry rides, so a
  remeshed-but-identical mesh (PR-7 replay rebinds a fresh ``Mesh``
  object over the same devices) reuses its compiled programs instead of
  recompiling both tiers.
* :func:`tier_layouts` — per-tier ``RaggedLayout`` accounting: stage-1
  ICI bytes and stage-2 DCN bytes as separate payload/wire pairs (the
  ``ExchangeReport.tiers`` contract), with cross-fabric row counts
  derived exactly from the metadata table's device matrix where one
  process holds it.
* :class:`PendingTieredShuffle` — the two-stage exchange as TWO compiled
  programs (stage-1 ICI, stage-2 DCN) driven host-side: per-tier
  watchdog deadlines (``failure.ici.timeoutMs`` / ``failure.dcn.
  timeoutMs`` — a PeerLostError and its flight postmortem name the tier
  that expired), per-tier walls on ``tier_walls`` (the doctor's
  ``slow_tier`` evidence), per-tier overflow retry (a stage-2 overflow
  re-runs ONLY the DCN hop — the relay data is still on device), and
  the int8 wire narrowing BOTH hops (quantize before each collective,
  dequantize after; key/partition/size lanes stay exact, so the
  between-stage partition recompute is untouched).

The multi-process path runs the SAME two per-tier programs through
:class:`sparkucx_tpu.shuffle.distributed.PendingDistributedTieredShuffle`,
which overrides this class's distributed seams (staging, overflow
reads, and the cross-process regrow/verdict agreement rounds of
``shuffle/agreement.py``). The fused single-program step in
``shuffle/hierarchical.py`` remains the low-level fallback shape; it
shares this module's cache key and the per-hop wire narrowing.
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkucx_tpu.ops.partition import destination_sort
from sparkucx_tpu.shuffle.alltoall import (ShuffleResult, ragged_shuffle,
                                           resolved_wire_impl,
                                           validate_topology,
                                           wire_pack_rows,
                                           wire_unpack_rows)
from sparkucx_tpu.shuffle.plan import (ShufflePlan, plan_takes_seed,
                                       wire_row_words)
from sparkucx_tpu.shuffle.reader import PendingExchangeBase
from sparkucx_tpu.utils.logging import get_logger

log = get_logger("shuffle.topology")

# FaultInjector sites of the tiered exchange (chaos matrix / straggler
# drills): checked INSIDE the tier's watchdog fence, so an armed
# ``delayMs`` inflates exactly that tier's measured wall (the slow_tier
# doctor drill) and a delay past the tier deadline expires the fence
# naming the tier (the per-tier PeerLostError contract).
TIER_FAULT_SITES = {"ici": "tier.ici", "dcn": "tier.dcn"}


def mesh_cache_key(mesh: Mesh) -> tuple:
    """Structural identity of a mesh for compiled-step cache keys:
    ``(devices.shape, axis_names, device ids)``. Keying on the live
    ``Mesh`` object ties program reuse to that object's hash semantics;
    a replay remesh (PR-7) rebuilds an IDENTICAL mesh as a fresh object,
    and the cache must serve the already-compiled tier programs for it
    rather than recompiling both tiers."""
    return (tuple(int(x) for x in mesh.devices.shape),
            tuple(mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.reshape(-1)))


@dataclass(frozen=True)
class TopologyDescriptor:
    """The resolved exchange topology of one manager binding — pure mesh
    facts, identical on every process by construction (the
    ``_waves_eligible`` discipline: branch decisions derived from it
    need no collective).

    ``kind``       — "flat" | "hier" (never "auto": this is the resolved
                     tier, the ``_resolve_wire`` discipline).
    ``ici_axis``   — the intra-slice mesh axis (every topology has one).
    ``dcn_axis``   — the cross-slice axis ("" on flat).
    ``num_slices`` — S (1 on flat).
    ``per_slice``  — D, devices per slice (the flat axis size on flat).
    """

    kind: str
    ici_axis: str
    dcn_axis: str = ""
    num_slices: int = 1
    per_slice: int = 0

    @property
    def hierarchical(self) -> bool:
        return self.kind == "hier"

    @property
    def tiers(self) -> tuple:
        """Fabric tiers an exchange of this topology rides, in dispatch
        order — the iteration key of every per-tier plane (accounting,
        deadlines, walls, counters)."""
        return ("ici", "dcn") if self.kind == "hier" else ("ici",)

    def tier_axis(self, tier: str) -> str:
        return self.ici_axis if tier == "ici" else self.dcn_axis

    def describe(self) -> Dict:
        return {"kind": self.kind, "ici_axis": self.ici_axis,
                "dcn_axis": self.dcn_axis,
                "num_slices": self.num_slices,
                "per_slice": self.per_slice}


def resolve_topology(mesh: Mesh, conf) -> TopologyDescriptor:
    """Resolve ``a2a.topology`` against the live mesh.

    ``auto`` (default) is slice detection: hier exactly when the mesh is
    2-D ``(dcn, ici)`` with more than one slice (the legacy boolean
    ``a2a.hierarchical=false`` still forces flat under auto — it
    predates this key and production confs carry it). An EXPLICIT
    ``hier`` on a mesh that cannot run two tiers is a conf error, not a
    silent flat fallback — the error names the key and what the mesh
    looks like."""
    want = validate_topology(conf.a2a_topology)
    ici = conf.mesh_ici_axis if conf.mesh_ici_axis in mesh.axis_names \
        else mesh.axis_names[-1]
    dcn = conf.mesh_dcn_axis
    two_d = len(mesh.axis_names) == 2 and mesh.axis_names == (dcn, ici)
    S = int(mesh.devices.shape[0]) if two_d else 1
    D = int(mesh.devices.shape[-1])
    if want == "hier":
        if not (two_d and S > 1):
            raise ValueError(
                f"spark.shuffle.tpu.a2a.topology=hier needs a 2-D "
                f"({dcn!r}, {ici!r}) mesh with >1 slice; this mesh is "
                f"{dict(zip(mesh.axis_names, mesh.devices.shape))} — "
                f"use mesh.numSlices (service/TpuNode) to shape it, or "
                f"a2a.topology=auto to fall back to flat")
        kind = "hier"
    elif want == "flat":
        kind = "flat"
    else:
        kind = "hier" if (two_d and S > 1
                          and conf.get_bool("a2a.hierarchical", True)) \
            else "flat"
    if kind == "hier":
        return TopologyDescriptor("hier", ici_axis=ici, dcn_axis=dcn,
                                  num_slices=S, per_slice=D)
    return TopologyDescriptor("flat", ici_axis=ici, per_slice=D)


def tier_timeouts(conf) -> Dict[str, float]:
    """Per-tier watchdog deadlines, resolved once per read:
    ``failure.ici.timeoutMs`` / ``failure.dcn.timeoutMs``, each
    defaulting to ``failure.collectiveTimeoutMs`` (0 = off)."""
    return {"ici": conf.ici_timeout_ms, "dcn": conf.dcn_timeout_ms}


# -- per-tier accounting ---------------------------------------------------
def tier_cross_rows(dev_matrix, topo: TopologyDescriptor) -> Dict[str, int]:
    """Rows that PHYSICALLY cross each fabric, exact, from the [P, P]
    source-device x dest-device row matrix (the metadata table's
    ``device_matrix`` — the same matrix the int32-range guard already
    derives on the local read path).

    Stage 1 moves a row from (s, d) to the relay (s, d') — a real ICI
    move iff the device COLUMN changes; stage 2 moves it from (s, d')
    to (s', d') — a real DCN move iff the SLICE changes. Each row
    appears in the DCN count at most once by construction: this is the
    each-row-crosses-the-slow-tier-exactly-once proof."""
    m = np.asarray(dev_matrix, dtype=np.int64)
    D = max(1, topo.per_slice)
    src = np.arange(m.shape[0])
    dst = np.arange(m.shape[1])
    ici = int(m[(src[:, None] % D) != (dst[None, :] % D)].sum())
    dcn = int(m[(src[:, None] // D) != (dst[None, :] // D)].sum())
    return {"ici": ici, "dcn": dcn}


def tier_layouts(plan: ShufflePlan, topo: TopologyDescriptor,
                 shard_rows, width: int,
                 dev_matrix=None,
                 backend: Optional[str] = None,
                 relay_cap: Optional[int] = None) -> List[Dict]:
    """Per-tier wire-contract descriptors of one hierarchical exchange —
    the ``RaggedLayout`` formula applied per fabric (the
    ``ExchangeReport.tiers`` entries):

    * ``payload_rows/bytes`` — the REAL rows/bytes that must cross this
      fabric: the exact cross-fabric count when the [P, P] device
      matrix is known (single-process reads hold the table), else every
      row entering the stage (the distributed upper bound, flagged by
      ``cross_exact: false``).
    * ``wire_rows/bytes`` — what the resolved transport moves over the
      fabric for it: the cross rows for the ragged-native collective
      (self-segments are local DMA), the full padded group cost for
      dense/gather — stage 1 pays ``S x D² x cap`` padded segments,
      stage 2 ``D x S² x cap`` (the collective ships self-segments
      through the same padded lanes, exactly like the flat dense
      accounting counts P² segments).
    * ``pad_ratio`` — wire/payload per tier; ``a2a.wire=int8`` narrows
      the per-row wire cost on BOTH hops, so int8+native tiers sit
      below 1.0 legally (the flat accounting's contract).

    ``relay_cap`` is the stage-2 input capacity (defaults to
    ``plan.cap_out``) — the gather transport replicates that buffer."""
    # the transport each hop rides: hier requires S>1 (and D>=1), so the
    # 1-shard 'local' resolution can never apply — force a multi-shard
    # group so 'auto' resolves to the real collective
    impl = resolved_wire_impl(plan.impl, max(2, topo.per_slice), backend)
    total = int(np.sum(np.asarray(shard_rows, dtype=np.int64)))
    S, D = topo.num_slices, topo.per_slice
    row_w = wire_row_words(plan, width)
    relay_cap = int(plan.cap_out if relay_cap is None else relay_cap)
    cross = tier_cross_rows(dev_matrix, topo) \
        if dev_matrix is not None else None
    out: List[Dict] = []
    for tier in topo.tiers:
        xrows = None if cross is None else cross[tier]
        if tier == "ici":
            groups, gshards = S, D
            dense_rows = S * D * D * plan.cap_out
            gather_rows = S * D * D * plan.cap_in
        else:
            groups, gshards = D, S
            dense_rows = D * S * S * plan.cap_out
            gather_rows = D * S * S * relay_cap
        payload_rows = total if xrows is None else xrows
        if impl == "native":
            wire_rows = payload_rows
        elif impl == "gather":
            wire_rows = gather_rows
        else:                      # dense (pallas never reaches here:
            wire_rows = dense_rows  # the hier path is native/dense/gather)
        payload_bytes = payload_rows * width * 4
        wire_bytes = wire_rows * row_w * 4
        out.append({
            "tier": tier,
            "axis": topo.tier_axis(tier),
            "impl": impl,
            "groups": groups,
            "group_shards": gshards,
            "rows_in": total,
            "payload_rows": int(payload_rows),
            "payload_bytes": int(payload_bytes),
            "cross_exact": xrows is not None,
            "wire_rows": int(wire_rows),
            "wire_bytes": int(wire_bytes),
            "pad_ratio": round(wire_bytes / payload_bytes, 6)
            if payload_bytes else 0.0,
            "wire": plan.wire,
            # walls/rates land at read settlement (manager on_done /
            # wave finalize) from the pending handle's tier_walls
            "ms": 0.0,
            "bw_gbps": 0.0,
            "effective_bw_gbps": 0.0,
        })
    return out


def settle_tier_walls(tiers: List[Dict], tier_walls: Dict[str, float],
                      width: int) -> None:
    """Stamp measured per-tier walls onto the accounting entries and
    derive the per-tier rates: ``bw_gbps`` = the tier's REAL payload
    bytes over its wall, ``effective_bw_gbps`` the EQuARX figure (the
    rate a RAW wire would have needed — equals bw_gbps off the int8
    tier). In place; never raises."""
    for t in tiers:
        ms = float(tier_walls.get(t.get("tier", ""), 0.0))
        t["ms"] = round(ms, 3)
        if ms > 0 and t.get("payload_bytes"):
            gbps = t["payload_bytes"] / (ms * 1e6)
            t["bw_gbps"] = round(gbps, 6)
            raw_row = t["payload_bytes"] / max(t["payload_rows"], 1)
            wire_row = t["wire_bytes"] / max(t["wire_rows"], 1)
            gain = raw_row / wire_row if wire_row else 1.0
            t["effective_bw_gbps"] = round(gbps * max(gain, 1.0), 6)


# -- the tiered steps ------------------------------------------------------
def _tier_wire_shuffle(plan: ShufflePlan, send, sizes, axis, seed,
                       out_capacity: int) -> ShuffleResult:
    """One tier's collective on the plan's wire tier: int8 narrows the
    value lanes around this hop's ragged_shuffle (quantize on send,
    dequantize on receive — key/partition/size lanes stay exact), so
    BOTH hops of the two-stage exchange ship narrowed rows while the
    between-stage partition recompute sees full rows."""
    if seed is None:
        return ragged_shuffle(send, sizes, axis,
                              out_capacity=out_capacity, impl=plan.impl)
    width = send.shape[1]
    packed = wire_pack_rows(send, plan.wire_words, seed)
    r = ragged_shuffle(packed, sizes, axis, out_capacity=out_capacity,
                       impl=plan.impl)
    data = wire_unpack_rows(r.data, width, plan.wire_words)
    return ShuffleResult(data, r.recv_sizes, r.total, r.overflow)


def _check_hier_mesh(mesh: Mesh, topo: TopologyDescriptor) -> None:
    if mesh.axis_names != (topo.dcn_axis, topo.ici_axis):
        raise ValueError(
            f"tiered shuffle needs mesh axes ({topo.dcn_axis!r}, "
            f"{topo.ici_axis!r}) in that order, got {mesh.axis_names}")


def _stage1_body(plan: ShufflePlan, topo: TopologyDescriptor,
                 relay_cap: int):
    """Stage 1 — ICI: within each slice, exchange rows grouped by the
    destination DEVICE INDEX d' (g % D), map-side combine first when the
    read combines (shrinks BOTH hops). Returns (relay, total, overflow)
    per shard."""
    from sparkucx_tpu.shuffle.reader import _blocked_map, _make_part_fn
    R = plan.num_partitions
    Pn = plan.num_shards
    D = topo.per_slice
    part_to_dest = np.asarray(_blocked_map(R, Pn))
    part_fn = _make_part_fn(plan, R)
    seeded = plan_takes_seed(plan)

    def step(payload, nvalid):
        seed = nvalid[1] if seeded else None
        n0 = nvalid[0]
        if plan.combine:
            from sparkucx_tpu.ops.aggregate import combine_rows
            payload, _, n1 = combine_rows(
                payload, part_fn(payload), n0, R,
                plan.combine_words, np.dtype(plan.combine_dtype),
                plan.combine, sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction)
            n0 = n1[0]
        g = jnp.take(part_to_dest, part_fn(payload))
        send1, counts1 = destination_sort(
            payload, g % D, n0, D, method=plan.sort_impl)
        r1 = _tier_wire_shuffle(plan, send1, counts1, topo.ici_axis,
                                seed, relay_cap)
        return r1.data, r1.total, r1.overflow

    return step


def _stage2_body(plan: ShufflePlan, topo: TopologyDescriptor,
                 out_cap: int):
    """Stage 2 — DCN: group the relay's rows by GLOBAL PARTITION id
    (monotone in the destination slice at fixed device index, so the
    sort groups by destination slice AND leaves each delivered segment
    partition-sorted — the flat reader's partition-major design), relay
    combine first when the read combines (the rows that shrink here are
    exactly the ones that would otherwise cross DCN), then the
    plain/ordered/combine finalize of the fused step. Returns
    (rows, seg, total, overflow) — the flat step contract."""
    from sparkucx_tpu.shuffle.reader import (_device_bounds, _make_part_fn)
    R = plan.num_partitions
    Pn = plan.num_shards
    S, D = topo.num_slices, topo.per_slice
    bounds = _device_bounds(R, Pn)
    part_fn = _make_part_fn(plan, R)
    seeded = plan_takes_seed(plan)

    def step(relay, nvalid):
        seed = nvalid[1] if seeded else None
        n = nvalid[0]
        part2 = part_fn(relay)
        if plan.combine:
            from sparkucx_tpu.ops.aggregate import combine_rows
            send2, rcounts2, _ = combine_rows(
                relay, part2, n, R, plan.combine_words,
                np.dtype(plan.combine_dtype), plan.combine,
                sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction)
        else:
            # ordered needs no key order at the relay — the final stage
            # fully re-sorts; the plain partition sort is cheaper and
            # byte-identical downstream
            send2, rcounts2 = destination_sort(
                relay, part2, n, R, method=plan.sort_impl)
        d_mine = jax.lax.axis_index(topo.ici_axis)
        cum2 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(rcounts2).astype(jnp.int32)])
        gs = jnp.arange(S, dtype=jnp.int32) * D + d_mine
        counts2 = jnp.take(cum2, jnp.take(bounds, gs + 1)) \
            - jnp.take(cum2, jnp.take(bounds, gs))          # [S]
        r2 = _tier_wire_shuffle(plan, send2, counts2, topo.dcn_axis,
                                seed, out_cap)
        if plan.combine:
            from sparkucx_tpu.ops.aggregate import combine_rows
            rows_out, pcounts, n_out = combine_rows(
                r2.data, part_fn(r2.data), r2.total[0], R,
                plan.combine_words, np.dtype(plan.combine_dtype),
                plan.combine, sum_words=plan.combine_sum_words,
                compaction=plan.combine_compaction)
            return rows_out, pcounts.reshape(1, R), \
                n_out.astype(r2.total.dtype), r2.overflow
        if plan.ordered:
            from sparkucx_tpu.ops.aggregate import keysort_rows
            _, rows_out, pcounts = keysort_rows(
                r2.data, part_fn(r2.data), r2.total[0], R)
            return rows_out, pcounts.reshape(1, R), r2.total, r2.overflow
        # receivers locate their runs with the relays' per-partition
        # counts: [S, R] per shard (relays share a device column, so the
        # dcn all_gather collects exactly this receiver's senders)
        seg = jax.lax.all_gather(rcounts2, topo.dcn_axis)
        return r2.data, seg, r2.total, r2.overflow

    return step


def _build_stage1_step(mesh: Mesh, topo: TopologyDescriptor,
                       plan: ShufflePlan, width: int, relay_cap: int):
    """Compiled stage-1 (ICI) program, served from the shared keyed step
    cache under the STRUCTURAL mesh key — one program per (mesh
    identity, topology, plan signature, width, relay capacity)."""
    from sparkucx_tpu.shuffle.stepcache import (GLOBAL_STEP_CACHE,
                                                combine_attrs, jit_named,
                                                program_name)
    _check_hier_mesh(mesh, topo)
    key = ("hier1", mesh_cache_key(mesh), topo.dcn_axis, topo.ici_axis,
           plan, width, int(relay_cap))
    name = program_name("tier_ici", plan)
    attrs = {"kind": "hier1", "program": name, "cap_in": plan.cap_in,
             "relay_cap": int(relay_cap), "width": width,
             "impl": plan.impl, "wire": plan.wire, **combine_attrs(plan)}

    def build():
        spec = P((topo.dcn_axis, topo.ici_axis))
        sm = jax.shard_map(_stage1_body(plan, topo, int(relay_cap)),
                           mesh=mesh, in_specs=(spec, spec),
                           out_specs=(spec,) * 3)
        return jit_named(sm, name)

    return GLOBAL_STEP_CACHE.get(key, build, attrs)


def _build_stage2_step(mesh: Mesh, topo: TopologyDescriptor,
                       plan: ShufflePlan, width: int, relay_cap: int,
                       out_cap: int):
    """Compiled stage-2 (DCN) program — keyed on BOTH capacities (its
    input is the stage-1 relay buffer; its output the final receive
    buffer), same structural mesh key discipline."""
    from sparkucx_tpu.shuffle.stepcache import (GLOBAL_STEP_CACHE,
                                                combine_attrs, jit_named,
                                                program_name)
    _check_hier_mesh(mesh, topo)
    key = ("hier2", mesh_cache_key(mesh), topo.dcn_axis, topo.ici_axis,
           plan, width, int(relay_cap), int(out_cap))
    name = program_name("tier_dcn", plan)
    attrs = {"kind": "hier2", "program": name, "relay_cap": int(relay_cap),
             "cap_out": int(out_cap), "width": width,
             "impl": plan.impl, "wire": plan.wire, **combine_attrs(plan)}

    def build():
        spec = P((topo.dcn_axis, topo.ici_axis))
        sm = jax.shard_map(_stage2_body(plan, topo, int(out_cap)),
                           mesh=mesh, in_specs=(spec, spec),
                           out_specs=(spec,) * 4)
        return jit_named(sm, name)

    return GLOBAL_STEP_CACHE.get(key, build, attrs)


# -- the tiered pending handle ---------------------------------------------
class TierHooks:
    """Manager-side plumbing for one tiered read: fault sites, tracer
    spans, flight events, per-tier deadlines. The null instance (module
    default) makes every hook a no-op, so the low-level submit stays
    framework-free."""

    __slots__ = ("faults", "tracer", "flight", "trace_id", "timeouts")

    def __init__(self, faults=None, tracer=None, flight=None,
                 trace_id: str = "", timeouts: Optional[Dict] = None):
        self.faults = faults
        self.tracer = tracer
        self.flight = flight
        self.trace_id = trace_id
        self.timeouts = dict(timeouts or {})

    def check_fault(self, tier: str) -> None:
        if self.faults is not None:
            self.faults.check(TIER_FAULT_SITES[tier])

    def span(self, tier: str):
        if self.tracer is None:
            return nullcontext()
        return self.tracer.span("shuffle.tier", tier=tier,
                                trace=self.trace_id)

    def named_span(self, name: str, **attrs):
        """A trace-tagged span for the result-side work the anatomy
        ledger must not leave dark (stage-2 redispatch, assembly)."""
        if self.tracer is None:
            return nullcontext()
        return self.tracer.span(name, trace=self.trace_id, **attrs)

    def record(self, kind: str, **data) -> None:
        if self.flight is not None:
            self.flight.record(kind, **data)


class PendingTieredShuffle(PendingExchangeBase):
    """Future-like handle for a two-tier (ICI, DCN) exchange driven as
    TWO compiled programs with a host join between them — the
    per-tier production contract:

    * stage 1 dispatches at submit (async, like every pending handle);
      ``result()`` joins it under the ICI deadline, retries a relay
      overflow by regrowing ONLY the relay capacity, then dispatches
      stage 2 over the ON-DEVICE relay buffer (no payload D2H — only
      the [P] totals and overflow flags cross to host, the
      metadata-exclusion precedent) and joins it under the DCN
      deadline; a stage-2 overflow re-runs only the DCN hop.
    * each tier's wall (dispatch -> join, retries included) accumulates
      in ``tier_walls`` — the ``ExchangeReport.tiers[*].ms`` source and
      the doctor's ``slow_tier`` evidence.
    * a deadline expiry raises :class:`PeerLostError` whose message —
      and the flight postmortem's ``stuck_sections`` — names the tier
      (``"hierarchical ici exchange"`` / ``"hierarchical dcn
      exchange"``), so replay/remesh can tell a slice-fabric hang from
      an inter-slice one.

    Lifecycle (exactly-once on_done, admission defer, dead-handle
    semantics) follows :class:`reader.PendingExchangeBase`."""

    def __init__(self, mesh: Mesh, topo: TopologyDescriptor,
                 plan: ShufflePlan, shard_rows: np.ndarray,
                 shard_nvalid: np.ndarray, val_shape, val_dtype,
                 on_done=None, admit=None, wire_seed: int = 0,
                 hooks: Optional[TierHooks] = None):
        _check_hier_mesh(mesh, topo)
        self._mesh = mesh
        self._topo = topo
        self._plan = plan
        self._relay_cap = int(plan.cap_out)
        self._rows_host = shard_rows
        self._nvalid_host = shard_nvalid
        self._val_shape = val_shape
        self._val_dtype = val_dtype
        self._wire_seed = int(wire_seed)
        self._hooks = hooks or TierHooks()
        self._sharding = NamedSharding(
            mesh, P((topo.dcn_axis, topo.ici_axis)))
        self.tier_walls: Dict[str, float] = {"ici": 0.0, "dcn": 0.0}
        self._t_stage = 0.0
        self._result = None
        # _attempt is the TOTAL regrow count (the on_done retry
        # accounting every pending handle reports); each stage bounds
        # its OWN loop by plan.max_retries — the two capacities grow
        # independently, so a shared bound would halve the budget a
        # skewed exchange legitimately needs
        self._attempt = 0
        self._retries1 = 0
        self._retries2 = 0
        # which stage the current _out belongs to: done() must not
        # report True after stage 1 alone (the whole DCN hop has not
        # even dispatched — the Future contract is that result() then
        # blocks only on D2H/consensus, never on a fresh collective)
        self._stage = 1
        self._on_done = None
        self._initial_dispatch(admit)
        self._on_done = on_done

    def _stage_to_device(self, arr):
        from sparkucx_tpu.io.dlpack import stage_to_device
        return stage_to_device(arr, self._sharding)

    # -- the distributed seams ---------------------------------------------
    # PendingDistributedTieredShuffle (shuffle/distributed.py) overrides
    # exactly these five hooks to run the SAME two per-tier programs over
    # a multi-process mesh: local staging, local overflow reads, and the
    # cross-process agreement rounds (shuffle/agreement.py) that keep the
    # regrow/verdict decisions in lockstep. Single-process they are
    # identities, so the hot path pays nothing.
    def _seed_nvalid(self, values, stream: int) -> np.ndarray:
        """Seeded nvalid lane for stage ``1 + stream``: distinct
        per-attempt noise base; stage 2 derives its own (odd) stream, so
        the two hops never reuse a wire-noise realization."""
        from sparkucx_tpu.shuffle.reader import seeded_nvalid
        return seeded_nvalid(
            self._plan, values,
            (self._wire_seed + self._attempt) * 2 + stream)

    def _local_overflow(self, ovf) -> bool:
        return bool(np.asarray(ovf).any())

    def _agree_overflow(self, tier: str, mine: bool) -> bool:
        """Cross-process overflow verdict (identity single-process)."""
        return mine

    def _agree_regrow(self, tier: str, cap: int) -> int:
        """Cross-process capacity-regrow agreement (identity
        single-process); returns the agreed capacity."""
        return int(cap)

    def _totals_host(self, tot1) -> np.ndarray:
        """Stage-1 per-shard totals as the host row stage-2 seeds from
        (this process's view — the full [P] row single-process)."""
        return np.asarray(tot1).astype(np.int64).reshape(-1)

    def _dispatch(self) -> None:
        """(Re)dispatch STAGE 1 — the PendingExchangeBase seam (the
        deferred-admission first dispatch lands here too)."""
        width = self._rows_host.shape[2]
        step = _build_stage1_step(self._mesh, self._topo, self._plan,
                                  width, self._relay_cap)
        self._step1 = step
        rows_flat = self._stage_to_device(
            self._rows_host.reshape(-1, width))
        nvalid = self._stage_to_device(
            self._seed_nvalid(self._nvalid_host, 0))
        self._t_stage = time.perf_counter()
        self._stage = 1
        self._out = step(rows_flat, nvalid)

    def done(self) -> bool:
        """Whole-exchange view: False until the DCN hop's outputs are
        computed (a stage-1-only readiness must not read as done — the
        stage-2 collective has not even dispatched). ``_outputs_ready``
        keeps the stage-local device-busy probe the wave pipeline's
        overlap accounting reads."""
        if self._result is not None or getattr(self, "_dead", False):
            return True
        if self._stage < 2:
            return False
        return self._outputs_ready()

    def _fenced_join(self, tier: str, ovf) -> bool:
        """Join the in-flight tier under its deadline; returns the
        host overflow verdict. The tier's fault site is consulted
        INSIDE the fence, so an armed delay inflates exactly this
        tier's wall — and past the deadline the fence expires naming
        the tier. The wall accumulates across retries."""
        from sparkucx_tpu.runtime.watchdog import current_watchdog
        hooks = self._hooks

        def join():
            hooks.check_fault(tier)
            return self._local_overflow(ovf)

        limit = float(hooks.timeouts.get(tier, 0.0))
        try:
            with hooks.span(tier):
                verdict = current_watchdog().call(
                    join, what=f"hierarchical {tier} exchange",
                    trace=hooks.trace_id or None, timeout_ms=limit)
                # cross-process verdict (identity single-process): the
                # agreement round rides INSIDE the tier span/wall, so a
                # peer stuck in this tier burns THIS tier's deadline
                # and a divergence records as this tier's fault
                verdict = self._agree_overflow(tier, verdict)
        except BaseException as e:
            # the postmortem names the tier even when the failure is an
            # injected fault rather than a deadline expiry (the chaos
            # cell's tier-named-in-the-postmortem contract)
            hooks.record("tier_fault", tier=tier,
                         error=repr(e)[:200])
            self.tier_walls[tier] += (time.perf_counter()
                                      - self._t_stage) * 1e3
            raise
        self.tier_walls[tier] += (time.perf_counter()
                                  - self._t_stage) * 1e3
        return verdict

    def _result_inner(self):
        plan = self._plan
        width = self._rows_host.shape[2]
        # -- stage 1: ICI, relay-capacity retry loop ----------------------
        while True:
            relay, tot1, ovf1 = self._out
            if not self._fenced_join("ici", ovf1):
                break
            if self._retries1 >= plan.max_retries:
                raise RuntimeError(
                    f"hierarchical stage-1 (ICI) still overflowing after "
                    f"{plan.max_retries} retries (relay capacity "
                    f"{self._relay_cap}); extreme skew — repartition")
            log.info("hier ICI overflow at relay_cap=%d (attempt %d); "
                     "growing", self._relay_cap, self._attempt)
            # the regrown capacity is AGREED before redispatch (identity
            # single-process): one peer regrowing alone would recompile
            # a different stage-1 program and desync the mesh
            self._relay_cap = self._agree_regrow("ici",
                                                 self._relay_cap * 2)
            self._retries1 += 1
            self._attempt += 1
            # anatomy span (pack phase): the grown-capacity redispatch
            # re-stages the rows and re-dispatches stage 1 inside
            # result() — the same dark window as the stage-2 redispatch
            # below, hit on every relay-capacity overflow
            with self._hooks.named_span("shuffle.dispatch", stage=1,
                                        retry=self._retries1):
                self._dispatch()
        # only tier metadata crosses to host: [P] totals + the flag —
        # a blocking D2H on the stage-1 collective's output, so it
        # rides the ICI tier span in the anatomy ledger
        with self._hooks.span("ici"):
            totals1 = self._totals_host(tot1)
        # -- stage 2: DCN, output-capacity retry loop ---------------------
        while True:
            # anatomy span (pack phase): the stage-2 redispatch — step
            # build + seed staging + the dispatch call — runs inside
            # result(), outside the manager's dispatch span; untagged it
            # is the hier ledger's biggest dark window. A stage-2 cache
            # miss traces under compile.step, which outranks pack in the
            # sweep, so the envelope never steals compile time.
            with self._hooks.named_span("shuffle.dispatch", stage=2):
                step2 = _build_stage2_step(self._mesh, self._topo, plan,
                                           width, self._relay_cap,
                                           plan.cap_out)
                self._step = step2  # device-plane join point (cost rec)
                nv2 = self._stage_to_device(
                    self._seed_nvalid(totals1, 1))
                self._t_stage = time.perf_counter()
                self._stage = 2
                self._out = step2(relay, nv2)
            rows_out, seg, total, ovf2 = self._out
            if not self._fenced_join("dcn", ovf2):
                break
            if self._retries2 >= plan.max_retries:
                raise RuntimeError(
                    f"hierarchical stage-2 (DCN) still overflowing after "
                    f"{plan.max_retries} retries "
                    f"(cap_out={plan.cap_out}); extreme skew — "
                    f"repartition the data")
            log.info("hier DCN overflow at cap_out=%d (attempt %d); "
                     "growing", plan.cap_out, self._attempt)
            plan = plan.grown()
            # agreement on the grown output capacity (identity
            # single-process) — the unanimity round every process must
            # pass before the group recompiles stage 2
            self._agree_regrow("dcn", plan.cap_out)
            self._plan = plan
            self._retries2 += 1
            self._attempt += 1
        # anatomy span (sink phase): result assembly — the seg pull and
        # the lazy-result wrapper — same tail as the flat path's. The
        # assembly itself is the last distributed seam (the multi-process
        # subclass builds a partial, process-local view instead).
        with self._hooks.named_span("shuffle.result", sink=plan.sink):
            return self._assemble(rows_out, seg, total)

    def _assemble(self, rows_out, seg, total):
        from sparkucx_tpu.shuffle.reader import (
            DeviceShuffleReaderResult, LazyShuffleReaderResult,
            _blocked_map, max_recv_rows, pull_to_host)
        plan = self._plan
        Pn = plan.num_shards
        R = plan.num_partitions
        cap_shard = rows_out.shape[0] // Pn
        res = LazyShuffleReaderResult(
            R, np.asarray(_blocked_map(R, Pn)), rows_out, seg,
            Pn, cap_shard, self._val_shape, self._val_dtype,
            per_shard_segs=True, align_chunk=0)
        res.cap_out_used = plan.cap_out
        res._totals_dev = total
        if not plan.combine:
            # plain/ordered: observable delivered-rows requirement
            # for the manager's learned-cap decay (combine's counts
            # are post-merge) — same tiny host read as the flat path
            seg_np = pull_to_host(seg, "seg").reshape(Pn, -1, R)
            res.recv_rows_needed = max_recv_rows(
                seg_np, np.asarray(_blocked_map(R, Pn)), Pn)
        if plan.sink == "device":
            # the stage-2 output is already partition-sorted on
            # device (partition-major stage-2 sort; ordered/combine
            # land fully merged) — the device sink holds it resident
            # exactly like the flat single-shot path
            return DeviceShuffleReaderResult(
                [res], plan, self._val_shape, self._val_dtype)
        return res


def submit_shuffle_tiered(
    mesh: Mesh,
    topo: TopologyDescriptor,
    plan: ShufflePlan,
    shard_rows: np.ndarray,
    shard_nvalid: np.ndarray,
    val_shape,
    val_dtype,
    on_done=None,
    admit=None,
    wire_seed: int = 0,
    hooks: Optional[TierHooks] = None,
) -> PendingTieredShuffle:
    """Dispatch the two-tier exchange without blocking — the
    submit/poll contract of :func:`shuffle.reader.submit_shuffle`, with
    per-tier deadlines/walls/faults via ``hooks``."""
    return PendingTieredShuffle(
        mesh, topo, plan, shard_rows, shard_nvalid, val_shape,
        val_dtype, on_done=on_done, admit=admit, wire_seed=wire_seed,
        hooks=hooks)


def read_shuffle_tiered(mesh, topo, plan, shard_rows, shard_nvalid,
                        val_shape, val_dtype, hooks=None):
    """Blocking two-tier exchange (submit + immediate result)."""
    return submit_shuffle_tiered(
        mesh, topo, plan, shard_rows, shard_nvalid, val_shape,
        val_dtype, hooks=hooks).result()
