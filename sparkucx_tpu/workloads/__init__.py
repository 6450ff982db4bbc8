"""Analytics workload plane — the suite the source system served.

Exoshuffle's (PAPERS.md) argument is that a library-level shuffle
matches specialized systems on exactly this suite — terasort, groupby,
join — and "Memory-efficient array redistribution" frames the
constraint that matters at scale: the working set must never exceed the
memory budget. The pipelines here are EXTERNAL-MEMORY formulations of
the three: data ≥ 10× a configured budget streams through the
production planes (chunked ingest sealing staged bytes through the
``SpillFiles`` path when the pool watermark crosses budget, waved
exchanges bounding the pinned pack footprint, sealed sorted runs merged
k-way off disk), with rows/s as a first-class contract — per-phase
walls on a :class:`WorkloadReport`, ``workload.rows`` /
``workload.phase.ms`` counters feeding the doctor's ``spill_bound``
rule, and ``tests/test_workloads.py`` holding each pipeline to its
oracle under a spilling budget.

The scale model: ``budget_bytes`` bounds the PINNED HOST POOL (the
staging arena every writer and pack buffer rides —
``runtime/memory.HostMemoryPool``'s byte watermark is the graded
number); the dataset is ``10 × budget × scale`` bytes. Spill keeps the
write side under budget (per-writer ``spill.threshold`` plus the
pool-watermark force-spill valve), waves keep the read side under it.

``WORKLOADS`` is the name→runner registry behind
``python -m sparkucx_tpu workload <name> [--scale] [--budget-mb]``;
:func:`run_workload` owns the node/manager lifecycle for that CLI,
deriving spill/wave conf from the budget.
"""

from __future__ import annotations

import dataclasses
import json
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import numpy as np

__all__ = [
    "WorkloadReport", "PhaseWalls", "MemoryBudget", "WORKLOADS",
    "run_workload", "workload_conf_overrides",
]

# the canonical phase vocabulary — the WorkloadReport walls, the
# workload.phase.ms labels and the doctor's spill_bound attribution all
# speak it (ingest = generation + staging, spill = forced/threshold
# disk moves, exchange = the collective reads, merge = cross-run/
# cross-wave merging, emit = verification + egress)
PHASES = ("ingest", "spill", "exchange", "merge", "emit")


@dataclass
class WorkloadReport:
    """The rows/s contract of one analytics pipeline run.

    ``phases`` holds wall ms per phase (the spill wall is the part of
    ingest spent moving staged bytes to disk — it is NOT double-counted
    inside ``ingest``); ``rows_per_s`` divides the dominant row count
    by each phase wall plus the total. ``oracle`` names the
    verification that ran (``exact`` below the small-row threshold,
    ``digest`` = the order-invariant sampled splitmix64 multiset check
    + structural invariants at scale); ``warm_programs`` counts
    compiled programs AFTER the pipeline's first exchange settled — the
    0-warm-recompiles gate (terasort rounds 2+, the join's second
    shuffle, groupby's warm re-read)."""

    workload: str
    rows_in: int = 0
    rows_out: int = 0
    bytes_in: int = 0
    budget_bytes: int = 0
    scale_ratio: float = 0.0          # bytes_in / budget_bytes
    spill_bytes: int = 0
    spill_count: int = 0
    pool_peak_bytes: int = 0
    phases: Dict[str, float] = field(default_factory=dict)    # ms
    rows_per_s: Dict[str, float] = field(default_factory=dict)
    wall_ms: float = 0.0
    programs: int = 0                 # compiled over the whole run
    warm_programs: int = 0            # compiled after the steady point
    exchanges: int = 0
    waves: int = 0
    replays: int = 0
    oracle: str = "exact"
    oracle_ok: bool = False
    backend: str = ""
    extra: Dict = field(default_factory=dict)

    def finalize(self, rows: int) -> None:
        """Fill the derived rate fields from the accumulated walls."""
        self.wall_ms = sum(self.phases.values())
        self.rows_per_s = {
            ph: round(rows / (ms / 1e3), 1) if ms > 0 else 0.0
            for ph, ms in self.phases.items()}
        if self.wall_ms > 0:
            self.rows_per_s["total"] = round(
                rows / (self.wall_ms / 1e3), 1)
        if self.budget_bytes:
            self.scale_ratio = round(self.bytes_in / self.budget_bytes, 2)

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def to_json(self, indent: Optional[int] = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)


class PhaseWalls:
    """Accumulates per-phase walls and publishes them as the labeled
    ``workload.phase.ms`` counters the spill_bound doctor rule reads.
    One instance per pipeline run; ``phase(name)`` is a context manager
    (re-enterable — chunked ingest opens it once per chunk)."""

    def __init__(self, workload: str, metrics=None):
        self.workload = workload
        self.ms: Dict[str, float] = {ph: 0.0 for ph in PHASES}
        self._metrics = metrics

    class _Span:
        def __init__(self, walls: "PhaseWalls", name: str):
            self._w, self._name = walls, name

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._w.ms[self._name] = self._w.ms.get(self._name, 0.0) \
                + (time.perf_counter() - self._t0) * 1e3
            return False

    def phase(self, name: str) -> "PhaseWalls._Span":
        if name not in PHASES:
            raise ValueError(f"unknown phase {name!r}; want one of "
                             f"{PHASES}")
        return self._Span(self, name)

    def add(self, name: str, ms: float) -> None:
        """Fold an externally-timed wall in (e.g. the report's
        ``merge_ms``, blocked-timed inside the read)."""
        self.ms[name] = self.ms.get(name, 0.0) + float(ms)

    def publish(self, rows: int) -> None:
        """Counters: workload.rows{workload=} + workload.phase.ms
        {workload=,phase=} (plus the unlabeled totals) — the doctor's
        spill_bound evidence. Publishing is cumulative-counter
        semantics, so repeat runs in one process accumulate like every
        other counter family."""
        if self._metrics is None:
            return
        from sparkucx_tpu.utils.metrics import (C_WORKLOAD_PHASE_MS,
                                                C_WORKLOAD_ROWS, labeled)
        self._metrics.inc(C_WORKLOAD_ROWS, float(rows))
        self._metrics.inc(labeled(C_WORKLOAD_ROWS,
                                  workload=self.workload), float(rows))
        for ph, ms in self.ms.items():
            if ms <= 0.0:
                continue
            self._metrics.inc(C_WORKLOAD_PHASE_MS, ms)
            self._metrics.inc(labeled(C_WORKLOAD_PHASE_MS,
                                      workload=self.workload, phase=ph),
                              ms)


class MemoryBudget:
    """The pool-watermark force-spill valve of chunked ingest.

    The per-writer ``spill.threshold`` bounds ONE writer's staging; N
    concurrent writers can still sum past the budget before any of them
    crosses it. After every ingest chunk the pipelines call
    :meth:`maybe_spill`: when the pool's checked-out bytes exceed
    ``watermark × budget``, every writer's staged batches move to its
    sealed spill files NOW (``MapOutputWriter.spill()`` — the same
    ``SpillFiles`` path, torn-write-proof), returning the arena blocks
    and keeping the watermark under budget."""

    def __init__(self, pool, budget_bytes: int, watermark: float = 0.5):
        if budget_bytes <= 0:
            raise ValueError(
                f"budget_bytes must be positive, got {budget_bytes}")
        self.pool = pool
        self.budget_bytes = int(budget_bytes)
        self.watermark = float(watermark)
        self.forced_spills = 0
        self.forced_bytes = 0

    def over_watermark(self) -> bool:
        in_use = self.pool.stats().get("in_use_bytes", 0)
        return in_use >= self.watermark * self.budget_bytes

    def maybe_spill(self, writers) -> int:
        """Force-spill every writer's staged batches when the pool
        watermark crossed the budget line; returns bytes moved."""
        if not self.over_watermark():
            return 0
        moved = 0
        for w in writers:
            moved += w.spill()
        if moved:
            self.forced_spills += 1
            self.forced_bytes += moved
        return moved


def workload_conf_overrides(budget_bytes: int, *, num_mappers: int = 8,
                            width_words: int = 6,
                            wave_depth: int = 2) -> Dict[str, str]:
    """Budget-derived conf for an external-memory pipeline: per-writer
    spill threshold at ``budget / (4 × mappers)`` (so even all writers
    staged at once sit under a quarter of the budget before the
    force-spill valve engages) and ``a2a.waveRows`` sized so the wave
    pipeline's pinned pack footprint (``depth × shards × waveRows ×
    width × 4 B``, pow2-rounded by the pool) stays under a quarter of
    the budget too — the two quarters together keep the POOL watermark
    the valve reads under the budget line."""
    num_shards = 8          # the virtual-device mesh every harness runs
    per_writer = max(64 << 10, budget_bytes // (4 * num_mappers))
    wave_rows = max(1024, budget_bytes
                    // (4 * wave_depth * num_shards * width_words * 4))
    return {
        "spark.shuffle.tpu.spill.threshold": str(per_writer),
        "spark.shuffle.tpu.a2a.waveRows": str(wave_rows),
        "spark.shuffle.tpu.a2a.waveDepth": str(wave_depth),
    }


def _registry() -> Dict[str, Callable]:
    # late imports: the workload modules import back into this package
    from sparkucx_tpu.workloads.groupby import groupby_pipeline
    from sparkucx_tpu.workloads.join import join_pipeline
    from sparkucx_tpu.workloads.terasort import terasort_pipeline
    return {
        "terasort": terasort_pipeline,
        "groupby": groupby_pipeline,
        "join": join_pipeline,
    }


class _Workloads(dict):
    """Lazy name→runner registry (populated on first access so
    importing :mod:`sparkucx_tpu.workloads` stays cheap)."""

    def _ensure(self):
        if not dict.__len__(self):
            super().update(_registry())

    def __getitem__(self, k):
        self._ensure()
        return super().__getitem__(k)

    def __iter__(self):
        self._ensure()
        return super().__iter__()

    def __len__(self):
        self._ensure()
        return super().__len__()

    def __contains__(self, k):
        self._ensure()
        return super().__contains__(k)

    def keys(self):
        self._ensure()
        return super().keys()

    def items(self):
        self._ensure()
        return super().items()


WORKLOADS = _Workloads()


def run_workload(name: str, *, budget_mb: float = 16.0,
                 scale: float = 1.0, seed: int = 0,
                 conf_overrides: Optional[Dict[str, str]] = None,
                 **kwargs) -> WorkloadReport:
    """Run one registered pipeline end to end, owning the node/manager
    lifecycle — the CLI subcommand's engine (``python -m sparkucx_tpu
    workload <name>``). ``scale`` multiplies the ≥10×-budget default
    dataset; conf is derived from the budget
    (:func:`workload_conf_overrides`) with ``conf_overrides`` layered
    on top (the CLI's ``--conf`` pins, e.g. ``a2a.impl``). Conf keys
    ``workload.budgetMb`` / ``workload.scale`` in the overrides take
    the same role for conf-driven callers."""
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager

    if name not in WORKLOADS:
        raise KeyError(
            f"unknown workload {name!r}; registered: "
            f"{sorted(WORKLOADS.keys())}")
    overrides = dict(conf_overrides or {})
    budget_mb = float(overrides.pop(
        "spark.shuffle.tpu.workload.budgetMb", budget_mb))
    scale = float(overrides.pop(
        "spark.shuffle.tpu.workload.scale", scale))
    budget_bytes = int(budget_mb * (1 << 20))
    conf_map = workload_conf_overrides(budget_bytes)
    conf_map.update(overrides)
    conf = TpuShuffleConf(conf_map, use_env=False)
    # TpuNode.start is an idempotent singleton: when a host process
    # already runs a node, ride it (the workload conf governs the
    # MANAGER's spill/wave planes either way) and do NOT close what
    # this call did not create
    created = TpuNode._instance is None or TpuNode._instance._closed
    node = TpuNode.start(conf)
    manager = TpuShuffleManager(node, conf)
    try:
        runner = WORKLOADS[name]
        return runner(manager, budget_bytes=budget_bytes, scale=scale,
                      seed=seed, **kwargs)
    finally:
        manager.stop()
        if created:
            node.close()


def _program_count() -> int:
    """Compiled-step-program counter read (GLOBAL registry — where the
    stepcache counts), shared by the pipelines' warm-recompile gates."""
    from sparkucx_tpu.utils.metrics import COMPILE_PROGRAMS, GLOBAL_METRICS
    return int(GLOBAL_METRICS.get(COMPILE_PROGRAMS))


def _spill_counters() -> tuple:
    from sparkucx_tpu.utils.metrics import (C_SPILL_BYTES, C_SPILL_COUNT,
                                            GLOBAL_METRICS)
    return (int(GLOBAL_METRICS.get(C_SPILL_BYTES)),
            int(GLOBAL_METRICS.get(C_SPILL_COUNT)))


def sampled_key_digest(keys: np.ndarray, stride: int = 1) -> tuple:
    """(digest, count) of the value-sampled key multiset — the scalable
    terasort oracle's third leg. Sampling is BY VALUE (rows whose
    splitmix64 mix lands in the 1/stride residue class), never by
    position, so the digest is invariant under every reorder the
    shuffle performs and the emit side samples exactly the rows the
    ingest side did. ``stride=1`` digests every row (still O(1)
    memory). Sums are mod 2^64 — order-free, split-free."""
    from sparkucx_tpu.shuffle.integrity import _mix64, digest_sum
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if stride > 1:
        mixed = _mix64(keys.view(np.uint64))
        keys = keys[mixed % np.uint64(stride) == 0]
    return digest_sum(keys, None), int(keys.shape[0])
