"""Multi-tenant service plane — quotas, priority classes, fair share.

The reference serves exactly one Spark app per executor: its shuffle
service is registered per-application and every policy (fetch window,
retry budget) is global to that app (ref: CommonUcxShuffleManager
registers one driver table per app). A serving tier multiplexing N
concurrent shuffles of wildly different sizes over ONE device mesh needs
what Exoshuffle (PAPERS.md) argues shuffle-as-a-library exists to
provide: *policy diversity per workload*. This module is that layer:

* :class:`TenantSpec` / :class:`TenantRegistry` — per-tenant policy
  resolved purely from conf (``spark.shuffle.tpu.tenant.*``): priority
  class (a weight multiplier in fair-share scheduling), an optional
  per-tenant admission quota layered UNDER the global
  ``a2a.maxBytesInFlight``, per-tenant replay budgets and integrity
  levels, async in-flight caps, and a wave-depth override.
* :class:`FairShareQueue` — the deficit-round-robin admission queue that
  replaces the manager's FIFO deferral list: when exchanges defer past
  the in-flight cap, grants interleave ACROSS tenants in proportion to
  priority weight instead of strictly by arrival, so a whale shuffle
  parked at the head of the queue can no longer starve every minnow
  behind it (the head-of-line problem Spark's FIFO fetch deferral has
  within one app, promoted to a cross-tenant contract).
* :class:`AsyncShuffleExecutor` / :class:`ShuffleFuture` — the async
  lifecycle both facades expose as ``submit_async``/``read_async``: a
  serving tier overlaps hundreds of small exchanges without blocking a
  thread per shuffle, with per-tenant in-flight caps enforced at submit.

Conf surface (all under ``spark.shuffle.tpu.``)::

    tenant.id                      this process's default tenant ("default")
    tenant.priority                default priority class (high|normal|batch)
    tenant.fairShare               fair-share admission on/off (default on;
                                   off = the historical FIFO queue)
    tenant.asyncWorkers            async read workers (default 4); in
                                   distributed mode K workers require the
                                   agreed submission order below
    tenant.asyncAgreedOrder        distributed K-worker async: agree the
                                   per-batch submission order collectively
                                   (default on; off clamps the pool to 1
                                   worker, warn-once — see
                                   AsyncShuffleExecutor)
    tenant.<id>.priority           per-tenant priority class
    tenant.<id>.maxBytesInFlight   per-tenant admission quota (0 = only the
                                   global cap applies)
    tenant.<id>.maxInflightReads   async reads in flight per tenant
                                   (0 = unlimited); submit blocks past it
    tenant.<id>.replayBudget       failure.replayBudget override
    tenant.<id>.integrity.verify   integrity.verify override (off|staged|full)
    tenant.<id>.waveDepth          a2a.waveDepth override
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, Optional

from sparkucx_tpu.utils.logging import get_logger

log = get_logger("shuffle.tenancy")

# Priority classes and their fair-share weight multipliers: a high tenant
# accrues deficit 4x as fast as a batch tenant, so over any contention
# window it is granted ~4x the admission bytes. The classes are a closed
# set (like a2a.impl) — a typo'd priority must fail at construction, not
# silently schedule as an unknown zero-weight class.
PRIORITY_WEIGHTS: Dict[str, int] = {"high": 4, "normal": 2, "batch": 1}
PRIORITIES = tuple(PRIORITY_WEIGHTS)

DEFAULT_TENANT = "default"

# One DRR quantum: the deficit a tenant accrues (times its weight) per
# scheduling round. Byte-denominated because grants are byte-denominated;
# 1 MiB keeps small exchanges granted within a round or two while a
# multi-hundred-MB whale accrues across rounds — during which the minnows
# it would have starved are granted ahead of it.
DRR_QUANTUM = 1 << 20


def validate_priority(value: str, conf_key: str = "tenant.priority") -> str:
    v = str(value).strip().lower()
    if v not in PRIORITY_WEIGHTS:
        raise ValueError(
            f"{conf_key}={value!r}: want one of {PRIORITIES}")
    return v


@dataclass(frozen=True)
class TenantSpec:
    """Resolved policy for one tenant. ``None`` fields mean "inherit the
    global conf" — the manager resolves them at the use site so a global
    conf change keeps applying to tenants without overrides."""

    tenant_id: str
    priority: str = "normal"
    # admission quota UNDER the global a2a.maxBytesInFlight (0 = only
    # the global cap applies). A single exchange larger than the quota
    # is admitted when the tenant has nothing else in flight — the same
    # never-deadlock rule the global cap carries.
    max_bytes_in_flight: int = 0
    # async submissions in flight at once (0 = unlimited); enforced by
    # AsyncShuffleExecutor at submit time
    max_inflight_reads: int = 0
    replay_budget: Optional[int] = None        # None = failure.replayBudget
    integrity_verify: Optional[str] = None     # None = integrity.verify
    wave_depth: Optional[int] = None           # None = a2a.waveDepth

    @property
    def weight(self) -> int:
        return PRIORITY_WEIGHTS[self.priority]


class TenantRegistry:
    """Per-tenant policy resolved from conf, cached per tenant id.

    Tenancy is DECLARATIVE: a tenant exists the moment a shuffle is
    registered under its id (``register_shuffle(..., tenant=...)`` or the
    conf default ``tenant.id``); the registry only answers "what policy
    applies to this id". Unknown ids get the conf-default priority and
    no overrides — the permissive posture the reference takes for conf
    keys generally (SparkConf never rejects an app id)."""

    def __init__(self, conf):
        self._conf = conf
        self._lock = threading.Lock()
        self._specs: Dict[str, TenantSpec] = {}
        self.default_id = str(
            conf._get("tenant.id", DEFAULT_TENANT)).strip() or DEFAULT_TENANT
        self.default_priority = validate_priority(
            conf._get("tenant.priority", "normal"),
            "spark.shuffle.tpu.tenant.priority")
        self.fair_share = conf.get_bool("tenant.fairShare", True)

    def resolve(self, tenant: Optional[str]) -> str:
        """Caller-supplied tenant id or the conf default."""
        t = (tenant or "").strip()
        return t or self.default_id

    def spec(self, tenant: Optional[str]) -> TenantSpec:
        tid = self.resolve(tenant)
        with self._lock:
            spec = self._specs.get(tid)
        if spec is not None:
            return spec
        spec = self._load_spec(tid)
        with self._lock:
            # first resolution wins (idempotent — conf is immutable here)
            return self._specs.setdefault(tid, spec)

    def _load_spec(self, tid: str) -> TenantSpec:
        conf = self._conf
        pre = f"tenant.{tid}."
        key = f"spark.shuffle.tpu.{pre}"
        priority = validate_priority(
            conf._get(pre + "priority", self.default_priority),
            key + "priority")
        quota = conf.get_bytes(pre + "maxBytesInFlight", 0)
        if quota < 0:
            raise ValueError(f"{key}maxBytesInFlight={quota}: want >= 0")
        inflight = conf.get_int(pre + "maxInflightReads", 0)
        if inflight < 0:
            raise ValueError(f"{key}maxInflightReads={inflight}: want >= 0")
        budget_raw = conf._get(pre + "replayBudget", "")
        budget = None
        if str(budget_raw).strip():
            budget = int(budget_raw)
            if budget < 0:
                raise ValueError(f"{key}replayBudget={budget}: want >= 0")
        verify_raw = str(conf._get(pre + "integrity.verify", "")).strip()
        verify = None
        if verify_raw:
            from sparkucx_tpu.shuffle.integrity import validate_verify_level
            verify = validate_verify_level(verify_raw,
                                           conf_key=key + "integrity.verify")
        depth_raw = str(conf._get(pre + "waveDepth", "")).strip()
        depth = None
        if depth_raw:
            from sparkucx_tpu.shuffle.plan import WAVE_DEPTH_RANGE
            depth = int(depth_raw)
            if not WAVE_DEPTH_RANGE[0] <= depth <= WAVE_DEPTH_RANGE[1]:
                raise ValueError(
                    f"{key}waveDepth={depth}: want "
                    f"{WAVE_DEPTH_RANGE[0]}..{WAVE_DEPTH_RANGE[1]}")
        return TenantSpec(tid, priority, quota, inflight, budget, verify,
                          depth)

    def known_tenants(self):
        with self._lock:
            return sorted(self._specs)


class FairShareQueue:
    """Deficit-round-robin admission queue across tenants.

    Replaces the manager's FIFO ticket list: tickets enqueue per tenant
    (FIFO *within* a tenant — submit order is the collective order and
    must never reorder inside one tenant), and :meth:`grantable` selects
    the next ticket to admit by DRR — each tenant with queued work
    accrues ``DRR_QUANTUM x priority weight`` of deficit per scheduling
    round and is granted its head ticket once the deficit covers the
    ticket's bytes. A whale ticket therefore waits out the rounds its
    size demands while minnow tickets (covered within a round) are
    granted past it; weights bias the byte share toward high-priority
    tenants. A tenant whose queue empties forfeits its remaining deficit
    (the classic DRR rule — credit must not be hoarded across idle
    periods).

    External synchronization: every method is called under the
    manager's admission lock (the same discipline the FIFO list had).
    """

    def __init__(self, registry: TenantRegistry,
                 quantum: int = DRR_QUANTUM):
        self._registry = registry
        self._quantum = int(quantum)
        self._queues: Dict[str, deque] = {}     # tid -> deque[(ticket, nb)]
        self._order: list = []                  # round-robin tenant order
        self._deficit: Dict[str, float] = {}
        self._where: Dict[int, str] = {}        # ticket -> tid
        self._rr = 0                            # round-robin pointer
        # has the tenant under the pointer received its arrival quantum
        # for the CURRENT visit? Serve-while-covered must not re-accrue
        # per grant, and repeated eligibility CHECKS (every waiter
        # re-polls grantable) must not accrue at all — scan frequency
        # would otherwise set the shares instead of the weights.
        self._charged = False
        # cached head: computed once per grant cycle, invalidated by
        # pop/discard of the head ticket — NOT by capacity checks
        self._head = None                       # (ticket, tid, nb)

    def __len__(self) -> int:
        return len(self._where)

    def __bool__(self) -> bool:
        return bool(self._where)

    def __contains__(self, ticket: int) -> bool:
        return ticket in self._where

    def enqueue(self, ticket: int, tenant: str, nbytes: int) -> None:
        q = self._queues.get(tenant)
        if q is None:
            q = self._queues[tenant] = deque()
            self._order.append(tenant)
            self._deficit.setdefault(tenant, 0.0)
        q.append((ticket, int(nbytes)))
        self._where[ticket] = tenant

    def discard(self, ticket: int) -> None:
        """Remove an abandoned ticket wherever it sits (the release-
        while-queued path). Missing tickets are a no-op, like
        list.remove guarded by ValueError was."""
        tid = self._where.pop(ticket, None)
        if tid is None:
            return
        if self._head is not None and self._head[0] == ticket:
            self._head = None
        q = self._queues[tid]
        for item in q:
            if item[0] == ticket:
                q.remove(item)
                break
        if not q:
            self._drop_tenant(tid)

    def _drop_tenant(self, tid: str) -> None:
        # an emptied tenant forfeits its remaining deficit (the classic
        # DRR rule — credit must not be hoarded across idle periods)
        self._queues.pop(tid, None)
        self._deficit.pop(tid, None)
        i = self._order.index(tid)
        self._order.remove(tid)
        if i < self._rr:
            self._rr -= 1
        elif i == self._rr:
            self._charged = False
        if self._order:
            self._rr %= len(self._order)
        else:
            self._rr = 0
        if self._head is not None and self._head[1] == tid:
            self._head = None

    def _weight(self, tid: str) -> int:
        return self._registry.spec(tid).weight

    def _ensure_head(self):
        """Compute (and cache) the next ticket DRR serves. Deficit
        accrues ONLY when the round-robin pointer ARRIVES at a tenant —
        never on repeated eligibility checks (every blocked waiter
        re-polls ``grantable``, and scan frequency must not set the
        shares) and never while serve-while-covered keeps the pointer
        on a tenant spending down its credit. When a full cycle covers
        no head (a whale ticket many quanta deep), virtual time
        fast-forwards: every queued tenant receives the exact number of
        weighted quanta that makes the NEAREST head servable — O(T) and
        work-conserving instead of O(rounds) re-scans."""
        if self._head is not None or not self._order:
            return self._head
        for _attempt in range(2):
            for _k in range(len(self._order) + 1):
                tid = self._order[self._rr]
                if not self._charged:
                    self._deficit[tid] += self._quantum * self._weight(tid)
                    self._charged = True
                ticket, nb = self._queues[tid][0]
                if self._deficit[tid] >= nb:
                    self._head = (ticket, tid, nb)
                    return self._head
                # not covered: pointer moves on, tenant keeps its credit
                self._rr = (self._rr + 1) % len(self._order)
                self._charged = False
            # full cycle, nothing covered — fast-forward virtual time
            rounds = max(1, min(
                math.ceil((q[0][1] - self._deficit[t])
                          / (self._quantum * self._weight(t)))
                for t, q in self._queues.items()))
            for t in self._queues:
                self._deficit[t] += rounds * self._quantum \
                    * self._weight(t)
        return self._head

    def grantable(self, fits: Callable[[str, int], bool],
                  quota_blocked: Optional[Callable[[str, int], bool]]
                  = None) -> Optional[int]:
        """The ticket DRR serves next, if it currently fits capacity;
        else None. ``fits(tenant, nbytes)`` is the capacity predicate
        (global room AND the tenant's own quota room). A head whose
        tenant is blocked on its OWN quota (``quota_blocked`` true —
        global room exists, the tenant's quota refuses) must not
        head-of-line-block everyone else: the other tenants'
        already-covered fronts are offered in pointer order as a bypass
        (the blocked tenant keeps its head position and credit for when
        its quota frees). A head blocked by the GLOBAL cap is NOT
        bypassed: it earned the next grant, and letting smaller tickets
        stream past it while it waits for in-flight bytes to drain
        would starve a bigger-than-remaining-room exchange forever —
        the convoy until the drain completes is the price of
        liveness."""
        head = self._ensure_head()
        if head is None:
            return None
        ticket, tid, nb = head
        if fits(tid, nb):
            return ticket
        if quota_blocked is None or not quota_blocked(tid, nb):
            return None
        candidates = []
        for k in range(1, len(self._order)):
            other = self._order[(self._rr + k) % len(self._order)]
            oticket, onb = self._queues[other][0]
            if not fits(other, onb):
                continue
            if self._deficit[other] >= onb:
                return oticket
            candidates.append((other, oticket, onb))
        if not candidates:
            return None
        # the head's tenant may stay quota-blocked indefinitely — the
        # unblocked tenants must not idle capacity behind it. Fast-
        # forward virtual time among THEM exactly to the nearest
        # servable front (idempotent: after the jump a candidate is
        # covered, so repeated checks take the covered branch above —
        # no scan-frequency inflation)
        rounds = max(1, min(
            math.ceil((onb - self._deficit[t])
                      / (self._quantum * self._weight(t)))
            for t, _tk, onb in candidates))
        for t, _tk, _onb in candidates:
            self._deficit[t] += rounds * self._quantum * self._weight(t)
        for t, tk, onb in candidates:
            if self._deficit[t] >= onb:
                return tk
        return None

    def pop(self, ticket: int, nbytes: int) -> None:
        """Consume a granted ticket: charge its bytes against the
        tenant's deficit; an emptied tenant forfeits leftover credit.
        The pointer STAYS on the tenant (serve-while-covered — the
        second half of DRR); _ensure_head advances it when the credit
        runs out."""
        tid = self._where.pop(ticket, None)
        if tid is None:
            return
        if self._head is not None and self._head[0] == ticket:
            self._head = None
        q = self._queues[tid]
        if q and q[0][0] == ticket:
            q.popleft()
        else:                                   # defensive: out-of-order
            for item in q:
                if item[0] == ticket:
                    q.remove(item)
                    break
        if not q:
            self._drop_tenant(tid)
        else:
            self._deficit[tid] = max(0.0, self._deficit[tid] - nbytes)

    def depth(self) -> int:
        return len(self._where)

    def tenants_queued(self):
        return list(self._order)


class FifoAdmitQueue:
    """The historical strictly-FIFO deferral order behind the same
    interface (``tenant.fairShare=false`` — the escape hatch)."""

    def __init__(self):
        self._q: deque = deque()                # (ticket, tenant, nbytes)

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)

    def __contains__(self, ticket: int) -> bool:
        return any(t == ticket for t, _, _ in self._q)

    def enqueue(self, ticket: int, tenant: str, nbytes: int) -> None:
        self._q.append((ticket, tenant, int(nbytes)))

    def discard(self, ticket: int) -> None:
        for item in self._q:
            if item[0] == ticket:
                self._q.remove(item)
                return

    def grantable(self, fits, quota_blocked=None) -> Optional[int]:
        if not self._q:
            return None
        ticket, tenant, nb = self._q[0]
        return ticket if fits(tenant, nb) else None

    def pop(self, ticket: int, nbytes: int) -> None:
        self.discard(ticket)

    def depth(self) -> int:
        return len(self._q)

    def tenants_queued(self):
        seen = []
        for _, t, _ in self._q:
            if t not in seen:
                seen.append(t)
        return seen


def agreed_submission_order(pending, weight_of) -> list:
    """Deterministic tenant-DRR dispatch order over ONE async batch.

    ``pending`` — ``(seq, tenant_id)`` pairs in local submission order;
    ``weight_of(tenant_id)`` — the tenant's priority weight. Returns the
    seqs in dispatch order: round-robin over tenants in first-appearance
    order, each tenant serving up to ``weight`` queued reads per round
    (count-denominated DRR — async reads are request-shaped, so the
    quantum is a read, not a byte), FIFO within a tenant (submit order
    is the collective order and must never reorder inside one tenant).

    Pure function of the batch: every process holding the same
    (seq, tenant) pairs — the standing SPMD submission discipline —
    computes the SAME order, which the executor then confirms over the
    agreement channel before dispatching."""
    queues: Dict[str, deque] = {}
    order = []
    for seq, tid in pending:
        q = queues.get(tid)
        if q is None:
            q = queues[tid] = deque()
            order.append(tid)
        q.append(seq)
    out = []
    while queues:
        for tid in list(order):
            q = queues.get(tid)
            if q is None:
                continue
            for _ in range(max(1, int(weight_of(tid)))):
                if not q:
                    break
                out.append(q.popleft())
            if not q:
                del queues[tid]
                order.remove(tid)
    return out


class ShuffleFuture:
    """Handle to one async shuffle read — ``done()`` / ``result()`` /
    ``exception()`` / ``add_done_callback()`` over the facade read that
    produced it. ``wall_ms`` (after completion) is the read's execution
    wall on the worker, EXCLUDING queue wait; ``queued_ms`` is the time
    it waited for a worker."""

    __slots__ = ("_fut", "_times", "tenant", "shuffle_id")

    def __init__(self, fut, times: Dict[str, float], tenant: str,
                 shuffle_id: int):
        self._fut = fut
        self._times = times
        self.tenant = tenant
        self.shuffle_id = shuffle_id

    @property
    def wall_ms(self) -> float:
        return self._times.get("wall_ms", 0.0)

    @property
    def queued_ms(self) -> float:
        return self._times.get("queued_ms", 0.0)

    def done(self) -> bool:
        return self._fut.done()

    def result(self, timeout: Optional[float] = None):
        return self._fut.result(timeout)

    def exception(self, timeout: Optional[float] = None):
        return self._fut.exception(timeout)

    def add_done_callback(self, fn) -> None:
        self._fut.add_done_callback(lambda _f: fn(self))


class AsyncShuffleExecutor:
    """The async read plane behind ``submit_async``/``read_async``.

    Single-process mode runs ``tenant.asyncWorkers`` worker threads
    (default 4) calling the facade read concurrently — overlap is real
    (N exchanges in flight at once, arbitrated by the admission plane)
    and bounded per tenant by ``tenant.<id>.maxInflightReads``.

    Distributed mode keeps K workers by making the dispatch order a
    COLLECTIVE decision (``tenant.asyncAgreedOrder``, default on): a
    single dispatcher thread drains submissions in batches, agrees the
    batch size over the agreement primitive (reduce-min of the pending
    counts — the straggler's view bounds the batch), computes the
    tenant-DRR order with :func:`agreed_submission_order` and CONFIRMS
    it unanimously (``async.order``) before releasing the batch to the
    pool in that order. A divergent order (one process submitted
    different work, or a different asyncWorkers/priority conf) fails
    ALL of the batch's futures with the typed divergence error naming
    the dissenter instead of deadlocking the mesh mid-collective.

    The agreed order alone is NOT enough: once released, each read's
    body issues its own collectives (schema gathers, wave agreements,
    per-tier programs, overflow rounds), and K OS-scheduled worker
    threads would interleave those differently per process — the exact
    cross-process hazard the historical width-1 clamp existed to
    prevent. So the agreed order is ENFORCED at execution: every
    dispatched read (and the dispatcher's own agreement rounds) holds a
    ticket from a per-process :class:`CollectiveTurnstile`, issued in
    the agreed sequence; a read's collective section — conservatively
    its whole body, since replay re-enters collectives on failure —
    runs only when every earlier ticket has released. Collective
    sections therefore execute in the identical order on every process
    while the K workers still overlap submission, queueing and future
    fan-out (a serving tier never blocks a thread per shuffle, tenant
    caps and the DRR schedule stay cross-process deterministic);
    overlapping the device phase of one read with the collective
    issue of the next needs a finer-grained end-of-collectives hook in
    the manager and is deliberately NOT attempted here.

    ``tenant.asyncAgreedOrder=false`` restores the historical width-1
    clamp (execution order == submission order by construction, no
    agreement traffic) — warned once, since a conf asking for K workers
    and silently getting 1 reads as unrequested serialization
    (ExchangeReport.async_workers carries the effective width).

    Per-tenant in-flight caps are enforced AT SUBMIT: a tenant at its
    cap blocks in ``submit`` until one of its reads resolves (counted in
    ``shuffle.submit.throttled.count{tenant=...}``) — backpressure, not
    an error, so a serving tier's request loop self-regulates. The cap
    check is deterministic given the submission order, so distributed
    callers throttle identically."""

    def __init__(self, conf, registry: TenantRegistry, metrics,
                 distributed: bool):
        self._registry = registry
        self._metrics = metrics
        workers = conf.get_int("tenant.asyncWorkers", 4)
        if workers < 1:
            raise ValueError(
                f"spark.shuffle.tpu.tenant.asyncWorkers={workers}: "
                f"want >= 1")
        self._agreed_order = conf.get_bool("tenant.asyncAgreedOrder", True)
        self._distributed = bool(distributed)
        if distributed and workers != 1 and not self._agreed_order:
            log.warning(
                "tenant.asyncWorkers=%d clamped to 1: "
                "tenant.asyncAgreedOrder=false opts out of the "
                "collectively agreed submission order, and distributed "
                "async reads without it must execute strictly in "
                "submission order — set "
                "spark.shuffle.tpu.tenant.asyncAgreedOrder=true "
                "(default) to run K workers over the agreement channel",
                workers)
            workers = 1
        self.workers = workers
        # the dispatcher (agreed-order batching) engages only when the
        # distributed pool is actually wider than one worker
        self._dispatching = distributed and workers > 1 \
            and self._agreed_order
        self._pool = None
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._inflight: Dict[str, int] = {}
        self._closed = False
        self._seq = 0                 # local submission counter
        self._queue: deque = deque()  # (seq, tid, run, outer_future)
        self._dispatcher = None
        self._turnstile = None
        if self._dispatching:
            from sparkucx_tpu.shuffle.agreement import CollectiveTurnstile
            self._turnstile = CollectiveTurnstile()

    def _executor(self):
        with self._lock:
            if self._closed:
                raise RuntimeError("async executor is stopped")
            if self._pool is None:
                from concurrent.futures import ThreadPoolExecutor
                self._pool = ThreadPoolExecutor(
                    max_workers=self.workers,
                    thread_name_prefix="sxt-async")
            return self._pool

    def inflight(self, tenant: str) -> int:
        with self._lock:
            return self._inflight.get(tenant, 0)

    def submit(self, fn, tenant: Optional[str], shuffle_id: int,
               timeout: Optional[float] = None) -> ShuffleFuture:
        """Run ``fn()`` on the async plane as ``tenant``; returns a
        :class:`ShuffleFuture`. Blocks at the tenant's in-flight cap."""
        from sparkucx_tpu.utils.metrics import labeled
        tid = self._registry.resolve(tenant)
        cap = self._registry.spec(tid).max_inflight_reads
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            throttled = False
            while cap and self._inflight.get(tid, 0) >= cap:
                if self._closed:
                    # stop() raced this submitter: its slot will never
                    # free (queued runs were cancelled) — raise instead
                    # of waiting on a drained pool forever
                    raise RuntimeError("async executor is stopped")
                if not throttled:
                    throttled = True
                    self._metrics.inc(
                        labeled("shuffle.submit.throttled.count",
                                tenant=tid), 1)
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"tenant {tid!r} is at "
                        f"tenant.{tid}.maxInflightReads={cap} and no "
                        f"read resolved within {timeout}s")
                self._cv.wait(1.0 if remaining is None
                              else min(remaining, 1.0))
            self._inflight[tid] = self._inflight.get(tid, 0) + 1
        t_submit = time.perf_counter()
        times: Dict[str, float] = {}

        def _release_slot():
            with self._cv:
                n = self._inflight.get(tid, 1) - 1
                if n > 0:
                    self._inflight[tid] = n
                else:
                    self._inflight.pop(tid, None)
                self._cv.notify_all()

        def run():
            t0 = time.perf_counter()
            times["queued_ms"] = (t0 - t_submit) * 1e3
            try:
                return fn()
            finally:
                times["wall_ms"] = (time.perf_counter() - t0) * 1e3
                _release_slot()

        if self._dispatching:
            # agreed-order mode: the run parks on the dispatcher queue;
            # the dispatcher batches, agrees the DRR order collectively
            # and releases the batch to the pool in that order
            from concurrent.futures import Future
            outer = Future()
            with self._cv:
                if self._closed:
                    _release_slot()
                    raise RuntimeError("async executor is stopped")
                self._seq += 1
                self._queue.append((self._seq, tid, run, outer,
                                    _release_slot))
                if self._dispatcher is None:
                    self._dispatcher = threading.Thread(
                        target=self._dispatch_loop,
                        name="sxt-async-dispatch", daemon=True)
                    self._dispatcher.start()
                self._cv.notify_all()
            return ShuffleFuture(outer, times, tid, shuffle_id)

        try:
            fut = self._executor().submit(run)
        except BaseException:
            _release_slot()
            raise
        # a queued run CANCELLED by stop(cancel_futures=True) never
        # executes its finally — release its slot here, or submitters
        # blocked at the tenant cap would wait on it forever
        fut.add_done_callback(
            lambda f: _release_slot() if f.cancelled() else None)
        return ShuffleFuture(fut, times, tid, shuffle_id)

    # -- agreed-order dispatch (distributed K-worker mode) -----------------
    def _dispatch_loop(self):
        """Single dispatcher: drains the submission queue in batches
        whose size and tenant-DRR order are AGREED across processes
        before any read of the batch enters the pool. The dispatcher's
        own agreement rounds and every dispatched read run under
        turnstile tickets issued in the agreed sequence, so the
        per-process collective stream is identical everywhere
        regardless of how the OS schedules the worker threads."""
        try:
            while True:
                with self._cv:
                    while not self._queue and not self._closed:
                        self._cv.wait(0.2)
                    if self._closed:
                        return
                    n_local = len(self._queue)
                try:
                    self._dispatch_batch(n_local)
                except Exception as e:
                    if getattr(e, "_sxt_batch_failed", False):
                        # the fault struck AFTER the batch was popped:
                        # those futures are already resolved and their
                        # tickets released — reads still queued (or
                        # submitted since) were never part of the failed
                        # order, so keep serving them
                        log.warning("async dispatch batch failed; "
                                    "dispatcher continues", exc_info=True)
                        continue
                    log.error("async dispatcher died; failing queued "
                              "reads", exc_info=True)
                    # unregister BEFORE draining, under one lock hold:
                    # a submit that enqueues after this sees no
                    # dispatcher and starts a fresh one — only reads
                    # already queued behind the dead dispatcher fail
                    with self._cv:
                        drained, self._queue = list(self._queue), deque()
                        if self._dispatcher is threading.current_thread():
                            self._dispatcher = None
                    self._fail_items(drained, RuntimeError(
                        "async agreed-order dispatcher failed"))
                    return
        finally:
            # a dead dispatcher unregisters itself so the next submit
            # can start a fresh one (stop() sets _closed, under which
            # submit refuses instead)
            with self._cv:
                if self._dispatcher is threading.current_thread():
                    self._dispatcher = None

    def _dispatch_batch(self, n_local: int):
        import numpy as np
        from sparkucx_tpu.shuffle.agreement import (
            AgreementDivergenceError, agree)
        conf_key = "spark.shuffle.tpu.tenant.asyncAgreedOrder"
        gate = self._turnstile
        my = gate.issue()
        try:
            # the dispatcher's agreement rounds take their own turn, so
            # they can never interleave with a still-running read's
            # collectives (batch N+1's rounds wait out batch N)
            gate.acquire(my)
            # reduce-min: the straggler's pending count bounds the
            # batch, so no process dispatches work a peer has not
            # submitted yet (the standing SPMD discipline: all
            # processes submit the same reads in the same local order)
            n = int(agree("async.batch",
                          np.array([n_local], dtype=np.int64),
                          reduce="min", conf_key=conf_key)[0])
        except BaseException:
            gate.release(my)
            raise
        if n < 1:
            gate.release(my)
            return
        with self._cv:
            take = min(n, len(self._queue))
            batch = [self._queue.popleft() for _ in range(take)]
        if len(batch) < n:
            # stop() drained the queue between the agreement and the
            # pop: the executor is closing — fail what we hold rather
            # than dispatch a partial batch under an order agreed for n
            gate.release(my)
            self._fail_items(batch, RuntimeError(
                "async executor is stopped"))
            return
        # From here the batch is OURS: the queue drain (_fail_queued)
        # can no longer see it, so EVERY exit path below must resolve
        # its futures and free its tenant slots — a leaked item would
        # block submitters at maxInflightReads forever.
        submitted = set()
        tickets: Dict[int, int] = {}
        try:
            by_seq = {item[0]: item for item in batch}
            order = agreed_submission_order(
                [(seq, tid) for seq, tid, _r, _f, _rel in batch],
                lambda t: self._registry.spec(t).weight)
            # unanimity over (seq, tenant) pairs: a process that queued
            # DIFFERENT work (or resolves different priority weights)
            # fails the whole batch typed, naming the dissenter, before
            # any collective runs under a divergent order
            import zlib
            proposal = np.array(
                [x for seq in order
                 for x in (seq,
                           zlib.crc32(by_seq[seq][1].encode()))],
                dtype=np.int64)
            agree("async.order", proposal, conf_key=conf_key)
            # tickets in the AGREED order: execution (not just
            # submission) of each read's collective section follows it
            tickets = {seq: gate.issue() for seq in order}
            gate.release(my)
            pool = self._executor()
            for seq in order:
                _s, _tid, run, outer, release = by_seq[seq]
                fut = pool.submit(self._turnstiled(
                    run, release, tickets[seq]))
                submitted.add(seq)
                # a run cancelled by stop(cancel_futures=True) never
                # enters its finally — release its tenant slot and its
                # ticket here (same rule as the direct path)
                fut.add_done_callback(
                    lambda f, rel=release, t=tickets[seq]:
                    (rel(), gate.release(t)) if f.cancelled() else None)
                self._chain(fut, outer)
        except AgreementDivergenceError as e:
            gate.release(my)
            self._fail_items(batch, e)
            return
        except BaseException as e:
            # anything else past the pop (PeerLost from the order
            # round, unknown-tenant conf error, pool refusal mid-loop):
            # fail the UNDISPATCHED remainder here, release its tickets
            # so later batches are not wedged behind abandoned turns,
            # then let the loop's handler drain the still-queued rest
            gate.release(my)
            for seq, t in tickets.items():
                if seq not in submitted:
                    gate.release(t)
            self._fail_items(
                [it for it in batch if it[0] not in submitted], e)
            # the batch is fully resolved: tell the loop it may keep
            # dispatching instead of failing unrelated queued reads
            e._sxt_batch_failed = True
            raise

    def _turnstiled(self, run, release_slot, ticket: int):
        """Wrap a read's body in its collective turn: acquire blocks
        until every earlier agreed ticket released, so the body's
        collectives join the per-process stream in the agreed order."""
        gate = self._turnstile

        def wrapped():
            try:
                gate.acquire(ticket)
            except BaseException:
                # never entered run(): its finally cannot free the
                # tenant slot — do it here or the slot leaks
                release_slot()
                raise
            try:
                return run()
            finally:
                gate.release(ticket)
        return wrapped

    @staticmethod
    def _fail_items(items, err: BaseException) -> None:
        for _seq, _tid, _run, outer, release in items:
            release()
            if not outer.done():
                outer.set_exception(err)

    @staticmethod
    def _chain(fut, outer):
        def done(f):
            if f.cancelled():
                outer.cancel()
            elif f.exception() is not None:
                outer.set_exception(f.exception())
            else:
                outer.set_result(f.result())
        fut.add_done_callback(done)

    def _fail_queued(self, err: BaseException) -> None:
        with self._cv:
            drained, self._queue = list(self._queue), deque()
        self._fail_items(drained, err)

    def stop(self, wait: bool = True) -> None:
        with self._cv:
            self._closed = True
            pool, self._pool = self._pool, None
            dispatcher = self._dispatcher
            # wake submitters blocked at a tenant cap so they observe
            # _closed and raise instead of waiting on a drained pool
            self._cv.notify_all()
        if self._turnstile is not None:
            # wake reads parked on their collective turn BEFORE the
            # pool drain below — a waiter that kept blocking in acquire
            # would hang shutdown(wait=True) forever
            self._turnstile.close()
        if dispatcher is not None:
            dispatcher.join(timeout=5.0)
            # Past the timeout the dispatcher may still be parked
            # inside an agree() under a (much longer) watchdog
            # deadline. It is fenced, not raced: _executor() refuses to
            # hand out a pool once _closed is set and the closed
            # turnstile fails its acquires typed, so whatever batch it
            # popped resolves through _dispatch_batch's own failure
            # path instead of dispatching into a recreated executor.
        # undispatched queued reads never reach the pool: fail them so
        # their futures resolve and their tenant slots free
        self._fail_queued(RuntimeError("async executor is stopped"))
        if pool is not None:
            # in-flight reads hold arena buffers and admission
            # reservations — draining them is the clean-teardown rule
            # (the manager's own stop() drains reads the same way);
            # queued-but-unstarted work is cancelled
            pool.shutdown(wait=wait, cancel_futures=True)
