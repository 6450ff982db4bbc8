"""Device-plane monitors — HBM sampler and the anomaly-triggered watcher.

The reference's memory observability is its pool's stats-at-close log
line (ref: MemoryPool.java:30-39) and whatever Spark's UI polls; nothing
in either stack reports DEVICE memory while a shuffle is running, which
is exactly when an operator needs it — Exoshuffle (arxiv 2203.05072)
argues shuffle systems live or die by runtime visibility into memory
pressure and in-flight transfer progress. Two pieces close that gap:

* :class:`DeviceMonitor` — a daemon thread (conf
  ``spark.shuffle.tpu.devmon.enabled`` / ``devmon.intervalMs``, default
  off with a null-object stand-in like the flight recorder) polling
  ``device.memory_stats()`` on every local device plus the
  :class:`~sparkucx_tpu.runtime.memory.HostMemoryPool` watermarks, and
  publishing them as **gauges** (``devmon.hbm.in_use/limit/peak`` per
  device index, ``pool.*``) into the node's registry — set-semantics
  values Prometheus types correctly, not the counter smuggling PR-4's
  watermarks rode in on. Samples taken while an exchange is in flight
  are stamped with its PR-3 trace id (``FlightRecorder.current_trace``),
  so a timeline can overlay HBM pressure against the wave that caused
  it. CPU backends return ``memory_stats() = None``: the sample still
  lands, with null device fields — presence of the record and presence
  of the data are separate facts.

* :class:`DoctorWatcher` — the closed loop (conf
  ``spark.shuffle.tpu.doctor.watchIntervalSecs``, default off): run the
  doctor's rule engine over the live snapshot on a rolling cadence and,
  on the FIRST occurrence of each distinct critical finding, capture a
  bounded ``jax.profiler`` trace window plus a flight-recorder
  postmortem tagged with the finding — the deep evidence an operator
  cannot capture after the fact, taken exactly when the rules say
  something is wrong. One capture per distinct finding while it
  persists — a steady condition must not fill the disk with identical
  postmortems — but a finding that CLEARS for
  ``doctor.rearmHealthyPasses`` consecutive passes re-arms, so a
  condition recurring an hour later is captured again (by then the
  bounded ring has evicted the first occurrence's context, which is
  exactly when fresh evidence matters).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.metrics import (G_HBM_IN_USE, G_HBM_LIMIT,
                                        G_HBM_PEAK, labeled)

log = get_logger("runtime.devmon")


class _NullDeviceMonitor:
    """Stand-in when ``devmon.enabled`` is off — the flight recorder's
    null-object pattern: call sites stay unconditional, the disabled
    path costs an attribute lookup."""

    __slots__ = ()
    enabled = False

    def start(self) -> "_NullDeviceMonitor":
        return self

    def stop(self) -> None:
        pass

    def sample_once(self) -> None:
        pass

    def samples(self) -> List[Dict]:
        return []


NULL_DEVMON = _NullDeviceMonitor()


class DeviceMonitor:
    """Daemon-thread device-memory sampler (see module docstring).

    Publishes into ``node.metrics`` gauges; keeps a bounded ring of raw
    samples (``samples()``) for tests.
    Sampling never raises into anything: every probe is guarded, and a
    backend without ``memory_stats`` simply yields null device fields.
    """

    enabled = True

    def __init__(self, node, interval_s: float = 1.0,
                 capacity: int = 256):
        self._node = node
        self._interval = max(0.02, float(interval_s))
        self._samples: deque = deque(maxlen=max(1, capacity))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="sparkucx-devmon", daemon=True)

    def start(self) -> "DeviceMonitor":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            self.sample_once()

    def sample_once(self) -> None:
        """Take one sample now (the loop body, public for tests and for
        snapshot-time freshness)."""
        try:
            self._sample()
        except Exception:
            log.debug("devmon sample failed", exc_info=True)

    def _sample(self) -> None:
        import jax
        node = self._node
        metrics = node.metrics
        # stamp: the exchange in flight RIGHT NOW (None when idle or the
        # flight recorder — which owns the in-flight stack — is off)
        trace = node.flight.current_trace()
        devices = []
        for i, dev in enumerate(jax.local_devices()):
            try:
                ms = dev.memory_stats()
            except Exception:
                ms = None
            in_use = ms.get("bytes_in_use") if ms else None
            limit = ms.get("bytes_limit") if ms else None
            peak = ms.get("peak_bytes_in_use") if ms else None
            # set_gauge(None) clears: a device that stopped reporting
            # must not leave a stale watermark for a scrape to trust
            metrics.set_gauge(labeled(G_HBM_IN_USE, device=i), in_use)
            metrics.set_gauge(labeled(G_HBM_LIMIT, device=i), limit)
            metrics.set_gauge(labeled(G_HBM_PEAK, device=i), peak)
            devices.append({"index": i, "device": str(dev),
                            "in_use": in_use, "limit": limit,
                            "peak": peak})
        pool = node.pool.stats()
        node.publish_pool_gauges(pool)
        metrics.inc("devmon.samples")
        sample = {"t": time.time(), "trace": trace, "devices": devices,
                  "pool_in_use_bytes": pool.get("in_use_bytes"),
                  "pool_peak_bytes": pool.get("peak_bytes")}
        self._samples.append(sample)
        hbm_total = sum(d["in_use"] for d in devices
                        if d["in_use"] is not None)
        # Flight-ring events ONLY while an exchange is in flight (the
        # ring stamps the trace itself): that is when a sample explains
        # a crash, and an idle sampler must not evict the fault/retry
        # events the bounded ring exists to keep — one idle sample per
        # second would purge a 512-slot ring in ~8.5 minutes.
        if trace is not None:
            node.flight.record("devmon", hbm_in_use=hbm_total,
                               pool_in_use=pool.get("in_use_bytes", 0))
        if node.tracer.enabled:
            node.tracer.instant("devmon.sample", hbm_in_use=hbm_total,
                                trace=trace or "")

    def samples(self) -> List[Dict]:
        """Bounded ring of raw samples, oldest first."""
        return list(self._samples)


class DoctorWatcher:
    """Rolling doctor pass + anomaly-triggered deep capture (see module
    docstring). ``check_once()`` is the loop body, public so tests (and
    an operator shell) can drive it synchronously."""

    # Per-rule capture budget for the node's lifetime: a distinct
    # finding (new trace ids) is new evidence and captures again, but a
    # persistent condition under ongoing traffic mints a "new" finding
    # every pass (the worst exchange changes) — without a cap that is a
    # profiler window + postmortem per interval, exactly the disk flood
    # the dedup exists to prevent. Past the budget the finding still
    # surfaces through /doctor; only the deep capture stops.
    RULE_CAPTURE_CAP = 5

    def __init__(self, node, interval_s: float,
                 profile_ms: float = 200.0,
                 capture_dir: Optional[str] = None,
                 rearm_passes: int = 3):
        self._node = node
        self._interval = max(0.1, float(interval_s))
        self._profile_ms = max(0.0, float(profile_ms))
        self._capture_dir = capture_dir
        self._seen = set()
        # Re-arm (conf doctor.rearmHealthyPasses): a captured finding
        # key that stays ABSENT for N consecutive passes leaves _seen,
        # so a condition that clears and recurs an hour later gets its
        # profile/postmortem again. The original once-per-lifetime set
        # silently dropped every recurrence — the bounded ring would
        # have long evicted the first occurrence's context by then,
        # which is exactly when the deep capture matters most.
        self._rearm_passes = max(1, int(rearm_passes))
        self._healthy_passes: Dict[tuple, int] = {}
        self._rule_healthy: Dict[str, int] = {}
        self._rule_captures: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.captures: List[Dict] = []       # tests/CI read this
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="sparkucx-doctor-watch", daemon=True)

    def start(self) -> "DoctorWatcher":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                self.check_once()
            except Exception:
                log.debug("doctor watch pass failed", exc_info=True)

    @staticmethod
    def _finding_key(f) -> tuple:
        """Identity of a finding for the one-capture-per-finding rule:
        the rule plus the exchanges it names. A straggler on a NEW
        exchange is new evidence and captures again; the same finding
        re-derived from the same cumulative telemetry does not."""
        return (f.rule, tuple(sorted(t for t in f.trace_ids if t)))

    def check_once(self) -> List[Dict]:
        """One doctor pass over the live snapshot; returns the captures
        this pass triggered (possibly empty). Reads through the node's
        pluggable ``doctor_provider`` so a facade's richer diagnosis
        (exchange reports included) is what gets watched."""
        findings = self._node.doctor_provider()
        current = {self._finding_key(f) for f in findings
                   if f.grade == "critical"}
        current_rules = {k[0] for k in current}
        with self._lock:
            # re-arm pass: a seen key absent from this pass's criticals
            # accrues one healthy pass; N consecutive absences re-arm it
            # (a present key resets its streak — flapping conditions
            # must not re-capture every oscillation)
            for key in list(self._seen):
                if key in current:
                    self._healthy_passes.pop(key, None)
                    continue
                n = self._healthy_passes.get(key, 0) + 1
                if n >= self._rearm_passes:
                    self._seen.discard(key)
                    self._healthy_passes.pop(key, None)
                    log.info("doctor watcher re-armed %s after %d "
                             "healthy pass(es)", key, n)
                else:
                    self._healthy_passes[key] = n
            # capture-budget refund is per RULE and only when the WHOLE
            # rule stayed quiet for the streak: a genuinely-cleared
            # condition recurring later must actually capture past the
            # cap, while a persistent condition minting a fresh key
            # every pass (the flood the cap exists for) keeps at least
            # one critical alive and never refunds itself
            for rule in list(self._rule_captures):
                if rule in current_rules:
                    self._rule_healthy.pop(rule, None)
                    continue
                n = self._rule_healthy.get(rule, 0) + 1
                if n >= self._rearm_passes:
                    self._rule_healthy.pop(rule, None)
                    self._rule_captures.pop(rule, None)
                else:
                    self._rule_healthy[rule] = n
        fired = []
        for f in findings:
            if f.grade != "critical":
                continue
            key = self._finding_key(f)
            with self._lock:
                if key in self._seen or \
                        self._rule_captures.get(f.rule, 0) \
                        >= self.RULE_CAPTURE_CAP:
                    continue
                self._seen.add(key)
                self._rule_captures[f.rule] = \
                    self._rule_captures.get(f.rule, 0) + 1
            fired.append(self._capture(f))
        return fired

    def _capture(self, f) -> Dict:
        """The deep capture for one finding: a bounded profiler window
        (best-effort — some CPU builds lack the profiler backend) and a
        flight postmortem tagged with the finding dict. Neither failure
        mode propagates — the watcher observes, it never breaks."""
        cap = {"rule": f.rule, "grade": f.grade, "ts": time.time(),
               "profile_dir": None, "flight_dump": None}
        base = self._capture_dir or self._node.flight_capture_dir()
        if self._profile_ms > 0:
            pdir = os.path.join(
                base, f"profile_{f.rule}_{int(time.time() * 1e3)}")
            try:
                import jax.profiler
                os.makedirs(pdir, exist_ok=True)
                jax.profiler.start_trace(pdir)
                try:
                    # bounded window: whatever the device is doing for
                    # the next profile_ms is the evidence
                    time.sleep(self._profile_ms / 1e3)
                finally:
                    jax.profiler.stop_trace()
                cap["profile_dir"] = pdir
            except Exception as e:
                log.info("doctor capture: profiler window unavailable "
                         "(%s)", e)
        try:
            cap["flight_dump"] = self._node.flight.dump(
                f"doctor finding: {f.rule}",
                extra={"finding": f.to_dict()})
        except Exception:
            log.debug("doctor capture: flight dump failed", exc_info=True)
        log.warning("doctor watcher captured %s (%s): profile=%s "
                    "flight=%s", f.rule, f.grade, cap["profile_dir"],
                    cap["flight_dump"])
        self.captures.append(cap)
        return cap
