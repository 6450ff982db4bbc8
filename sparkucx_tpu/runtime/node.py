"""TpuNode — the per-process runtime singleton.

The UcxNode analog (ref: UcxNode.java:31-96): one instance per process
owning the process-wide resources every layer above shares. The reference's
UcxNode holds {UcpContext, MemoryPool, global worker, listener thread,
cluster address book}; TpuNode holds {device mesh, host memory pool,
shuffle registry, metrics, distributed bootstrap state}.

Bootstrap parity:

  reference                                   TPU-native
  ---------                                   ----------
  driver opens UcpListener on sockaddr        jax.distributed coordinator
    (UcxNode.java:98-104)                       (coordinator_address conf)
  executors dial driver, send worker addr     jax.distributed.initialize(...)
    (UcxNode.java:111-145)                      per process
  driver full-mesh introduction RPC           implicit: the global device
    (RpcConnectionCallback.java:70-84)          list IS the address book
  thread-local worker per task thread         SPMD: no per-thread progress
    (UcxNode.java:85-95)                        engine needed; XLA owns it

Multi-process note: ``start(distributed=True)`` wires
``jax.distributed.initialize`` so ``jax.devices()`` spans all hosts; the
same mesh/collective code then runs unmodified (SPMD). Single-process
multi-device (tests, single chip) skips that step.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional

import jax

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.meta.registry import ShuffleRegistry
from sparkucx_tpu.parallel.mesh import make_shuffle_mesh
from sparkucx_tpu.runtime.failures import (NULL_FLIGHT_RECORDER,
                                           EpochManager, FaultInjector,
                                           FlightRecorder, HealthMonitor,
                                           RetryPolicy)
from sparkucx_tpu.runtime.memory import HostMemoryPool
from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.metrics import Metrics
from sparkucx_tpu.utils.trace import configure_from_conf

log = get_logger("runtime.node")


class TpuNode:
    """Process-wide runtime state. Use :func:`TpuNode.start` /
    :func:`TpuNode.get` — mirroring UcxNode's guarded singleton start
    (ref: CommonUcxShuffleManager.scala:67-71 startUcxNodeIfMissing)."""

    _instance: Optional["TpuNode"] = None
    _lock = threading.Lock()

    def __init__(self, conf: TpuShuffleConf, distributed: bool = False,
                 process_id: int = 0):
        self.conf = conf
        self.process_id = process_id
        self._distributed = distributed
        self.is_distributed = distributed and conf.num_processes > 1
        # Persistent compile cache FIRST — before any code path can
        # trigger a compile — so service.connect()/warmup() amortize XLA
        # compile across processes instead of re-paying minutes per
        # restart (runtime/compile_cache.py; conf compile.*).
        from sparkucx_tpu.runtime.compile_cache import configure_compile_cache
        self.compile_cache_dir = configure_compile_cache(conf)
        if self.is_distributed:
            # Multi-host: rendezvous at the coordinator like executors
            # dialing the driver sockaddr (UcxNode.java:130-134).
            import time as _time
            t0 = _time.monotonic()
            try:
                jax.distributed.initialize(
                    coordinator_address=conf.coordinator_address,
                    num_processes=conf.num_processes,
                    process_id=process_id)
            except Exception as e:
                # The observed intermittent is HERE (back-to-back worlds,
                # load-sensitive; <10%). Classify it loudly so harnesses
                # retry THIS failure mode specifically instead of masking
                # every failure with a blanket re-run.
                log.error(
                    "RENDEZVOUS FAILED after %.1fs: coordinator=%s "
                    "process %d/%d: %r", _time.monotonic() - t0,
                    conf.coordinator_address, process_id,
                    conf.num_processes, e)
                raise RuntimeError(
                    f"RENDEZVOUS FAILED after "
                    f"{_time.monotonic() - t0:.1f}s (coordinator "
                    f"{conf.coordinator_address}, process {process_id}/"
                    f"{conf.num_processes}): {e!r}") from e
            log.info("jax.distributed up: process %d/%d via %s in %.2fs",
                     process_id, conf.num_processes,
                     conf.coordinator_address, _time.monotonic() - t0)
        self.mesh = make_shuffle_mesh(conf=conf)
        self.pool = HostMemoryPool(conf)
        self.registry = ShuffleRegistry()
        self.metrics = Metrics()
        self.tracer = configure_from_conf(conf)
        # Flight recorder (spark.shuffle.tpu.flightRecorder.enabled):
        # created BEFORE the failure plane so the injector/retry/health
        # pieces record into it. Enabling it implies span recording —
        # a postmortem without a timeline answers nothing.
        if conf.get_bool("flightRecorder.enabled", False):
            self.flight = FlightRecorder(conf)
            self.flight.metrics_sources.append(self.metrics)
            self.metrics.add_reporter(self.flight.metrics_reporter)
            self.tracer.enabled = True
            self.flight.install_abort_hook()
        else:
            self.flight = NULL_FLIGHT_RECORDER
        # Failure plane (SURVEY.md §5 do-better): injection sites, bounded
        # retries, active liveness probing, epoch fencing for remesh.
        self.faults = FaultInjector(conf, flight=self.flight)
        self.retry_policy = RetryPolicy.from_conf(
            conf, metrics=self.metrics, flight=self.flight)
        self.health = HealthMonitor(
            self.mesh, timeout_ms=conf.connection_timeout_ms,
            flight=self.flight)
        # Collective watchdog (failure.collectiveTimeoutMs): the deadline
        # fence around every distributed rendezvous and in-flight
        # collective wait — installed process-global so the module-level
        # collectives in shuffle/distributed.py fence themselves (the
        # GLOBAL_TRACER pattern). 0 = disabled instance, call sites stay
        # unconditional.
        from sparkucx_tpu.runtime.watchdog import configure_from_conf \
            as _configure_watchdog
        self.watchdog = _configure_watchdog(
            conf, health=self.health, flight=self.flight,
            metrics=self.metrics)
        self.epochs = EpochManager()
        self.epochs.on_bump(self.flight.on_epoch_bump)
        # Agreement plane (shuffle/agreement.py): the epoch-scoped round
        # sequence resets at every mesh epoch bump, so a process that
        # missed a remesh diverges TYPED in the next round's header
        # instead of feeding a stale round into a fresh world. Seed the
        # current epoch at construction (remesh re-seeds via the bump).
        from sparkucx_tpu.shuffle import agreement as _agreement
        _agreement.reset_epoch(self.epochs.current)
        self.epochs.on_bump(_agreement.reset_epoch)
        # Cluster clock anchors: every process's wall↔perf pair,
        # allgathered at connect (every process constructs its node in
        # lockstep, so the collective is safe here) — the alignment data
        # merge_timeline needs to put N monotonic span clocks on one
        # wall-clock axis. Single-process: just the local anchor.
        self.cluster_anchors = self._gather_anchors()
        self._closed = False
        # -- device-plane observability ---------------------------------
        # Health verdict behind /healthz: clear until an epoch bump (a
        # remesh drops registered shuffles — not ready until the operator
        # re-registers and calls mark_healthy) or a failed device probe.
        self._health_lock = threading.Lock()
        self._unhealthy_reason: Optional[str] = None
        self._unhealthy_cause: Optional[str] = None
        self.health.on_unhealthy = self._on_device_unhealthy
        self.epochs.on_bump(self._on_epoch_health)
        # -- SLO plane (utils/history.py + utils/slo.py) -----------------
        # Windowed telemetry history: frames are deltas between
        # successive snapshots, retained in a bounded ring and (when
        # history.dir is set) an on-disk JSONL a restarted process can
        # replay. NO new sampling thread — the facade's PeriodicDumper
        # cadence drives tick(); objectives ride each frame so a
        # replayed history dir is self-describing.
        from sparkucx_tpu.utils.history import TelemetryHistory
        from sparkucx_tpu.utils.slo import BurnPolicy, objectives_from_conf
        self.slo_objectives = objectives_from_conf(conf)
        self.slo_policy = BurnPolicy.from_conf(conf)
        frame_extra = {}
        if self.slo_objectives:
            frame_extra = {
                "slo_objectives": [o.to_dict()
                                   for o in self.slo_objectives],
                "slo_policy": self.slo_policy.to_dict()}
        self.history = TelemetryHistory(
            self._history_collect,
            window_secs=conf.get_float("history.windowSecs", 60.0),
            retain_windows=conf.get_int("history.retainWindows", 120),
            out_dir=conf.get("spark.shuffle.tpu.history.dir"),
            process_id=process_id, extra=frame_extra)
        self._slo_cache = (None, -1)   # (verdict, history.version)
        if self.slo_objectives:
            # flight postmortems embed the SLO verdict at fault time —
            # the first thing an operator reads next to the findings
            self.flight.add_context_provider(self.slo_verdict)
        # -- decision plane (shuffle/decisions.py) -----------------------
        # Ledger of every agreement round this process closes: bounded
        # ring plus (when history.dir is set) a rank-keyed JSONL beside
        # the history log. Installed through the module seam so agree()
        # and the turnstile — module-level, no node handle — reach it;
        # flight postmortems embed the tail (last-decision position
        # beside the last-span position).
        from sparkucx_tpu.shuffle.decisions import (NULL_DECISION_LEDGER,
                                                    DecisionLedger,
                                                    set_ledger)
        if conf.get_bool("decisions.enabled", True):
            self.decisions = DecisionLedger(
                retain=conf.get_int("decisions.retain", 256),
                out_dir=conf.get("spark.shuffle.tpu.history.dir"),
                process_id=process_id)
        else:
            self.decisions = NULL_DECISION_LEDGER
        set_ledger(self.decisions)
        self.flight.add_context_provider(self.decision_ledger)
        # Cost capture master switch (shuffle/stepcache.py harvest of
        # XLA cost/memory analysis per compiled program; on by default —
        # off keeps the records, nulls the fields).
        from sparkucx_tpu.shuffle import stepcache as _stepcache
        _stepcache.COST_CAPTURE = conf.get_bool("compile.costCapture",
                                                True)
        # the memory_analysis probe re-compiles the lowered module —
        # only affordable when the persistent compile cache can turn
        # that into a deserialize; with the cache disabled/unavailable
        # the probe would re-pay the full XLA compile inside the first
        # read, so it degrades to cost_analysis-only (null memory
        # fields, the documented partial-record shape)
        _stepcache.MEMORY_PROBE = self.compile_cache_dir is not None
        # Device memory sampler (runtime/devmon.py): daemon thread
        # publishing HBM + pool gauges; null object when off, like the
        # flight recorder.
        from sparkucx_tpu.runtime.devmon import (NULL_DEVMON,
                                                 DeviceMonitor,
                                                 DoctorWatcher)
        if conf.get_bool("devmon.enabled", False):
            self.devmon = DeviceMonitor(
                self,
                interval_s=conf.get_float("devmon.intervalMs",
                                          1000.0) / 1e3).start()
        else:
            self.devmon = NULL_DEVMON
        # Pluggable telemetry providers: the node serves its own
        # snapshot/diagnosis by default; a facade swaps in its richer
        # pair (exchange reports included) at connect and restores at
        # stop — the live server and doctor watcher read THROUGH these,
        # so they upgrade transparently.
        self.telemetry_provider = self.telemetry_snapshot
        self.doctor_provider = self._default_doctor
        from sparkucx_tpu.utils.live import start_from_conf
        self.live = start_from_conf(
            conf, lambda: self.telemetry_provider(),
            lambda: self.doctor_provider(), self.health_status,
            slo_fn=self.slo_verdict, cluster_fn=self._cluster_view,
            decisions_fn=(self.decision_ledger
                          if self.decisions.enabled else None))
        # Fleet telemetry registry (utils/collector.py): publish this
        # process's scrape URL through ONE boot-time allgather (the live
        # server exists by now, so the URL does too), persist the agreed
        # address book beside the durable ledger for restart adoption,
        # and wire the out-of-band scraper — including the watchdog's
        # expiry-path postmortem scrape. Best-effort like the clock
        # anchors: a node must never fail to BOOT over observability.
        self.fleet = None
        self.collector = None
        try:
            self._init_fleet()
        except Exception:
            log.warning("fleet telemetry registry unavailable",
                        exc_info=True)
        # Anomaly-triggered deep capture (doctor.watchIntervalSecs):
        # rolling doctor pass; first critical finding => bounded
        # profiler window + tagged flight postmortem.
        watch_s = conf.get_float("doctor.watchIntervalSecs", 0.0)
        if watch_s > 0:
            self.watcher = DoctorWatcher(
                self, watch_s,
                profile_ms=conf.get_float("doctor.captureMs", 200.0),
                capture_dir=conf.get(
                    "spark.shuffle.tpu.doctor.captureDir"),
                rearm_passes=conf.get_int(
                    "doctor.rearmHealthyPasses", 3)).start()
        else:
            self.watcher = None
        log.info("TpuNode up: %d devices, mesh axes %s",
                 len(jax.devices()), self.mesh.axis_names)

    def _init_fleet(self) -> None:
        """Build the fleet registry + collector (utils/collector.py).
        Distributed: the entry list comes from the ONE permitted
        boot-time allgather — every process calls in lockstep, even
        with its live server off (it publishes {}). Single-process /
        collective-less backends: the local entry alone."""
        from sparkucx_tpu.utils import collector as _collector
        url = _collector.advertised_url(self.conf, self.live,
                                        multiprocess=self.is_distributed)
        entry = None
        if url is not None:
            entry = _collector.registry_entry(
                self.process_id, url, self.tracer.anchor())
        if self.is_distributed:
            from sparkucx_tpu.shuffle.distributed import \
                gather_fleet_registry
            try:
                entries = gather_fleet_registry(entry)
            except Exception as e:
                # same posture as the clock-anchor gather: some backends
                # lack cross-process collectives — the fleet then knows
                # only this process (scraping still works locally)
                log.warning("fleet-registry allgather unavailable (%s); "
                            "fleet view covers this process only", e)
                entries = [entry] if entry else []
        else:
            entries = [entry] if entry else []
        self.fleet = _collector.FleetRegistry(entries)
        root = self.conf.ledger_dir
        if root and len(self.fleet):
            try:
                path = self.fleet.save(root)
                log.info("fleet registry: %d peer(s) -> %s",
                         len(self.fleet), path)
            except OSError as e:
                log.warning("fleet registry not persisted (%s): %s",
                            root, e)
        if len(self.fleet):
            self.collector = _collector.ClusterCollector(
                self.fleet, self_id=self.process_id,
                timeout_s=self.conf.get_float("fleet.scrapeTimeoutMs",
                                              2000.0) / 1e3)
            # the survivor's expiry-path postmortem: scrape the fleet
            # out-of-band and embed each peer's last-known phase ledger
            self.watchdog.peer_scrape = self.collector.postmortem

    def _cluster_view(self):
        """The /cluster/* provider: a fresh fleet scrape, or None while
        no registry exists (the route 404s with the reason)."""
        coll = getattr(self, "collector", None)
        if coll is None:
            return None
        return coll.scrape()

    def telemetry_snapshot(self, reports=None,
                           include_history: bool = True) -> dict:
        """THE canonical live-snapshot shape for this process: both
        registries (process-global + node), the tracer, the arena
        watermark and the process identity — one seam so the facades,
        the CLI's live mode and the cluster
        harness cannot drift on which fields a doctor rule can rely on.
        ``reports`` is the manager's exchange-report list when the
        caller owns a manager (the node itself does not).

        ``include_history`` embeds the retained window frames
        (``history_frames``) plus the declared SLO objectives, so every
        consumer of a snapshot — dumps, flight postmortems, the live
        /snapshot endpoint, the doctor's build_view — carries the time
        axis; the history plane itself collects with it off (a frame
        must not embed the ring it is about to join)."""
        from sparkucx_tpu.utils.export import collect_snapshot
        from sparkucx_tpu.utils.metrics import GLOBAL_METRICS
        # pool watermarks ride as GAUGES (set semantics — Prometheus
        # must not type a value that goes down as a counter); the flat
        # "pool" dict below keeps its keys for the doctor's build_view.
        # ONE stats() call feeds both.
        pool_stats = self.pool.stats()
        self.publish_pool_gauges(pool_stats)
        extra = {"pool": pool_stats,
                 "process_id": self.process_id,
                 # the connect-time anchor table: ONE process's dump
                 # can place every peer's clock on the shared wall
                 # axis even when the peers' own dumps are missing
                 # (a crashed peer's flight dump may never land)
                 "cluster_anchors": self.cluster_anchors}
        # Clock re-anchor carriage: every snapshot stamps a FRESH anchor
        # (collect_snapshot), and the boot anchor rides along in the
        # ``anchors`` history so merge_timeline/critical_path can prefer
        # whichever sample is freshest; ``anchor_skew_s`` is this
        # process's drift estimate since boot (scrape-time re-anchor
        # minus boot anchor — what the clock_drift rule grades).
        boot = next((a for a in self.cluster_anchors
                     if isinstance(a, dict)
                     and a.get("process_id") == self.process_id
                     and "wall_epoch" in a), None)
        if boot is not None:
            extra["anchors"] = [dict(boot)]
            extra["anchor_skew_s"] = round(
                self.tracer.anchor()["wall_epoch"]
                - float(boot["wall_epoch"]), 6)
        fleet = getattr(self, "fleet", None)
        if fleet is not None and len(fleet):
            extra["fleet_registry"] = fleet.to_doc()
        if include_history and getattr(self, "history", None) is not None:
            frames = self.history.frames()
            if frames:
                extra["history_frames"] = frames
            if self.slo_objectives:
                extra["slo_objectives"] = [o.to_dict()
                                           for o in self.slo_objectives]
                extra["slo_policy"] = self.slo_policy.to_dict()
        # decision-ledger tail: every snapshot consumer — dumps, fleet
        # scrapes, the doctor's build_view, the decisions CLI — sees the
        # retained rounds without new plumbing (the history_frames
        # carriage discipline). Bounded: the ring is already bounded.
        decisions = getattr(self, "decisions", None)
        if decisions is not None:
            recs = decisions.tail()
            if recs:
                extra["decisions"] = recs
        return collect_snapshot(
            [GLOBAL_METRICS, self.metrics], tracer=self.tracer,
            reports=reports, extra=extra)

    # -- SLO plane (utils/slo.py over the retained history) ---------------
    def _history_collect(self) -> dict:
        """The history plane's LEAN snapshot: counters + histograms +
        gauges + anchor only. The full telemetry_snapshot additionally
        summarizes spans and serializes chrome events — per-scrape
        costs a per-window delta never reads, and the roll rides the
        read path's cadence budget."""
        from sparkucx_tpu.utils.export import collect_snapshot
        from sparkucx_tpu.utils.metrics import GLOBAL_METRICS
        self.publish_pool_gauges()
        return collect_snapshot(
            [GLOBAL_METRICS, self.metrics], populated_only=True,
            extra={"process_id": self.process_id})

    def slo_verdict(self) -> dict:
        """The SLO verdict over the retained windows, cached per rolled
        frame (the ring's ``version``): /healthz consults this on every
        probe, and re-evaluating an unchanged ring would be pure waste.
        Objective-less nodes return the empty verdict (healthy)."""
        cached, ver = self._slo_cache
        if cached is not None and ver == self.history.version:
            return cached
        from sparkucx_tpu.utils.slo import evaluate
        verdict = evaluate(self.history.frames(), self.slo_objectives,
                           policy=self.slo_policy)
        self._slo_cache = (verdict, self.history.version)
        return verdict

    def decision_ledger(self) -> dict:
        """The decision plane's postmortem/live face: the last-decision
        position (epoch/seq/topic — printed beside the last-span
        position in peer postmortems) plus the retained tail. Flight
        context provider (keyed ``decision_ledger``) AND the
        ``/decisions`` live route serve this same doc."""
        led = getattr(self, "decisions", None)
        if led is None:
            return {"enabled": False, "position": None, "decisions": []}
        return {"enabled": bool(led.enabled),
                "total": int(led.total),
                "path": led.path,
                "position": led.position(),
                "decisions": led.tail()}

    def slo_fast_burn(self):
        """The /healthz face of the verdict: the burning objective
        names, or an empty list when healthy / objective-less."""
        if not self.slo_objectives:
            return []
        try:
            return self.slo_verdict().get("burning", [])
        except Exception:
            log.debug("slo evaluation failed", exc_info=True)
            return []

    def publish_pool_gauges(self, stats: Optional[dict] = None) -> None:
        """Arena watermarks -> ``pool.*`` gauges in this node's registry
        (the set-not-add migration: in_use and peak go DOWN — on put()
        and reset_peak_bytes() — so exporting them through counters lied
        to every rate() query)."""
        st = stats if stats is not None else self.pool.stats()
        for key in ("in_use", "in_use_bytes", "peak_bytes", "allocated",
                    "preallocated"):
            if key in st:
                self.metrics.set_gauge(f"pool.{key}", st[key])

    def _default_doctor(self):
        """The node's own diagnosis (no manager, so no exchange
        reports) — the doctor_provider default a facade upgrades."""
        from sparkucx_tpu.utils.doctor import diagnose
        return diagnose(self.telemetry_snapshot())

    def reset_providers(self) -> None:
        """Restore the default telemetry/doctor providers (facade
        stop() calls this so a dead manager is not kept reachable
        through the live server's closures)."""
        self.telemetry_provider = self.telemetry_snapshot
        self.doctor_provider = self._default_doctor

    # -- health (the /healthz verdict) ------------------------------------
    def mark_unhealthy(self, reason: str,
                       cause: str = "operator") -> None:
        """``cause`` is the MACHINE face of the verdict — a stable enum
        (``epoch_bump`` / ``device_unhealthy`` / ``slo_fast_burn`` /
        ``closed`` / ``operator``) a probe script switches on, where
        ``reason`` is the human sentence that changes wording freely."""
        with self._health_lock:
            self._unhealthy_reason = reason
            self._unhealthy_cause = cause

    def mark_healthy(self) -> None:
        """Operator acknowledgment: shuffles re-registered after a
        remesh / the flagged device replaced — serve traffic again."""
        with self._health_lock:
            self._unhealthy_reason = None
            self._unhealthy_cause = None

    def _on_device_unhealthy(self, bad) -> None:
        self.mark_unhealthy(f"DeviceUnhealthy: {bad}",
                            cause="device_unhealthy")

    def _on_epoch_health(self, epoch: int) -> None:
        self.mark_unhealthy(
            f"epoch bumped to {epoch}: registered shuffles dropped — "
            f"re-register and mark_healthy()", cause="epoch_bump")

    def health_status(self) -> dict:
        """The /healthz body: ``ok`` plus the evidence — epoch, device
        count, the human ``reason`` AND the stable machine ``cause``
        (epoch_bump / device_unhealthy / slo_fast_burn / closed) so a
        probe can switch on WHY without parsing prose. A fast-burning
        SLO degrades health like a device fault: the node still serves,
        but it is eating its error budget at page-now speed and a
        load balancer should know."""
        with self._health_lock:
            reason, cause = self._unhealthy_reason, self._unhealthy_cause
        closed = self._closed
        if closed:
            reason, cause = "node closed", "closed"
        elif reason is None:
            burning = self.slo_fast_burn()
            if burning:
                reason = ("SLO fast burn: " + ", ".join(burning)
                          + " — error budget burning at page-now speed")
                cause = "slo_fast_burn"
        return {
            "ok": reason is None,
            "epoch": self.epochs.current,
            "devices": self.num_devices,
            "process_id": self.process_id,
            "reason": reason,
            "cause": cause,
        }

    def flight_capture_dir(self) -> str:
        """Where the doctor watcher parks deep captures: next to the
        flight recorder's postmortems when it is on, a per-pid temp dir
        otherwise."""
        d = getattr(self.flight, "out_dir", None)
        if d:
            return d
        import tempfile
        return os.path.join(tempfile.gettempdir(),
                            f"sparkucx_tpu_capture_{os.getpid()}")

    def _gather_anchors(self) -> list:
        if self.is_distributed:
            from sparkucx_tpu.shuffle.distributed import \
                gather_clock_anchors
            try:
                return gather_clock_anchors(self.tracer)
            except Exception as e:
                # best-effort: some backends lack cross-process
                # collectives (jax 0.4.x CPU without gloo) — timeline
                # merging then falls back to per-dump anchors; a node
                # must never fail to BOOT over alignment metadata
                log.warning("clock-anchor allgather unavailable (%s); "
                            "cluster timeline will align from per-dump "
                            "anchors", e)
        a = self.tracer.anchor()
        a["process_id"] = self.process_id
        return [a]

    # -- singleton management --------------------------------------------
    @classmethod
    def start(cls, conf: Optional[TpuShuffleConf] = None,
              distributed: bool = False, process_id: int = 0) -> "TpuNode":
        """Idempotent start; the startUcxNodeIfMissing analog."""
        with cls._lock:
            if cls._instance is None or cls._instance._closed:
                cls._instance = cls(conf or TpuShuffleConf(),
                                    distributed, process_id)
                atexit.register(cls._instance.close)
            return cls._instance

    @classmethod
    def get(cls) -> "TpuNode":
        inst = cls._instance
        if inst is None or inst._closed:
            raise RuntimeError("TpuNode not started; call TpuNode.start()")
        return inst

    # -- address book -----------------------------------------------------
    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def local_shard_ids(self):
        """Global flat shard indices owned by this process (all of them in
        single-process mode) — the "which executor owns which block"
        half of the address book (ref: UcxNode.java:42-44)."""
        if not self.is_distributed:
            return list(range(self.num_devices))
        from sparkucx_tpu.shuffle.distributed import local_shard_ids
        return local_shard_ids(self.mesh)

    def device_of_shard(self, shard: int):
        """Shard index -> device, the BlockManagerId->workerAddress lookup
        analog (ref: UcxNode.java:170-172)."""
        return self.mesh.devices.reshape(-1)[shard]

    # -- elastic membership (SURVEY.md §7 hard part (e)) ------------------
    def remesh(self, devices=None, reason: str = "") -> int:
        """Rebuild the mesh over ``devices`` (default: re-probe all) and
        bump the epoch — the elastic answer to executor loss.

        The reference admits late joiners through the driver's full-mesh
        introduction RPC (ref: RpcConnectionCallback.java:70-84) and leans
        on Spark to re-run work after a loss. JAX's process set is static,
        so membership change = new mesh + new epoch: every handle pinned to
        the old epoch fails fast (StaleEpochError) instead of hanging a
        collective; callers re-register their shuffles and re-run — the
        stage-resubmission analog. Registered shuffle state is dropped,
        like unregisterShuffle on all live shuffles
        (ref: CommonUcxShuffleManager.scala:73-77).

        Returns the new epoch."""
        import jax as _jax
        if devices is None:
            if self.is_distributed:
                # Each process probes independently and jax.devices() spans
                # the cluster: deriving the survivor set locally can diverge
                # across processes and build inconsistent meshes that wedge
                # the next collective instead of failing fast. Survivor
                # agreement lives in the recovery controller
                # (buildlib/run_cluster.py): it restarts the world with an
                # explicitly agreed membership and passes it here.
                raise RuntimeError(
                    "distributed remesh requires an explicitly agreed "
                    "device list; probe verdicts are process-local and can "
                    "diverge. Re-bootstrap with the surviving processes "
                    "and pass devices=.")
            alive = self.health.probe()
            devices = [d for d in _jax.devices() if alive.get(str(d), True)]
        if not devices:
            raise RuntimeError("remesh with zero surviving devices")
        self.mesh = make_shuffle_mesh(devices, self.conf)
        self.health = HealthMonitor(
            self.mesh, timeout_ms=self.conf.connection_timeout_ms,
            flight=self.flight)
        self.health.on_unhealthy = self._on_device_unhealthy
        # the watchdog probes through the CURRENT monitor — a stale one
        # would probe devices the remesh just removed
        self.watchdog.health = self.health
        self.registry.clear()
        # Fresh membership, fresh alignment data. Single-process: a
        # local re-anchor. Distributed: NO collective here — remesh runs
        # precisely when a peer is dead, and an allgather over the old
        # process set would hang on it; keep only the local anchor (the
        # recovery controller re-bootstraps a fresh world, whose
        # __init__ re-gathers cluster-wide).
        if self.is_distributed:
            a = self.tracer.anchor()
            a["process_id"] = self.process_id
            self.cluster_anchors = [a]
        else:
            self.cluster_anchors = self._gather_anchors()
        epoch = self.epochs.bump(reason or "remesh")
        log.warning("remesh: %d devices, epoch %d (%s)",
                    self.mesh.devices.size, epoch, reason or "requested")
        return epoch

    # -- teardown ---------------------------------------------------------
    def close(self) -> None:
        """Clean shutdown ordering mirrors UcxNode.close
        (ref: UcxNode.java:194-221): stop accepting work, drop shuffle
        state, then release memory."""
        if self._closed:
            return
        self._closed = True
        # device-plane monitors first: their threads read the pool and
        # registries this teardown is about to drop
        if self.watcher is not None:
            self.watcher.stop()
        self.devmon.stop()
        if self.live is not None:
            self.live.stop()
        self.reset_providers()
        # drop the expiry-path scrape hook with the collector: a dead
        # node's registry must not be scraped through the watchdog
        self.watchdog.peer_scrape = None
        self.collector = None
        # drop the process-global fence if it is ours (a later node
        # installs its own): dead-node health/flight refs must not
        # outlive the node through the module global
        from sparkucx_tpu.runtime.watchdog import (current_watchdog,
                                                   set_global_watchdog)
        if current_watchdog() is self.watchdog:
            set_global_watchdog(None)
        self.epochs.remove_listener(self._on_epoch_health)
        self.flight.remove_context_provider(self.slo_verdict)
        self.flight.remove_context_provider(self.decision_ledger)
        # drop the module-seam ledger if it is ours (a later node
        # installs its own) — agree() after close records nowhere
        from sparkucx_tpu.shuffle.decisions import (NULL_DECISION_LEDGER,
                                                    current_ledger,
                                                    set_ledger)
        if current_ledger() is self.decisions:
            set_ledger(NULL_DECISION_LEDGER)
        self.decisions.close()
        self.flight.uninstall_abort_hook()
        self.metrics.remove_reporter(self.flight.metrics_reporter)
        self.epochs.remove_listener(self.flight.on_epoch_bump)
        self.registry.clear()
        self.pool.close()
        if self._distributed and self.conf.num_processes > 1:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # already down at interpreter exit
                log.info("distributed shutdown: %s", e)
        log.info("TpuNode closed; metrics: %s", self.metrics.snapshot())
        with TpuNode._lock:
            if TpuNode._instance is self:
                TpuNode._instance = None
