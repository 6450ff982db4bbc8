"""Windowed telemetry history — the time axis of the observability plane.

Every observable the engine carries is either cumulative-since-boot
(counters, histograms) or a bounded ring (64 exchange reports, flight
events). Neither can answer the one question a production operator asks:
*"is it getting worse right now, and for whom?"* — a 5-minute regression
drowns inside hours of healthy boot-to-now aggregates. This module adds
retention: :class:`TelemetryHistory` turns successive canonical
snapshots (``TpuNode.telemetry_snapshot`` — the ONE live-snapshot seam)
into fixed-cadence **window frames**:

* counters subtract (a frame carries the window's deltas, zero-delta
  names dropped);
* histograms subtract bucket-wise (:meth:`Histogram.snapshot_delta` —
  same fixed ladder, so per-bucket counts diff exactly and the window's
  p50/p99 are real quantiles of the window, not of all history);
* gauges sample point-in-time (a watermark is attributed, never
  differenced).

Frames live in a bounded in-memory ring AND, when
``spark.shuffle.tpu.history.dir`` is set, append to an on-disk JSONL
(``history_p<process_id>.jsonl`` — keyed by the STABLE cluster rank,
not the pid, so a restarted rank adopts its predecessor's log instead
of minting a fresh per-pid file forever; one frame per line, written
through utils/atomicio) that is size-bounded to
``history.retainWindows`` lines with oldest-first truncation — the log
can run for weeks and a fresh process replays the retained windows
through the ``slo``/``doctor`` CLIs after a restart.

Cadence: NO new sampling thread. Rolling is driven off the
:class:`~sparkucx_tpu.utils.export.PeriodicDumper` tick (service.py
starts one whenever history or a dump dir is configured); ``tick()``
closes a window only once ``history.windowSecs`` elapsed, and
``roll()`` force-closes one (tests).

Conf surface (all under ``spark.shuffle.tpu.``)::

    history.dir            on-disk JSONL directory (unset = ring only)
    history.windowSecs     window length (default 60)
    history.retainWindows  ring + on-disk retention (default 120)
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.metrics import Histogram

log = get_logger("history")

DEFAULT_WINDOW_SECS = 60.0
DEFAULT_RETAIN = 120

FRAME_KIND = "history_frame"


def counters_delta(cur: Dict[str, float],
                   prev: Dict[str, float]) -> Dict[str, float]:
    """Per-name counter deltas between two cumulative snapshots.
    Zero-delta names are dropped (frames stay compact); a counter that
    SHRANK means the source registry restarted mid-window — the honest
    window value is the current cumulative count, not a negative."""
    out: Dict[str, float] = {}
    for name, v in cur.items():
        try:
            d = float(v) - float(prev.get(name, 0.0))
        except (TypeError, ValueError):
            continue
        if d < 0:
            d = float(v)
        if d:
            out[name] = d
    return out


def histograms_delta(cur: Dict[str, Dict],
                     prev: Dict[str, Dict]) -> Dict[str, Dict]:
    """Bucket-wise histogram deltas; empty windows are dropped."""
    out: Dict[str, Dict] = {}
    for name, snap in cur.items():
        d = Histogram.snapshot_delta(snap, prev.get(name), name)
        if int(d.get("count", 0)):
            out[name] = d
    return out


class TelemetryHistory:
    """Fixed-cadence window frames over a snapshot callable.

    ``collect()`` must return the canonical snapshot document
    (``export.collect_snapshot`` shape: counters / histograms / gauges /
    anchor). Each :meth:`roll` computes one frame as the delta against
    the previous snapshot, appends it to the bounded ring and (when
    ``out_dir`` is set) to the JSONL log. ``extra`` rides into every
    frame verbatim — the node stamps the SLO objectives there so a
    replayed history dir is self-describing."""

    def __init__(self, collect: Callable[[], Dict],
                 window_secs: float = DEFAULT_WINDOW_SECS,
                 retain_windows: int = DEFAULT_RETAIN,
                 out_dir: Optional[str] = None,
                 process_id: int = 0,
                 extra: Optional[Dict] = None):
        self._collect = collect
        self.window_secs = max(0.1, float(window_secs))
        self.retain = max(1, int(retain_windows))
        self.out_dir = out_dir
        self.process_id = process_id
        self._extra = dict(extra or {})
        self._lock = threading.Lock()
        self._frames: deque = deque(maxlen=self.retain)
        self._prev: Optional[Dict] = None
        self._prev_ts = time.time()
        self._seq = 0
        self.version = 0          # bumps per rolled frame (healthz cache)
        self._warned_tick = False
        self._warned_disk = False
        self._disk_lines: Optional[int] = None   # counted lazily
        # serialized lines mirroring the on-disk tail: once the log is
        # at capacity, retention rewrites come straight from here —
        # no read-back of the file it is about to replace
        self._disk_ring: deque = deque(maxlen=self.retain)
        self._dir_ready = False

    @property
    def path(self) -> Optional[str]:
        # keyed by the stable cluster rank: a restarted rank writes the
        # SAME file (adoption keeps the retention bound spanning
        # restarts) instead of leaving one orphan per dead pid — the
        # frames themselves carry the writing pid
        if not self.out_dir:
            return None
        return os.path.join(self.out_dir,
                            f"history_p{self.process_id}.jsonl")

    def frames(self) -> List[Dict]:
        """Retained frames, oldest first."""
        with self._lock:
            return list(self._frames)

    def tick(self) -> Optional[Dict]:
        """The PeriodicDumper cadence hook: roll iff a full window
        elapsed since the last frame. Never raises — history must never
        fail a shuffle (the telemetry-plane rule)."""
        try:
            if time.time() - self._prev_ts >= self.window_secs:
                return self.roll()
        except Exception:
            if not self._warned_tick:
                self._warned_tick = True
                log.exception("history tick failed; further failures "
                              "are silenced")
        return None

    def roll(self, now: Optional[float] = None) -> Optional[Dict]:
        """Force-close the current window into one frame (tests call
        this to make window boundaries deterministic; production rides
        :meth:`tick`)."""
        now = time.time() if now is None else float(now)
        doc = self._collect()
        with self._lock:
            prev, t0 = self._prev, self._prev_ts
            self._prev = {
                "counters": dict(doc.get("counters") or {}),
                "histograms": dict(doc.get("histograms") or {}),
            }
            self._prev_ts = now
            if prev is None:
                # the first snapshot only OPENS the window: a frame needs
                # two endpoints, and boot-to-now is exactly the
                # aggregate this module exists to replace
                return None
            self._seq += 1
            frame = {
                "kind": FRAME_KIND,
                "seq": self._seq,
                "t_start": t0,
                "t_end": now,
                "window_s": round(now - t0, 3),
                "pid": os.getpid(),
                "process_id": self.process_id,
                "anchor": doc.get("anchor"),
                "counters": counters_delta(
                    doc.get("counters") or {}, prev["counters"]),
                "histograms": histograms_delta(
                    doc.get("histograms") or {}, prev["histograms"]),
                "gauges": dict(doc.get("gauges") or {}),
            }
            frame.update(self._extra)
            self._frames.append(frame)
            self.version += 1
        self._append_disk(frame)
        return frame

    # -- on-disk JSONL -----------------------------------------------------
    def _append_disk(self, frame: Dict) -> None:
        """Size-bounded JSONL append. Below capacity this is ONE plain
        append (the hot path). At capacity, oldest-first truncation is
        an atomic whole-file rewrite (tmp + rename via utils/atomicio —
        a reader never sees a torn file) served straight from the
        in-memory line ring, so retention never reads back the file it
        is about to replace. An existing log (restart) is adopted into
        the ring once, at first append, so the bound spans restarts."""
        path = self.path
        if not path:
            return
        try:
            if not self._dir_ready:
                os.makedirs(self.out_dir, exist_ok=True)
                self._dir_ready = True
            if self._disk_lines is None:
                self._disk_lines = 0
                if os.path.exists(path):
                    with open(path) as f:
                        prior = [ln for ln in f if ln.strip()]
                    self._disk_lines = len(prior)
                    self._disk_ring.extend(
                        ln.rstrip("\n") for ln in prior)
            line = json.dumps(frame, sort_keys=True, default=repr)
            self._disk_ring.append(line)
            if self._disk_lines < self.retain:
                with open(path, "a") as f:
                    f.write(line + "\n")
                self._disk_lines += 1
            else:
                from sparkucx_tpu.utils.atomicio import atomic_write_text
                atomic_write_text(
                    path, "\n".join(self._disk_ring) + "\n",
                    fsync=False)
                self._disk_lines = len(self._disk_ring)
        except Exception:
            if not self._warned_disk:
                self._warned_disk = True
                log.exception("history append to %s failed; further "
                              "failures are silenced", path)


# -- replay (CLI / restart) --------------------------------------------------
def load_history_file(path: str) -> List[Dict]:
    """Parse one ``history_*.jsonl`` into frames, oldest first. Torn or
    foreign lines are skipped with a warning — an append interrupted by
    SIGKILL must not take the whole replay down; anchor enforcement is
    the caller's (CLI) job, per the stats/trace/timeline discipline."""
    frames: List[Dict] = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                log.warning("%s:%d: unparseable history line skipped",
                            path, i + 1)
                continue
            if isinstance(doc, dict) and doc.get("kind") == FRAME_KIND:
                frames.append(doc)
    return frames


def history_files(directory: str) -> List[str]:
    """Window logs in a dump/history dir — THE definition of what the
    CLI treats as a history input (``__main__._expand_inputs``)."""
    import glob
    return sorted(glob.glob(os.path.join(directory, "history_*.jsonl")))


def frames_to_doc(frames: List[Dict], source: str = "history") -> Dict:
    """Wrap replayed frames as a snapshot-shaped doc the doctor's
    ``build_view`` folds (``history_frames`` key) — a history dir is a
    first-class ``--input`` for the slo/doctor CLIs. The doc inherits
    the newest frame's anchor/identity; counters/histograms stay empty
    (cumulative state did not survive the restart — that is the point
    of the retained log)."""
    if not frames:
        raise ValueError(f"{source}: no history frames")
    last = frames[-1]
    doc = {
        "ts": last.get("t_end"),
        "pid": last.get("pid"),
        "process_id": last.get("process_id"),
        "anchor": last.get("anchor"),
        "counters": {},
        "histograms": {},
        "history_frames": list(frames),
    }
    objs = last.get("slo_objectives")
    if objs:
        doc["slo_objectives"] = objs
    return doc
