"""Decision ledger — the agreement plane's durable, auditable log.

The reference keeps every cross-executor control decision in ONE
driver-hosted metadata buffer (ref: CommonUcxShuffleManager.scala:39-56)
— implicitly a log: the driver's copy is authoritative and inspectable,
so "what did the cluster decide" always has an answer. Our driverless
:func:`~sparkucx_tpu.shuffle.agreement.agree` primitive (PR 19) replays
that rendezvous as a collective, which left the plane observable through
exactly two counters. This module is the log rebuilt for the
multi-controller world: every process appends every round it closes —
``{epoch, seq, topic, winner digest, per-peer proposal digests, round
wall ms, per-peer header arrival lag, implicated conf key}`` — to a
bounded in-memory ring plus (when ``history.dir`` is set) a
restart-durable, rank-keyed, retention-bounded JSONL beside the history
log (the PR-14 ``history_p<rank>.jsonl`` adoption discipline, atomic
rewrites at capacity via utils/atomicio).

The asymmetry is honest and is the point: the driver's log was a single
authoritative copy; ours is N replicas that are byte-comparable *by
construction* (each record is a pure function of the gathered round —
"Memory-efficient array redistribution"'s pure-function-of-agreed-inputs
discipline, PAPERS.md), so consistency is a property to AUDIT after the
fact, not assume. :func:`align_rounds` joins N ledgers by ``(epoch,
seq)`` and :func:`audit_round` grades each aligned round: topic and
winner digest must be identical everywhere, and on a *reduced* topic
(min/max/sum — which settles WITHOUT a unanimity check) differing
per-peer proposal digests are the silent conf split unanimity can never
catch. Because most reduced rounds aggregate BY-DESIGN-divergent shares
(queue depths, row sums, overflow votes), each round carries its audit
contract from the call site — ``agree(audit="strict")`` declares "every
peer derives this proposal from conf, divergence is a split";
``"aggregate"`` (the default under a reducer) exempts within-list
divergence. The doctor's ``decision_split`` / ``slow_proposer`` rules
and the ``python -m sparkucx_tpu decisions`` CLI both run on these
helpers.

Never on the failure path: recording is wrapped so a ledger fault can
never fail a shuffle (the telemetry-plane rule), and the disabled plane
is a NULL object whose ``record`` is a constant-time no-op.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from sparkucx_tpu.utils.logging import get_logger

log = get_logger("shuffle.decisions")

DECISION_KIND = "decision"
DEFAULT_RETAIN = 256

# reduce codes whose rounds settle WITHOUT a unanimity check — per-peer
# proposals may legitimately differ, so a conf split under them wins
# silently at agree() time and only the after-the-fact audit can see it
REDUCED = ("max", "min", "sum", "any", "all", "callable")


def digest_row(row) -> int:
    """Stable digest of one proposal/winner vector: crc32 over the
    canonical int64 little-endian bytes — identical on every process for
    identical values (a pure function of the agreed inputs), cheap
    enough for the hot path, and small enough to log per peer."""
    arr = np.ascontiguousarray(np.asarray(row, dtype=np.int64))
    return zlib.crc32(arr.astype("<i8", copy=False).tobytes()) & 0xFFFFFFFF


class DecisionLedger:
    """Bounded ring + rank-keyed JSONL of closed agreement rounds.

    ``record()`` never raises (warn-once on disk faults); ``tail()`` /
    ``position()`` serve the snapshot, postmortem and live-route
    surfaces; ``total`` is the monotonic append count (the
    ExchangeReport attribution mark — ring wrap safe)."""

    def __init__(self, retain: int = DEFAULT_RETAIN,
                 out_dir: Optional[str] = None, process_id: int = 0):
        self.enabled = True
        self.retain = max(1, int(retain))
        self.out_dir = out_dir
        self.process_id = process_id
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.retain)
        self.total = 0            # monotonic appends (never wraps)
        self._warned = False
        self._disk_lines: Optional[int] = None   # counted lazily
        # serialized lines mirroring the on-disk tail (the history.py
        # retention discipline): at capacity the rewrite comes straight
        # from here, never reading back the file it replaces
        self._disk_ring: deque = deque(maxlen=self.retain)
        self._dir_ready = False
        self._fh = None          # persistent append handle (hot path)
        self._path = (os.path.join(
            out_dir, f"decisions_p{process_id}.jsonl")
            if out_dir else None)

    @property
    def path(self) -> Optional[str]:
        # keyed by the STABLE cluster rank (not the pid): a restarted
        # rank adopts its predecessor's log, so the retention bound
        # spans restarts — the history_p<rank>.jsonl discipline.
        # Precomputed (out_dir and rank are fixed at construction):
        # this sits on the per-round settlement path
        return self._path

    def record(self, *, epoch: int, seq: int, topic: str,
               reduce: str = "unanimous", nprocs: int = 1,
               winner: int = 0, proposals: Optional[List[int]] = None,
               round_ms: float = 0.0,
               lag_ms: Optional[List[float]] = None,
               conf_key: str = "", ok: bool = True,
               error: str = "", audit: str = "strict") -> Optional[Dict]:
        """Append one closed round. Called from agree() on EVERY exit
        (unanimous return, reduced return, typed divergence, peer
        loss), so the ledger is a complete account of the plane — a
        divergent round is exactly the record the postmortem wants.
        Never raises."""
        try:
            rec = {
                "kind": DECISION_KIND,
                "n": 0,                      # monotonic index, set below
                "ts": time.time(),
                "pid": os.getpid(),
                "process_id": self.process_id,
                "epoch": int(epoch), "seq": int(seq), "topic": str(topic),
                "reduce": str(reduce), "nprocs": int(nprocs),
                "winner": int(winner),
                "proposals": [int(p) for p in (proposals or [])],
                "round_ms": round(float(round_ms), 3),
                "lag_ms": [round(float(v), 3) for v in (lag_ms or [])],
                "conf_key": str(conf_key),
                "ok": bool(ok),
                "audit": str(audit),
            }
            if error:
                rec["error"] = str(error)[:200]
            with self._lock:
                self.total += 1
                rec["n"] = self.total
                self._ring.append(rec)
            self._append_disk(rec)
            return rec
        except Exception:
            if not self._warned:
                self._warned = True
                log.exception("decision record failed; further failures "
                              "are silenced")
            return None

    def tail(self, n: Optional[int] = None) -> List[Dict]:
        """Newest-last retained records (all, or the last ``n``)."""
        with self._lock:
            recs = list(self._ring)
        return recs if n is None else recs[-int(n):]

    def since(self, mark: int) -> List[Dict]:
        """Records appended after monotonic index ``mark`` — the
        ExchangeReport attribution window (ring-wrap safe: wrapped-out
        records are simply gone, never double-counted)."""
        with self._lock:
            return [r for r in self._ring if r.get("n", 0) > mark]

    def position(self) -> Optional[Dict]:
        """The newest record's (epoch, seq, topic, ok) — the
        'last-decision position' the peer postmortem prints beside the
        last-span position."""
        with self._lock:
            if not self._ring:
                return None
            r = self._ring[-1]
        return {"epoch": r["epoch"], "seq": r["seq"],
                "topic": r["topic"], "ok": r["ok"], "ts": r["ts"]}

    # -- on-disk JSONL (the history.py _append_disk discipline) ----------
    def _append_disk(self, rec: Dict) -> None:
        path = self.path
        if not path:
            return
        try:
            if not self._dir_ready:
                os.makedirs(self.out_dir, exist_ok=True)
                self._dir_ready = True
            if self._disk_lines is None:
                # adopt a predecessor's log ONCE, at first append, so
                # the retention bound spans restarts
                self._disk_lines = 0
                if os.path.exists(path):
                    with open(path) as f:
                        prior = [ln for ln in f if ln.strip()]
                    self._disk_lines = len(prior)
                    self._disk_ring.extend(
                        ln.rstrip("\n") for ln in prior)
            line = json.dumps(rec, sort_keys=True, default=repr,
                              separators=(",", ":"))
            self._disk_ring.append(line)
            if self._disk_lines < 2 * self.retain:
                # amortized compaction: decisions land once per agree()
                # round (every distributed exchange), so unlike the
                # per-window history log a full atomic rewrite per
                # append would put an O(retain) file rewrite on the hot
                # settlement path. Append (through a persistent
                # line-flushed handle — live on disk for the postmortem
                # after a SIGKILL, no per-round open()) until the file
                # holds 2x the retention target, then compact back to
                # the newest ``retain`` lines — the on-disk bound is 2x
                # retain, the rewrite cost amortizes to O(1) per round
                if self._fh is None:
                    self._fh = open(path, "a")
                self._fh.write(line + "\n")
                self._fh.flush()
                self._disk_lines += 1
            else:
                from sparkucx_tpu.utils.atomicio import atomic_write_text
                if self._fh is not None:
                    self._fh.close()
                    self._fh = None
                atomic_write_text(
                    path, "\n".join(self._disk_ring) + "\n",
                    fsync=False)
                self._disk_lines = len(self._disk_ring)
        except Exception:
            if not self._warned:
                self._warned = True
                log.exception("decision append to %s failed; further "
                              "failures are silenced", path)

    def close(self) -> None:
        """Release the persistent append handle (node teardown).
        Records after close still land in the ring and re-open the
        file lazily — close is a flush point, not a tombstone."""
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except Exception:
                    pass
                self._fh = None


class _NullDecisionLedger:
    """The disabled plane: constant-time no-ops, no state, no disk —
    assigning through it must never raise (the __slots__ null-object
    discipline of runtime/failures.py)."""

    __slots__ = ()
    enabled = False
    total = 0
    path = None
    process_id = 0

    def record(self, **kw):
        return None

    def close(self):
        return None

    def tail(self, n=None):
        return []

    def since(self, mark):
        return []

    def position(self):
        return None


NULL_DECISION_LEDGER = _NullDecisionLedger()

# module seam (the current_watchdog() pattern): agree() and the
# turnstile are module functions/classes with no node handle, so the
# node installs its ledger here at start and nulls it at close
_CURRENT: object = NULL_DECISION_LEDGER
_CURRENT_LOCK = threading.Lock()


def set_ledger(ledger) -> object:
    """Install the process-wide ledger; returns the previous one (the
    node restores NULL_DECISION_LEDGER at close)."""
    global _CURRENT
    with _CURRENT_LOCK:
        prev = _CURRENT
        _CURRENT = ledger if ledger is not None else NULL_DECISION_LEDGER
    return prev


def current_ledger():
    return _CURRENT


# -- replay (CLI / restart / CI artifacts) -----------------------------------
def load_decisions_file(path: str) -> List[Dict]:
    """Parse one ``decisions_*.jsonl`` into records, oldest first. Torn
    or foreign lines are skipped with a warning — a SIGKILLed append
    must not take the whole audit down (the load_history_file rule)."""
    recs: List[Dict] = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                log.warning("%s:%d: unparseable decision line skipped",
                            path, i + 1)
                continue
            if isinstance(doc, dict) and doc.get("kind") == DECISION_KIND:
                recs.append(doc)
    return recs


def decisions_files(directory: str) -> List[str]:
    """Decision ledgers in a dump/history dir — THE definition of what
    the CLI treats as a decisions input (``__main__._expand_inputs``)."""
    import glob
    return sorted(glob.glob(os.path.join(directory,
                                         "decisions_*.jsonl")))


def decisions_to_doc(records: List[Dict],
                     source: str = "decisions") -> Dict:
    """Wrap replayed records as a snapshot-shaped doc the doctor's
    ``build_view`` folds (``decisions`` key) — a ledger file is a
    first-class ``--input`` for the decisions/doctor CLIs, mirroring
    history.frames_to_doc."""
    if not records:
        raise ValueError(f"{source}: no decision records")
    last = records[-1]
    return {
        "ts": last.get("ts"),
        "pid": last.get("pid"),
        "process_id": last.get("process_id"),
        "counters": {},
        "histograms": {},
        "decisions": list(records),
    }


# -- the consistency audit ---------------------------------------------------
def align_rounds(ledgers: Dict[int, List[Dict]]) -> List[Dict]:
    """Join N peers' ledgers by ``(epoch, seq)``, oldest round first.

    Each aligned round is ``{"epoch", "seq", "records": {peer: rec}}``.
    A peer whose retention window no longer covers a round simply has
    no entry — the audit degrades to the peers that do (warn, never
    crash: the missing-peer contract)."""
    by_round: Dict[tuple, Dict[int, Dict]] = {}
    for peer, recs in ledgers.items():
        for r in recs:
            if not isinstance(r, dict) or "epoch" not in r:
                continue
            key = (int(r["epoch"]), int(r.get("seq", -1)))
            by_round.setdefault(key, {})[peer] = r
    return [{"epoch": e, "seq": s, "records": peers}
            for (e, s), peers in sorted(by_round.items())]


def audit_round(aligned: Dict) -> Optional[Dict]:
    """Grade one aligned round; ``None`` = consistent.

    Three split shapes, in severity order: **topic** (peers closed
    DIFFERENT rounds under the same (epoch, seq) — the sequencing split
    after the fact), **winner** (same round, different agreed result —
    should be impossible while the reduction is deterministic, so it
    means broken determinism), **proposal** (reduced topic, identical
    winner, differing proposals — the silent conf split min/max-reduce
    settles without raising; THE case the auditor exists for).
    Divergent rounds the primitive already fenced typed (``ok=False``)
    are skipped here — the ``desync`` rule owns them. The dissenting
    peer set is the minority by value (ties toward the lowest peer,
    matching agreement._majority_row)."""
    recs = aligned["records"]
    if len(recs) < 2:
        return None
    if not all(r.get("ok", True) for r in recs.values()):
        return None

    def _minority(values: Dict[int, object]) -> List[int]:
        counts: Dict[object, int] = {}
        for v in values.values():
            counts[v] = counts.get(v, 0) + 1
        best = max(counts.values())
        # majority value = the lowest peer holding a maximally-common
        # value (ties toward the lowest peer, agreement._majority_row)
        majority = None
        for p in sorted(values):
            if counts[values[p]] == best:
                majority = values[p]
                break
        return [p for p in sorted(values) if values[p] != majority]

    topics = {p: r.get("topic", "") for p, r in recs.items()}
    if len(set(topics.values())) > 1:
        return {"split": "topic", "dissenters": _minority(topics),
                "values": topics}
    winners = {p: r.get("winner", 0) for p, r in recs.items()}
    if len(set(winners.values())) > 1:
        return {"split": "winner", "dissenters": _minority(winners),
                "values": winners}
    any_rec = next(iter(recs.values()))
    if any_rec.get("reduce", "unanimous") in REDUCED:
        props = {p: tuple(r.get("proposals") or ())
                 for p, r in recs.items()}
        # each peer logged the same gathered matrix, so every peer's
        # proposal LIST must agree regardless of contract; a cross-peer
        # list mismatch means the gather itself delivered different
        # matrices — broken transport/determinism, always a split
        rows = [r.get("proposals") or [] for r in recs.values()]
        base = rows[0]
        if any(tuple(r) != tuple(base) for r in rows[1:]):
            return {"split": "proposal", "dissenters": _minority(props),
                    "values": {p: list(v) for p, v in props.items()}}
        # within-list divergence is contract-dependent: an "aggregate"
        # round reduces BY-DESIGN-divergent shares (async.batch queue
        # depths, tier.crossRows sums, hier overflow votes) and is
        # clean; a "strict" round reduces a value every peer derives
        # from conf, so differing digests ARE the silent conf split
        # the reducer settled without raising — THE case this auditor
        # exists for. The contract rides each record (agree(audit=)).
        if any_rec.get("audit", "strict") == "strict" \
                and base and len(set(base)) > 1:
            counts: Dict[int, int] = {}
            for d in base:
                counts[d] = counts.get(d, 0) + 1
            best = max(counts.values())
            maj = next(d for d in base if counts[d] == best)
            dissent = [i for i, d in enumerate(base) if d != maj]
            return {"split": "proposal", "dissenters": dissent,
                    "values": {"proposal_digests": list(base)}}
    return None
