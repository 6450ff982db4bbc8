"""Decision-plane observability (shuffle/decisions.py): the agreement
ledger every agree() round appends to, the turnstile's ticket
telemetry, the joined-ledger consistency audit (align + audit_round),
the doctor rules riding it (decision_split, slow_proposer, the desync
ledger link), the ExchangeReport.agreement summary, the /decisions
live route and the offline `decisions` CLI.

The flagship scenario: a min/max-reduced agreement round settles
WITHOUT a unanimity check, so one peer proposing a divergent
conf-derived bound loses the reduction silently — the fleet keeps
running on an answer it believes was agreed. The ledger records every
round's per-peer proposal digests with an audit contract
(strict = conf-derived, aggregate = by-design-divergent shares), and
the after-the-fact auditor is the ONLY detector."""

import json
import threading
import time

import numpy as np
import pytest

from sparkucx_tpu.shuffle import agreement
from sparkucx_tpu.shuffle.agreement import (AgreementDivergenceError,
                                            CollectiveTurnstile, agree,
                                            reset_epoch)
from sparkucx_tpu.shuffle.decisions import (NULL_DECISION_LEDGER,
                                            DecisionLedger, align_rounds,
                                            audit_round, current_ledger,
                                            decisions_files, digest_row,
                                            load_decisions_file,
                                            set_ledger)
from sparkucx_tpu.utils.metrics import (C_AGREE_ROUNDS,
                                        C_TURNSTILE_ABANDONED,
                                        G_TURNSTILE_DEPTH, H_AGREE_ROUND,
                                        H_TURNSTILE_WAIT, Metrics,
                                        labeled)


@pytest.fixture()
def ledger_seam():
    """Install a fresh ring-only ledger through the module seam and
    restore whatever was there after (a conftest node may own it)."""
    prev = current_ledger()
    led = DecisionLedger(retain=64)
    set_ledger(led)
    yield led
    set_ledger(prev)


def _rec(epoch=0, seq=0, topic="hier.dcn.capms", reduce="min",
         winner=250, proposals=(250, 250), audit="strict", ok=True,
         lag_ms=(0.0, 0.0), process_id=0, n=1, **kw):
    out = {"kind": "decision", "n": n, "ts": 1000.0 + seq, "pid": 1,
           "process_id": process_id, "epoch": epoch, "seq": seq,
           "topic": topic, "reduce": reduce, "nprocs": len(proposals),
           "winner": winner, "proposals": list(proposals),
           "round_ms": 0.4, "lag_ms": list(lag_ms),
           "conf_key": "spark.shuffle.tpu.a2a.capacityFactor",
           "ok": ok, "audit": audit}
    out.update(kw)
    return out


# -- the ledger --------------------------------------------------------------
def test_ledger_ring_retention_and_monotonic_index():
    led = DecisionLedger(retain=4)
    for i in range(10):
        led.record(epoch=0, seq=i, topic="t", winner=i)
    assert led.total == 10
    tail = led.tail()
    assert len(tail) == 4                      # ring bound
    assert [r["n"] for r in tail] == [7, 8, 9, 10]
    assert [r["seq"] for r in tail] == [6, 7, 8, 9]
    assert led.tail(2)[0]["seq"] == 8
    assert [r["n"] for r in led.since(8)] == [9, 10]
    pos = led.position()
    assert pos["seq"] == 9 and pos["topic"] == "t" and pos["ok"]


def test_ledger_jsonl_live_append_and_retention_bound(tmp_path):
    """Records land on disk LIVE (present after a SIGKILL) under the
    amortized retention bound: the file never exceeds 2x retain lines
    and compacts back to the newest retain."""
    led = DecisionLedger(retain=3, out_dir=str(tmp_path), process_id=5)
    path = tmp_path / "decisions_p5.jsonl"
    led.record(epoch=0, seq=0, topic="t")
    assert path.exists()                       # live, not buffered
    assert len(load_decisions_file(str(path))) == 1
    for i in range(1, 20):
        led.record(epoch=0, seq=i, topic="t")
        assert len(path.read_text().splitlines()) <= 6   # 2x retain
    led.close()
    recs = load_decisions_file(str(path))
    assert [r["seq"] for r in recs][-3:] == [17, 18, 19]
    assert decisions_files(str(tmp_path)) == [str(path)]


def test_ledger_restart_adoption_spans_retention(tmp_path):
    """A restarted rank adopts its predecessor's log: the retention
    bound spans restarts and the monotonic window keeps the old tail."""
    a = DecisionLedger(retain=4, out_dir=str(tmp_path), process_id=0)
    for i in range(6):
        a.record(epoch=0, seq=i, topic="before")
    a.close()
    b = DecisionLedger(retain=4, out_dir=str(tmp_path), process_id=0)
    for i in range(2):
        b.record(epoch=1, seq=i, topic="after")
    b.close()
    recs = load_decisions_file(str(tmp_path / "decisions_p0.jsonl"))
    assert [r["topic"] for r in recs[-2:]] == ["after", "after"]
    assert any(r["topic"] == "before" for r in recs)   # adopted tail
    assert len(recs) <= 8                              # 2x retain


def test_ledger_torn_line_skipped(tmp_path):
    led = DecisionLedger(retain=8, out_dir=str(tmp_path), process_id=1)
    led.record(epoch=0, seq=0, topic="t")
    led.close()
    path = tmp_path / "decisions_p1.jsonl"
    with open(path, "a") as f:
        f.write('{"kind": "decision", "n": 2, "epo')   # torn write
    assert len(load_decisions_file(str(path))) == 1


def test_null_ledger_stateless_and_never_raises():
    assert NULL_DECISION_LEDGER.record(epoch=0, seq=0, topic="t") is None
    assert NULL_DECISION_LEDGER.tail() == []
    assert NULL_DECISION_LEDGER.since(0) == []
    assert NULL_DECISION_LEDGER.position() is None
    assert NULL_DECISION_LEDGER.close() is None
    assert not NULL_DECISION_LEDGER.enabled


def test_record_never_raises(ledger_seam):
    # un-serializable extras route through default=repr; a bad field
    # degrades to the warn-once path, never an exception
    assert ledger_seam.record(epoch=0, seq=0, topic="t",
                              proposals=[1, 2]) is not None
    assert ledger_seam.record(epoch="bogus", seq=0, topic="t") is None


# -- the turnstile under K concurrent workers --------------------------------
def test_turnstile_k_workers_ordered_abandoned_counted():
    """K workers acquire in strict ticket order regardless of start
    order; an abandoned ticket (released unentered) is counted and
    skipped; no release is lost — the depth gauge returns to zero and
    every wait lands in the histogram."""
    m = Metrics()
    gate = CollectiveTurnstile(metrics=m)
    K = 8
    tickets = [gate.issue() for _ in range(K)]
    assert m.gauges()[G_TURNSTILE_DEPTH] == float(K)
    ran = []
    lock = threading.Lock()

    def work(t):
        gate.acquire(t)
        with lock:
            ran.append(t)
        gate.release(t)

    abandoned = tickets[3]
    gate.release(abandoned)                    # never entered
    live = [t for t in tickets if t != abandoned]
    threads = [threading.Thread(target=work, args=(t,))
               for t in reversed(live)]        # start in REVERSE order
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert ran == live                         # agreed order enforced
    assert m.gauges()[G_TURNSTILE_DEPTH] == 0.0
    assert m.get(C_TURNSTILE_ABANDONED) == 1.0
    assert m.histogram(H_TURNSTILE_WAIT).snapshot()["count"] == len(live)
    gate.close()


# -- agree() instrumentation (satellite: every exit path counts) -------------
class _FakeGather:
    def __init__(self, mutate=None):
        self.mutate = mutate

    def __call__(self, payload, what="", timeout_ms=None):
        mine = np.asarray(payload)
        rows = [mine, mine, mine.copy()]
        if self.mutate is not None and not what.startswith(
                "agreement header"):
            rows[2] = self.mutate(mine.copy())
        return np.stack(rows)


def test_agree_round_metrics_and_ledger_on_success(ledger_seam):
    reset_epoch(0)
    m = Metrics()
    agree("a2a.waveRows", [4096], metrics=m,
          conf_key="spark.shuffle.tpu.a2a.waveRows")
    assert m.get(C_AGREE_ROUNDS) == 1.0
    assert m.get(labeled(C_AGREE_ROUNDS, topic="a2a.waveRows")) == 1.0
    assert m.histogram(H_AGREE_ROUND).snapshot()["count"] == 1
    assert m.histogram(labeled(
        H_AGREE_ROUND, topic="a2a.waveRows")).snapshot()["count"] == 1
    rec = ledger_seam.tail(1)[0]
    assert rec["topic"] == "a2a.waveRows" and rec["ok"]
    assert rec["audit"] == "strict"            # unanimity default
    assert rec["winner"] == digest_row(np.array([4096]))
    assert rec["conf_key"] == "spark.shuffle.tpu.a2a.waveRows"
    assert rec["round_ms"] >= 0.0 and len(rec["lag_ms"]) == 1


def test_agree_divergent_round_still_counts(ledger_seam, monkeypatch):
    """The satellite bugfix pinned: a FAILED round must land in
    rounds.count (and its labeled twin) and observe round_ms — the
    divergence ratio divergence{topic=}/rounds{topic=} stays
    computable — and the ledger records it ok=False with the error
    kind."""
    from sparkucx_tpu.shuffle import distributed as dist
    reset_epoch(0)
    m = Metrics()

    def bump(row):
        row[0] += 9
        return row

    monkeypatch.setattr(dist, "allgather_blob", _FakeGather(mutate=bump))
    with pytest.raises(AgreementDivergenceError):
        agree("async.order", [1, 2], metrics=m,
              conf_key="spark.shuffle.tpu.tenant.asyncAgreedOrder")
    assert m.get(C_AGREE_ROUNDS) == 1.0
    assert m.get(labeled(C_AGREE_ROUNDS, topic="async.order")) == 1.0
    assert m.histogram(H_AGREE_ROUND).snapshot()["count"] == 1
    assert m.histogram(labeled(
        H_AGREE_ROUND, topic="async.order")).snapshot()["count"] == 1
    rec = ledger_seam.tail(1)[0]
    assert rec["ok"] is False and rec["error"] == "value"
    assert rec["nprocs"] == 3 and len(rec["proposals"]) == 3
    assert rec["proposals"][0] != rec["proposals"][2]


def test_agree_audit_contract_defaults_and_validation(ledger_seam):
    reset_epoch(0)
    agree("x.unanimous", [1])
    agree("x.reduced", [2], reduce="min")
    agree("x.optin", [3], reduce="min", audit="strict")
    a, b, c = ledger_seam.tail(3)
    assert a["audit"] == "strict"              # unanimity default
    assert b["audit"] == "aggregate"           # reduced default
    assert c["audit"] == "strict"              # explicit opt-in
    with pytest.raises(ValueError, match="audit contract"):
        agree("x.bad", [1], audit="paranoid")


def test_agree_lag_recovered_from_header_stamps(ledger_seam,
                                               monkeypatch):
    """Per-peer arrival lag comes from the send stamps the header
    round already gathers — no extra wire traffic; the baseline is
    the earliest stamp."""
    from sparkucx_tpu.shuffle import distributed as dist
    reset_epoch(0)

    def gather(payload, what="", timeout_ms=None):
        mine = np.asarray(payload)
        rows = np.stack([mine, mine, mine])
        if what.startswith("agreement header"):
            rows = rows.copy()
            rows[1, 5] -= 7                    # peer 1 sent earliest
            rows[2, 5] += 5
        return rows

    monkeypatch.setattr(dist, "allgather_blob", gather)
    agree("x.lag", [1])
    rec = ledger_seam.tail(1)[0]
    assert rec["lag_ms"] == [7.0, 0.0, 12.0]


# -- the joined-ledger audit -------------------------------------------------
def test_align_rounds_joins_by_epoch_seq():
    led = {0: [_rec(seq=0, n=1), _rec(seq=1, n=2)],
           1: [_rec(seq=1, n=1, process_id=1)]}
    rows = align_rounds(led)
    assert [(r["epoch"], r["seq"]) for r in rows] == [(0, 0), (0, 1)]
    assert set(rows[1]["records"]) == {0, 1}
    assert set(rows[0]["records"]) == {0}      # retention gap: degraded


def test_audit_clean_fleet_quiet():
    """An honest fleet is QUIET: unanimity rounds, strict rounds with
    identical proposals, and aggregate rounds with by-design-divergent
    proposals all pass."""
    for row in align_rounds({
            0: [_rec(seq=0, topic="u", reduce="unanimous",
                     proposals=(9, 9)),
                _rec(seq=1, proposals=(250, 250), audit="strict"),
                _rec(seq=2, topic="async.batch", reduce="min",
                     proposals=(3, 5), audit="aggregate")],
            1: [_rec(seq=0, topic="u", reduce="unanimous",
                     proposals=(9, 9), process_id=1),
                _rec(seq=1, proposals=(250, 250), audit="strict",
                     process_id=1),
                _rec(seq=2, topic="async.batch", reduce="min",
                     proposals=(3, 5), audit="aggregate",
                     process_id=1)]}):
        assert audit_round(row) is None, row



def test_agree_rounds_audit_clean_against_own_ledger(ledger_seam):
    """Real agree() rounds under every contract (unanimity, min and sum
    reductions, a strict min) settle ok into the ledger, and a two-peer
    view of that ledger (what an honest fleet's aligned ledgers look
    like) audits clean through the topic, winner and proposal checks."""
    reset_epoch(0)
    rounds = 5
    for i in range(rounds):
        agree("t.rows", np.array([256], dtype=np.int64),
              conf_key="spark.shuffle.tpu.a2a.waveRows")
        agree("t.depth", np.array([i % 5], dtype=np.int64), reduce="min",
              conf_key="spark.shuffle.tpu.tenant.asyncAgreedOrder")
        agree("t.cross", np.array([i * 3], dtype=np.int64), reduce="sum",
              conf_key="spark.shuffle.tpu.topology")
        agree("t.capms", np.array([250], dtype=np.int64), reduce="min",
              audit="strict",
              conf_key="spark.shuffle.tpu.a2a.capacityFactor")
    led = ledger_seam.tail()
    assert len(led) == 4 * rounds and all(r["ok"] for r in led)
    rows = align_rounds({0: led, 1: led})
    assert len(rows) == 4 * rounds
    assert [audit_round(row) for row in rows] == [None] * (4 * rounds)

def test_audit_detects_silent_strict_split():
    """THE case the auditor exists for: a strict min-reduce settles
    green while the peers' conf-derived proposals differ — flagged as
    a proposal split naming the dissenting position."""
    rows = align_rounds({
        0: [_rec(seq=0, proposals=(250, 256), audit="strict")],
        1: [_rec(seq=0, proposals=(250, 256), audit="strict",
                 process_id=1)]})
    v = audit_round(rows[0])
    assert v is not None and v["split"] == "proposal"
    assert v["dissenters"] == [1]              # position 1 dissented
    # the same proposals under the AGGREGATE contract are clean
    rows = align_rounds({
        0: [_rec(seq=0, proposals=(250, 256), audit="aggregate")],
        1: [_rec(seq=0, proposals=(250, 256), audit="aggregate",
                 process_id=1)]})
    assert audit_round(rows[0]) is None


def test_audit_topic_winner_and_fenced_rounds():
    # topic split: peers closed DIFFERENT rounds under one (epoch, seq)
    rows = align_rounds({0: [_rec(seq=0, topic="a")],
                         1: [_rec(seq=0, topic="b", process_id=1)]})
    assert audit_round(rows[0])["split"] == "topic"
    # winner split: broken determinism
    rows = align_rounds({0: [_rec(seq=0, winner=111)],
                         1: [_rec(seq=0, winner=222, process_id=1)]})
    assert audit_round(rows[0])["split"] == "winner"
    # a round the primitive already fenced typed is the desync rule's
    # business, not a second finding here
    rows = align_rounds({
        0: [_rec(seq=0, ok=False, error="value", winner=111)],
        1: [_rec(seq=0, winner=222, process_id=1)]})
    assert audit_round(rows[0]) is None
    # single-peer rounds (missing peer) degrade to no-verdict
    rows = align_rounds({0: [_rec(seq=0)]})
    assert audit_round(rows[0]) is None


# -- doctor rules ------------------------------------------------------------
def _doc(pid, decisions, counters=None):
    return {"process_id": pid, "pid": 100 + pid,
            "counters": counters or {}, "histograms": {}, "gauges": {},
            "decisions": decisions}


def test_doctor_decision_split_golden():
    from sparkucx_tpu.utils.doctor import diagnose
    docs = [_doc(0, [_rec(seq=0, proposals=(250, 256), audit="strict",
                          conf_key="")]),
            _doc(1, [_rec(seq=0, proposals=(250, 256), audit="strict",
                          process_id=1, conf_key="")])]
    fs = [f for f in diagnose(docs) if f.rule == "decision_split"]
    assert len(fs) == 1 and fs[0].grade == "critical"
    assert "hier.dcn.capms" in fs[0].summary
    # topic → conf key through the desync table ("hier." prefix)
    assert fs[0].conf_key == "spark.shuffle.tpu.a2a.capacityFactor"
    ev = fs[0].evidence
    assert ev["splits"] == 1
    assert ev["split_rounds"][0]["dissenters"] == [1]
    assert "decisions --input" in fs[0].remediation


def test_doctor_decision_split_quiet_on_clean_fleet():
    from sparkucx_tpu.utils.doctor import diagnose
    docs = [_doc(0, [_rec(seq=0), _rec(seq=1, topic="async.batch",
                                       reduce="min", proposals=(3, 7),
                                       audit="aggregate")]),
            _doc(1, [_rec(seq=0, process_id=1),
                     _rec(seq=1, topic="async.batch", reduce="min",
                          proposals=(3, 7), audit="aggregate",
                          process_id=1)])]
    assert [f for f in diagnose(docs)
            if f.rule in ("decision_split", "slow_proposer")] == []


def test_doctor_decision_split_partial_audit_warns():
    """A peer without a ledger (plane off, dump lost) degrades the
    audit to a warn naming the blind spot — never a crash."""
    from sparkucx_tpu.utils.doctor import diagnose
    docs = [_doc(0, [_rec(seq=0)]), _doc(1, [_rec(seq=0, process_id=1)]),
            {"process_id": 2, "pid": 102, "counters": {},
             "histograms": {}, "gauges": {}}]       # no ledger
    fs = [f for f in diagnose(docs) if f.rule == "decision_split"]
    assert len(fs) == 1 and fs[0].grade == "warn"
    assert "PARTIAL" in fs[0].summary
    assert fs[0].conf_key == "spark.shuffle.tpu.decisions.enabled"


def test_doctor_slow_proposer_golden_and_floors():
    from sparkucx_tpu.utils.doctor import diagnose

    def fleet(lag_fn, n=10):
        return [_doc(p, [_rec(seq=i, proposals=(1, 1, 1),
                              lag_ms=lag_fn(i), process_id=p,
                              audit="aggregate", reduce="min", n=i + 1)
                         for i in range(n)]) for p in (0, 1, 2)]

    # process 2 consistently last with a real lag → warn names it
    fs = [f for f in diagnose(fleet(lambda i: [0.0, 1.0, 9.0]))
          if f.rule == "slow_proposer"]
    assert len(fs) == 1 and fs[0].grade == "warn"
    assert fs[0].evidence["process"] == 2
    assert fs[0].evidence["per_process_slow_counts"][2] == 10
    assert "process 2" in fs[0].summary
    assert fs[0].conf_key == \
        "spark.shuffle.tpu.failure.collectiveTimeoutMs"
    # under the ms floor (NTP-skew noise) → quiet
    assert [f for f in diagnose(fleet(lambda i: [0.0, 0.5, 2.0]))
            if f.rule == "slow_proposer"] == []
    # rotating last arrival (no single culprit) → quiet
    rot = [f for f in diagnose(fleet(
        lambda i: [9.0 if i % 3 == j else 0.0 for j in range(3)]))
        if f.rule == "slow_proposer"]
    assert rot == []
    # too few rounds → quiet
    assert [f for f in diagnose(fleet(lambda i: [0.0, 1.0, 9.0], n=3))
            if f.rule == "slow_proposer"] == []


def test_doctor_desync_links_ledger_record():
    """The stale-doc satellite: a desync finding links the divergent
    round's ledger coordinate so the operator can replay it through
    the decisions CLI."""
    from sparkucx_tpu.utils.metrics import C_AGREE_DIVERGENCE
    from sparkucx_tpu.utils.doctor import diagnose
    counters = {C_AGREE_DIVERGENCE: 1.0,
                labeled(C_AGREE_DIVERGENCE, topic="async.order"): 1.0,
                C_AGREE_ROUNDS: 5.0}
    docs = [_doc(0, [_rec(seq=3, topic="async.order", ok=False,
                          error="value")], counters=counters)]
    fs = [f for f in diagnose(docs) if f.rule == "desync"]
    assert len(fs) == 1
    # async.order maps to the agreed-order knob, not the wildcard
    assert fs[0].conf_key == \
        "spark.shuffle.tpu.tenant.asyncAgreedOrder"
    lr = fs[0].evidence["ledger_record"]
    assert lr == {"epoch": 0, "seq": 3, "topic": "async.order",
                  "error": "value", "process_id": 0}


def test_dedupe_process_docs_unions_decisions():
    """A decisions JSONL beside a metrics snapshot of the same process
    must survive the dedupe: the group's records union by monotonic
    n."""
    from sparkucx_tpu.utils.export import dedupe_process_docs
    snap = {"process_id": 0, "pid": 100, "ts": 2000.0,
            "counters": {"x": 1.0},
            "decisions": [_rec(seq=0, n=1), _rec(seq=1, n=2)]}
    side = {"process_id": 0, "pid": 100, "ts": 1000.0,
            "counters": {},
            "decisions": [_rec(seq=1, n=2), _rec(seq=2, n=3)]}
    out = dedupe_process_docs([snap, side])
    assert len(out) == 1
    assert [r["n"] for r in out[0]["decisions"]] == [1, 2, 3]
    assert out[0]["counters"]["x"] == 1.0      # snapshot stays primary


# -- node wiring: report summary, anatomy phase, live route, postmortem ------
@pytest.fixture(scope="module")
def dist_node(mesh8):
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.a2a.impl": "dense",
        "spark.shuffle.tpu.mesh.numSlices": "2",
        "spark.shuffle.tpu.metrics.httpPort": "0",
    }, use_env=False)
    node = TpuNode.start(conf)
    mgr = TpuShuffleManager(node, conf)
    node.is_distributed = True
    yield node, mgr
    node.is_distributed = False
    mgr.stop()
    node.close()


def _run_read(mgr, sid, rng, M=4, R=8, rows=96):
    h = mgr.register_shuffle(sid, M, R)
    for m in range(M):
        w = mgr.get_writer(h, m)
        w.write(rng.integers(0, 1 << 18, size=rows))
        w.commit(R)
    mgr.read(h).partition(0)
    rep = mgr.report(sid)
    mgr.unregister_shuffle(sid)
    return rep


def test_exchange_report_agreement_summary(dist_node, rng):
    """Settlement diffs the ledger's monotonic index across the read
    wall into the public summary: rounds closed, total agree_ms, the
    slowest topic."""
    node, mgr = dist_node
    rep = _run_read(mgr, 7101, rng)
    agg = rep.agreement
    assert agg and agg["rounds"] >= 1
    assert agg["agree_ms"] >= 0.0
    assert isinstance(agg["slowest_topic"], str) and agg["slowest_topic"]
    assert agg["rounds"] <= node.decisions.total
    d = rep.to_dict()
    assert d["agreement"]["rounds"] == agg["rounds"]


def test_anatomy_agree_phase_conserved(dist_node, rng):
    """The distributed read's anatomy ledger attributes the agreement
    rounds to the new `agree` phase and still conserves ≥95% of the
    wall."""
    from sparkucx_tpu.utils.trace import GLOBAL_TRACER
    node, mgr = dist_node
    GLOBAL_TRACER.enabled = True
    try:
        GLOBAL_TRACER.clear()
        # best-attributed of the post-cold walls (test_anatomy's
        # _best_warm_report discipline): the bar tests instrumentation
        # coverage; one OS descheduling blip must not flake it
        reps = [_run_read(mgr, 7110 + i, rng) for i in range(3)]
    finally:
        GLOBAL_TRACER.enabled = False
        GLOBAL_TRACER.clear()
    rep = max(reps[1:], key=lambda r: -r.dark_ms / r.anatomy_wall_ms
              if r.anatomy_wall_ms else -1e9)
    assert rep.anatomy_wall_ms > 0
    assert rep.phases.get("agree", 0.0) > 0.0
    attributed = 1.0 - rep.dark_ms / rep.anatomy_wall_ms
    assert attributed >= 0.95, (attributed, rep.phases)


def test_live_decisions_route(dist_node, rng):
    import urllib.request
    node, mgr = dist_node
    _run_read(mgr, 7103, rng)
    with urllib.request.urlopen(node.live.url + "/decisions",
                                timeout=10) as r:
        doc = json.loads(r.read().decode())
    assert doc["enabled"] and doc["total"] >= 1
    assert doc["decisions"][-1]["topic"]
    assert doc["position"]["topic"] == doc["decisions"][-1]["topic"]


def test_snapshot_embeds_decisions_and_postmortem_position(dist_node,
                                                           rng):
    from sparkucx_tpu.utils.collector import last_known_decision
    node, mgr = dist_node
    _run_read(mgr, 7104, rng)
    doc = node.telemetry_snapshot()
    assert doc["decisions"], "snapshot must embed the ledger tail"
    last = last_known_decision(doc)
    assert last["topic"] == doc["decisions"][-1]["topic"]
    assert last["since_s"] is not None


def test_decisions_disabled_null_object(mesh8):
    """decisions.enabled=false installs the NULL ledger: agree()
    settles with zero records, the route 404s, the report summary is
    empty — the disabled plane costs nothing and crashes nothing."""
    import urllib.error
    import urllib.request
    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.runtime.node import TpuNode
    # TpuNode.start is an idempotent singleton: retire any live node
    # (the module-scoped dist_node outlives its last test) so the
    # disabled conf actually takes effect. Its fixture teardown is
    # double-close safe.
    inst = TpuNode._instance
    if inst is not None and not inst._closed:
        inst.close()
    conf = TpuShuffleConf({
        "spark.shuffle.tpu.a2a.impl": "dense",
        "spark.shuffle.tpu.decisions.enabled": "false",
        "spark.shuffle.tpu.metrics.httpPort": "0",
    }, use_env=False)
    node = TpuNode.start(conf)
    try:
        assert node.decisions is NULL_DECISION_LEDGER
        reset_epoch(0)
        agree("x.off", [1])
        assert node.decisions.tail() == []
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(node.live.url + "/decisions",
                                   timeout=10)
        assert ei.value.code == 404
    finally:
        node.close()


# -- the offline CLI ---------------------------------------------------------
def _write_ledger(tmp_path, pid, recs):
    p = tmp_path / f"decisions_p{pid}.jsonl"
    with open(p, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return p


def test_cli_decisions_offline_flags_silent_split(tmp_path, capsys):
    from sparkucx_tpu.__main__ import main as cli_main
    common = [_rec(seq=0, topic="a2a.waveRows", reduce="unanimous",
                   proposals=(9, 9), n=1),
              _rec(seq=1, topic="async.batch", reduce="min",
                   proposals=(3, 5), audit="aggregate", n=2)]
    split = _rec(seq=2, proposals=(250, 256), audit="strict", n=3)
    _write_ledger(tmp_path, 0, common + [split])
    _write_ledger(tmp_path, 1,
                  [dict(r, process_id=1) for r in common + [split]])
    rc = cli_main(["decisions", "--input", str(tmp_path),
                   "--fail-on", "critical"])
    out = capsys.readouterr().out
    assert rc == 3
    assert "decision_split" in out
    assert "hier.dcn.capms" in out
    assert "a2a.capacityFactor" in out
    assert "SPLIT" in out


def test_cli_decisions_offline_clean_and_json(tmp_path, capsys):
    from sparkucx_tpu.__main__ import main as cli_main
    recs = [_rec(seq=0, n=1),
            _rec(seq=1, topic="async.batch", reduce="min",
                 proposals=(3, 5), audit="aggregate", n=2)]
    _write_ledger(tmp_path, 0, recs)
    _write_ledger(tmp_path, 1, [dict(r, process_id=1) for r in recs])
    rc = cli_main(["decisions", "--input", str(tmp_path),
                   "--fail-on", "critical"])
    assert rc == 0
    capsys.readouterr()
    rc = cli_main(["decisions", "--input", str(tmp_path),
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["rounds_audited"] >= 2 and doc["splits"] == []
    assert sorted(int(p) for p in doc["ledgers"]) == [0, 1]


def test_cli_decisions_no_ledgers_exit2(tmp_path, capsys):
    from sparkucx_tpu.__main__ import main as cli_main
    (tmp_path / "metrics_1.json").write_text(json.dumps(
        {"process_id": 0, "pid": 1, "counters": {}}))
    rc = cli_main(["decisions", "--input", str(tmp_path)])
    assert rc == 2
    assert "no decision-ledger records" in capsys.readouterr().err
