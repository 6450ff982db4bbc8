"""Device-plane observability tests — the devmon sampler (gauges,
null-object off path, trace stamping), the live telemetry server (all
four endpoints over a real socket, /metrics↔/snapshot agreement,
healthz flips), the doctor watcher's one-capture-per-finding contract,
and the stepcache cost capture joined into ExchangeReports."""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.utils.metrics import (G_HBM_IN_USE, H_BW, Metrics,
                                        labeled, parse_labeled)

BASE_CONF = {
    "spark.shuffle.tpu.a2a.impl": "dense",
    "spark.shuffle.tpu.io.format": "raw",
}


@pytest.fixture()
def service_factory(mesh8):
    """connect() with overrides over BASE_CONF; tears down after (and
    between calls — TpuNode is a singleton)."""
    from sparkucx_tpu.service import connect

    created = []

    def make(overrides=None):
        while created:
            created.pop().stop()
        conf = dict(BASE_CONF)
        conf.update(overrides or {})
        svc = connect(conf, use_env=False)
        created.append(svc)
        return svc

    yield make
    while created:
        created.pop().stop()


def _run_exchange(svc, sid, rows=256, maps=2, partitions=4, seed=0):
    rng = np.random.default_rng(seed)
    h = svc.register_shuffle(sid, maps, partitions)
    for m in range(maps):
        svc.write(h, m, rng.integers(0, 1 << 30, size=rows,
                                     dtype=np.int64))
    res = svc.read(h)
    res.partition(0)
    svc.unregister_shuffle(sid)


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# -- gauge kind -------------------------------------------------------------
def test_gauge_set_semantics_and_clear():
    m = Metrics()
    m.set_gauge("g.x", 10)
    m.set_gauge("g.x", 3)            # goes DOWN — the counter can't
    assert m.get_gauge("g.x") == 3
    m.set_gauge("g.x", None)         # unsampleable source clears
    assert "g.x" not in m.gauges()


def test_labeled_roundtrip_and_pathological_values():
    ident = labeled("devmon.hbm.in_use", device=3)
    assert ident == 'devmon.hbm.in_use{device="3"}'
    base, labels = parse_labeled(ident)
    assert base == "devmon.hbm.in_use" and labels == {"device": "3"}
    # pathological label value: quote, backslash, newline round-trip
    evil = 'a"b\\c\nd'
    base, labels = parse_labeled(labeled("m", rule=evil))
    assert base == "m" and labels == {"rule": evil}


def test_gauges_in_snapshot_and_prometheus_export():
    from sparkucx_tpu.utils.export import (collect_snapshot,
                                           render_prometheus)
    m = Metrics()
    m.set_gauge("pool.peak_bytes", 4096)
    m.set_gauge(labeled(G_HBM_IN_USE, device=0), 1e9)
    m.set_gauge(labeled(G_HBM_IN_USE, device=1), 2e9)
    doc = collect_snapshot(m)
    assert doc["gauges"]["pool.peak_bytes"] == 4096
    text = render_prometheus(doc)
    assert "# TYPE sparkucx_tpu_pool_peak_bytes gauge" in text
    assert "sparkucx_tpu_pool_peak_bytes 4096" in text
    # labeled family: ONE TYPE line, one series per device label
    assert text.count(
        "# TYPE sparkucx_tpu_devmon_hbm_in_use gauge") == 1
    assert 'sparkucx_tpu_devmon_hbm_in_use{device="0"} 1000000000' \
        in text
    assert 'sparkucx_tpu_devmon_hbm_in_use{device="1"} 2000000000' \
        in text


def test_prometheus_hardening_golden():
    """A hostile label value and a junk-braces metric name must both
    render as legal exposition — escaped, never raw."""
    from sparkucx_tpu.utils.export import render_prometheus
    evil = 'x"y\\z\nw'
    doc = {"gauges": {labeled("devmon.capture", rule=evil): 1.0,
                      "junk{not=labels": 2.0}}
    text = render_prometheus(doc)
    assert ('sparkucx_tpu_devmon_capture{rule="x\\"y\\\\z\\nw"} 1'
            in text)
    # junk braces are sanitized into the name, not emitted as syntax
    assert "sparkucx_tpu_junk_not_labels 2" in text
    for ln in text.splitlines():
        assert "\n" not in ln  # trivially true, but parse every sample:
    for ln in text.splitlines():
        if not ln.startswith("#"):
            name, val = ln.rsplit(" ", 1)
            float(val)
            assert re.match(r"^sparkucx_tpu_[A-Za-z0-9_]+(\{.*\})?$",
                            name), name


# -- devmon sampler ---------------------------------------------------------
def test_devmon_null_object_when_off(service_factory):
    from sparkucx_tpu.runtime.devmon import NULL_DEVMON
    svc = service_factory()
    assert svc.node.devmon is NULL_DEVMON
    assert svc.node.devmon.enabled is False
    assert svc.node.devmon.samples() == []
    assert svc.node.live is None
    assert svc.node.watcher is None


def test_devmon_samples_and_pool_gauges(service_factory):
    svc = service_factory({"spark.shuffle.tpu.devmon.enabled": "true",
                           "spark.shuffle.tpu.devmon.intervalMs": "20"})
    assert svc.node.devmon.enabled
    _run_exchange(svc, sid=1)
    deadline = time.monotonic() + 5.0
    while not svc.node.devmon.samples() and time.monotonic() < deadline:
        time.sleep(0.02)
    samples = svc.node.devmon.samples()
    assert samples, "sampler thread produced nothing"
    s = samples[-1]
    # CPU backend: memory_stats() is None — device fields are PRESENT
    # but null (the record exists, the data doesn't)
    assert len(s["devices"]) == 8
    for d in s["devices"]:
        assert set(d) >= {"index", "in_use", "limit", "peak"}
        assert d["in_use"] is None and d["limit"] is None
    # pool watermarks ride as gauges in the node registry
    gauges = svc.node.metrics.gauges()
    assert "pool.peak_bytes" in gauges
    assert "pool.in_use_bytes" in gauges
    assert svc.node.metrics.get("devmon.samples") >= 1
    # and the stats() snapshot carries them (the scrape surface)
    doc = svc.stats("json")
    assert doc["gauges"]["pool.peak_bytes"] >= 0


def test_devmon_trace_id_stamping(service_factory):
    """Samples taken while an exchange is in flight carry its trace id
    (the flight recorder owns the in-flight stack)."""
    svc = service_factory({
        "spark.shuffle.tpu.devmon.enabled": "true",
        "spark.shuffle.tpu.devmon.intervalMs": "3600000",  # manual only
        "spark.shuffle.tpu.flightRecorder.enabled": "true"})
    svc.node.flight.begin_trace("s9.e0.x9")
    try:
        svc.node.devmon.sample_once()
    finally:
        svc.node.flight.end_trace("s9.e0.x9")
    svc.node.devmon.sample_once()       # idle: no stamp
    samples = svc.node.devmon.samples()
    assert samples[-2]["trace"] == "s9.e0.x9"
    assert samples[-1]["trace"] is None
    # the flight ring's devmon event carries the same stamp
    events = [e for e in list(svc.node.flight._events)
              if e["kind"] == "devmon"]
    assert any(e.get("trace") == "s9.e0.x9" for e in events)


# -- live telemetry server --------------------------------------------------
def test_live_endpoints_match_facade(service_factory):
    """Acceptance: /metrics parsed families agree with /snapshot for
    counters, gauges and histogram quantiles; /doctor equals
    service.doctor(); port 0 auto-assigns."""
    svc = service_factory({"spark.shuffle.tpu.metrics.httpPort": "0"})
    for sid in (1, 2, 3):
        _run_exchange(svc, sid=sid, seed=sid)
    live = svc.node.live
    assert live is not None and live.port > 0
    status, snap_body = _get(live.url + "/snapshot")
    assert status == 200
    snap = json.loads(snap_body)
    status, prom = _get(live.url + "/metrics")
    assert status == 200
    # parse the exposition into {series: value}
    series = {}
    for ln in prom.splitlines():
        if ln and not ln.startswith("#"):
            name, val = ln.rsplit(" ", 1)
            series[name] = float(val) if val not in ("+Inf", "-Inf") \
                else float("inf")
    from sparkucx_tpu.utils.export import prom_name, prom_series
    # counters (read.count advanced by this loop; the endpoint hit is
    # idle-time so the two captures agree)
    for cname in ("shuffle.read.count", "shuffle.rows"):
        assert series[prom_name(cname)] == \
            pytest.approx(snap["counters"][cname])
    # gauges (pool watermarks published at snapshot time)
    for gname, gval in snap["gauges"].items():
        assert series[prom_series(gname)] == pytest.approx(gval)
    # histogram quantiles: the _p50/_p99 companions match the snapshot
    from sparkucx_tpu.utils.metrics import H_FETCH_WAIT
    hsnap = snap["histograms"][H_FETCH_WAIT]
    assert series[prom_name(H_FETCH_WAIT) + "_count"] == hsnap["count"]
    assert series[prom_name(H_FETCH_WAIT) + "_p50"] == \
        pytest.approx(hsnap["p50"])
    assert series[prom_name(H_FETCH_WAIT) + "_p99"] == \
        pytest.approx(hsnap["p99"])
    # /doctor serves the same findings as the facade's doctor()
    status, doc_body = _get(live.url + "/doctor")
    assert status == 200
    assert json.loads(doc_body) == svc.doctor("json")
    # unknown path: a clean 404, not a hung socket
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(live.url + "/nope")
    assert ei.value.code == 404


def test_live_endpoints_respond_during_exchange(service_factory):
    """All four endpoints return while exchanges are running — the
    scrape must never wait for the data plane."""
    svc = service_factory({"spark.shuffle.tpu.metrics.httpPort": "0"})
    live = svc.node.live
    stop = threading.Event()
    errors = []

    def churn():
        sid = 100
        while not stop.is_set():
            try:
                _run_exchange(svc, sid=sid, rows=2048, seed=sid)
            except Exception as e:   # pragma: no cover - surfaced below
                errors.append(e)
                return
            sid += 1

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    try:
        time.sleep(0.05)            # let the churn actually start
        for ep in ("/metrics", "/snapshot", "/doctor", "/healthz"):
            status, body = _get(live.url + ep, timeout=30)
            assert status == 200 and body
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors


def test_healthz_flips_on_epoch_bump_and_device_unhealthy(
        service_factory):
    svc = service_factory({"spark.shuffle.tpu.metrics.httpPort": "0"})
    url = svc.node.live.url + "/healthz"
    status, body = _get(url)
    assert status == 200 and json.loads(body)["ok"] is True
    svc.node.epochs.bump("test membership change")
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(url)
    assert ei.value.code == 503
    assert "epoch" in json.loads(ei.value.read().decode())["reason"]
    # operator acknowledges (re-registered shuffles) -> healthy again
    svc.node.mark_healthy()
    assert _get(url)[0] == 200
    # device probe failure flips it too (the HealthMonitor callback
    # route assert_healthy takes)
    svc.node._on_device_unhealthy(["TFRT_CPU_7"])
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(url)
    assert ei.value.code == 503
    assert "DeviceUnhealthy" in \
        json.loads(ei.value.read().decode())["reason"]


def test_cli_live_url_stats_and_doctor(service_factory, capsys):
    from sparkucx_tpu.__main__ import main as cli_main
    svc = service_factory({"spark.shuffle.tpu.metrics.httpPort": "0"})
    _run_exchange(svc, sid=7)
    url = svc.node.live.url
    assert cli_main(["stats", "--live-url", url,
                     "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["counters"]["shuffle.read.count"] == 1
    assert cli_main(["doctor", "--live-url", url,
                     "--format", "json"]) == 0
    json.loads(capsys.readouterr().out)   # findings parse (maybe [])


# -- doctor watcher ---------------------------------------------------------
def test_watcher_one_capture_per_distinct_finding(service_factory,
                                                  tmp_path):
    from sparkucx_tpu.utils.doctor import Finding
    svc = service_factory({
        "spark.shuffle.tpu.flightRecorder.enabled": "true",
        "spark.shuffle.tpu.flightRecorder.dir": str(tmp_path / "flight"),
        "spark.shuffle.tpu.doctor.watchIntervalSecs": "3600",
        "spark.shuffle.tpu.doctor.captureMs": "0"})  # no profiler window
    watcher = svc.node.watcher
    assert watcher is not None
    crit = Finding(rule="hbm_pressure", grade="critical",
                   summary="synthetic", trace_ids=["s1.e0.x1"])
    warn = Finding(rule="bw_underutilization", grade="warn",
                   summary="synthetic-warn")
    svc.node.doctor_provider = lambda: [crit, warn]
    fired = watcher.check_once()
    assert len(fired) == 1                      # warn does not trigger
    assert fired[0]["rule"] == "hbm_pressure"
    assert fired[0]["flight_dump"] is not None
    # the postmortem is TAGGED with the finding
    dump = json.loads(open(fired[0]["flight_dump"]).read())
    assert dump["finding"]["rule"] == "hbm_pressure"
    assert dump["reason"].startswith("doctor finding")
    # same finding again: no second capture
    assert watcher.check_once() == []
    # a DISTINCT finding (new exchange) captures again
    crit2 = Finding(rule="hbm_pressure", grade="critical",
                    summary="synthetic", trace_ids=["s2.e0.x2"])
    svc.node.doctor_provider = lambda: [crit2]
    assert len(watcher.check_once()) == 1
    assert len(watcher.captures) == 2
    # ...but a persistent condition minting a fresh trace id every pass
    # is bounded by the per-rule capture budget (no postmortem flood)
    for i in range(3, 20):
        svc.node.doctor_provider = (
            lambda i=i: [Finding(rule="hbm_pressure", grade="critical",
                                 summary="synthetic",
                                 trace_ids=[f"s{i}.e0.x{i}"])])
        watcher.check_once()
    assert len(watcher.captures) == watcher.RULE_CAPTURE_CAP


# -- per-program cost capture ------------------------------------------------
def test_report_carries_device_cost_and_bw(service_factory):
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    GLOBAL_STEP_CACHE.clear()
    svc = service_factory()
    for sid in (1, 2, 3):
        _run_exchange(svc, sid=sid, rows=512, seed=sid)
    rep = svc.manager.report(3)
    assert rep is not None and rep.completed
    dc = rep.device_cost
    assert dc is not None
    # field surface is fixed; on the CPU backend the analyses exist
    for k in ("backend", "flops", "bytes_accessed", "argument_bytes",
              "output_bytes", "temp_bytes"):
        assert k in dc
    assert dc["backend"] == "cpu"
    assert dc["captured"] is True
    assert dc["flops"] and dc["bytes_accessed"] > 0
    assert dc["argument_bytes"] > 0
    from sparkucx_tpu.utils.metrics import (COMPILE_PROG_CAPTURED,
                                            GLOBAL_METRICS)
    assert GLOBAL_METRICS.get(COMPILE_PROG_CAPTURED) >= 1
    # achieved bw: field on every completed read, histogram only for
    # steady-state (non-compile-bearing) ones
    assert rep.bw_gbps > 0
    bw = svc.node.metrics.histogram(H_BW)
    assert bw.count >= 1
    assert bw.count < 3 or rep.stepcache_programs == 0
    # device_cost survives to_dict/json (the flight-dump path)
    json.dumps(rep.to_dict())


def test_cost_capture_disabled_keeps_null_record(service_factory):
    from sparkucx_tpu.shuffle import stepcache
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    GLOBAL_STEP_CACHE.clear()
    svc = service_factory({
        "spark.shuffle.tpu.compile.costCapture": "false"})
    try:
        assert stepcache.COST_CAPTURE is False
        _run_exchange(svc, sid=1, rows=128)
        dc = svc.manager.report(1).device_cost
        # the record EXISTS (field presence is the contract), the data
        # doesn't — exactly the null-field backend shape
        assert dc is not None and dc["captured"] is False
        assert dc["flops"] is None and dc["temp_bytes"] is None
    finally:
        stepcache.COST_CAPTURE = True
        GLOBAL_STEP_CACHE.clear()


def test_memory_probe_gated_on_persistent_cache(service_factory):
    """With the persistent compile cache disabled, the memory_analysis
    probe (a second lowered.compile()) must NOT run — it would re-pay
    the full XLA compile inside the first read. cost_analysis (free,
    from the lowered module) still captures."""
    from sparkucx_tpu.shuffle import stepcache
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    GLOBAL_STEP_CACHE.clear()
    svc = service_factory({
        "spark.shuffle.tpu.compile.cacheEnabled": "false"})
    try:
        assert stepcache.MEMORY_PROBE is False
        _run_exchange(svc, sid=1, rows=128)
        dc = svc.manager.report(1).device_cost
        assert dc["flops"] is not None          # lowered-module analysis
        assert dc["temp_bytes"] is None         # compile probe skipped
        assert dc["argument_bytes"] is None
        assert dc["captured"] is True
    finally:
        stepcache.MEMORY_PROBE = True
        GLOBAL_STEP_CACHE.clear()


def test_watcher_rearms_after_consecutive_healthy_passes(
        service_factory, tmp_path):
    """PR-14 satellite bugfix: a captured finding key that CLEARS for
    doctor.rearmHealthyPasses consecutive passes re-arms, so the same
    condition recurring later is captured again; a flapping condition
    (present every other pass) never re-arms."""
    from sparkucx_tpu.utils.doctor import Finding
    svc = service_factory({
        "spark.shuffle.tpu.flightRecorder.enabled": "true",
        "spark.shuffle.tpu.flightRecorder.dir": str(tmp_path / "fl"),
        "spark.shuffle.tpu.doctor.watchIntervalSecs": "3600",
        "spark.shuffle.tpu.doctor.rearmHealthyPasses": "2",
        "spark.shuffle.tpu.doctor.captureMs": "0"})
    watcher = svc.node.watcher
    crit = Finding(rule="hbm_pressure", grade="critical",
                   summary="synthetic", trace_ids=["s1.e0.x1"])
    svc.node.doctor_provider = lambda: [crit]
    assert len(watcher.check_once()) == 1      # first occurrence
    assert watcher.check_once() == []          # persists: no re-capture
    # clears for ONE pass only, then recurs: streak reset, still armed
    svc.node.doctor_provider = lambda: []
    watcher.check_once()
    svc.node.doctor_provider = lambda: [crit]
    assert watcher.check_once() == []          # 1 healthy < rearm 2
    # clears for TWO consecutive passes -> re-armed
    svc.node.doctor_provider = lambda: []
    watcher.check_once()
    watcher.check_once()
    svc.node.doctor_provider = lambda: [crit]
    fired = watcher.check_once()
    assert len(fired) == 1 and fired[0]["rule"] == "hbm_pressure"
    assert len(watcher.captures) == 2
    # a persistent flood (fresh key every pass, rule never quiet)
    # still hits the per-rule cap — the refund only follows a streak
    # where the WHOLE rule went quiet
    for i in range(10, 30):
        svc.node.doctor_provider = (
            lambda i=i: [Finding(rule="hbm_pressure", grade="critical",
                                 summary="synthetic",
                                 trace_ids=[f"s{i}.e0.x{i}"])])
        watcher.check_once()
    assert len(watcher.captures) == watcher.RULE_CAPTURE_CAP + 1
    # ...but once the rule clears for the streak, a recurrence past
    # the cap captures again (the budget refunds with the re-arm)
    svc.node.doctor_provider = lambda: []
    watcher.check_once()
    watcher.check_once()
    svc.node.doctor_provider = lambda: [Finding(
        rule="hbm_pressure", grade="critical", summary="synthetic",
        trace_ids=["s99.e0.x99"])]
    assert len(watcher.check_once()) == 1


def test_healthz_cause_enum_flips_per_cause(service_factory):
    """PR-14 satellite: the 503 body carries a stable machine ``cause``
    a probe can switch on — epoch_bump / device_unhealthy /
    slo_fast_burn — not just the human reason sentence."""
    import urllib.error
    svc = service_factory({
        "spark.shuffle.tpu.metrics.httpPort": "0",
        "spark.shuffle.tpu.history.windowSecs": "86400",
        "spark.shuffle.tpu.slo.read.p99Ms": "10",
        "spark.shuffle.tpu.slo.minEvents": "4"})
    url = svc.node.live.url + "/healthz"
    status, body = _get(url)
    assert status == 200 and json.loads(body)["cause"] is None

    def _cause():
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url)
        assert ei.value.code == 503
        return json.loads(ei.value.read().decode())["cause"]

    svc.node.epochs.bump("membership change")
    assert _cause() == "epoch_bump"
    svc.node.mark_healthy()
    svc.node._on_device_unhealthy(["TFRT_CPU_7"])
    assert _cause() == "device_unhealthy"
    svc.node.mark_healthy()
    assert _get(url)[0] == 200
    # SLO fast burn: every windowed read blows the 10 ms bound
    svc.node.history.roll()
    for _ in range(8):
        svc.node.metrics.observe("shuffle.read.wait_ms", 500.0)
    svc.node.metrics.inc("shuffle.read.count", 8)
    svc.node.history.roll()
    assert _cause() == "slo_fast_burn"


def test_prometheus_full_grammar_golden_strict_checker():
    """PR-14 satellite: ONE exposition document exercising the whole
    grammar — a labeled histogram family beside its unlabeled sibling,
    labeled counters, gauges, and a pathological escaped label —
    validated by the strict line-grammar checker, so a future exporter
    edit cannot silently break scrapers."""
    from sparkucx_tpu.utils.export import (render_prometheus,
                                           validate_exposition)
    from sparkucx_tpu.utils.metrics import Metrics
    m = Metrics()
    m.inc("shuffle.read.count", 7)
    m.inc(labeled("shuffle.read.count", tenant="whale"), 3)
    m.inc(labeled("shuffle.read.count", tenant='e"v\\i\nl'), 1)
    for v in (1.0, 5.0, 50.0):
        m.observe("shuffle.read.wait_ms", v)
        m.observe(labeled("shuffle.read.wait_ms", tenant="whale"),
                  v * 2)
    m.set_gauge("pool.peak_bytes", 4096)
    m.set_gauge(labeled(G_HBM_IN_USE, device=0), 12345)
    from sparkucx_tpu.utils.export import collect_snapshot
    text = render_prometheus(collect_snapshot(m))
    validate_exposition(text)          # the golden: full grammar, legal
    # the checker has TEETH: a decreasing bucket series must fail...
    broken = text.replace(
        'sparkucx_tpu_shuffle_read_wait_ms_bucket{le="+Inf"} 3',
        'sparkucx_tpu_shuffle_read_wait_ms_bucket{le="+Inf"} 0')
    assert broken != text
    with pytest.raises(ValueError):
        validate_exposition(broken)
    # ...and so must a sample with no TYPE declaration
    with pytest.raises(ValueError, match="no preceding # TYPE"):
        validate_exposition("orphan_metric 1\n")
    # ...and a family split away from its TYPE block (adjacency rule)
    lines = text.splitlines()
    lines.append(lines[next(i for i, ln in enumerate(lines)
                            if not ln.startswith("#"))])
    with pytest.raises(ValueError, match="adjacent"):
        validate_exposition("\n".join(lines))
