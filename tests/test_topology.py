"""Topology plane tests — shuffle/topology.py.

The two-tier ICI/DCN exchange as a production subsystem: descriptor
resolution (a2a.topology, slice detection), the structural step-cache
key, per-tier accounting (payload/wire pairs, exact cross-fabric rows),
the tiered two-step read path through the manager (tiers on the report,
per-tier walls/counters, per-tier watchdog deadlines naming the tier,
waved tier timelines, device sink, admission), and the GPU capability-
gate smoke (ROADMAP #5 satellite)."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.shuffle.alltoall import (ALLOWED_TOPOLOGIES,
                                           backend_supports_ragged,
                                           resolved_wire_impl,
                                           validate_topology)
from sparkucx_tpu.shuffle.plan import ShufflePlan
from sparkucx_tpu.shuffle.reader import KEY_WORDS, pack_rows
from sparkucx_tpu.shuffle.topology import (TopologyDescriptor,
                                           mesh_cache_key,
                                           resolve_topology,
                                           tier_cross_rows, tier_layouts,
                                           tier_timeouts)
from sparkucx_tpu.shuffle.writer import _hash32_np
from sparkucx_tpu.utils.metrics import C_TIER_BYTES, labeled


def _conf(extra=None):
    m = {"spark.shuffle.tpu.a2a.impl": "dense",
         "spark.shuffle.tpu.mesh.numSlices": "2"}
    m.update(extra or {})
    return TpuShuffleConf(m, use_env=False)


def _mesh2x4():
    devs = jax.devices()
    assert len(devs) == 8
    return Mesh(np.array(devs).reshape(2, 4), ("dcn", "shuffle"))


def partition_of(keys, R):
    return (_hash32_np(np.asarray(keys)) % np.uint32(R)).astype(np.int64)


# -- descriptor / conf seam ------------------------------------------------
def test_topology_conf_seam():
    assert validate_topology("hier") == "hier"
    with pytest.raises(ValueError, match="a2a.topology"):
        validate_topology("nope")
    with pytest.raises(ValueError):
        TpuShuffleConf({"spark.shuffle.tpu.a2a.topology": "bogus"},
                       use_env=False)
    assert "auto" in ALLOWED_TOPOLOGIES


def test_resolve_topology_auto_and_pins():
    mesh = _mesh2x4()
    conf = _conf()
    topo = resolve_topology(mesh, conf)
    assert topo.kind == "hier" and topo.hierarchical
    assert topo.tiers == ("ici", "dcn")
    assert (topo.num_slices, topo.per_slice) == (2, 4)
    assert topo.tier_axis("ici") == "shuffle"
    assert topo.tier_axis("dcn") == "dcn"
    # explicit flat pin wins over the 2-D mesh
    flat = resolve_topology(
        mesh, _conf({"spark.shuffle.tpu.a2a.topology": "flat"}))
    assert flat.kind == "flat" and flat.tiers == ("ici",)
    # legacy boolean still forces flat under auto
    legacy = resolve_topology(
        mesh, _conf({"spark.shuffle.tpu.a2a.hierarchical": "false"}))
    assert legacy.kind == "flat"
    # 1-D mesh: auto=flat, explicit hier is a conf error naming the key
    flat_mesh = Mesh(np.array(jax.devices()), ("shuffle",))
    assert resolve_topology(flat_mesh, _conf()).kind == "flat"
    with pytest.raises(ValueError, match="a2a.topology=hier"):
        resolve_topology(
            flat_mesh, _conf({"spark.shuffle.tpu.a2a.topology": "hier"}))


def test_tier_timeouts_default_from_collective():
    t = tier_timeouts(_conf(
        {"spark.shuffle.tpu.failure.collectiveTimeoutMs": "700"}))
    assert t == {"ici": 700.0, "dcn": 700.0}
    t = tier_timeouts(_conf(
        {"spark.shuffle.tpu.failure.collectiveTimeoutMs": "700",
         "spark.shuffle.tpu.failure.dcn.timeoutMs": "2500"}))
    assert t == {"ici": 700.0, "dcn": 2500.0}


# -- structural step-cache key (satellite: remeshed-identical reuse) -------
def test_mesh_cache_key_reuses_programs_across_mesh_objects():
    """A remeshed-but-identical mesh is a FRESH Mesh object over the
    same devices; both the tiered builders and the fused hier builder
    must serve the already-compiled program for it (PR-7 replay used to
    recompile both tiers)."""
    from sparkucx_tpu.shuffle.hierarchical import _build_hier_step
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    from sparkucx_tpu.shuffle.topology import (_build_stage1_step,
                                               _build_stage2_step)
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh_a = Mesh(devs, ("dcn", "shuffle"))
    # jax interns Mesh objects (a remesh over the same devices may hand
    # back the same instance) — the structural key must not RELY on
    # that implementation detail, so it is derived from shape + axis
    # names + device ids alone and must agree across constructions
    mesh_b = Mesh(np.array(jax.devices()).reshape(2, 4),
                  ("dcn", "shuffle"))
    assert mesh_cache_key(mesh_a) == mesh_cache_key(mesh_b)
    topo = resolve_topology(mesh_a, _conf())
    plan = ShufflePlan(8, 8, cap_in=32, cap_out=64, impl="dense")
    before = GLOBAL_STEP_CACHE.stats()["programs"]
    s1a = _build_stage1_step(mesh_a, topo, plan, KEY_WORDS, 64)
    s2a = _build_stage2_step(mesh_a, topo, plan, KEY_WORDS, 64, 64)
    fa = _build_hier_step(mesh_a, "dcn", "shuffle", plan, KEY_WORDS)
    mid = GLOBAL_STEP_CACHE.stats()["programs"]
    assert mid - before == 3
    s1b = _build_stage1_step(mesh_b, topo, plan, KEY_WORDS, 64)
    s2b = _build_stage2_step(mesh_b, topo, plan, KEY_WORDS, 64, 64)
    fb = _build_hier_step(mesh_b, "dcn", "shuffle", plan, KEY_WORDS)
    assert GLOBAL_STEP_CACHE.stats()["programs"] == mid
    assert s1a is s1b and s2a is s2b and fa is fb
    # the blocking convenience entry point rides the SAME cached tier
    # programs (read_shuffle_tiered = submit + result, the
    # read_shuffle_hierarchical twin) and lands oracle partitions
    from sparkucx_tpu.shuffle.topology import read_shuffle_tiered
    rng2 = np.random.default_rng(11)
    rows = 32
    ks = [rng2.integers(0, 1 << 16, size=rows) for _ in range(8)]
    shard_rows = np.zeros((8, rows, KEY_WORDS), np.int32)
    for p, k in enumerate(ks):
        shard_rows[p] = pack_rows(k, None, KEY_WORDS)
    res = read_shuffle_tiered(mesh_b, topo, plan, shard_rows,
                              np.full(8, rows, np.int64), None, None)
    assert GLOBAL_STEP_CACHE.stats()["programs"] == mid   # all cached
    ak = np.concatenate(ks)
    parts = partition_of(ak, 8)
    for r in range(8):
        k, _ = res.partition(r)
        assert sorted(k.tolist()) == sorted(ak[parts == r].tolist())


# -- per-tier accounting ---------------------------------------------------
def test_tier_cross_rows_exact():
    topo = TopologyDescriptor("hier", "shuffle", "dcn", 2, 4)
    m = np.zeros((8, 8), dtype=np.int64)
    m[0, 0] = 5     # self: crosses nothing
    m[0, 1] = 7     # same slice, different column: ICI only
    m[0, 4] = 11    # other slice, same column: DCN only
    m[1, 6] = 13    # other slice, other column: both fabrics
    cross = tier_cross_rows(m, topo)
    assert cross == {"ici": 7 + 13, "dcn": 11 + 13}


def test_tier_layouts_formulas():
    topo = TopologyDescriptor("hier", "shuffle", "dcn", 2, 4)
    plan = ShufflePlan(8, 16, cap_in=64, cap_out=128, impl="dense")
    rows = np.full(8, 64)
    ici, dcn = tier_layouts(plan, topo, rows, KEY_WORDS)
    # dense: S*D^2*cap vs D*S^2*cap padded segments
    assert ici["wire_rows"] == 2 * 16 * 128
    assert dcn["wire_rows"] == 4 * 4 * 128
    assert ici["payload_rows"] == dcn["payload_rows"] == 512
    assert not ici["cross_exact"]
    # exact cross rows with a device matrix: payload becomes the rows
    # that PHYSICALLY cross each fabric
    m = np.zeros((8, 8), dtype=np.int64)
    m[0, 4] = 100   # DCN-only move
    m[0, 1] = 50    # ICI-only move
    ici, dcn = tier_layouts(plan, topo, [150], KEY_WORDS, dev_matrix=m)
    assert ici["cross_exact"] and dcn["cross_exact"]
    assert ici["payload_rows"] == 50 and dcn["payload_rows"] == 100
    # gather: stage 1 replicates cap_in send buffers, stage 2 the relay
    gplan = dataclasses.replace(plan, impl="gather")
    gici, gdcn = tier_layouts(gplan, topo, rows, KEY_WORDS,
                              relay_cap=256)
    assert gici["wire_rows"] == 2 * 16 * 64
    assert gdcn["wire_rows"] == 4 * 4 * 256
    # int8 narrows the per-row wire cost on BOTH hops
    iplan = dataclasses.replace(plan, wire="int8", wire_words=8)
    w = KEY_WORDS + 8
    i8 = tier_layouts(iplan, topo, rows, w)
    raw = tier_layouts(plan, topo, rows, w)
    for a, b in zip(i8, raw):
        assert a["wire_bytes"] < b["wire_bytes"]


# -- GPU capability-gate smoke (ROADMAP #5 satellite) ----------------------
def test_gpu_capability_gates_without_a_gpu():
    """Pure gate logic: the claims the capability gates make for GPU
    backend names must be derivable with no GPU present — the ragged
    gate keys on the backend name, and the topology resolver is pure
    mesh math (backend-free)."""
    assert backend_supports_ragged("gpu") is True
    assert backend_supports_ragged("cpu") is False
    assert backend_supports_ragged("tpu") is True
    want = "native"
    assert resolved_wire_impl("auto", 8, backend="gpu") == want
    # per-tier accounting under a GPU backend name resolves the same
    # transport the dispatch would
    topo = TopologyDescriptor("hier", "shuffle", "dcn", 2, 4)
    plan = ShufflePlan(8, 16, cap_in=64, cap_out=128, impl="auto")
    tiers = tier_layouts(plan, topo, np.full(8, 64), KEY_WORDS,
                         backend="gpu")
    assert all(t["impl"] == want for t in tiers)
    # the resolver itself never touches a backend
    topo2 = resolve_topology(_mesh2x4(), _conf())
    assert topo2.kind == "hier"


# -- the tiered read path through the manager ------------------------------
# Tier-1 budget discipline (the PR-12 precedent): the suite runs within
# ~40 s of the 870 s fence on this 2-core box, so only the tests whose
# contract has NO other home stay in-tier (per-tier accounting + cross
# oracle + counters, the admission pin, the structural cache key); the
# device-sink / per-tier-deadline / replay / waved-timeline e2e legs are
# slow-marked and run under -m slow.
@pytest.fixture(scope="module")
def hier_mgr():
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    conf = _conf()
    node = TpuNode.start(conf)
    mgr = TpuShuffleManager(node, conf)
    yield node, mgr
    mgr.stop()
    node.close()


def _stage(mgr, sid, rng, M=4, R=8, rows=120, values=False):
    h = mgr.register_shuffle(sid, M, R)
    ks, vs = [], []
    for m in range(M):
        w = mgr.get_writer(h, m)
        k = rng.integers(0, 1 << 18, size=rows)
        if values:
            v = rng.random((rows, 1), dtype=np.float32)
            w.write(k, v)
            vs.append(v)
        else:
            w.write(k)
        w.commit(R)
        ks.append(k)
    return h, np.concatenate(ks), (np.concatenate(vs) if values else None)


def test_manager_hier_read_tiers_and_counters(hier_mgr, rng):
    """A hierarchical read routes through the tiered two-step path:
    oracle-correct partitions, BOTH tier entries on the report with
    exact cross rows (the metadata table's device matrix), measured
    per-tier walls, headline wire = the two-hop sum, and the
    tenant-labeled per-tier byte counters."""
    node, mgr = hier_mgr
    assert mgr.hierarchical and mgr.topology.kind == "hier"
    h, ak, _ = _stage(mgr, 701, rng)
    res = mgr.read(h)
    R = 8
    parts = partition_of(ak, R)
    for r in range(R):
        k, _ = res.partition(r)
        assert sorted(k.tolist()) == sorted(ak[parts == r].tolist())
    rep = mgr.report(701)
    assert rep.hierarchical and rep.completed
    assert [t["tier"] for t in rep.tiers] == ["ici", "dcn"]
    ici, dcn = rep.tiers
    assert ici["cross_exact"] and dcn["cross_exact"]
    # the crosses-DCN-exactly-once proof: the DCN payload is EXACTLY
    # the rows whose destination slice differs from their source slice
    from sparkucx_tpu.shuffle.reader import _blocked_map
    M, rows = 4, 120
    src_dev = np.concatenate([np.full(rows, m % 8) for m in range(M)])
    dst_dev = np.asarray(_blocked_map(R, 8))[parts]
    assert dcn["payload_rows"] == int(
        ((src_dev // 4) != (dst_dev // 4)).sum())
    assert ici["payload_rows"] == int(
        ((src_dev % 4) != (dst_dev % 4)).sum())
    assert ici["ms"] > 0 and dcn["ms"] > 0
    assert rep.wire_bytes == ici["wire_bytes"] + dcn["wire_bytes"]
    for tier in ("ici", "dcn"):
        assert node.metrics.get(labeled(
            C_TIER_BYTES, tier=tier, tenant="default")) > 0
    mgr.unregister_shuffle(701)


def test_manager_hier_admission_fair_share_path(hier_mgr, rng):
    """Satellite: hierarchical reads ride the SAME admission/fair-share
    plane as flat ones — under a 1-byte maxBytesInFlight the second
    submit defers into the queue and dispatches when the first
    releases; both land oracle-correct and the deferral is accounted."""
    node, mgr = hier_mgr
    old = mgr.conf.get("spark.shuffle.tpu.a2a.maxBytesInFlight")
    mgr.conf.set("spark.shuffle.tpu.a2a.maxBytesInFlight", "1")
    try:
        h1, ak1, _ = _stage(mgr, 702, rng)
        h2, ak2, _ = _stage(mgr, 703, rng)
        p1 = mgr.submit(h1)
        p2 = mgr.submit(h2)
        assert not p2.done()        # deferred behind the cap
        r1 = p1.result()
        r2 = p2.result()
        R = 8
        for ak, res in ((ak1, r1), (ak2, r2)):
            parts = partition_of(ak, R)
            for r in range(R):
                k, _ = res.partition(r)
                assert sorted(k.tolist()) == \
                    sorted(ak[parts == r].tolist())
        rep2 = mgr.report(703)
        assert rep2.completed and rep2.tiers
        assert rep2.admit_wait_ms >= 0.0
    finally:
        mgr.conf.set("spark.shuffle.tpu.a2a.maxBytesInFlight",
                     old if old is not None else "0")
        mgr.unregister_shuffle(702)
        mgr.unregister_shuffle(703)


@pytest.mark.slow
def test_manager_hier_device_sink_single_shot(hier_mgr, rng):
    """Single-shot hierarchical reads keep the device sink (the stage-2
    output is already partition-sorted on device) — combine lands fully
    merged, the report says sink=device, and the escape-hatch host view
    is oracle-exact."""
    node, mgr = hier_mgr
    R, M, rows = 8, 4, 100
    h = mgr.register_shuffle(704, M, R)
    want = {}
    for m in range(M):
        w = mgr.get_writer(h, m)
        k = (np.arange(m * rows, (m + 1) * rows) % 64).astype(np.int64)
        v = np.ones((rows, 1), np.float32)
        w.write(k, v)
        w.commit(R)
        for kk in k:
            want[int(kk)] = want.get(int(kk), 0.0) + 1.0
    res = mgr.read(h, combine="sum", sink="device")
    rep = mgr.report(704)
    assert rep.sink == "device" and rep.hierarchical and rep.tiers
    hv = res.host_view()
    got = {}
    for r in range(R):
        k, v = hv.partition(r)
        for a, b in zip(k, v[:, 0]):
            got[int(a)] = float(b)
    assert got == want
    mgr.unregister_shuffle(704)


@pytest.mark.slow
def test_hier_dcn_deadline_names_tier(rng):
    """failure.dcn.timeoutMs fences the DCN join alone: a straggler
    past it raises PeerLostError naming the dcn tier (the postmortem
    attribution contract), counted into failure.peer_timeout.count."""
    from sparkucx_tpu.runtime.failures import PeerLostError
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    from sparkucx_tpu.utils.metrics import C_PEER_TIMEOUT
    conf = _conf({"spark.shuffle.tpu.failure.dcn.timeoutMs": "150",
                  "spark.shuffle.tpu.network.timeoutMs": "2000"})
    node = TpuNode.start(conf)
    mgr = TpuShuffleManager(node, conf)
    try:
        h, _, _ = _stage(mgr, 705, rng, rows=40)
        before = node.metrics.get(C_PEER_TIMEOUT)
        node.faults.arm("tier.dcn", delay_ms=1200)
        with pytest.raises(PeerLostError, match="dcn"):
            mgr.read(h)
        node.faults.disarm("tier.dcn")
        assert node.metrics.get(C_PEER_TIMEOUT) == before + 1
    finally:
        mgr.stop()
        node.close()


@pytest.mark.slow
def test_hier_replay_absorbs_tier_fault(rng):
    """failure.policy=replay absorbs a DCN-phase fault: the read
    re-plans on the (still 2-D) mesh, stays hierarchical, reports
    replays>=1 and oracle bytes."""
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    conf = _conf({"spark.shuffle.tpu.failure.policy": "replay"})
    node = TpuNode.start(conf)
    mgr = TpuShuffleManager(node, conf)
    try:
        h, ak, _ = _stage(mgr, 706, rng, rows=80)
        node.faults.arm("tier.dcn", fail_count=1)
        res = mgr.read(h)
        rep = mgr.report(706)
        assert rep.replays >= 1 and rep.hierarchical and rep.tiers
        R = 8
        parts = partition_of(ak, R)
        for r in range(R):
            k, _ = res.partition(r)
            assert sorted(k.tolist()) == sorted(ak[parts == r].tolist())
    finally:
        node.faults.disarm("tier.dcn")
        mgr.stop()
        node.close()


@pytest.mark.slow
def test_hier_waved_tier_timelines(rng):
    """Hierarchical waves ride the tiered path: per-wave tier timeline
    entries (ici_ms/dcn_ms), summed tier walls on the report's tier
    entries, oracle-correct result; a device-sink ask on a WAVED hier
    read demotes to host COUNTED (reason hierarchical_waved)."""
    from sparkucx_tpu.runtime.node import TpuNode
    from sparkucx_tpu.shuffle.manager import TpuShuffleManager
    from sparkucx_tpu.utils.metrics import C_SINK_FALLBACK
    conf = _conf({"spark.shuffle.tpu.a2a.waveRows": "64"})
    node = TpuNode.start(conf)
    mgr = TpuShuffleManager(node, conf)
    try:
        h, ak, _ = _stage(mgr, 707, rng, rows=200)
        before = node.metrics.get(C_SINK_FALLBACK)
        res = mgr.read(h, sink="device")      # waved hier: demoted
        rep = mgr.report(707)
        assert rep.waves > 1 and rep.hierarchical
        assert rep.sink == "host"
        assert node.metrics.get(C_SINK_FALLBACK) == before + 1
        assert node.metrics.get(labeled(
            C_SINK_FALLBACK, mode="plain",
            reason="hierarchical_waved")) >= 1
        assert all("ici_ms" in e and "dcn_ms" in e
                   for e in rep.wave_timeline)
        assert rep.tiers and rep.tiers[0]["ms"] > 0
        assert rep.tiers[1]["ms"] > 0
        R = 8
        parts = partition_of(ak, R)
        for r in range(R):
            k, _ = res.partition(r)
            assert sorted(k.tolist()) == sorted(ak[parts == r].tolist())
    finally:
        mgr.stop()
        node.close()
