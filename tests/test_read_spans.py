"""Program spans at the read and write paths' layer boundaries.

Every device-to-host copy of a read's output is one ``shuffle.d2h``
span: the ``bytes`` of its payload (``what="rows"``) spans add up to
the report's ``d2h_bytes``, so the count and the span sit at one seam.
A waved ordered read merges its waves on the host under
``shuffle.merge`` (``impl="host"``), and a map's write into staging is
one ``shuffle.write`` before its ``shuffle.publish``."""

import jax
import numpy as np
import pytest

import sparkucx_tpu

TRACE_ON = {"spark.shuffle.tpu.trace.enabled": "true"}
M, R, N, VW = 4, 16, 300, 3
_SID = [7000]


def _manager(manager_factory, chips, conf=None):
    mgr = manager_factory(dict(TRACE_ON, **(conf or {})))
    mgr.node.remesh(devices=jax.devices()[:chips], reason="test mesh")
    assert mgr.node.num_devices == chips
    mgr.node.tracer.clear()
    return mgr


def _stage(mgr, partitioner="hash", bounds=None, seed=0, vw=VW):
    rng = np.random.default_rng(seed)
    _SID[0] += 1
    h = mgr.register_shuffle(_SID[0], M, R, partitioner=partitioner,
                             bounds=bounds)
    for m in range(M):
        w = mgr.get_writer(h, m)
        w.write(rng.integers(0, 1 << 40, size=N, dtype=np.int64),
                rng.integers(0, 1 << 20, size=(N, vw)).astype(np.int32))
        w.commit(R)
    return h


def _range_bounds():
    return tuple(int(x) for x in np.linspace(0, 1 << 40, R + 1)[1:-1])


READS = {
    "plain": ({}, {}),
    "ordered": ({"partitioner": "range", "bounds": _range_bounds()},
                {"ordered": True}),
    "combine": ({}, {"combine": "sum"}),
    "waved": ({"partitioner": "range", "bounds": _range_bounds()},
              {"ordered": True}),
}


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("mode", sorted(READS))
def test_d2h_spans_add_up_to_report(manager_factory, chips, mode):
    conf = {"spark.shuffle.tpu.a2a.waveRows": "128"} \
        if mode == "waved" else {}
    mgr = _manager(manager_factory, chips, conf)
    stage_kw, read_kw = READS[mode]
    h = _stage(mgr, **stage_kw)
    try:
        res = mgr.read(h, **read_kw)
        for _r, _kv in res.partitions_ready():
            pass
        rep = mgr.report(h.shuffle_id)
        if mode == "waved":
            assert rep.waves > 1
        d2h = mgr.node.tracer.spans("shuffle.d2h")
        rows = [s for s in d2h if s.attrs["what"] == "rows"]
        assert rows and rep.d2h_bytes > 0
        assert sum(s.attrs["bytes"] for s in rows) == rep.d2h_bytes
        assert {s.attrs["what"] for s in d2h} == {"rows", "seg"}
    finally:
        mgr.unregister_shuffle(h.shuffle_id)
        mgr.node.tracer.clear()


@pytest.mark.parametrize("mode", ["plain", "ordered", "combine"])
def test_exchange_program_named_by_mode(manager_factory, mode):
    """The exchange step compiles under its role and read mode, which the
    profiler's XLA Modules line shows as ``jit_<name>``."""
    mgr = _manager(manager_factory, 4)
    stage_kw, read_kw = READS[mode]
    h = _stage(mgr, **stage_kw)
    try:
        pending = mgr.submit(h, **read_kw)
        step = pending._step
        assert step.__name__ == f"shuffle_exchange_{mode}"
        assert step._attrs["program"] == step.__name__
        pending.result()
    finally:
        mgr.unregister_shuffle(h.shuffle_id)


@pytest.mark.parametrize("vw,form", [(1, "carried"), (VW, "perm")])
def test_combine_program_records_its_form(manager_factory, vw, form):
    """A combining exchange's ``compile.step`` span carries which form its
    combine takes: a 3-word (key, count) row sorts with its columns
    carried, a wider row by permutation and gather."""
    mgr = _manager(manager_factory, 1)
    h, h_plain = _stage(mgr, vw=vw), _stage(mgr, vw=vw)
    try:
        pending = mgr.submit(h, combine="sum")
        assert pending._step._attrs["combine_form"] == form
        pending.result()
        plain = mgr.submit(h_plain)
        assert "combine_form" not in plain._step._attrs
        plain.result()
    finally:
        mgr.unregister_shuffle(h.shuffle_id)
        mgr.unregister_shuffle(h_plain.shuffle_id)


@pytest.mark.parametrize("chips", [1, 4])
def test_waved_ordered_read_merges_on_host(manager_factory, chips):
    mgr = _manager(manager_factory, chips,
                   {"spark.shuffle.tpu.a2a.waveRows": "128"})
    h = _stage(mgr, partitioner="range", bounds=_range_bounds())
    try:
        res = mgr.read(h, ordered=True)
        keys = [kv[0] for _r, kv in res.partitions()]
        merged = np.concatenate(keys)
        assert (np.diff(merged) >= 0).all()
        merges = mgr.node.tracer.spans("shuffle.merge")
        assert merges
        assert {s.attrs["impl"] for s in merges} == {"host"}
        assert all(s.attrs["waves"] > 1 for s in merges)
        # a merge runs inside the fetch of its partition
        fetches = mgr.node.tracer.spans("shuffle.fetch")
        assert all(any(f.start_us <= s.start_us and s.start_us + s.dur_us
                       <= f.start_us + f.dur_us for f in fetches)
                   for s in merges)
    finally:
        mgr.unregister_shuffle(h.shuffle_id)
        mgr.node.tracer.clear()


def test_service_write_opens_write_and_publish_per_map():
    from sparkucx_tpu.utils.trace import GLOBAL_TRACER
    conf = dict(TRACE_ON, **{"spark.shuffle.tpu.io.format": "raw",
                             "spark.shuffle.tpu.a2a.impl": "dense"})
    with sparkucx_tpu.connect(conf, use_env=False) as svc:
        GLOBAL_TRACER.clear()
        try:
            h = svc.register_shuffle(7900, M, R)
            rng = np.random.default_rng(3)
            for m in range(M):
                svc.write(h, m, rng.integers(0, 1 << 30, size=N),
                          rng.integers(0, 9, size=(N, VW)).astype(np.int32))
            writes = GLOBAL_TRACER.spans("shuffle.write")
            pubs = GLOBAL_TRACER.spans("shuffle.publish")
            assert sorted(s.attrs["map_id"] for s in writes) == list(range(M))
            assert sorted(s.attrs["map_id"] for s in pubs) == list(range(M))
            # each map stages before it publishes
            for m in range(M):
                w = next(s for s in writes if s.attrs["map_id"] == m)
                p = next(s for s in pubs if s.attrs["map_id"] == m)
                assert w.start_us + w.dur_us <= p.start_us
            svc.unregister_shuffle(7900)
        finally:
            GLOBAL_TRACER.enabled = False
            GLOBAL_TRACER.clear()
