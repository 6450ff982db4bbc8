"""``wordcount.4chip`` through the harness on 4 virtual CPU devices, at a
small size: a clean run is correct and reports exactly the cell's
end-to-end metrics, each planted fault reads ``correct: false``, and an
unwaved combining read over the same 4 devices, through the service,
delivers the plain reference's rows in every partition.

The device count is fixed when JAX starts, so every case runs in one
process of its own, started once for the module."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)

SEED = 2**31 + 4343
ROWS = 12_000           # pairs a chip: 1,500-pair waves
CELL = "wordcount.4chip"
FAULTS = ("no_exchange", "altered", "half")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """{case: result}: the clean run, each fault, and ``unwaved``, the
    comparison numbers of a single-shot read of every partition."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        import pytest, faults, run, yardstick, sparkucx_tpu
        cfg = run.load_cell({ROOT!r}, {CELL!r})[2]

        def once(fault):
            mp = pytest.MonkeyPatch()
            if fault:
                faults.plant(mp, fault, cfg, 4, {SEED}, {ROWS})
            try:
                return run.run({ROOT!r}, {CELL!r}, {SEED}, 0.3, False,
                               allow_cpu=True, rows_per_chip={ROWS})
            finally:
                mp.undo()

        out = {{f or "clean": once(f) for f in ("",) + {FAULTS!r}}}
        inputs = yardstick.make_inputs(cfg, 4, {SEED}, {ROWS})
        svc = sparkucx_tpu.connect(cfg["conf"], use_env=False)
        try:
            devices = svc.node.num_devices
            rec = run.run_shuffle(svc, 1, inputs, cfg,
                                  keep=range(inputs.num_partitions))
        finally:
            svc.close()
        got = yardstick.compare(yardstick.reference(inputs, cfg),
                                rec.sizes, rec.parts, cfg)
        out["unwaved"] = dict(got, waves=rec.report["waves"],
                              devices=devices,
                              rows=sum(rec.sizes.values()))
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("cache")))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_clean_run_is_correct(runs):
    res = runs["clean"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["count"] == 4
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert set(res["metrics"]) == {"shuffle_GBps_per_chip", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(runs, fault):
    res = runs[fault]
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_unwaved_combine_on_four_devices_matches_reference(runs):
    got = runs["unwaved"]
    assert got["waves"] == 0 and got["devices"] == 4     # single-shot
    assert got["rows"] > 0
    assert {k: got[k] for k in ("rows_wrong", "partition_sizes_wrong",
                                "partitions_missing")} == {
        "rows_wrong": 0, "partition_sizes_wrong": 0,
        "partitions_missing": 0}
