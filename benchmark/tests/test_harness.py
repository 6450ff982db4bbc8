"""The harness end to end on the CPU at small sizes: a clean run is
correct, each planted fault and the control are not, a run without a TPU
prints no result, and a new cell with a new per-layer metric runs from
new files alone."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, TESTS)

import faults  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 4242
ROWS = {"terasort.1chip": 20_000, "wordcount.1chip": 100_000}


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(cell, rows=None, root=ROOT, trace=False):
    return run.run(root, cell, SEED, 0.5, trace, allow_cpu=True,
                   rows_per_chip=rows or ROWS[cell])


def _config(cell):
    return run.load_cell(ROOT, cell)[2]


@pytest.mark.parametrize("cell", sorted(ROWS))
def test_clean_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert set(res["metrics"]) == {"shuffle_GBps_per_chip",
                                   "fetch_wait_p50_ms", "fetch_wait_p95_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("cell", sorted(ROWS))
@pytest.mark.parametrize("fault", ["altered", "half", "unchanged"])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    faults.plant(monkeypatch, fault, _config(cell), 1, SEED, ROWS[cell])
    res = _run(cell)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("cell,rows", [("terasort.1chip", 300_000),
                                       ("wordcount.1chip", 1_000_000)])
def test_control_in_the_programs_place_is_not_correct(cell, rows,
                                                      monkeypatch):
    faults.plant(monkeypatch, "control", _config(cell), 1, SEED, rows)
    res = _run(cell, rows)
    assert res["correct"] is False
    assert res["checks"]["rows_wrong"]["value"] > 0


def _run_4chip(fault):
    """terasort.4chip on 4 virtual CPU devices, in a process of its own
    (the device count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        import pytest, faults, run
        mp = pytest.MonkeyPatch()
        cfg = run.load_cell({ROOT!r}, "terasort.4chip")[2]
        if {fault!r}:
            faults.plant(mp, {fault!r}, cfg, 4, {SEED}, 4000)
        res = run.run({ROOT!r}, "terasort.4chip", {SEED}, 0.5, False,
                      allow_cpu=True, rows_per_chip=4000)
        print(json.dumps(res))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["", "no_exchange", "altered"])
def test_four_chips(fault):
    res = _run_4chip(fault)
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "")


@pytest.mark.parametrize("cell,devices", [("terasort.1chip", 1),
                                          ("terasort.4chip", 4)])
def test_no_tpu_no_result(cell, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_new_cell_and_metric_from_new_files_alone(tmp_path, monkeypatch):
    """A later PR adds a cell and a per-layer metric as new files and one
    entry each; the harness runs them with no other edit."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "benchmark" / "metrics" / "throwaway.shuffles.py").write_text(
        "def read(ctx):\n    return float(len(ctx.shuffles))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "throwaway.1chip", "config": "hibench-terasort",
        "traffic": "serial-loop", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway.shuffles", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "map side",
        "moves": "shuffle_GBps_per_chip", "workloads": ["throwaway.1chip"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the CPU has no published peaks: give it the chip's, in this copy only
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "benchmark" / "peaks.json").write_text(json.dumps(peaks))
    # a CPU trace holds no TPU plane: stand in the hand-built one
    import devtrace
    from jax.profiler import ProfileData
    from test_devtrace import SYNTHETIC
    summary = devtrace.summarize(*devtrace.read_planes(
        ProfileData.from_text_proto(SYNTHETIC)))
    monkeypatch.setattr(devtrace, "load_summary", lambda d: summary)
    res = run.run(str(root), "throwaway.1chip", SEED, 0.5, True,
                  allow_cpu=True, rows_per_chip=20_000)
    assert res["correct"] is True
    assert res["metrics"]["throwaway.shuffles"]["value"] == res["attempted"]
    assert res["metrics"]["map.write_ms"]["unit"] == "ms"
    assert "a2a_roofline" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
