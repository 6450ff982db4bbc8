"""The harness end to end on the CPU at small sizes: a clean run is
correct, each planted fault and the control are not, a run without a TPU
prints no result, a new cell with a new per-layer metric runs from new
files alone, and so does a new plain-read cell at 1 and 4 devices."""

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


def _run_4chip(fault, root=ROOT, cell="terasort.4chip"):
    """``cell`` of the checkout at ``root`` on 4 virtual CPU devices, in a
    process of its own (the device count is fixed when JAX starts)."""
    root = str(root)
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {TESTS!r})
        import pytest, faults, run
        mp = pytest.MonkeyPatch()
        cfg = run.load_cell({root!r}, {cell!r})[2]
        if {fault!r}:
            faults.plant(mp, {fault!r}, cfg, 4, {SEED}, 4000)
        res = run.run({root!r}, {cell!r}, {SEED}, 0.5, False,
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


def _checkout(tmp_path, add):
    """A copy of the benchmark's files under ``tmp_path``, with
    ``add(root, bench)`` adding new files and entries to it."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    add(root, bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the CPU has no published peaks: give it the chip's, in this copy only
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "benchmark" / "peaks.json").write_text(json.dumps(peaks))
    return root


def _synthetic_trace(monkeypatch):
    """A CPU trace holds no TPU plane: stand in the hand-built one."""
    import devtrace
    from jax.profiler import ProfileData
    from test_devtrace import SYNTHETIC
    summary = devtrace.summarize(*devtrace.read_planes(
        ProfileData.from_text_proto(SYNTHETIC)))
    monkeypatch.setattr(devtrace, "load_summary", lambda d: summary)


def test_new_cell_and_metric_from_new_files_alone(tmp_path, monkeypatch):
    """A later PR adds a cell and a per-layer metric as new files and one
    entry each; the harness runs them with no other edit."""
    def add(root, bench):
        (root / "benchmark" / "metrics" / "throwaway.shuffles.py"
         ).write_text("def read(ctx):\n    return float(len(ctx.shuffles))\n")
        bench["workloads"].append({
            "name": "throwaway.1chip", "config": "hibench-terasort",
            "traffic": "serial-loop", "chips": 1, "why": "test"})
        bench["per_layer"].append({
            "name": "throwaway.shuffles", "unit": "count",
            "better": "higher", "source": "host_clock", "layer": "map side",
            "moves": "shuffle_GBps_per_chip",
            "workloads": ["throwaway.1chip"]})
    root = _checkout(tmp_path, add)
    _synthetic_trace(monkeypatch)
    res = run.run(str(root), "throwaway.1chip", SEED, 0.5, True,
                  allow_cpu=True, rows_per_chip=20_000)
    assert res["correct"] is True
    assert res["metrics"]["throwaway.shuffles"]["value"] == res["attempted"]
    assert res["metrics"]["map.write_ms"]["unit"] == "ms"
    assert "a2a_roofline" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


# A plain repartition: TeraSort's records, hash partitioner, plain read().
PLAIN = "repartition.1chip"
PLAIN_4 = "repartition.4chip"


def _add_plain(root, bench):
    """The plain-read cells as a later PR adds them: a config file and
    one entry each, no other edit."""
    cfg = json.loads((root / "benchmark" / "configs" /
                      "hibench-terasort.json").read_text())
    cfg.update(name="hibench-repartition", partitioner={"kind": "hash"},
               read={})
    (root / "benchmark" / "configs" / "hibench-repartition.json"
     ).write_text(json.dumps(cfg))
    bench["configs"].append({
        "name": "hibench-repartition", "source": "test",
        "file": "benchmark/configs/hibench-repartition.json",
        "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": PLAIN, "config": "hibench-repartition",
         "traffic": "serial-loop", "chips": 1, "why": "test"},
        {"name": PLAIN_4, "config": "hibench-repartition",
         "traffic": "serial-loop-waved", "chips": 4, "why": "test"}]


@pytest.fixture
def plain_root(tmp_path):
    return _checkout(tmp_path, _add_plain)


def test_plain_read_cell_from_new_files_alone(plain_root, monkeypatch):
    """A clean plain-read run is correct and reports the end-to-end
    metrics BENCHMARK.json gives its name. Traced, with the cell also
    listed for the fetch waits, every per-layer reader that applies to
    it returns a number: the exchange roofline among them."""
    cell, bench = run.load_cell(str(plain_root), PLAIN)[:2]
    e2e = {m["name"] for m in bench["end_to_end"] if run.applies(m, cell)}
    assert {"shuffle_GBps_per_chip", "setup_s"} <= e2e
    res = run.run(str(plain_root), PLAIN, SEED, 0.5, False, allow_cpu=True,
                  rows_per_chip=20_000)
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for m in bench["end_to_end"]:
        if m["name"].startswith("fetch_wait_"):
            m["workloads"].append(PLAIN)
    (plain_root / "BENCHMARK.json").write_text(json.dumps(bench))
    e2e = {m["name"] for m in bench["end_to_end"] if run.applies(m, cell)}
    _synthetic_trace(monkeypatch)
    res = run.run(str(plain_root), PLAIN, SEED, 0.5, True, allow_cpu=True,
                  rows_per_chip=20_000)
    assert res["correct"] is True
    want = {m["name"] for m in bench["per_layer"]
            if run.applies(m, cell, e2e)}
    assert "exchange_roofline" in want and "a2a_roofline" not in want
    assert set(res["metrics"]) == want
    assert all(isinstance(m["value"], (int, float))
               for m in res["metrics"].values())


@pytest.mark.parametrize("fault", ["altered", "half", "unchanged",
                                   "control"])
def test_plain_read_fault_is_not_correct(plain_root, fault, monkeypatch):
    cfg = run.load_cell(str(plain_root), PLAIN)[2]
    faults.plant(monkeypatch, fault, cfg, 1, SEED, 20_000)
    res = run.run(str(plain_root), PLAIN, SEED, 0.5, False, allow_cpu=True,
                  rows_per_chip=20_000)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("fault", ["", "no_exchange", "altered"])
def test_plain_read_four_chips(plain_root, fault):
    res = _run_4chip(fault, plain_root, PLAIN_4)
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "")
