"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout. Its configuration is ``benchmark/configs/<config>.json``,
its traffic ``benchmark/traffic/<traffic>.json``, and each per-layer
metric a reader ``benchmark/metrics/<name>.py``: adding a cell is new
files and one entry, with no edit here.

Set-up makes the map outputs from ``--seed``, connects to the shuffle
service (``sparkucx_tpu.connect``) and runs one whole warm-up shuffle,
which compiles or loads from the compile cache in ``<checkout>/.jax_cache``
every program the window uses. The window is a closed loop of one client
with one shuffle in flight: register, every map's write and commit, one
read, every partition taken to the host through ``partitions_ready()``,
unregister. Shuffles run back to back until ``--seconds`` have passed; the
one in flight then finishes and the window ends with it. Once the window
has closed and the service is stopped, the plain reference
(``yardstick.py``) is computed and every shuffle of the window compared
with it: the row count of every partition, and the rows of an eighth of
its partitions, drawn from the seed anew for each shuffle (holding every
row of every shuffle would grow the host's memory by gigabytes a
window).

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (shuffles), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared beside its limit, which are also the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import devtrace  # noqa: E402
import yardstick  # noqa: E402


class NoChip(RuntimeError):
    """The machine does not hold the TPU chips the cell asks for."""


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str):
    """(cell, benchmark, config, traffic) for workload ``name``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{sorted(cells)}")
    cell = cells[name]
    config = _load_json(os.path.join(root, "benchmark", "configs",
                                     cell["config"] + ".json"))
    traffic = _load_json(os.path.join(root, "benchmark", "traffic",
                                      cell["traffic"] + ".json"))
    return cell, bench, config, traffic


def load_metric(root: str, name: str):
    """The reader module of per-layer metric ``name``."""
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict, cell: Dict, e2e_names=None) -> bool:
    """Whether the cell reports ``metric``: the cells its ``workloads``
    lists, else every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it ``moves`` (a per-layer one)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


@dataclass
class ShuffleRecord:
    """Host-clock record of one shuffle in the window (seconds)."""

    bytes: int
    write_s: float
    read_s: float
    drain_s: float
    waits_s: List[float]
    report: Dict
    sizes: Dict = field(default_factory=dict, repr=False)
    parts: Dict = field(default_factory=dict, repr=False)
    error: Optional[str] = None


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Dict
    config: Dict
    chips: int
    shuffles: List[ShuffleRecord]
    window_s: float
    trace: Optional[devtrace.Summary]
    inputs: yardstick.MapOutputs
    expected: yardstick.Expected
    device_kind: str
    root: str

    def peak(self, key: str) -> float:
        """A published peak of this device kind (``benchmark/peaks.json``);
        an unknown kind is an error."""
        peaks = _load_json(os.path.join(self.root, "benchmark", "peaks.json"))
        if self.device_kind not in peaks["devices"]:
            raise KeyError(f"no peaks for device kind {self.device_kind!r}")
        return float(peaks["devices"][self.device_kind][key])


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_shuffle(svc, sid: int, inputs: yardstick.MapOutputs, config: Dict,
                keep=()) -> ShuffleRecord:
    """One iteration of the closed loop. The delivered partitions in
    ``keep`` are held for the comparison; of the others only the row
    count is."""
    part = config["partitioner"]["kind"]
    read_kw = dict(config["read"])
    rec = ShuffleRecord(bytes=inputs.rows * yardstick.record_bytes(config),
                        write_s=0.0, read_s=0.0, drain_s=0.0, waits_s=[],
                        report={})
    with _annotate("bench.register"):
        h = svc.register_shuffle(
            sid, len(inputs.maps), inputs.num_partitions, partitioner=part,
            bounds=inputs.bounds if part == "range" else None)
    try:
        t0 = time.perf_counter()
        with _annotate("bench.write"):
            for m, (keys, vals) in enumerate(inputs.maps):
                svc.write(h, m, keys, vals)
        t1 = time.perf_counter()
        with _annotate("bench.read"):
            res = svc.read(h, **read_kw)
        t2 = time.perf_counter()
        with _annotate("bench.drain"):
            for r, kv in res.partitions_ready():
                rec.waits_s.append(time.perf_counter() - t1)
                rec.sizes[r] = kv[0].shape[0]
                if r in keep:
                    rec.parts[r] = kv
        t3 = time.perf_counter()
        del res
        rec.write_s, rec.read_s, rec.drain_s = t1 - t0, t2 - t1, t3 - t2
        rep = svc.manager.report(sid)
        if rep is not None:
            rec.report = {k: getattr(rep, k) for k in (
                "plan_ms", "pack_ms", "dispatch_ms", "group_ms", "impl",
                "kernel", "wire", "waves", "stepcache_programs")}
    finally:
        with _annotate("bench.unregister"):
            svc.unregister_shuffle(sid)
    return rec


def _percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def end_to_end(name: str, shuffles: List[ShuffleRecord], window_s: float,
               chips: int, setup_s: float) -> float:
    """The end-to-end metrics, on the host clock."""
    waits = [w for s in shuffles for w in s.waits_s]
    if name == "shuffle_GBps_per_chip":
        return sum(s.bytes for s in shuffles) / window_s / chips / 1e9
    if name == "fetch_wait_p50_ms":
        return _percentile(waits, 50) * 1e3
    if name == "fetch_wait_p95_ms":
        return _percentile(waits, 95) * 1e3
    if name == "setup_s":
        return setup_s
    raise KeyError(f"no end-to-end metric {name!r}")


def _memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except OSError:
        return 0


def _log(obj: Dict) -> None:
    print(json.dumps(obj), flush=True)


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, allow_cpu: bool = False,
        rows_per_chip: Optional[int] = None) -> Dict:
    """Run the cell once and return the result line as a dict.

    ``allow_cpu`` and ``rows_per_chip`` are for the tests, which drive the
    harness on the CPU at small sizes."""
    cell, bench, config, traffic = load_cell(root, workload)
    if (traffic.get("loop"), traffic.get("clients"),
            traffic.get("in_flight")) != ("closed", 1, 1):
        raise ValueError(f"traffic {cell['traffic']!r}: only a closed loop "
                         f"of one client with one shuffle in flight runs")
    chips = int(cell["chips"])
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR",
                               os.path.join(root, ".jax_cache"))

    t = time.perf_counter()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX finds no TPU (platform {platform!r})")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    init_s = time.perf_counter() - t

    if root not in sys.path:
        sys.path.insert(0, root)
    import sparkucx_tpu
    rows = rows_per_chip or config["records_per_chip"]
    conf = dict(config["conf"])
    if traffic.get("waves"):
        conf["spark.shuffle.tpu.a2a.waveRows"] = str(
            -(-rows // traffic["waves"]))
    t = time.perf_counter()
    svc = sparkucx_tpu.connect(conf, use_env=False)
    connect_s = time.perf_counter() - t
    try:
        if svc.node.num_devices != chips:
            raise NoChip(f"the service's mesh holds {svc.node.num_devices} "
                         f"devices, the cell {chips}")
        t = time.perf_counter()
        inputs = yardstick.make_inputs(config, chips, seed, rows_per_chip)
        data_s = time.perf_counter() - t

        cached0 = _cache_entries(cache_dir)
        t = time.perf_counter()
        warm = run_shuffle(svc, 1, inputs, config)
        warmup_s = time.perf_counter() - t
        cached1 = _cache_entries(cache_dir)
        t_window = time.time()
        setup_s = t_window - T_PROCESS
        _log({"setup": {
            "setup_s": setup_s, "init_s": init_s, "connect_s": connect_s,
            "data_s": data_s, "warmup_s": warmup_s,
            "compile_cache": "miss" if cached1 > cached0 else "hit",
            "cache_entries_written": cached1 - cached0,
            "warmup_report": warm.report}})

        trace_dir = os.path.join(root, ".bench_trace", workload)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                trace_dir, profiler_options=devtrace.profile_options())
        shuffles: List[ShuffleRecord] = []
        t0 = time.perf_counter()
        with _annotate("bench.window"):
            sid = 2
            while True:
                try:
                    rec = run_shuffle(
                        svc, sid, inputs, config,
                        keep=yardstick.sample_partitions(
                            seed, len(shuffles), inputs.num_partitions))
                except Exception as e:      # counted as a failed shuffle
                    rec = ShuffleRecord(0, 0.0, 0.0, 0.0, [], {},
                                        error=repr(e)[:500])
                shuffles.append(rec)
                sid += 1
                if rec.error or time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        memory_peak = _memory_peak(devices)
        for i, s in enumerate(shuffles):
            _log({"shuffle": i, "bytes": s.bytes, "write_s": s.write_s,
                  "read_s": s.read_s, "drain_s": s.drain_s,
                  "partitions": len(s.waits_s), "report": s.report,
                  "error": s.error})
    finally:
        svc.close()

    summary = devtrace.load_summary(trace_dir) if trace else None
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    # the reference, once the window has closed and the service is stopped
    expected = yardstick.reference(inputs, config)
    checks = {k: 0 for k in yardstick.LIMITS}
    failed = 0
    for s in shuffles:
        if s.error:
            failed += 1
            continue
        got = yardstick.compare(expected, s.sizes, s.parts, config)
        if any(got[k] > yardstick.LIMITS[k] for k in got):
            failed += 1
        for k, v in got.items():
            checks[k] += v
        s.parts = {}
    checks["shuffles_failed"] = failed

    e2e = [m for m in bench["end_to_end"] if applies(m, cell)]
    metrics = {}
    if trace:
        ctx = Context(cell, config, chips, shuffles, window_s, summary,
                      inputs, expected, devices[0].device_kind, root)
        names = {m["name"] for m in e2e}
        for m in bench["per_layer"]:
            if not applies(m, cell, names):
                continue
            value = load_metric(root, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif failed < len(shuffles):
        ok = [s for s in shuffles if not s.error]
        for m in e2e:
            metrics[m["name"]] = {
                "value": end_to_end(m["name"], ok, window_s, chips, setup_s),
                "unit": m["unit"]}
    limits = dict(yardstick.LIMITS, shuffles_failed=0)
    result = {"correct": failed == 0 and len(shuffles) > 0,
              "attempted": len(shuffles), "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = devtrace.breakdown(summary)
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"benchmark: {e}; no result", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: finalizers of device buffers and threads
    # would print after the result and the checks
    os._exit(code)
