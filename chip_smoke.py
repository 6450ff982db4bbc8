"""Chip smoke: the shuffle main path, end to end, once, on a TPU.

Drives ``sparkucx_tpu.connect()`` -> ``register_shuffle`` -> map-side
``write``/``commit`` -> ``read`` through the entry points a host engine
calls, at the HiBench ``large`` size, and checks every result against an
independent numpy oracle:

* ``terasort``  — HiBench TeraSort (conf/workloads/micro/terasort.conf,
  ``large`` profile): 32,000,000 records of 100 B per chip, an int64
  splitmix64 key plus 23 int32 payload lanes (the TeraGen 10 B key and
  90 B payload, padded to lanes); range partitioner with reservoir-
  sampled bounds, 16 map writers, 64 partitions per chip,
  ``read(ordered=True)``. Oracle: concatenated partitions globally
  sorted, sorted keys equal the ingest's, and the order-free row digest
  (keys and payload) equals the ingest's.
* ``wordcount`` — ``read(combine="sum")`` over 32,000,000 (int64 word
  id, int32 1) rows, word ids Zipf(1.1) over 2^20 words. Oracle: numpy
  ``bincount``, exact.

Each phase reads twice from the same committed map outputs: the first
read compiles, the second is warm; compile seconds are the difference.
One JSON line per phase, then the last line
``{"ok": true, "device": {...}}``. The script exits non-zero, without
that line, when the backend is not a TPU, when a phase raises or
mismatches its oracle, or when a kernel fell back.

``--chips 4`` runs the same two phases over a 4-chip mesh (the ragged
all-to-all over ICI) at the same rows per chip. ``--profile small`` is
HiBench's 3,200,000-record profile, for a run whose time or chip budget
cannot take ``large``; every phase line names the profile it ran.
``--rows N`` is a rehearsal size; on the CPU it runs the phases and then
exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmark.yardstick import keystream, payload_lanes, slices, zipf_words

# HiBench conf/workloads/micro/terasort.conf profiles, records per chip
PROFILES = {"large": 32_000_000, "small": 3_200_000}
VALUE_LANES = 23                    # 92 B payload: 10 B key + 90 B value
NUM_MAPS = 16
PARTITIONS_PER_CHIP = 64
WORDS = 1 << 20
ZIPF_S = 1.1
# 3.2 GB a chip fits one exchange on one chip, not on four: there the
# receive buffer carries a2a.capacityFactor headroom, so the exchange is
# streamed in this many waves (a2a.waveRows = rows per chip / WAVES)
WAVES_MULTICHIP = 8


def _device_line(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes(jax) -> int:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _timed_read(svc, handle, **kw):
    t0 = time.perf_counter()
    res = svc.read(handle, **kw)
    for r in range(handle.num_partitions):
        res.partition(r)        # the host sees every partition
    return res, time.perf_counter() - t0


def _check_terasort(res, num_partitions, want_keys_sorted, want_digest):
    from sparkucx_tpu.shuffle.integrity import digest_sum
    keys, vals = [], []
    for r in range(num_partitions):
        k, v = res.partition(r)
        keys.append(k)
        vals.append(v)
    keys = np.concatenate(keys)
    vals = np.concatenate(vals)
    if keys.shape[0] != want_keys_sorted.shape[0]:
        raise AssertionError(f"terasort: {keys.shape[0]} rows out, "
                             f"{want_keys_sorted.shape[0]} in")
    if (np.diff(keys) < 0).any():
        raise AssertionError("terasort: output is not globally sorted")
    if not np.array_equal(keys, want_keys_sorted):
        raise AssertionError("terasort: sorted keys differ from ingest")
    if digest_sum(keys, vals) != want_digest:
        raise AssertionError("terasort: row digest differs from ingest")


def _check_wordcount(res, num_partitions, want_counts):
    keys, sums = [], []
    for r in range(num_partitions):
        k, v = res.partition(r)
        if k.shape[0] > 1 and (np.diff(k) <= 0).any():
            raise AssertionError(f"wordcount: partition {r} keys not "
                                 f"unique and sorted")
        keys.append(k)
        sums.append(v.reshape(-1))
    keys = np.concatenate(keys)
    sums = np.concatenate(sums)
    if keys.shape[0] and (keys.min() < 0 or keys.max() >= WORDS):
        raise AssertionError(f"wordcount: word ids out of [0, {WORDS}): "
                             f"{keys.min()}..{keys.max()}")
    got = np.zeros(WORDS, np.int64)
    got[keys] = sums
    if np.unique(keys).shape[0] != keys.shape[0]:
        raise AssertionError("wordcount: a word id appears twice")
    if not np.array_equal(got, want_counts):
        raise AssertionError("wordcount: counts differ from bincount")


def _expected_impls(chips: int, jax) -> dict:
    """What ``auto`` advertises on this backend: the a2a transport an
    exchange over ``chips`` shards rides, and the device-kernel tier of
    the ordered/combine fold (4-byte combine values)."""
    from sparkucx_tpu.ops.pallas.segmented import resolve_kernel_impl
    from sparkucx_tpu.shuffle.alltoall import resolved_wire_impl
    backend = jax.default_backend()
    return {"a2a_impl": resolved_wire_impl("auto", chips, backend),
            "merge_impl": resolve_kernel_impl("auto", backend,
                                              combine_dtype="<i4")[0]}


def _check_resolved(line: dict, chips: int, jax) -> None:
    """A kernel that fell back, or an impl other than the one ``auto``
    advertises here, fails the phase."""
    if line["kernel_fallback"]:
        raise AssertionError(f"{line['phase']}: kernel fell back "
                             f"{line['kernel_fallback']:g} times")
    for key, want in _expected_impls(chips, jax).items():
        if line[key] != want:
            raise AssertionError(f"{line['phase']}: {key} resolved to "
                                 f"{line[key]!r}, auto advertises {want!r}")


def _pallas_reduce_ran(chips: int) -> bool:
    """Whether the exchange program of the combined read carries the
    blocked pallas segment-reduce: the cached step of a multi-shard
    combine plan, lowered again (no compile), names the Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from sparkucx_tpu.shuffle.stepcache import GLOBAL_STEP_CACHE
    for key, step in list(GLOBAL_STEP_CACHE._entries.items()):
        plan, width = key[3], key[4]
        if not (key[0] == "flat" and plan.combine
                and plan.num_shards == chips):
            continue
        lowered = step.lower(
            jax.ShapeDtypeStruct((chips * plan.cap_in, width), jnp.int32),
            jax.ShapeDtypeStruct((chips,), jnp.int32))
        if "tpu_custom_call" in lowered.as_text():
            return True
    return False


def _phase_report(svc, name, sid, rows, row_bytes, walls, jax, profile):
    from sparkucx_tpu.utils.metrics import C_KERNEL_FALLBACK
    rep = svc.manager.report(sid)
    cold, warm = walls
    return {
        "phase": name, "profile": profile, "rows": rows,
        "bytes": rows * row_bytes,
        "compile_s": cold - warm, "warm_wall_s": warm,
        "first_wall_s": cold,
        "peak_bytes_in_use": _peak_bytes(jax),
        "a2a_impl": rep.impl, "merge_impl": rep.kernel, "wire": rep.wire,
        "waves": rep.waves,
        "kernel_fallback": svc.node.metrics.get(C_KERNEL_FALLBACK),
        # the warm read's host-clock split (ExchangeReport)
        "warm_report_ms": {k: getattr(rep, k + "_ms")
                           for k in ("plan", "pack", "dispatch", "group")},
    }


def run_terasort(svc, rows, seed, chips, jax, profile):
    from sparkucx_tpu.ops.partition import ReservoirSampler
    from sparkucx_tpu.shuffle.integrity import digest_sum
    nparts = PARTITIONS_PER_CHIP * chips
    sampler = ReservoirSampler(capacity=max(4096, 128 * nparts), seed=seed)
    maps = []
    digest = 0
    for a, b in slices(rows, NUM_MAPS):
        keys = keystream(seed, a, b - a)
        vals = payload_lanes(seed, a, b - a, VALUE_LANES)
        sampler.add(keys)
        digest = (digest + digest_sum(keys, vals)) % (1 << 64)
        maps.append((keys, vals))
    want = np.sort(np.concatenate([k for k, _ in maps]))
    sid = 1
    h = svc.register_shuffle(sid, NUM_MAPS, nparts, partitioner="range",
                             bounds=sampler.bounds(nparts))
    t0 = time.perf_counter()
    for m, (keys, vals) in enumerate(maps):
        svc.write(h, m, keys, vals)
    write_s = time.perf_counter() - t0
    del maps
    walls = []
    for _ in range(2):
        res, wall = _timed_read(svc, h, ordered=True)
        _check_terasort(res, nparts, want, digest)
        walls.append(wall)
        del res
    out = _phase_report(svc, "terasort", sid, rows,
                        8 + 4 * VALUE_LANES, walls, jax, profile)
    out["write_s"] = write_s
    svc.unregister_shuffle(sid)
    return out


def run_wordcount(svc, rows, seed, chips, jax, profile):
    nparts = PARTITIONS_PER_CHIP * chips
    words = zipf_words(seed, rows, WORDS, ZIPF_S)
    want = np.bincount(words, minlength=WORDS).astype(np.int64)
    ones = np.ones((rows, 1), np.int32)
    sid = 2
    h = svc.register_shuffle(sid, NUM_MAPS, nparts)
    t0 = time.perf_counter()
    for m, (a, b) in enumerate(slices(rows, NUM_MAPS)):
        svc.write(h, m, words[a:b], ones[a:b])
    write_s = time.perf_counter() - t0
    walls = []
    for _ in range(2):
        res, wall = _timed_read(svc, h, combine="sum")
        _check_wordcount(res, nparts, want)
        walls.append(wall)
        del res
    out = _phase_report(svc, "wordcount", sid, rows, 12, walls, jax,
                        profile)
    out["write_s"] = write_s
    svc.unregister_shuffle(sid)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--profile", choices=sorted(PROFILES), default="large",
                    help="HiBench TeraSort profile (records per chip)")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per chip for a rehearsal (overrides "
                         "--profile)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu" and args.rows is None:
        print(f"chip_smoke: backend is {backend!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, have {len(jax.devices())}", file=sys.stderr)
        return 2
    rows = args.rows or PROFILES[args.profile]
    profile = "rehearsal" if args.rows else args.profile

    import sparkucx_tpu
    conf = {"spark.shuffle.tpu.io.format": "raw"}
    if args.chips > 1:
        conf["spark.shuffle.tpu.a2a.waveRows"] = str(
            -(-rows // WAVES_MULTICHIP))
    with sparkucx_tpu.connect(conf) as svc:
        if svc.node.num_devices != args.chips:
            print(f"chip_smoke: mesh holds {svc.node.num_devices} "
                  f"devices, --chips {args.chips}", file=sys.stderr)
            return 2
        total = rows * args.chips
        for phase in (run_terasort, run_wordcount):
            line = phase(svc, total, args.seed, args.chips, jax, profile)
            if phase is run_wordcount and args.chips > 1:
                line["pallas_reduce"] = _pallas_reduce_ran(args.chips)
            print(json.dumps(line), flush=True)
            if backend == "tpu":
                _check_resolved(line, args.chips, jax)
                if line.get("pallas_reduce") is False:
                    raise AssertionError(
                        "wordcount: the receive-side combine did not run "
                        "the pallas segment-reduce")
    if backend != "tpu":
        print("chip_smoke: rehearsal on a non-TPU backend; no result",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": _device_line(jax)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
