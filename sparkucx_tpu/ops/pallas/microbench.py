"""Backend-agnostic microbench for the blocked segmented kernels.

One harness, three arms per case, honest on every backend:

``jnp``
    The XLA oracle path (``impl="jnp"`` through the same public
    wrappers the reader calls). Runs and is TIMED everywhere — this is
    the number a CPU run is allowed to claim.

``pallas``
    The blocked kernels compiled NATIVELY
    (``blocked_compile_supported`` — TPU). Timed where legal; anywhere
    else the arm records ``status="skipped"`` with the shared gate
    helper's reason instead of wearing an interpret wall-time as a
    perf claim (interpret mode is a correctness vehicle, ~1000x off).

``parity``
    The blocked kernels vs the jnp oracle, run wherever they can run
    at all (native on a TPU, interpret on the CPU).
    Not timed — graded: bit-exact on int32 sums and carried lanes,
    order-tolerance on f32/int8-fused sums (the kernels sum per-tile
    with a carry, the oracle differences a global cumsum; both are
    correct, the last-ulp order is not part of the contract).

Every timed step goes through ``GLOBAL_STEP_CACHE`` under a
``("kernelbench", impl, case-family...)`` key, so the artifact can gate
the compile invariant the acceptance bar names: the first pass compiles
exactly one program per (shape family, kernel impl) and a second warm
pass compiles ZERO — the same programs/hits counters the exchange
stepcache gates ride (``compile.step.programs``).

``python -m sparkucx_tpu kernelbench`` prints the artifact as one JSON
doc.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

_FLIP = np.int32(-0x80000000)


def make_sorted_rows(rng, n: int, cap: int, num_parts: int, width: int,
                     groups: int, sum_words: int,
                     float_vals: bool = False, zipf: float = 0.0):
    """Sorted-contract transport rows for the kernels: ``n`` valid rows
    in (part, key) order, sentinel-padded to ``cap``, carried lanes
    (past ``sum_words``) per-key constants per the data contract (the
    unstable keysort makes the representative row arbitrary, so any
    non-constant carried lane would be a parity bug in the DATA).
    ``zipf`` > 0 draws each row's group with weight ``rank ** -zipf``
    (word frequencies), else uniformly."""
    import jax.numpy as jnp
    groups = max(1, min(groups, n)) if n else 1
    part = np.sort(rng.integers(0, num_parts, size=groups)
                   .astype(np.int32))
    hi = rng.integers(-5, 5, size=groups).astype(np.int32)
    lo = rng.integers(-2**31, 2**31, size=groups,
                      dtype=np.int64).astype(np.int32)
    order = np.lexsort((lo ^ _FLIP, hi, part))
    part, hi, lo = part[order], hi[order], lo[order]
    if zipf > 0:
        w = np.arange(1, groups + 1, dtype=np.float64) ** -zipf
        gid = np.sort(rng.choice(groups, size=n, p=w / w.sum()))
    else:
        gid = np.sort(rng.integers(0, groups, size=n))
    sw = sum_words if sum_words > 0 else width - 2
    rows = np.zeros((cap, width), np.int32)
    p = np.full(cap, num_parts, np.int32)
    rows[:n, 0] = lo[gid]
    rows[:n, 1] = hi[gid]
    p[:n] = part[gid]
    carried = rng.integers(-1000, 1000,
                           size=(groups, width - 2 - sw)).astype(np.int32)
    if float_vals:
        # integer-valued f32: exactly summable in any order, so the
        # bit-exact grade is meaningful on the float arm too
        sums = rng.integers(-64, 64, size=(n, sw)).astype(np.float32)
        rows[:n, 2:2 + sw] = sums.view(np.int32)
    else:
        rows[:n, 2:2 + sw] = rng.integers(
            -2**31, 2**31, size=(n, sw), dtype=np.int64).astype(np.int32)
    rows[:n, 2 + sw:] = carried[gid]
    return jnp.asarray(rows), jnp.asarray(p)


def default_cases(rows_log2: int = 13) -> List[dict]:
    """The shape families the sweep times. ``big`` carries the bulk
    signal (2^rows_log2 rows); the small ones pin the ragged corners
    (non-tile-aligned, single-group, many-tiles-one-segment) so a
    blocked-kernel regression on an edge shows up as a parity failure
    here before it ships. ``sparse_wave`` is a WordCount receive buffer
    on one of four chips: 3-word rows, a 23rd of the capacity live,
    Zipf(1.1) words, so it times the tiles the kernel skips."""
    n = 1 << rows_log2
    return [
        dict(name="big_i32", n=n, cap=n, parts=16, width=8, groups=256,
             sum_words=2, float_vals=False),
        dict(name="big_f32", n=n, cap=n, parts=16, width=8, groups=256,
             sum_words=2, float_vals=True),
        dict(name="ragged_unaligned", n=129, cap=256, parts=4, width=6,
             groups=37, sum_words=2, float_vals=False),
        dict(name="one_segment_many_tiles", n=max(384, n // 4),
             cap=max(384, n // 4), parts=2, width=6, groups=1,
             sum_words=0, float_vals=False),
        dict(name="wire_int8_fused", n=n, cap=n, parts=16, width=6,
             groups=256, sum_words=0, float_vals=True, wire=True),
        dict(name="sparse_wave", n=max(1, n // 23), cap=n, parts=64,
             width=3, groups=max(1, n // 23), sum_words=0,
             float_vals=False, zipf=1.1),
    ]


def _build_step(case: dict, impl: str, interpret: Optional[bool]):
    """A jit-wrapped closure over the case's static shape params —
    the unit the step cache keys. Returns (callable, input tuple)."""
    import jax
    from sparkucx_tpu.ops.pallas.segmented import (
        segment_reduce_rows, segment_reduce_wire_rows)
    if case.get("wire"):
        width = case["width"]
        vw = width - 2

        def fn(rows, part):
            return segment_reduce_wire_rows(
                rows, part, case["parts"], width, vw,
                sum_words=case["sum_words"], impl=impl,
                interpret=interpret)
    else:
        import numpy as _np
        vdt = _np.float32 if case["float_vals"] else _np.int32

        def fn(rows, part):
            return segment_reduce_rows(
                rows, part, case["parts"], case["width"] - 2, vdt,
                sum_words=case["sum_words"], impl=impl,
                interpret=interpret)
    return jax.jit(fn)


def _case_inputs(case: dict, seed: int = 0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    rows, part = make_sorted_rows(
        rng, case["n"], case["cap"], case["parts"], case["width"],
        case["groups"], case["sum_words"],
        float_vals=case["float_vals"], zipf=case.get("zipf", 0.0))
    if case.get("wire"):
        from sparkucx_tpu.shuffle.alltoall import wire_pack_rows
        vw = case["width"] - 2
        # scale the float lanes so quantization is non-trivial
        f = np.asarray(rows).copy()
        n = case["n"]
        fl = f[:n, 2:].view(np.float32) * np.float32(0.37)
        f[:n, 2:] = fl.view(np.int32)
        rows = wire_pack_rows(jnp.asarray(f), vw, jnp.uint32(7))
    return rows, part


def _time_step(step, rows, part, reps: int) -> dict:
    import jax
    out = step(rows, part)           # warmup + compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(step(rows, part))
    wall = (time.perf_counter() - t0) / max(1, reps)
    return {"wall_ms": wall * 1e3,
            "rows_per_s": (rows.shape[0] / wall) if wall > 0 else 0.0}


def _parity_grade(case: dict, jout, pout) -> dict:
    jr, jc, jn = jout
    pr, pc, pn = pout
    k = int(np.asarray(jn)[0])
    ok_n = k == int(np.asarray(pn)[0])
    ok_c = np.array_equal(np.asarray(jc), np.asarray(pc))
    ja, pa = np.asarray(jr)[:k], np.asarray(pr)[:k]
    if case.get("wire"):
        # dequant is bit-exact; the f32 SUM order is not part of the
        # contract — grade keys exactly, values within the dequant
        # bound's noise floor
        ok_keys = np.array_equal(ja[:, :2], pa[:, :2])
        jv = ja[:, 2:].view(np.float32)
        pv = pa[:, 2:].view(np.float32)
        maxdiff = float(np.abs(jv - pv).max()) if k else 0.0
        ok_v = bool(np.allclose(jv, pv, rtol=1e-5, atol=1e-4))
        return {"ok": bool(ok_n and ok_c and ok_keys and ok_v),
                "n_out": k, "maxdiff": maxdiff}
    ok_r = np.array_equal(ja, pa)
    return {"ok": bool(ok_n and ok_c and ok_r), "n_out": k,
            "bitexact": bool(ok_r)}


def run_microbench(reps: int = 5, rows_log2: int = 13,
                   backend: Optional[str] = None,
                   cases: Optional[List[dict]] = None) -> Dict:
    """The artifact: per-case jnp timing everywhere, pallas timing
    where the kernels compile natively, parity grades wherever the
    kernels run at all, and the compile.step.programs invariant gated
    over a first-pass/warm-pass split of the step cache counters."""
    import jax
    from sparkucx_tpu.ops.pallas.segmented import (
        blocked_compile_supported, kernel_gate_reason)
    from sparkucx_tpu.shuffle.stepcache import CompiledStepCache

    backend = backend or jax.default_backend()
    native = blocked_compile_supported(backend)
    gate = kernel_gate_reason(backend)
    cases = cases if cases is not None else default_cases(rows_log2)

    # a PRIVATE cache per run: the invariant under gate is this run's
    # own compile discipline (first pass builds exactly its keys, warm
    # pass builds zero) — riding the global exchange cache would let a
    # prior identical run's warm entries fake a 0-program first pass
    # and fail expected==first_pass for the wrong reason
    step_cache = CompiledStepCache()

    def cached(case, impl, interpret):
        key = ("kernelbench", impl, bool(interpret), case["name"],
               case["cap"], case["width"], case["parts"],
               case["sum_words"], case["float_vals"],
               bool(case.get("wire")))
        return step_cache.get(
            key, lambda: _build_step(case, impl, interpret),
            {"kind": "kernelbench", "impl": impl, "case": case["name"]})

    stats0 = step_cache.stats()
    results = []
    steps = []                       # (step, rows, part) for warm pass
    expected_programs = 0
    for case in cases:
        rows, part = _case_inputs(case)
        row = {"case": case["name"], "rows": case["n"],
               "cap": case["cap"], "width": case["width"],
               "wire": "int8" if case.get("wire") else "raw"}
        jstep = cached(case, "jnp", None)
        expected_programs += 1
        steps.append((jstep, rows, part))
        row["jnp"] = dict(status="ok", **_time_step(jstep, rows, part,
                                                    reps))
        if native:
            pstep = cached(case, "pallas", None)
            expected_programs += 1
            steps.append((pstep, rows, part))
            row["pallas"] = dict(status="ok",
                                 **_time_step(pstep, rows, part, reps))
        else:
            # interpret wall-times are ~1000x off — a skip with the
            # gate's reason is the honest record, never a number
            row["pallas"] = {"status": "skipped",
                             "reason": "backend_unsupported"}
        if gate is None:
            interp = None if native else True
            pk = cached(case, "pallas", interp) if not native else pstep
            if not native:
                expected_programs += 1
                steps.append((pk, rows, part))
            jout = jstep(rows, part)
            pout = pk(rows, part)
            row["parity"] = dict(
                status="ok",
                mode="native" if native else "interpret",
                **_parity_grade(case, jout, pout))
        else:
            row["parity"] = {"status": "skipped", "reason": gate}
        results.append(row)

    stats1 = step_cache.stats()
    first_pass = int(stats1["programs"] - stats0["programs"])
    # warm pass: every step again — zero new programs is the invariant
    for step, rows, part in steps:
        jax.block_until_ready(step(rows, part))
    stats2 = step_cache.stats()
    warm = int(stats2["programs"] - stats1["programs"])
    programs = {"first_pass": first_pass,
                "expected": expected_programs,
                "warm_recompiles": warm,
                "ok": first_pass == expected_programs and warm == 0}
    parity_ok = all(r["parity"].get("ok", True) for r in results
                    if r["parity"]["status"] == "ok")
    return {"metric": "kernelbench", "backend": backend,
            "native_pallas": bool(native),
            "gate_reason": gate, "reps": reps,
            "cases": results, "programs": programs,
            "ok": bool(parity_ok and programs["ok"])}
