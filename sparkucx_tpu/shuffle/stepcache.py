"""Keyed compiled-step cache — ONE in-process home for exchange programs.

The exchange step is compiled once per plan signature ``(mesh, axes,
cap_in, cap_out, width, impl, combine, ordered, strips, ...)`` — the
hashable :class:`~sparkucx_tpu.shuffle.plan.ShufflePlan` plus the mesh
and row width. Before this module, the flat and hierarchical builders
each kept a private ``functools.lru_cache`` with no observability: a
warmup that missed, or a row-count drift that compiled 20 programs for
one logical shuffle, was invisible until someone timed a read.

This cache is shared by ``reader._build_step``,
``hierarchical._build_hier_step`` and (through them)
``manager._warm_step``, and instruments every lookup:

* ``compile.step.programs``   — distinct step programs built (cache misses)
* ``compile.step.hits``       — lookups served by an already-built program
* ``compile.step.seconds``    — wall seconds of first invocations (XLA
  compile + first execute; later calls are untimed passthrough)

(counter names: :mod:`sparkucx_tpu.utils.metrics`), plus a
``compile.step`` tracer span around each first invocation so compile
cost shows up on the shuffle timeline next to plan/pack/dispatch.

Cache hits return the IDENTICAL callable (tests pin this: a warmed step
and the read that follows must share one jit call cache). Eviction is
LRU with a bounded capacity, matching the old per-builder
``lru_cache(maxsize=64)`` discipline.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Callable

from sparkucx_tpu.utils.logging import get_logger
from sparkucx_tpu.utils.metrics import (COMPILE_HITS, COMPILE_PROG_BYTES,
                                        COMPILE_PROG_CAPTURED,
                                        COMPILE_PROG_FLOPS,
                                        COMPILE_PROG_TEMP,
                                        COMPILE_PROGRAMS, COMPILE_SECONDS,
                                        GLOBAL_METRICS, H_COMPILE_SECS)
from sparkucx_tpu.utils.trace import GLOBAL_TRACER

log = get_logger("shuffle.stepcache")

# Device-plane cost capture (conf spark.shuffle.tpu.compile.costCapture,
# wired by TpuNode init). Off = every program's record carries null
# fields but still EXISTS — ExchangeReport.device_cost never disappears
# under a conf flip, only its contents do.
COST_CAPTURE = True
# memory_analysis needs a Compiled, i.e. a second lowered.compile() —
# affordable ONLY when the persistent compile cache can absorb it (the
# jit call that just ran populated the cache, so the probe deserializes
# instead of rebuilding). TpuNode init clears this when the cache is
# disabled/unavailable: re-paying a multi-minute XLA compile inside the
# first read for a memory figure is the wrong trade, and the stall
# would be invisible (the harvest runs after the timed call by design).
# cost_analysis (from the lowered module, no compile) always runs.
MEMORY_PROBE = True

# Field surface of one program cost record — fixed so consumers (the
# ExchangeReport join, dashboards) can rely on
# key presence even when a backend yields nothing (CPU memory_stats-less
# paths, older jax): absent data is None, never a missing key.
_COST_FIELDS = ("backend", "flops", "bytes_accessed", "argument_bytes",
                "output_bytes", "temp_bytes", "generated_code_bytes")


def harvest_cost_record(fn, args, kwargs) -> dict:
    """Best-effort XLA cost/memory analysis for a just-compiled step.

    ``cost_analysis`` comes from the LOWERED module (no second backend
    compile — the jit call that preceded this already built the
    executable); ``memory_analysis`` needs a ``Compiled``, so the module
    is compiled once more — a deserialize when the persistent compile
    cache (compile.cacheEnabled, on by default) holds the program, and a
    bounded one-time cost per distinct program otherwise. Every probe is
    guarded independently: a backend that refuses one analysis still
    contributes the other, and a backend that refuses both yields a
    record of nulls (arxiv 2112.01075's point stands only where XLA
    exposes the byte-movement model). Captured figures also sum into the
    ``compile.program.*`` counters."""
    rec = {k: None for k in _COST_FIELDS}
    rec["captured"] = False
    rec["harvest_ms"] = None
    if not COST_CAPTURE:
        return rec
    t_harvest = time.perf_counter()
    try:
        import jax
        rec["backend"] = jax.default_backend()
        lowered = fn.lower(*args, **kwargs)
    except Exception as e:
        log.debug("cost capture: lower() unavailable (%r)", e)
        return rec
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            if ca.get("flops") is not None:
                rec["flops"] = float(ca["flops"])
            if ca.get("bytes accessed") is not None:
                rec["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception as e:
        log.debug("cost capture: cost_analysis unavailable (%r)", e)
    if MEMORY_PROBE:
        try:
            ma = lowered.compile().memory_analysis()
            if ma is not None:
                rec["argument_bytes"] = int(ma.argument_size_in_bytes)
                rec["output_bytes"] = int(ma.output_size_in_bytes)
                rec["temp_bytes"] = int(ma.temp_size_in_bytes)
                rec["generated_code_bytes"] = int(
                    ma.generated_code_size_in_bytes)
        except Exception as e:
            log.debug("cost capture: memory_analysis unavailable (%r)", e)
    rec["captured"] = any(
        rec[k] is not None
        for k in ("flops", "bytes_accessed", "temp_bytes"))
    # the harvest's own cost, visible in the record (it runs after the
    # timed first call, so compile.step.seconds does not include it)
    rec["harvest_ms"] = round(
        (time.perf_counter() - t_harvest) * 1e3, 3)
    if rec["captured"]:
        GLOBAL_METRICS.inc(COMPILE_PROG_CAPTURED)
        if rec["flops"] is not None and rec["flops"] > 0:
            GLOBAL_METRICS.inc(COMPILE_PROG_FLOPS, rec["flops"])
        if rec["bytes_accessed"] is not None:
            GLOBAL_METRICS.inc(COMPILE_PROG_BYTES, rec["bytes_accessed"])
        if rec["temp_bytes"] is not None:
            GLOBAL_METRICS.inc(COMPILE_PROG_TEMP, rec["temp_bytes"])
    return rec


def program_name(kind: str, plan=None) -> str:
    """The name a read-path program compiles under: what it does and,
    given the plan, the read mode (``shuffle_exchange_ordered``). The
    profiler's ``XLA Modules`` line shows it as ``jit_<name>``, and the
    program's ``compile.step`` span carries it as ``program``."""
    if plan is None:
        return f"shuffle_{kind}"
    mode = ("combine" if plan.combine
            else "ordered" if plan.ordered else "plain")
    return f"shuffle_{kind}_{mode}"


def combine_attrs(plan) -> dict:
    """``{"combine_form": "carried" | "perm"}`` for a combining plan, else
    empty: how the program's ``combine_rows`` orders its rows
    (ops/aggregate.combine_form), carried on its ``compile.step`` span."""
    if not plan.combine:
        return {}
    from sparkucx_tpu.ops.aggregate import combine_form
    return {"combine_form": combine_form(
        plan.num_partitions, plan.combine_words, plan.combine_dtype,
        plan.combine_sum_words)}


def jit_named(fn: Callable, name: str, **jit_kwargs) -> Callable:
    """``jax.jit(fn)`` compiled under ``name`` (jit names its program
    after the function, which for a shard_map is the inner body's)."""
    import jax
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


class _TimedStep:
    """Callable proxy over a jitted step: the FIRST invocation — where
    XLA actually compiles — is timed into ``compile.step.seconds`` and
    wrapped in a ``compile.step`` tracer span; every later call is plain
    passthrough. Attribute access (``_cache_size``, ``lower``, ...)
    delegates to the underlying jit function, so callers that inspect
    the step see the real thing."""

    __slots__ = ("_fn", "_attrs", "_first", "_lock", "cost_record")

    def __init__(self, fn: Callable, attrs: dict):
        self._fn = fn
        self._attrs = attrs
        self._first = True
        self._lock = threading.Lock()
        # populated on the first call (device-plane cost capture); None
        # until the program exists — readers of a never-invoked step see
        # the distinction
        self.cost_record = None

    def __call__(self, *args, **kwargs):
        if self._first:
            # serialize concurrent first calls: both would compile the
            # same program anyway, and blocking the second is cheaper
            with self._lock:
                if self._first:
                    t0 = time.perf_counter()
                    with GLOBAL_TRACER.span("compile.step", **self._attrs):
                        out = self._fn(*args, **kwargs)
                    secs = time.perf_counter() - t0
                    GLOBAL_METRICS.inc(COMPILE_SECONDS, secs)
                    # the flat sum hides one 400 s program among twenty
                    # 2 s ones; the distribution doesn't
                    GLOBAL_METRICS.observe(H_COMPILE_SECS, secs)
                    # harvest AFTER the timed call: the capture must not
                    # inflate compile.step.seconds, and the executable it
                    # re-derives is already in the compile cache. Guarded
                    # inside — a failed harvest still yields a null-field
                    # record, never an exception into the read path.
                    self.cost_record = harvest_cost_record(
                        self._fn, args, kwargs)
                    log.debug("step first-call (compile+run) %.2fs: %s",
                              secs, self._attrs)
                    self._first = False
                    return out
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class CompiledStepCache:
    """LRU map ``(kind, mesh, axes..., plan, width) -> compiled step``.

    ``kind`` namespaces the builder ("flat" | "hier") so the two step
    families can never collide on a shared plan. Thread-safe; a miss
    builds OUTSIDE the lock (tracing can be slow) and the first stored
    entry wins, so two racing builders converge on one program."""

    def __init__(self, capacity: int = 128):
        self._capacity = capacity
        self._entries: "OrderedDict" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: tuple, builder: Callable[[], Callable],
            attrs: dict) -> Callable:
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                GLOBAL_METRICS.inc(COMPILE_HITS)
                return hit
        step = _TimedStep(builder(), attrs)
        with self._lock:
            # first stored wins: a racing builder's duplicate is dropped
            # so every caller shares ONE jit call cache per signature
            won = self._entries.setdefault(key, step)
            if won is step:
                GLOBAL_METRICS.inc(COMPILE_PROGRAMS)
                while len(self._entries) > self._capacity:
                    self._entries.popitem(last=False)
            else:
                GLOBAL_METRICS.inc(COMPILE_HITS)
        return won

    def stats(self) -> dict:
        """{entries, capacity, programs, hits, compile_seconds} — entries
        is this cache's live size; the counters are process-global
        (GLOBAL_METRICS), matching how the cache itself is shared."""
        with self._lock:
            entries = len(self._entries)
        return {
            "entries": entries,
            "capacity": self._capacity,
            "programs": GLOBAL_METRICS.get(COMPILE_PROGRAMS),
            "hits": GLOBAL_METRICS.get(COMPILE_HITS),
            "compile_seconds": GLOBAL_METRICS.get(COMPILE_SECONDS),
            "cost_captured": GLOBAL_METRICS.get(COMPILE_PROG_CAPTURED),
        }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


GLOBAL_STEP_CACHE = CompiledStepCache()
