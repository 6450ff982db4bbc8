"""AOT multi-chip lowering proof for the native collective.

Single-chip environments can execute ``impl="native"``
(`jax.lax.ragged_all_to_all`) only at n=1, which never exercises the
multi-peer offset plumbing. The reference's CI answers the same problem
by running its real transport multi-process over shm without an RDMA
fabric (ref: buildlib/test.sh:147-166). The TPU answer is ahead-of-time
compilation against an UNATTACHED device topology
(jax.experimental.topologies): build an 8-chip TPU topology description,
compile the production exchange step against it, and assert the
ragged-all-to-all survives into the post-optimization HLO with all 8
replicas — proof the multi-peer program is compilable on real-fleet
shapes without owning the fleet.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

# Topology specs to try, most-specific first: the accelerator generation
# string and chip grid for one v5e host (2x4 = 8 chips). Names vary
# across libtpu versions, so each is attempted in order.
TOPOLOGY_CANDIDATES: Tuple[Tuple[str, dict], ...] = (
    ("v5e:2x4", {}),
    ("v5e", {"topology": "2x4"}),
    ("", {"accelerator_type": "v5litepod-8"}),
)


def _resolve_topology(report: dict, topology_name: Optional[str]):
    """Try the topology candidates most-specific first; return the
    topology desc or None (report['error'] set). Shared by every AOT
    proof so the name-spelling fallbacks cannot drift apart."""
    from jax.experimental import topologies
    cands = ([(topology_name, {})] if topology_name
             else list(TOPOLOGY_CANDIDATES))
    errors = []
    for name, kwargs in cands:
        try:
            topo = topologies.get_topology_desc(
                name, platform="tpu", **kwargs)
            report["topology"] = name or str(kwargs)
            return topo
        except Exception as e:  # libtpu absent / unknown name spelling
            errors.append(f"{name or kwargs}: {str(e)[:120]}")
    report.update(ok=False, error="; ".join(errors))
    return None


def aot_compile_native_step(
    n_devices: int = 8,
    rows_per_shard: int = 1024,
    width: int = 10,
    topology_name: Optional[str] = None,
) -> dict:
    """Compile the production exchange step (impl='native') against an
    n-chip TPU topology, WITHOUT attached devices. Returns a report dict:

      {"ok": bool, "topology": str, "devices": n,
       "hlo_post_opt_ragged": bool, "replica_groups_n": int,
       "error": str (on failure)}

    ``hlo_post_opt_ragged`` is the load-bearing bit: the op survived
    XLA:TPU optimization at n>1, so the multi-peer offset plumbing
    produces a compilable collective — the strongest validation available
    without multi-chip hardware."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.shuffle.plan import ShufflePlan
    from sparkucx_tpu.shuffle.reader import step_body

    report: dict = {"devices": n_devices}
    topo = _resolve_topology(report, topology_name)
    if topo is None:
        return report

    devs = list(topo.devices)
    if len(devs) < n_devices:
        report.update(ok=False,
                      error=f"topology exposes {len(devs)} devices, "
                            f"need {n_devices}")
        return report
    mesh = topologies.make_mesh(topo, (n_devices,), ("shuffle",))

    # sort_impl pinned to the TPU formulation: inside an AOT compile the
    # tracing process's default backend is usually CPU, and "auto" keys
    # on THAT — it would silently compile the counting-sort (scatter)
    # path the chip never runs (verified by HLO census: auto under a CPU
    # host put a 2M-row scatter in the "TPU" program; pinned multisort
    # puts zero)
    plan = ShufflePlan(num_shards=n_devices,
                       num_partitions=4 * n_devices,
                       cap_in=rows_per_shard,
                       cap_out=2 * rows_per_shard,
                       impl="native",
                       sort_impl="multisort")
    step = step_body(plan, "shuffle")
    sm = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("shuffle"), P("shuffle")),
        out_specs=(P("shuffle"), P(), P("shuffle"), P("shuffle")),
        check_vma=False)
    sharding = NamedSharding(mesh, P("shuffle"))
    args = (
        jax.ShapeDtypeStruct((n_devices * rows_per_shard, width),
                             jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((n_devices,), jnp.int32, sharding=sharding),
    )
    try:
        lowered = jax.jit(sm).lower(*args)
        report["hlo_pre_opt_ragged"] = "ragged" in lowered.as_text()
        compiled = lowered.compile()
        txt = compiled.as_text()
    except Exception as e:
        report.update(ok=False, error=f"compile: {str(e)[:300]}")
        return report
    report["hlo_post_opt_ragged"] = "ragged-all-to-all" in txt
    # the collective must span ALL n shards: the largest replica group
    # attached to any ragged-all-to-all line (_ragged_group_sizes
    # handles both textual forms XLA emits)
    groups_n = max(_ragged_group_sizes(txt), default=0)
    report["replica_groups_n"] = groups_n
    report["ok"] = bool(report["hlo_post_opt_ragged"]
                        and groups_n == n_devices)
    return report


def aot_compile_pallas_step(
    n_devices: int = 8,
    rows_per_shard: int = 1024,
    width: int = 10,
    topology_name: Optional[str] = None,
) -> dict:
    """Compile the FULL pallas-transport exchange step (aligned sort +
    remote-DMA kernel + seg all_gather) against an n-chip topology
    without attached devices — the step-level companion of the raw
    kernel proof in tests/test_ragged_a2a_pallas.py.

    Exercises plan.pallas_interpret=False pinning: the tracing host's
    default backend is CPU, and without the pin the interpreter would be
    baked into the "TPU" program (the round-3 advisor hazard). Returns
    {"ok", "topology", "devices", "hlo_tpu_custom_call", "error"?}."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.shuffle.plan import ShufflePlan
    from sparkucx_tpu.shuffle.reader import step_body

    report: dict = {"devices": n_devices}
    topo = _resolve_topology(report, topology_name)
    if topo is None:
        return report
    mesh = topologies.make_mesh(topo, (n_devices,), ("shuffle",))

    plan = ShufflePlan(num_shards=n_devices,
                      num_partitions=4 * n_devices,
                      cap_in=rows_per_shard,
                      cap_out=2 * rows_per_shard,
                      impl="pallas",
                      sort_impl="multisort",
                      pallas_interpret=False)
    step = step_body(plan, "shuffle")
    sm = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("shuffle"), P("shuffle")),
        out_specs=(P("shuffle"), P(), P("shuffle"), P("shuffle")),
        check_vma=False)
    sharding = NamedSharding(mesh, P("shuffle"))
    args = (
        jax.ShapeDtypeStruct((n_devices * rows_per_shard, width),
                             jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((n_devices,), jnp.int32, sharding=sharding),
    )
    try:
        txt = jax.jit(sm).lower(*args).compile().as_text().lower()
    except Exception as e:
        report.update(ok=False, error=f"compile: {str(e)[:300]}")
        return report
    # the Mosaic kernel must survive optimization as the TPU custom call;
    # an interpreter-baked trace would have no custom call at all
    report["hlo_tpu_custom_call"] = "tpu_custom_call" in txt
    report["ok"] = report["hlo_tpu_custom_call"]
    return report


def _ragged_group_size_counts(txt: str) -> dict:
    """replica-group size -> number of ragged-all-to-all HLO lines
    carrying it (post-opt), both textual forms ('{{0,1,..}}' braces and
    iota-v2 '[G,K]<=[N]'). The COUNT matters: a two-stage proof must see
    two distinct collective occurrences, not one line satisfying two
    membership checks (ADVICE r4)."""
    counts: dict = {}
    for line in txt.splitlines():
        if "ragged-all-to-all" not in line or "replica_groups" not in line:
            continue
        inner = line.split("replica_groups=")[1]
        if inner.startswith("["):
            dims = inner[1:].split("]")[0].split(",")
            if "<=" in inner.split("]")[1][:3] and len(dims) == 2:
                size = int(dims[1].strip())
                counts[size] = counts.get(size, 0) + 1
            continue
        ids = inner.split("}")[0].strip("{").replace("{", "")
        size = len([x for x in ids.split(",") if x.strip()])
        counts[size] = counts.get(size, 0) + 1
    return counts


def _ragged_group_sizes(txt: str):
    """Distinct replica-group sizes attached to ragged-all-to-all lines
    in post-opt HLO (set view of _ragged_group_size_counts)."""
    return set(_ragged_group_size_counts(txt))


def _two_stage_ok(counts: dict, slices: int, per_slice: int) -> bool:
    """BOTH hierarchical stages present in post-opt HLO. The general
    case needs a collective of each group size; when slices ==
    per_slice one size must occur TWICE — the earlier sum-over-all-
    sizes check let one required-size collective plus one of any
    UNRELATED size pass vacuously (ADVICE r5 low: the r4 hole narrowed
    but not closed)."""
    if slices == per_slice:
        return counts.get(per_slice, 0) >= 2
    return counts.get(per_slice, 0) >= 1 and counts.get(slices, 0) >= 1


def aot_compile_hier_step(
    slices: int = 2,
    per_slice: int = 4,
    rows_per_shard: int = 1024,
    width: int = 10,
    topology_name: Optional[str] = None,
) -> dict:
    """Compile the two-stage hierarchical (ICI, DCN) exchange
    (shuffle/hierarchical._build_hier_step) against an unattached TPU
    topology reshaped (slices, per_slice) — the multi-slice lowering
    proof closing the distributed-backend evidence gap the flat n=8
    proof leaves: BOTH collectives must
    survive post-opt HLO, the ICI stage spanning ``per_slice`` replicas
    and the DCN stage spanning ``slices``.

    Returns {"ok", "topology", "group_sizes", "error"?}."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkucx_tpu.shuffle.plan import ShufflePlan

    n = slices * per_slice
    report: dict = {"devices": n, "slices": slices}
    topo = _resolve_topology(report, topology_name)
    if topo is None:
        return report
    if len(list(topo.devices)) < n:
        report.update(ok=False,
                      error=f"topology exposes {len(list(topo.devices))} "
                            f"devices, need {n}")
        return report

    plan = ShufflePlan(num_shards=n, num_partitions=4 * n,
                       cap_in=rows_per_shard,
                       cap_out=2 * rows_per_shard,
                       impl="native", sort_impl="multisort")
    try:
        mesh = topologies.make_mesh(topo, (slices, per_slice),
                                    ("dcn", "ici"))
        # the UNCACHED builder: a proof against a fake unattached
        # topology must not occupy the production step cache or inflate
        # its compile.step.programs observability counter
        from sparkucx_tpu.shuffle.hierarchical import \
            _build_hier_step_uncached
        fn = _build_hier_step_uncached(mesh, "dcn", "ici", plan, width)
        sharding = NamedSharding(mesh, P(("dcn", "ici")))
        args = (
            jax.ShapeDtypeStruct((n * rows_per_shard, width), jnp.int32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding),
        )
        txt = fn.lower(*args).compile().as_text()
    except Exception as e:
        report.update(ok=False, error=f"compile: {str(e)[:300]}")
        return report
    counts = _ragged_group_size_counts(txt)
    report["group_sizes"] = sorted(counts)
    report["group_size_counts"] = {str(k): v for k, v in
                                   sorted(counts.items())}
    # both stages present: ICI groups of per_slice AND DCN groups of
    # slices, counted per size (_two_stage_ok) — slices == per_slice
    # requires that size twice, so neither a one-stage lowering nor an
    # unrelated extra collective can satisfy the proof vacuously.
    report["ok"] = _two_stage_ok(counts, slices, per_slice)
    return report


def aot_compile_strip_step(
    strips: int = 64,
    rows: int = 1 << 21,
    width: int = 10,
    topology_name: Optional[str] = None,
) -> dict:
    """Compile the single-shard STRIP-sorted plain step (a2a.sortStrips,
    reader.step_body fast path) against one chip of an unattached TPU
    topology — proof the batched-strip sort program lowers for the chip
    at full size without a chip.

    The load-bearing bits: the program compiles, carries NO collective
    (n=1 strips path is pure sort — no ragged-all-to-all, no
    all-gather), and NO scatter (the counting-sort hazard the n=8 proof
    pins sort_impl against; histograms are searchsorted differences).
    Returns {"ok", "topology", "strips", "hlo_sort",
    "hlo_no_collective", "hlo_no_scatter", "error"?}."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkucx_tpu.shuffle.plan import ShufflePlan
    from sparkucx_tpu.shuffle.reader import step_body

    report: dict = {"strips": strips, "rows": rows}
    topo = _resolve_topology(report, topology_name)
    if topo is None:
        return report
    mesh = Mesh(np.array(list(topo.devices))[:1], ("shuffle",))

    plan = ShufflePlan(num_shards=1, num_partitions=64,
                       cap_in=rows, cap_out=rows,
                       impl="native", sort_impl="multisort",
                       sort_strips=strips)
    assert plan.strips_active()
    step = step_body(plan, "shuffle")
    sm = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("shuffle"), P("shuffle")),
        out_specs=(P("shuffle"), P(), P("shuffle"), P("shuffle")),
        check_vma=False)
    sharding = NamedSharding(mesh, P("shuffle"))
    args = (
        jax.ShapeDtypeStruct((rows, width), jnp.int32,
                             sharding=sharding),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sharding),
    )
    try:
        txt = jax.jit(sm).lower(*args).compile().as_text().lower()
    except Exception as e:
        report.update(ok=False, error=f"compile: {str(e)[:300]}")
        return report
    import re
    report["hlo_sort"] = " sort" in txt or "sort(" in txt
    report["hlo_no_collective"] = ("all-to-all" not in txt
                                   and "all-gather" not in txt)
    # match scatter INSTRUCTIONS (the serializing colliding-index op),
    # not custom-call names: the batched searchsorted legitimately emits
    # a tiny "GatherScatterIndicesBitpacked" gather-index helper
    report["hlo_no_scatter"] = not re.search(r"=\s*[^=\n]*\bscatter\(",
                                             txt)
    report["ok"] = bool(report["hlo_sort"] and report["hlo_no_collective"]
                        and report["hlo_no_scatter"])
    return report
