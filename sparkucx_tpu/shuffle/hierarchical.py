"""Hierarchical multi-slice shuffle — two-stage ragged exchange (ICI, DCN).

SURVEY.md §7 hard part (d): on one slice, the flat one-collective exchange
(shuffle/reader.py) rides ICI and is optimal. Across slices a flat
all-to-all over all P = S x D devices pushes most pairs over DCN — the slow
inter-slice fabric — exactly the regime where the reference's one-big-read
model "degrades to point-to-point transfers again". The classic fix is the
two-stage decomposition of the all-to-all:

    route (s, d) -> (s', d')  as  (s, d) --ICI--> (s, d') --DCN--> (s', d')

    stage 1 (ici axis):  within each slice, exchange rows grouped by the
                         *destination device index* d' — all traffic on ICI.
    stage 2 (dcn axis):  exchange rows grouped by the *destination slice*
                         s' at fixed device index d' — each row crosses DCN
                         exactly once, on the one link pair that must carry
                         it.

Load balance falls out of the algebra: with T total rows, the stage-1
intermediate at (s, d') holds (rows of slice s) ∩ (destined to device
index d') ≈ T/S x 1/D = T/P — the same balanced share as the final state,
so both stages run with the same capacity plan.

Destinations are *recomputed from row keys* between stages (the partitioner
is deterministic), so no routing metadata rides the wire — the same trick
the reference plays by deriving block sizes from the index-file offsets
instead of shipping a size manifest (ref: OnOffsetsFetchCallback.java:44-52).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from sparkucx_tpu.shuffle.plan import ShufflePlan
from sparkucx_tpu.shuffle.reader import ShuffleReaderResult
from sparkucx_tpu.utils.logging import get_logger

log = get_logger("shuffle.hierarchical")


def _build_hier_step(mesh: Mesh, dcn_axis: str, ici_axis: str,
                     plan: ShufflePlan, width: int):
    """The FUSED two-stage exchange for one (mesh, plan, width), served
    from the shared keyed step cache (shuffle/stepcache.py — one
    compiled program per plan signature, observable, shared with the
    flat builder and manager.warmup). Keyed on the STRUCTURAL mesh
    identity (topology.mesh_cache_key: devices.shape, axis names,
    device ids) — a remeshed-but-identical mesh (PR-7 replay rebinds a
    fresh Mesh object over the same devices) reuses its compiled
    programs instead of recompiling both tiers."""
    from sparkucx_tpu.shuffle.stepcache import (GLOBAL_STEP_CACHE,
                                                combine_attrs, program_name)
    from sparkucx_tpu.shuffle.topology import mesh_cache_key
    from sparkucx_tpu.utils.trace import GLOBAL_TRACER

    name = program_name("exchange_hier", plan)
    # anatomy span (compile phase): on a cache hit this is ~ns; on a
    # miss it wraps the trace+lower of BOTH tiers (the inner
    # compile.step span from stepcache covers the jit alone)
    with GLOBAL_TRACER.span("shuffle.hier.build", ici=ici_axis,
                            dcn=dcn_axis, width=width):
        return GLOBAL_STEP_CACHE.get(
            ("hier", mesh_cache_key(mesh), dcn_axis, ici_axis, plan, width),
            lambda: _build_hier_step_uncached(mesh, dcn_axis, ici_axis, plan,
                                              width),
            {"kind": "hier", "program": name, "cap_in": plan.cap_in,
             "cap_out": plan.cap_out, "width": width, "impl": plan.impl,
             "wire": plan.wire, **combine_attrs(plan)})


def _build_hier_step_uncached(mesh: Mesh, dcn_axis: str, ici_axis: str,
                              plan: ShufflePlan, width: int):
    """Mesh must be 2-D ``(dcn=S, ici=D)``; global shard id g = s*D + d
    matches ``mesh.devices.reshape(-1)`` order, so the flat
    ``blocked_partition_map`` routing is identical to the flat reader's.

    The stage ALGEBRA has one home — ``topology._stage1_body`` /
    ``_stage2_body`` (the split tiered path composes the same bodies as
    two programs with a host join; this fused form inlines the join:
    stage 1's in-graph totals feed stage 2, a distinct noise stream is
    derived for the second hop, and the overflow flags OR) — so a fix
    to the relay grouping or the finalize can never drift between the
    single-process tiered path and this multi-process fused one."""
    from sparkucx_tpu.shuffle.alltoall import wire_noise_seed
    from sparkucx_tpu.shuffle.plan import plan_takes_seed
    from sparkucx_tpu.shuffle.topology import (TopologyDescriptor,
                                               _check_hier_mesh,
                                               _stage1_body, _stage2_body)
    S, D = mesh.devices.shape
    assert plan.num_shards == S * D, (plan.num_shards, S, D)
    topo = TopologyDescriptor("hier", ici_axis=ici_axis,
                              dcn_axis=dcn_axis, num_slices=int(S),
                              per_slice=int(D))
    _check_hier_mesh(mesh, topo)
    stage1 = _stage1_body(plan, topo, int(plan.cap_out))
    stage2 = _stage2_body(plan, topo, int(plan.cap_out))
    seeded = plan_takes_seed(plan)

    def step(payload, nvalid):
        # payload [cap_in, W] int32, col 0 = key_lo; nvalid [1] — or
        # [count, seed] on the int8 wire (reader.seeded_nvalid): the
        # wire tier narrows BOTH hops, the second drawing a distinct
        # noise stream derived in-graph from the per-shard seed
        relay, tot1, ovf1 = stage1(payload, nvalid)
        if seeded:
            nv2 = jnp.stack([tot1[0],
                             wire_noise_seed(nvalid[1], 1)]
                            ).astype(jnp.int32)
        else:
            nv2 = tot1
        rows_out, seg, total, ovf2 = stage2(relay, nv2)
        return rows_out, seg, total, ovf1 | ovf2

    from sparkucx_tpu.shuffle.stepcache import jit_named, program_name
    spec = P((dcn_axis, ici_axis))
    sm = jax.shard_map(step, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec,) * 4)
    return jit_named(sm, program_name("exchange_hier", plan))


def submit_shuffle_hierarchical(
    mesh: Mesh,
    dcn_axis: str,
    ici_axis: str,
    plan: ShufflePlan,
    shard_rows: np.ndarray,
    shard_nvalid: np.ndarray,
    val_shape,
    val_dtype,
    on_done=None,
    admit=None,
):
    """Dispatch the two-stage exchange without blocking — same
    submit/poll contract as :func:`shuffle.reader.submit_shuffle`."""
    from jax.sharding import NamedSharding

    from sparkucx_tpu.shuffle.reader import PendingShuffle

    width = shard_rows.shape[2]
    return PendingShuffle(
        lambda p: _build_hier_step(mesh, dcn_axis, ici_axis, p, width),
        NamedSharding(mesh, P((dcn_axis, ici_axis))), plan,
        shard_rows, shard_nvalid, val_shape, val_dtype, on_done=on_done,
        admit=admit, per_shard_segs=True)


def read_shuffle_hierarchical(
    mesh: Mesh,
    dcn_axis: str,
    ici_axis: str,
    plan: ShufflePlan,
    shard_rows: np.ndarray,
    shard_nvalid: np.ndarray,
    val_shape,
    val_dtype,
) -> ShuffleReaderResult:
    """Two-stage exchange with the same overflow-retry contract as the
    flat :func:`sparkucx_tpu.shuffle.reader.read_shuffle`."""
    return submit_shuffle_hierarchical(
        mesh, dcn_axis, ici_axis, plan, shard_rows, shard_nvalid,
        val_shape, val_dtype).result()
