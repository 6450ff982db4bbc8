"""The shuffle data plane — ragged all-to-all over the device mesh.

This is the TPU-native replacement for the reference's entire reduce-side
fetch machinery. Where SparkUCX issues, per (mapper, reducer) pair, a
two-phase chain of one-sided RDMA reads —

  phase 1: ``ucp_get`` of the ``[start, end)`` offset pair from the remote
           index file (ref: reducer/compat/spark_3_0/UcxShuffleClient.java:95-127)
  phase 2: ``ucp_get`` of the data bytes at those offsets
           (ref: OnOffsetsFetchCallback.java:78-91)

— the TPU build batches the *whole* reduce side into one collective: every
device contributes its destination-sorted send buffer plus a [P] size row,
and a single ``ragged_all_to_all`` moves all segments over ICI with no
per-block host round-trips. This preserves the reference's headline property
("the mapper's CPU is never involved in serving a fetch") in its TPU form:
no host code runs per block — the whole exchange is one XLA op on the wire.

Four production implementations (conf key ``spark.shuffle.tpu.a2a.impl``),
ragged-first: ``auto`` resolves to the ragged native collective wherever
the backend carries the op, so real bytes — never padded caps — are the
default wire contract (ROADMAP item 1; Ragged Paged Attention makes the
same case at the kernel level):

``native``  — ``jax.lax.ragged_all_to_all``. True per-peer row counts on
              the wire: each device ships exactly its [P] size row's worth
              of rows, pad_ratio ≈ 1.0 by construction.
``dense``   — pad each peer segment to a static per-peer capacity and use
              ``jax.lax.all_to_all``, then recompact. Portable (XLA:CPU has
              no ragged-all-to-all thunk) — the automatic fallback where
              the native op is missing; its wire cost is P x the padded
              peer capacity regardless of occupancy, which the real-bytes
              accounting (plan.ragged_layout, ExchangeReport.pad_ratio,
              doctor rule ``padding_waste``) makes visible.
``gather``  — ``all_gather`` everything and slice locally. O(P·cap) memory;
              the test oracle, and the DCN-friendly shape for tiny tables.
``pallas``  — the first-party remote-DMA transport
              (ops/pallas/ragged_a2a.py), integrated at the READER level
              (chunk-aligned segment layout); validated here, dispatched by
              shuffle/reader._pallas_step_body.

All share static buffer shapes (SURVEY.md §7 hard part (a)): callers choose
``out_capacity`` (and ``peer_capacity`` for dense) via the conf's
``capacityFactor``; overflow is *reported*, never silently truncated.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sparkucx_tpu.meta.segments import exchange_plan

# The transports ragged_shuffle dispatches itself (dense receive contract).
IMPLS = ("native", "dense", "gather")
# Every production impl, including the reader-integrated pallas transport —
# THE source of truth for what a2a.impl accepts (config.py validates
# through validate_impl below; no second copy to drift).
ALL_IMPLS = IMPLS + ("pallas",)
ALLOWED_IMPLS = ("auto",) + ALL_IMPLS

A2A_IMPL_KEY = "spark.shuffle.tpu.a2a.impl"

# Wire-compression tiers (conf key ``spark.shuffle.tpu.a2a.wire``) — the
# ORTHOGONAL axis to a2a.impl: the impl picks the collective, the wire
# tier picks how many bytes each row costs on it (EQuARX's thesis:
# in-collective quantization buys 2-4x effective bandwidth; PAPERS.md).
#
# ``raw``      — int32 transport lanes verbatim (the PR-6 contract).
# ``int8``     — float32 VALUE lanes ride as stochastic-rounded int8 + one
#                f32 scale per row, packed into int32 lanes inside the
#                compiled step; key/partition/size lanes stay exact int
#                lanes. Lossy (one rounding step per element, unbiased).
# ``lossless`` — byte-plane + deflate re-encoding of host-staged blocks
#                on the wave drain path (shuffle/wire.py); bit-exact, the
#                device collective itself is untouched (Exoshuffle's
#                library-level-policy posture: the tier lives where the
#                payload is already host-bound).
ALLOWED_WIRES = ("raw", "int8", "lossless")

A2A_WIRE_KEY = "spark.shuffle.tpu.a2a.wire"

# Read-sink tiers (conf key ``spark.shuffle.tpu.read.sink``) — where a
# completed exchange LANDS, orthogonal to impl and wire:
#
# ``host``   — the reader drains receive buffers D2H and serves numpy
#              partition views (the historical contract; arrow/varlen IO
#              and the lossless drain codec live here).
# ``device`` — partitions stay sharded jax Arrays; the result hands them
#              (donation-safe, zero D2H) straight to a jitted consumer
#              step (DeviceShuffleReaderResult.consume) — MoE expert
#              dispatch and the SP/EP attention consumers are the
#              flagship shapes. Exoshuffle's thesis applied to the
#              landing zone: the consumer, not the engine, dictates
#              where bytes end up.
# ``auto``   — host unless the consumer declares a device sink per read
#              (read(sink="device")); the default.
ALLOWED_SINKS = ("host", "device", "auto")

READ_SINK_KEY = "spark.shuffle.tpu.read.sink"


# Device-merge / device-kernel implementations (conf key ``spark.
# shuffle.tpu.read.mergeImpl``) — how the ordered/combine fold path
# (receive-side reduce, cross-wave device merge) runs on device
# (ops/pallas/segmented.py; resolution is segmented.resolve_kernel_impl,
# backend-conditional):
#
# ``auto``   — the blocked pallas kernels exactly where they COMPILE
#              natively (a TPU backend), ``jnp`` everywhere else (the
#              default; auto never advertises pallas off-chip, so the
#              jnp landing is not a fallback).
# ``jnp``    — batched keysort / combine_rows over the concatenation
#              (the XLA sort-network formulation — the bit-exact oracle,
#              runs on every backend).
# ``pallas`` — the blocked merge-path merge / tiled segment-reduce
#              kernels (TPU native, CPU interpret for tests); combine
#              additionally needs a 4-byte value dtype
#              (segmented.pallas_reduce_supported) or the fold falls
#              back to jnp with a log line + C_KERNEL_FALLBACK count.
ALLOWED_MERGE_IMPLS = ("auto", "jnp", "pallas")

READ_MERGE_IMPL_KEY = "spark.shuffle.tpu.read.mergeImpl"


# Exchange topologies (conf key ``spark.shuffle.tpu.a2a.topology``) — how
# the collective decomposes over the mesh fabric, orthogonal to a2a.impl
# (which transport each hop rides) and a2a.wire (how many bytes each row
# costs on it):
#
# ``flat`` — ONE collective over every device, the single-slice contract;
#            on a multi-slice mesh most device pairs ride DCN, the regime
#            where the reference's one-big-read model "degrades to
#            point-to-point transfers again" (shuffle/hierarchical.py:6-8).
# ``hier`` — the two-stage ICI-then-DCN decomposition
#            (shuffle/topology.py): stage 1 exchanges within each slice
#            over ICI grouped by destination DEVICE INDEX, stage 2
#            exchanges across slices over DCN grouped by destination
#            SLICE — each row crosses the slow fabric exactly once.
#            Requires a 2-D ``(dcn, ici)`` mesh with >1 slice.
# ``auto`` — slice detection from the mesh (the default): hier exactly
#            when the mesh is 2-D ``(dcn, ici)`` with more than one
#            slice, flat otherwise.
ALLOWED_TOPOLOGIES = ("flat", "hier", "auto")

A2A_TOPOLOGY_KEY = "spark.shuffle.tpu.a2a.topology"


def validate_topology(topology: str,
                      conf_key: str = A2A_TOPOLOGY_KEY) -> str:
    """The one validation seam for the exchange-topology set (the
    validate_impl/validate_wire/validate_sink discipline): config.py,
    and the topology resolver (shuffle/topology.resolve_topology) accept
    exactly ``ALLOWED_TOPOLOGIES``."""
    if topology not in ALLOWED_TOPOLOGIES:
        raise ValueError(
            f"{conf_key}={topology!r}: want one of {ALLOWED_TOPOLOGIES} "
            f"(flat = one collective over every device, hier = the "
            f"two-stage ICI/DCN decomposition on a 2-D (dcn, ici) mesh, "
            f"auto = hier exactly when the mesh has >1 slice)")
    return topology


def validate_merge_impl(impl: str,
                        conf_key: str = READ_MERGE_IMPL_KEY) -> str:
    """The one validation seam for the device-merge impl set (the
    validate_impl/validate_wire/validate_sink discipline): config.py and
    the reader's fold resolve accept exactly ``ALLOWED_MERGE_IMPLS``."""
    if impl not in ALLOWED_MERGE_IMPLS:
        raise ValueError(
            f"{conf_key}={impl!r}: want one of {ALLOWED_MERGE_IMPLS} "
            f"(jnp = XLA sort-network merge, pallas = the blocked "
            f"ops/pallas/segmented.py kernels, auto = pallas where the "
            f"kernels compile natively i.e. on TPU, jnp elsewhere)")
    return impl


def validate_sink(sink: str, conf_key: str = READ_SINK_KEY) -> str:
    """The one validation seam for the read-sink tier set: config.py
    and the manager's per-read resolve accept exactly
    ``ALLOWED_SINKS`` (the validate_impl/validate_wire discipline)."""
    if sink not in ALLOWED_SINKS:
        raise ValueError(
            f"{conf_key}={sink!r}: want one of {ALLOWED_SINKS} "
            f"(host = drain results D2H, device = partitions stay "
            f"sharded jax Arrays handed to a consumer step, auto = "
            f"device when the consumer declares one per read)")
    return sink

# Distinct noise streams one training/read step may draw from the same
# base seed (forward dispatch, forward combine, spare, backward) — the
# seed discipline every int8 wire move shares (wire_noise_seed below).
WIRE_SEED_STREAMS = 4


def validate_wire(wire: str, conf_key: str = A2A_WIRE_KEY) -> str:
    """The one validation seam for the wire-compression tier set:
    config.py accepts exactly ``ALLOWED_WIRES``, and the
    error names the conf key to turn (the validate_impl discipline)."""
    if wire not in ALLOWED_WIRES:
        raise ValueError(
            f"{conf_key}={wire!r}: want one of {ALLOWED_WIRES} "
            f"(raw = exact int32 lanes, int8 = quantized float value "
            f"lanes + per-row scale, lossless = host-side byte-plane "
            f"compression of staged blocks)")
    return wire


def wire_noise_seed(seed, stream: int):
    """Derive noise stream ``stream`` (< WIRE_SEED_STREAMS) from a base
    step seed — THE seed discipline for every int8 wire move sharing one
    step counter: the MoE dispatch/combine pair, the backward pass's
    gradient compression, and any caller threading its own counter all
    space their streams through here, so no two moves in one step ever
    reuse a rounding-noise realization. Works on traced jnp scalars and
    host ints alike (int32 ring arithmetic either way)."""
    import jax.numpy as _jnp
    if isinstance(seed, (int, np.integer)):
        return int((int(seed) * WIRE_SEED_STREAMS + int(stream))
                   & 0x7FFFFFFF)
    return (_jnp.asarray(seed, _jnp.int32) * WIRE_SEED_STREAMS
            + _jnp.int32(stream))


def int8_wire_words(value_words: int) -> int:
    """int32 lanes ``value_words`` float32 value lanes cost on the int8
    wire: the int8 payload packed 4-per-word plus ONE f32 row scale —
    the lane arithmetic shared by wire_pack_rows/wire_unpack_rows, the
    plan accounting (plan.wire_row_words) and the MoE traffic recorder,
    so the format and its accounting cannot drift."""
    return -(-int(value_words) // 4) + 1


def wire_pack_rows(rows: jnp.ndarray, wire_words: int, seed,
                   quant_impl: str = "auto") -> jnp.ndarray:
    """Narrow the trailing ``wire_words`` float32-bit-pattern lanes of an
    int32 row matrix to the int8 wire format, leaving the leading lanes
    (keys) exact: [n, W] -> [n, W - wire_words + int8_wire_words(...)].
    Stochastic rounding draws from ``seed`` (a traced int32 scalar — the
    caller threads a step counter so every exchange sees fresh noise)."""
    from sparkucx_tpu.ops.pallas.quant import quantize_rows
    n, width = rows.shape
    head = width - wire_words
    exact = rows[:, :head]
    vals = jax.lax.bitcast_convert_type(rows[:, head:], jnp.float32)
    q, scale = quantize_rows(vals, seed, impl=quant_impl)
    pad = (-wire_words) % 4
    if pad:
        q = jnp.concatenate([q, jnp.zeros((n, pad), jnp.int8)], axis=1)
    qi = jax.lax.bitcast_convert_type(
        q.reshape(n, -1, 4), jnp.int32).reshape(n, -1)
    si = jax.lax.bitcast_convert_type(scale, jnp.int32).reshape(n, 1)
    return jnp.concatenate([exact, qi, si], axis=1)


def wire_unpack_rows(rows: jnp.ndarray, width: int,
                     wire_words: int) -> jnp.ndarray:
    """Inverse of :func:`wire_pack_rows` (up to the rounding noise):
    expand the int8 wire lanes back to float32 bit patterns in int32
    lanes — [n, W'] -> [n, ``width``]. Zero wire rows (transport padding
    past the delivered total) decode to zero rows."""
    from sparkucx_tpu.ops.pallas.quant import dequantize_rows
    n = rows.shape[0]
    head = width - wire_words
    qw = -(-wire_words // 4)
    q = jax.lax.bitcast_convert_type(
        rows[:, head:head + qw].reshape(n, qw, 1), jnp.int8
    ).reshape(n, qw * 4)[:, :wire_words]
    scale = jax.lax.bitcast_convert_type(
        rows[:, head + qw:head + qw + 1], jnp.float32)
    vals = dequantize_rows(q, scale, jnp.float32)
    return jnp.concatenate(
        [rows[:, :head], jax.lax.bitcast_convert_type(vals, jnp.int32)],
        axis=1)


def validate_impl(impl: str, conf_key: str = A2A_IMPL_KEY) -> str:
    """The one validation seam for the a2a implementation set: config.py
    and select_impl both accept exactly ``ALLOWED_IMPLS``,
    and the error names the conf key to turn."""
    if impl not in ALLOWED_IMPLS:
        raise ValueError(
            f"{conf_key}={impl!r}: want one of {ALLOWED_IMPLS} "
            f"(auto resolves to 'native' on a TPU or GPU backend, "
            f"else 'dense')")
    return impl


def backend_supports_ragged(backend: Optional[str] = None) -> bool:
    """THE capability gate for ``a2a.impl=auto``: the backend has an XLA
    thunk for ragged-all-to-all (TPU/GPU). CPU has none, so auto
    resolves to dense there."""
    backend = backend or jax.default_backend()
    return backend in ("tpu", "gpu")


def select_impl(impl: str, backend: Optional[str] = None) -> str:
    """Resolve 'auto' to the best implementation for the backend:
    ragged-native wherever :func:`backend_supports_ragged` (always on a
    TPU: there is no silent dense fallback on the chip), dense on the
    CPU backend, which has no ragged thunk.

    The reference's analog decision is UCX picking RDMA vs TCP vs shm
    transports under the same API (ref: README.md:2-3)."""
    if impl != "auto":
        return validate_impl(impl)
    return "native" if backend_supports_ragged(backend) else "dense"


def resolved_wire_impl(impl: str, num_shards: int,
                       backend: Optional[str] = None) -> str:
    """The transport an exchange with this (impl, shard count) actually
    rides — including the 1-shard ``local`` move ragged_shuffle takes
    under 'auto' — for reports and real-bytes accounting
    (plan.ragged_layout). Mirrors ragged_shuffle's dispatch exactly so
    the accounting can never claim a transport the data plane didn't
    run."""
    if impl == "pallas":
        return "pallas"
    if impl == "auto" and num_shards == 1:
        return "local"
    return select_impl(impl, backend)


@dataclass
class ShuffleResult:
    """Per-shard outcome of one exchange.

    ``data``       — [out_capacity, ...] received rows, densely packed from 0.
    ``recv_sizes`` — [P] rows received from each peer.
    ``total``      — [1] valid prefix length of ``data``.
    ``overflow``   — [1] bool: capacities were exceeded somewhere; data is
                     garbage and the caller must retry with a bigger plan
                     (never silently truncated).
    """

    data: jnp.ndarray
    recv_sizes: jnp.ndarray
    total: jnp.ndarray
    overflow: jnp.ndarray


def _global_overflow(local_sizes, total, data_rows, out_capacity, axis_name):
    """Mesh-wide overflow consensus: True everywhere if ANY device would
    overrun its input buffer (send side) or output capacity (recv side).

    Must be global: an overflowing exchange is retried by *all* participants
    with a bigger plan, and the native path must not even issue the
    collective with out-of-range offsets (undefined behavior on TPU)."""
    local_bad = (total > out_capacity) | (local_sizes.sum() > data_rows)
    return jax.lax.psum(local_bad.astype(jnp.int32), axis_name) > 0


def _compact_from_segments(recv_sizes, out_capacity):
    """Build [out_capacity] gather indices that concatenate P ragged segments.

    For output slot j: find sender s via searchsorted over the inclusive
    cumsum of recv_sizes, then offset-within-segment. Returns (sender_idx,
    within_idx, valid_mask)."""
    recv_cum = jnp.cumsum(recv_sizes)
    total = recv_cum[-1]
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    sender = jnp.searchsorted(recv_cum, j, side="right").astype(jnp.int32)
    sender_c = jnp.minimum(sender, recv_sizes.shape[0] - 1)
    excl = recv_cum - recv_sizes
    within = j - excl[sender_c]
    valid = j < total
    return sender_c, within, valid


def _a2a_native(data, local_sizes, axis_name, out_capacity):
    in_off, send, out_off, recv, total = exchange_plan(local_sizes, axis_name)
    overflow = _global_overflow(local_sizes, total, data.shape[0],
                                out_capacity, axis_name)
    # Out-of-range offsets are UB for ragged_all_to_all on TPU — on overflow
    # every device sends a zeroed plan (consistent mesh-wide, since the flag
    # is a psum) and the caller retries with a larger capacity.
    z = jnp.where(overflow, 0, 1).astype(jnp.int32)
    out_shape = (out_capacity,) + data.shape[1:]
    output = jnp.zeros(out_shape, dtype=data.dtype)
    result = jax.lax.ragged_all_to_all(
        data, output, in_off * z, send * z, out_off * z, recv * z,
        axis_name=axis_name)
    return ShuffleResult(result, recv, total.reshape(1), overflow.reshape(1))


def _a2a_gather(data, local_sizes, axis_name, out_capacity):
    in_off, send, out_off, recv, total = exchange_plan(local_sizes, axis_name)
    p = jax.lax.axis_index(axis_name)
    all_data = jax.lax.all_gather(data, axis_name)          # [P, cap_in, ...]
    all_in_off = jax.lax.all_gather(in_off, axis_name)      # [P, P]
    sender, within, valid = _compact_from_segments(recv, out_capacity)
    # source row inside sender s's buffer: in_off[s][p] + within
    src = all_in_off[sender, p] + within
    src = jnp.minimum(src, all_data.shape[1] - 1)
    out = all_data[sender, src]
    mask_shape = (out_capacity,) + (1,) * (data.ndim - 1)
    out = jnp.where(valid.reshape(mask_shape), out, jnp.zeros_like(out))
    overflow = _global_overflow(local_sizes, total, data.shape[0],
                                out_capacity, axis_name)
    return ShuffleResult(out, recv, total.reshape(1), overflow.reshape(1))


def _a2a_local(data, local_sizes, axis_name, out_capacity):
    """Single-device mesh axis: the exchange is the identity move.

    The reference's UCX layer picks the shared-memory transport when the
    peer is the same host rather than routing through the NIC loopback
    (ref: README.md:2-3 — transport selection is UCX's whole job); the TPU
    analog is skipping the collective when the axis has one shard. Measured
    on v5e: ``ragged_all_to_all`` on a 1-device axis costs ~23 ms for an
    80 MB payload (per-segment DMA bookkeeping, no overlap win available),
    while this formulation is a slice/pad XLA folds into the surrounding
    program. Output contract matches the collectives exactly: rows packed
    from 0, zero past ``total``, same overflow flag."""
    total = local_sizes.sum().astype(jnp.int32)
    overflow = (total > out_capacity) | (total > data.shape[0])
    cap_in = data.shape[0]
    if out_capacity <= cap_in:
        out = data[:out_capacity]
    else:
        out = jnp.concatenate(
            [data, jnp.zeros((out_capacity - cap_in,) + data.shape[1:],
                             data.dtype)], axis=0)
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    mask_shape = (out_capacity,) + (1,) * (data.ndim - 1)
    out = jnp.where((j < total).reshape(mask_shape), out,
                    jnp.zeros_like(out))
    return ShuffleResult(out, local_sizes, total.reshape(1),
                         overflow.reshape(1))


def _a2a_dense(data, local_sizes, axis_name, out_capacity, peer_capacity):
    in_off, send, out_off, recv, total = exchange_plan(local_sizes, axis_name)
    # Pad my P segments into [P, peer_capacity, ...]
    k = jnp.arange(peer_capacity, dtype=jnp.int32)
    src = in_off[:, None] + k[None, :]                      # [P, peer_cap]
    src_c = jnp.minimum(src, data.shape[0] - 1)
    padded = data[src_c]                                    # [P, peer_cap, ...]
    seg_mask = k[None, :] < send[:, None]
    mask_shape = seg_mask.shape + (1,) * (data.ndim - 1)
    padded = jnp.where(seg_mask.reshape(mask_shape), padded,
                       jnp.zeros_like(padded))
    swapped = jax.lax.all_to_all(
        padded, axis_name, split_axis=0, concat_axis=0, tiled=False)
    # swapped[s] = the segment sender s aimed at me, padded to peer_capacity
    sender, within, valid = _compact_from_segments(recv, out_capacity)
    within_c = jnp.minimum(within, peer_capacity - 1)
    out = swapped[sender, within_c]
    vshape = (out_capacity,) + (1,) * (data.ndim - 1)
    out = jnp.where(valid.reshape(vshape), out, jnp.zeros_like(out))
    local_seg_bad = (send.max() > peer_capacity) | (recv.max() > peer_capacity)
    overflow = _global_overflow(local_sizes, total, data.shape[0],
                                out_capacity, axis_name) \
        | (jax.lax.psum(local_seg_bad.astype(jnp.int32), axis_name) > 0)
    return ShuffleResult(out, recv, total.reshape(1), overflow.reshape(1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def exchange(data: jnp.ndarray, local_sizes: jnp.ndarray, axis_name: str,
             out_capacity: int, impl: str = "auto") -> jnp.ndarray:
    """Differentiable ragged exchange — the MoE-dispatch form of the data
    plane (SURVEY.md §2.6: the shuffle primitive IS expert-parallel ragged
    dispatch; same kernel serves both).

    Forward: move destination-sorted rows, return the packed receive
    buffer. Backward: the cotangent exchange is the SAME collective with
    the transposed plan — each device sends back the gradient segments it
    received, which land exactly in the sender's original segment layout.
    Sizes are integer routing data and get no gradient.

    Overflow policy: there is no host retry loop inside a training step, so
    a capacity overflow NaN-poisons the (float) output instead of returning
    silently zeroed activations — the loss goes NaN loudly and the caller
    fixes the capacity. Integer payloads cannot be poisoned; use
    :func:`ragged_shuffle` directly and check ``overflow`` for those."""
    return _exchange_impl(data, local_sizes, axis_name, out_capacity, impl)


def _exchange_impl(data, local_sizes, axis_name, out_capacity, impl):
    r = ragged_shuffle(data, local_sizes, axis_name,
                       out_capacity=out_capacity, impl=impl)
    if jnp.issubdtype(r.data.dtype, jnp.floating):
        poison = jnp.where(r.overflow[0], jnp.nan, 0.0).astype(r.data.dtype)
        return r.data + poison
    return r.data


def _exchange_fwd(data, local_sizes, axis_name, out_capacity, impl):
    r = ragged_shuffle(data, local_sizes, axis_name,
                       out_capacity=out_capacity, impl=impl)
    out = r.data
    if jnp.issubdtype(out.dtype, jnp.floating):
        poison = jnp.where(r.overflow[0], jnp.nan, 0.0).astype(out.dtype)
        out = out + poison
    return out, (local_sizes, r.recv_sizes, data.shape[0])


def _exchange_bwd(axis_name, out_capacity, impl, res, g):
    local_sizes, recv_sizes, cap_in = res
    rb = ragged_shuffle(g, recv_sizes, axis_name,
                        out_capacity=cap_in, impl=impl)
    return rb.data, jnp.zeros_like(local_sizes)


exchange.defvjp(_exchange_fwd, _exchange_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def exchange_quantized(data: jnp.ndarray, local_sizes: jnp.ndarray,
                       seed: jnp.ndarray, axis_name: str, out_capacity: int,
                       impl: str = "auto") -> jnp.ndarray:
    """Differentiable ragged exchange with int8 wire compression.

    Float rows are stochastically quantized to int8 + one float32 scale per
    row, bit-packed into the int32 transport format, moved with ONE
    collective, and dequantized on arrival — 4x fewer ICI/DCN bytes than
    :func:`exchange` for bf16/f32 activations. The reference's wire-cost
    lever is transport selection (RDMA vs TCP, ref: README.md:2-3); on TPU
    the lever is payload width. Output matches ``data``'s dtype.

    ``seed`` is a TRACED int32 scalar — thread a step counter through it so
    each training step draws fresh rounding noise; a static constant would
    freeze the noise realization and the stochastic rounding would no
    longer average out across steps. The backward pass derives its own
    stream from the same seed.

    Gradients use the straight-through estimator (quantization treated as
    identity) and the cotangent exchange is ALSO int8-quantized — gradient
    compression, the standard trade for distributed training traffic.
    Rounding is unbiased (stochastic), so compressed gradients stay
    unbiased in expectation."""
    out, _ = _exchange_quantized_fwd(data, local_sizes, seed, axis_name,
                                     out_capacity, impl)
    return out


def _quantized_move(data, local_sizes, axis_name, out_capacity, impl, seed):
    # the SAME int8 wire-lane format the production a2a.wire=int8 read
    # path ships (wire_pack_rows/wire_unpack_rows): all-value rows here,
    # key-prefixed rows there — one layout, one accounting formula
    in_dtype = data.dtype
    n, w = data.shape
    rows = jax.lax.bitcast_convert_type(
        data.astype(jnp.float32), jnp.int32)
    packed = wire_pack_rows(rows, w, seed)
    r = ragged_shuffle(packed, local_sizes, axis_name,
                       out_capacity=out_capacity, impl=impl)
    out = jax.lax.bitcast_convert_type(
        wire_unpack_rows(r.data, w, w), jnp.float32)
    poison = jnp.where(r.overflow[0], jnp.nan, 0.0)
    return (out + poison).astype(in_dtype), r.recv_sizes


def _exchange_quantized_fwd(data, local_sizes, seed, axis_name,
                            out_capacity, impl):
    seed = jnp.asarray(seed, jnp.int32)
    out, recv_sizes = _quantized_move(data, local_sizes, axis_name,
                                      out_capacity, impl, seed)
    return out, (local_sizes, recv_sizes, seed, data.shape[0])


def _exchange_quantized_bwd(axis_name, out_capacity, impl, res, g):
    local_sizes, recv_sizes, seed, cap_in = res
    # independent noise stream for the gradient compression (the shared
    # seed discipline: stream 3 = backward); the output dtype matches the
    # primal input (the forward casts back), so the cotangent g already
    # carries the right dtype through _quantized_move
    gb, _ = _quantized_move(g, recv_sizes, axis_name, cap_in, impl,
                            wire_noise_seed(seed, 3))
    return gb, jnp.zeros_like(local_sizes), jnp.zeros_like(seed)


exchange_quantized.defvjp(_exchange_quantized_fwd, _exchange_quantized_bwd)


def ragged_shuffle(data: jnp.ndarray, local_sizes: jnp.ndarray, axis_name: str,
                   *, out_capacity: int, peer_capacity: Optional[int] = None,
                   impl: str = "auto") -> ShuffleResult:
    """One all-to-all exchange of destination-sorted rows. Call inside
    ``shard_map`` over the mesh axis ``axis_name``.

    ``data``        — [cap_in, ...] this shard's send buffer, rows grouped by
                      destination device in ascending order (the map-side
                      sort-shuffle invariant the reference inherits from
                      SortShuffleManager, ref: CommonUcxShuffleManager.scala:22).
    ``local_sizes`` — [P] rows destined to each peer; rows beyond
                      ``local_sizes.sum()`` are padding and never sent.
    """
    if data.ndim < 1:
        raise ValueError("data must have a leading row axis")
    if impl == "pallas":
        raise ValueError(
            "impl='pallas' (the first-party remote-DMA transport) is "
            "integrated at the reader level — its chunk-aligned segment "
            "layout cannot ride ragged_shuffle's dense contract; use "
            "TpuShuffleManager.read with spark.shuffle.tpu.a2a.impl="
            "pallas (plain flat reads)")
    if impl == "auto" and local_sizes.shape[0] == 1:
        # one shard on this axis — no peer exists; 'auto' means "best
        # transport", so take the local move (see _a2a_local). An EXPLICIT
        # impl is honored verbatim: the TPU-test lowering proofs
        # pass impl='native' precisely to exercise the real collective.
        return _a2a_local(data, local_sizes, axis_name, out_capacity)
    impl = select_impl(impl)
    if impl == "native":
        return _a2a_native(data, local_sizes, axis_name, out_capacity)
    if impl == "gather":
        return _a2a_gather(data, local_sizes, axis_name, out_capacity)
    if peer_capacity is None:
        peer_capacity = out_capacity
    return _a2a_dense(data, local_sizes, axis_name, out_capacity, peer_capacity)
