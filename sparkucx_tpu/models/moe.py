"""Flagship model: expert-parallel MoE riding the shuffle data plane.

SURVEY.md §2.6: the reference's shuffle primitive *is* an MoE-style ragged
dispatch — R reducers pulling ragged segments from M mappers is exactly E
experts pulling ragged token segments from P token shards. This module
demonstrates (and stress-tests) that claim: the expert dispatch AND combine
are the framework's own :func:`sparkucx_tpu.shuffle.alltoall.exchange`
collective, differentiable end-to-end, so a training step drives the whole
data plane — hash-free routing (router logits instead of key hashes) but
the identical segment-table/exchange machinery.

Parallelism: mesh axes ``(dp, ep)`` — tokens sharded over both, experts
sharded over ``ep`` and replicated over ``dp``; dispatch crosses only the
``ep`` axis (each data-parallel row dispatches within itself), so gradient
psum over ``dp`` is handled by shard_map's replicated-input transpose.

Token overflow per expert follows standard MoE capacity semantics: tokens
beyond an expert's capacity are dropped (contribute zero). Exchange-level
capacity overflow NaN-poisons activations (see alltoall.exchange): a
collapsed router that overflows recv_capacity turns the loss NaN loudly
instead of silently zeroing the batch; raise ``capacity_factor`` to fix.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from sparkucx_tpu.ops.partition import counts_from_sorted
from sparkucx_tpu.shuffle.alltoall import (
    exchange, exchange_quantized, int8_wire_words, ragged_shuffle,
    wire_noise_seed)


@dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_hidden: int = 128
    num_experts: int = 8
    tokens_per_shard: int = 64     # static per-(dp,ep)-shard token count
    capacity_factor: float = 2.0   # exchange + expert capacity headroom
    impl: str = "auto"             # data-plane implementation
    # Wire tier of the dispatch/combine collectives — the MODEL-side
    # face of the production a2a.wire contract: "raw" moves exact f32
    # rows, "int8" rides the same stochastic-int8+per-row-scale lane
    # format the a2a.wire=int8 read path ships (4x fewer ICI bytes, STE
    # gradients). "f32" is accepted as a legacy alias of "raw".
    wire: str = "raw"

    @property
    def recv_capacity(self) -> int:
        return max(8, int(self.tokens_per_shard * self.capacity_factor))

    @property
    def wire_int8(self) -> bool:
        if self.wire in ("raw", "f32"):
            return False
        if self.wire == "int8":
            return True
        raise ValueError(
            f"MoEConfig.wire={self.wire!r}: want raw|int8 (the a2a.wire "
            f"tiers the exchange carries; 'f32' = legacy raw alias — "
            f"lossless is a host-staging tier, meaningless inside a "
            f"compiled training step)")


def init_params(rng: jax.Array, cfg: MoEConfig) -> Dict[str, jnp.ndarray]:
    """Global (unsharded) parameter pytree."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = cfg.d_model ** -0.5
    return {
        "router": jax.random.normal(k1, (cfg.d_model, cfg.num_experts)) * s,
        "w1": jax.random.normal(
            k2, (cfg.num_experts, cfg.d_model, cfg.d_hidden)) * s,
        "w2": jax.random.normal(
            k3, (cfg.num_experts, cfg.d_hidden, cfg.d_model))
        * cfg.d_hidden ** -0.5,
        "wout": jax.random.normal(k4, (cfg.d_model, cfg.d_model)) * s,
    }


def param_specs(cfg: MoEConfig, dp: str = "dp", ep: str = "ep"):
    """shard_map in_specs for the param pytree: experts sharded over ep,
    everything else replicated."""
    return {
        "router": P(),
        "w1": P(ep),
        "w2": P(ep),
        "wout": P(),
    }


def _moe_shard(params, x, seed, *, cfg: MoEConfig, ep_axis: str,
               ep_size: int):
    """Per-shard forward: route -> dispatch (exchange) -> expert FFN ->
    combine (exchange back) -> unsort. x: [T, D] local tokens; ``seed`` —
    [1] int32 step counter feeding the wire-quantization noise stream."""
    T = cfg.tokens_per_shard
    E = cfg.num_experts
    e_local = E // ep_size
    cap_out = cfg.recv_capacity

    # -- route (top-1) ----------------------------------------------------
    logits = x @ params["router"]                       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(logits, axis=-1)                # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    # -- dispatch over ep: destination shard owns expert block -----------
    dest = (expert // e_local).astype(jnp.int32)        # [T]
    order = jnp.argsort(dest, stable=True)
    inv_order = jnp.argsort(order)                      # unsort permutation
    x_sorted = jnp.take(x, order, axis=0)
    # counts off the sorted keys, not bincount: XLA:TPU serializes the
    # colliding scatter-add (ops/partition.counts_from_sorted rationale)
    counts = counts_from_sorted(jnp.take(dest, order),
                                ep_size).astype(jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape(())
    if cfg.wire_int8:
        # stream 0 of the shared seed discipline (alltoall.wire_noise_seed)
        # — the combine below takes stream 1, and each exchange's backward
        # pass derives stream 3 of ITS seed, so no two moves in one step
        # ever reuse a rounding-noise realization
        recv = exchange_quantized(x_sorted, counts,
                                  wire_noise_seed(seed, 0), ep_axis,
                                  cap_out, cfg.impl)
    else:
        recv = exchange(x_sorted, counts, ep_axis, cap_out, cfg.impl)

    # -- local expert assignment of received tokens ----------------------
    shard_id = jax.lax.axis_index(ep_axis)
    if cfg.wire_int8:
        # lossy wire: the expert id must travel WITH the token as lossless
        # integer rows (its own small exchange) — recomputing argmax on
        # dequantized rows would disagree with the sender whenever the
        # quantization noise perturbs near-tied logits, silently zeroing
        # tokens. Its recv_sizes doubles as the reverse-exchange size row.
        expert_sorted = jnp.take(expert.astype(jnp.int32), order)
        rid = ragged_shuffle(expert_sorted[:, None], counts, ep_axis,
                             out_capacity=cap_out, impl=cfg.impl)
        rexpert = rid.data[:, 0]
        recv_sizes = rid.recv_sizes
    else:
        # exact wire: recomputing routing on received rows is provably
        # identical (router replicated, rows bit-exact) — no extra
        # collective needed, just the tiny count all_gather
        rexpert = jnp.argmax(recv @ params["router"], axis=-1)
        recv_sizes = jax.lax.all_gather(counts, ep_axis)[:, shard_id]
    le = rexpert - shard_id * e_local                   # local expert id
    my_recv = recv_sizes.sum()
    j = jnp.arange(cap_out, dtype=jnp.int32)
    rvalid = j < my_recv

    # -- group by local expert, capacity-bounded scatter ------------------
    cap_e = max(8, int(cap_out * cfg.capacity_factor / max(e_local, 1)))
    le_key = jnp.where(rvalid, le.astype(jnp.int32), jnp.int32(e_local))
    eorder = jnp.argsort(le_key, stable=True)
    le_sorted = jnp.take(le_key, eorder)
    rows_sorted = jnp.take(recv, eorder, axis=0)
    ecounts = counts_from_sorted(le_sorted, e_local)
    excl = jnp.concatenate(
        [jnp.zeros((1,), ecounts.dtype), jnp.cumsum(ecounts)[:-1]])
    le_c = jnp.minimum(le_sorted, e_local - 1)
    within = jnp.arange(cap_out, dtype=jnp.int32) - excl[le_c].astype(jnp.int32)
    fits = (within < cap_e) & (le_sorted < e_local)
    within_c = jnp.clip(within, 0, cap_e - 1)
    # Pack expert buffers by GATHER off the expert-sorted rows (slot
    # [e, c] pulls row excl[e] + c), not scatter: the clipped overflow
    # rows would collide, and colliding scatters serialize on TPU.
    slot = excl[:, None].astype(jnp.int32) \
        + jnp.arange(cap_e, dtype=jnp.int32)[None, :]     # [e_local, cap_e]
    slot_valid = jnp.arange(cap_e, dtype=jnp.int32)[None, :] \
        < jnp.minimum(ecounts, cap_e)[:, None]
    ebuf = jnp.where(
        slot_valid[:, :, None],
        jnp.take(rows_sorted, jnp.clip(slot, 0, cap_out - 1), axis=0),
        jnp.zeros((), x.dtype))

    # -- expert FFN on the MXU: batched per-expert matmuls ----------------
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", ebuf, params["w1"]))
    y = jnp.einsum("ech,ehd->ecd", h, params["w2"])     # [e_local,cap_e,D]

    # -- un-scatter to received order, combine back -----------------------
    out_sorted = jnp.where(fits[:, None], y[le_c, within_c], 0.0)
    # unsort by inverse-permutation GATHER (eorder is a permutation; a
    # row scatter would serialize on TPU)
    out_recv = jnp.take(out_sorted, jnp.argsort(eorder), axis=0)
    # reverse exchange: send back what we received (sizes = what each peer
    # sent us); result arrives in our original destination-sorted layout
    if cfg.wire_int8:
        back = exchange_quantized(out_recv, recv_sizes.astype(jnp.int32),
                                  wire_noise_seed(seed, 1), ep_axis, T,
                                  cfg.impl)
    else:
        back = exchange(out_recv, recv_sizes.astype(jnp.int32), ep_axis,
                        T, cfg.impl)                    # [T, D]
    combined = jnp.take(back, inv_order, axis=0)        # original order
    out = combined * gate[:, None]
    return out @ params["wout"]


def exchange_traffic(cfg: MoEConfig, tokens: int) -> Tuple[int, int]:
    """(payload_bytes, wire_bytes) ONE forward's dispatch+combine
    collectives move for ``tokens`` global tokens — the same
    payload-vs-achieved-wire split the production ExchangeReport
    carries, from the same lane arithmetic
    (``alltoall.int8_wire_words``): every token row crosses the ep axis
    twice (dispatch + combine) at d_model f32 lanes, and the int8 tier
    additionally ships the exact expert-id rows (one int32 lane)."""
    payload = 2 * tokens * cfg.d_model * 4
    if not cfg.wire_int8:
        return payload, payload
    # the int8 tier runs a THIRD collective — the exact expert-id rows
    # (one int32 lane each): a real exchange whose payload equals its
    # wire cost, counted on BOTH sides so the cumulative wire/payload
    # quotient stays internally consistent
    ids = tokens * 4
    wire = 2 * tokens * int8_wire_words(cfg.d_model) * 4 + ids
    return payload + ids, wire


def _record_exchange_traffic(cfg: MoEConfig, x,
                             backward: bool = False) -> None:
    """Host-side telemetry hook: MoE dispatch traffic lands in the SAME
    cumulative counters the production read path feeds
    (``shuffle.payload.bytes`` / ``shuffle.wire.bytes`` — summed across
    processes by doctor.build_view), plus its own ``moe.exchange.*``
    attribution, so expert-parallel traffic shows up in stats/doctor
    like every other exchange instead of bypassing the plane. No-op at
    trace time (a jitted caller accounts through its own host wrapper —
    make_train_step) and never raises into the model."""
    if isinstance(x, jax.core.Tracer):
        return
    try:
        from sparkucx_tpu.runtime.node import TpuNode
        from sparkucx_tpu.utils.metrics import GLOBAL_METRICS
        node = TpuNode._instance
        metrics = node.metrics if node is not None \
            and not getattr(node, "_closed", True) else GLOBAL_METRICS
        tokens = int(x.shape[0])
        payload, wire = exchange_traffic(cfg, tokens)
        if backward and cfg.wire_int8:
            # the exact expert-id exchange is integer routing metadata —
            # it has no backward counterpart, only the two quantized
            # value moves differentiate
            payload -= tokens * 4
            wire -= tokens * 4
        metrics.inc("shuffle.payload.bytes", float(payload))
        metrics.inc("shuffle.wire.bytes", float(wire))
        metrics.inc("moe.exchange.count", 2.0)
        metrics.inc("moe.exchange.rows", float(2 * tokens))
    except Exception:
        pass


def forward(params, x, mesh: Mesh, cfg: MoEConfig,
            dp_axis: str = "dp", ep_axis: str = "ep", seed=0):
    """Full-model forward under shard_map. x: [B, D] global tokens,
    B = dp*ep*tokens_per_shard. ``seed``: step counter for the wire-
    quantization noise stream (ignored for the raw wire)."""
    _record_exchange_traffic(cfg, x)
    return _forward_fn(cfg, mesh, dp_axis, ep_axis)(
        params, x, jnp.asarray(seed, jnp.int32).reshape(1))


@functools.lru_cache(maxsize=64)
def _forward_fn(cfg: MoEConfig, mesh: Mesh, dp_axis: str, ep_axis: str):
    """ONE jitted shard_map callable per (cfg, mesh, axes) — rebuilding
    the closure per forward() call hands pjit a fresh function identity
    every time, so nothing ever hits the executable cache and every
    eager forward re-traces (tens of seconds on CPU SPMD)."""
    ep_size = dict(zip(mesh.axis_names, mesh.devices.shape))[ep_axis]
    fn = functools.partial(_moe_shard, cfg=cfg, ep_axis=ep_axis,
                           ep_size=ep_size)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs(cfg, dp_axis, ep_axis), P((dp_axis, ep_axis)),
                  P()),
        out_specs=P((dp_axis, ep_axis))))


def loss_fn(params, x, y, mesh, cfg, dp_axis="dp", ep_axis="ep", seed=0):
    pred = forward(params, x, mesh, cfg, dp_axis, ep_axis, seed)
    return jnp.mean((pred - y) ** 2)


def make_train_step(mesh: Mesh, cfg: MoEConfig, lr: float = 1e-3,
                    dp_axis: str = "dp", ep_axis: str = "ep"):
    """Jitted full training step (fwd + bwd through both exchanges + SGD).

    The gradient of the dispatch/combine collectives flows through the
    custom VJP in shuffle/alltoall.py — the transposed exchange."""

    import optax
    opt = optax.adam(lr)

    def init(rng):
        params = init_params(rng, cfg)
        return params, opt.init(params)

    # donate params + optimizer state: the updated pytrees reuse the same
    # HBM instead of holding two copies live across the update
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _jit_step(params, opt_state, x, y, step_idx=None):
        # the wire-quantization noise stream must advance every step; by
        # default ride the optimizer's own step counter so plain
        # step(params, opt_state, x, y) callers get fresh noise for free
        if step_idx is None:
            # a NamedTuple state with a `count` FIELD (e.g. ScaleByAdamState)
            # — plain tuples also have a .count (the method), so test fields
            def has_count(s):
                return "count" in getattr(s, "_fields", ())
            counts = [s.count for s in jax.tree_util.tree_leaves(
                opt_state, is_leaf=has_count) if has_count(s)]
            step_idx = counts[0] if counts else 0
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x, y, mesh, cfg, dp_axis, ep_axis, step_idx)
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def step(params, opt_state, x, y, step_idx=None):
        # host wrapper: the telemetry hook is a no-op under tracing, so
        # a jitted step would never account — record per INVOCATION
        # here (fwd + the transposed bwd exchange = 2x the forward's
        # traffic, the gradient-compression cost on the same tier)
        out = _jit_step(params, opt_state, x, y, step_idx)
        _record_exchange_traffic(cfg, x)
        _record_exchange_traffic(cfg, x, backward=True)
        return out

    return init, step


# ---------------------------------------------------------------------------
# Read-path expert dispatch (read.sink=device) — the flagship device-sink
# workload: token shuffle by expert id THROUGH manager.read(), consumed
# entirely in HBM. Where the in-step exchange() path above embeds the
# collective inside one compiled program, this path drives the whole
# PRODUCTION read plane (staging, plans, waves, wire tiers, reports) and
# hands the receive buffers — donated, zero D2H — to a jitted train step.
# ---------------------------------------------------------------------------

def stage_tokens_by_expert(mgr, handle, tokens: np.ndarray,
                           expert_ids: np.ndarray) -> None:
    """Stage one shuffle's map outputs for expert dispatch: keys are the
    expert ids (``partitioner="direct"`` routes key == reduce partition
    == expert), values the f32 token vectors. Tokens split contiguously
    over the handle's map count — the map-task placement of a host
    engine feeding the engine one block per task."""
    n = tokens.shape[0]
    per = -(-n // handle.num_maps)
    for mid in range(handle.num_maps):
        lo, hi = mid * per, min(n, (mid + 1) * per)
        w = mgr.get_writer(handle, mid)
        w.write(np.asarray(expert_ids[lo:hi], dtype=np.int64),
                np.ascontiguousarray(tokens[lo:hi], dtype=np.float32))
        w.commit(handle.num_partitions)


def make_device_dispatch_step(mesh: Mesh, cfg: MoEConfig, cap: int,
                              axis: str = "shuffle", lr: float = 1e-2):
    """The device-sink consumer: ONE jitted train step (forward + backward
    + SGD) over the exchange's packed receive rows, donated straight from
    :class:`~sparkucx_tpu.shuffle.reader.DeviceShuffleReaderResult`.

    Per shard the step decodes the transport format on device — expert id
    from the key_lo lane (the 'direct' partitioner's contract), token
    vectors by bit-cast from the value lanes — groups tokens by local
    expert (partition-major delivery means every valid row's expert lives
    on this shard), runs the expert FFN, and trains against a
    reconstruction loss so gradients flow through w1/w2. ``cap`` is the
    per-shard receive capacity of the plan the read dispatched
    (``ExchangeReport.plan_bucket[1]`` / result cap) — one compiled
    consumer per (cap, cfg) family, reused across every wave and every
    same-shape exchange.

    Returns ``(init, step)``: ``params = init(rng)`` (expert weights
    sharded over ``axis``), ``params, loss = step(params, rows, nvalid)``
    — ``rows`` and ``params`` are DONATED (the HBM handoff the device
    sink exists for). Requires ``num_experts %% axis size == 0``."""
    from jax.sharding import PartitionSpec
    ep_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    E = cfg.num_experts
    if E % ep_size != 0:
        raise ValueError(
            f"num_experts={E} must divide over the {axis} axis "
            f"({ep_size} shards) for the read-path dispatch")
    e_local = E // ep_size
    D, Hd = cfg.d_model, cfg.d_hidden

    def init(rng: jax.Array):
        from jax.sharding import NamedSharding
        k1, k2 = jax.random.split(rng)
        s = D ** -0.5
        # land expert weights ALREADY mesh-sharded: the step donates its
        # params, so its outputs carry the expert sharding — unsharded
        # inputs on call 1 would mint a second compiled variant the
        # moment call 2 feeds the sharded outputs back
        sh = NamedSharding(mesh, PartitionSpec(axis))
        return {
            "w1": jax.device_put(
                jax.random.normal(k1, (E, D, Hd)) * s, sh),
            "w2": jax.device_put(
                jax.random.normal(k2, (E, Hd, D)) * Hd ** -0.5, sh),
        }

    def shard_loss(w1, w2, rows, nvalid):
        # rows [cap, width] int32; nvalid [1] — the per-shard delivered
        # count (DeviceShuffleReaderResult.device_totals)
        shard = jax.lax.axis_index(axis)
        j = jnp.arange(cap, dtype=jnp.int32)
        valid = j < nvalid[0]
        eid = rows[:, 0]                      # key_lo = expert id (direct)
        x = jax.lax.bitcast_convert_type(
            rows[:, 2:2 + D], jnp.float32)    # [cap, D] decoded tokens
        le = eid - shard * e_local            # local expert of each row
        # group by local expert via gather off the expert-sorted rows
        # (the _moe_shard discipline: colliding scatters serialize on
        # TPU); invalid rows sort past every real expert
        le_key = jnp.where(valid, le, jnp.int32(e_local))
        order = jnp.argsort(le_key, stable=True)
        le_sorted = jnp.take(le_key, order)
        x_sorted = jnp.take(x, order, axis=0)
        counts = counts_from_sorted(le_sorted, e_local)
        excl = jnp.concatenate(
            [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
        slot = excl[:, None].astype(jnp.int32) \
            + jnp.arange(cap, dtype=jnp.int32)[None, :]   # [e_local, cap]
        slot_valid = jnp.arange(cap, dtype=jnp.int32)[None, :] \
            < counts[:, None]
        ebuf = jnp.where(
            slot_valid[:, :, None],
            jnp.take(x_sorted, jnp.clip(slot, 0, cap - 1), axis=0),
            0.0)                                          # [e_local,cap,D]
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", ebuf, w1))
        y = jnp.einsum("ech,ehd->ecd", h, w2)
        # reconstruction objective: masked MSE against the decoded
        # tokens — enough signal to drive a real backward pass through
        # both expert matmuls
        err = jnp.where(slot_valid[:, :, None], y - ebuf, 0.0)
        sq = jnp.sum(err * err)
        cnt = jnp.sum(slot_valid) * D
        sq = jax.lax.psum(sq, axis)
        cnt = jax.lax.psum(cnt, axis)
        return sq / jnp.maximum(cnt, 1)

    sm = jax.shard_map(
        shard_loss, mesh=mesh,
        in_specs=(PartitionSpec(axis), PartitionSpec(axis),
                  PartitionSpec(axis), PartitionSpec(axis)),
        out_specs=PartitionSpec(), check_vma=False)

    def loss_fn(params, rows, nvalid):
        return sm(params["w1"], params["w2"], rows, nvalid)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, rows, nvalid):
        loss, grads = jax.value_and_grad(loss_fn)(params, rows, nvalid)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                        params, grads)
        return params, loss

    return init, step


def host_staged_consume(result, step, params, mesh: Mesh, cap: int,
                        width: int, axis: str = "shuffle"):
    """The legacy round-trip the device sink deletes, as one callable —
    the host arm of the device-sink tests and the doctor's
    ``host_roundtrip`` evidence source: drain every partition of a
    HOST-sink result to numpy (D2H — counted by the reader into
    ``shuffle.read.d2h.bytes``), re-pack the rows, re-upload them to the
    mesh (H2D — counted here into ``shuffle.consume.h2d.bytes``), and
    run the SAME jitted consumer step. Returns ``(params, loss)``."""
    from jax.sharding import NamedSharding, PartitionSpec
    from sparkucx_tpu.ops.partition import blocked_partition_map
    from sparkucx_tpu.shuffle.reader import pack_rows
    from sparkucx_tpu.utils.metrics import C_H2D, GLOBAL_METRICS

    Pn = mesh.devices.size
    R = result.num_partitions
    p2d = np.asarray(blocked_partition_map(R, Pn))
    rows = np.zeros((Pn, cap, width), dtype=np.int32)
    fill = np.zeros(Pn, dtype=np.int32)
    for r in range(R):
        if not result.is_local(r):
            continue
        k, v = result.partition(r)
        n = k.shape[0]
        if not n:
            continue
        s = int(p2d[r])
        off = int(fill[s])
        pack_rows(k, v, width, out=rows[s, off:off + n])
        fill[s] += n
    sharding = NamedSharding(mesh, PartitionSpec(axis))
    rows_dev = jax.device_put(rows.reshape(Pn * cap, width), sharding)
    nv_dev = jax.device_put(fill, sharding)
    jax.block_until_ready(rows_dev)
    GLOBAL_METRICS.inc(C_H2D, float(rows.nbytes + fill.nbytes))
    return step(params, rows_dev, nv_dev)
