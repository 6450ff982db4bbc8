"""Device-native segmented merge & segment-reduce — the on-device half
of the ``ordered`` and ``combine`` read modes (ROADMAP items 2/3).

The host used to be the merge engine: per-wave key-sorted runs came back
D2H and ``reader.merge_sorted_rows`` / ``reader.combine_packed_rows``
restored the cross-wave contract in numpy.  This module keeps that merge
in the compiled step, in the Ragged Paged Attention posture (PAPERS.md):
ragged-native device kernels beat host fallbacks at any realistic shape,
so the fold over wave buffers happens where the buffers already live.

Two primitives, each with a jnp/XLA path (the bit-exact oracle on every
backend) and a BLOCKED pallas kernel in the ``ops/pallas`` lineage
(``ragged_a2a.py`` discipline: gate predicates tests consult,
interpret resolution from the backend at trace time):

* :func:`merge_rows` — merge TWO partition-major key-sorted row buffers
  into one, sentinel-padded rows last.  jnp path: one batched
  ``keysort_rows`` over the concatenation (a sort network subsumes the
  merge).  Pallas path: a blocked MERGE-PATH kernel — grid over output
  tiles of ``_TILE`` rows, each tile binary-searching its merge-path
  diagonal into the two sorted runs (GPU merge-path transplanted to the
  TPU grid), then ranking the two ``_TILE``-row windows against each
  other with broadcast compares and materializing the tile by exact
  one-hot selection (split-16 f32 matmuls — see :func:`_exact_gather`).
  O(T log n) scalar work per tile instead of the seed kernel's O(n)
  scalar loop over the whole output; the sequential two-pointer seed
  this replaces lives on only as the docstring above and the jnp oracle.

* :func:`segment_reduce_rows` — reduce runs of equal (partition, key)
  in an ALREADY-SORTED buffer to one row each: the leading
  ``sum_words`` transport words accumulate (float32 accumulation for
  float schemas, int32 ring arithmetic for ints — the
  ``ops/aggregate.combine_rows`` numerics), the remaining value words
  are CARRIED per key (any representative is THE value).  jnp path:
  ``combine_rows``.  Pallas path: a TILED run-scan — grid over input
  tiles, per-tile segment boundaries -> local segment ids (triangular
  matmul cumsum) -> per-segment partial sums by one-hot matmul, with
  the OPEN segment (a run crossing the tile edge) carried across grid
  steps in scratch (TPU grid iterations are sequential, the documented
  accumulation idiom).  int32 sums ride the split-16 decomposition so
  the ring arithmetic stays exact mod 2^32; f32 sums are f32-matmul
  partials + a f32 carry add (same dtype ladder as the oracle; the
  accumulation ORDER differs, so float parity is tolerance-bounded —
  the documented combine_packed_rows trade).

* :func:`segment_reduce_wire_rows` — the int8-dequant-FUSED variant
  (EQuARX posture): input rows still in the ``a2a.wire=int8`` wire
  format (exact key head + packed int8 value lanes + f32 row scale),
  dequantized IN the reduce kernel's tile load, so a device-sink
  combine read lands combined without a separate dequant program.  The
  kernel tiles over the NARROWED wire row width
  (``plan.wire_row_words``), not the logical width — the lane
  arithmetic pinned by tests/test_segmented.py.

Transport rows are the reader's fused int32 format: cols 0,1 = int64
key as [lo, hi]; key order is signed int64 = lexicographic (hi signed,
lo unsigned via the ``_FLIP`` trick — see ops/aggregate's module
docstring).  Partition ids arrive as an explicit per-row lane with the
SENTINEL ``num_parts`` marking invalid rows, because validity is not a
prefix once two buffers concatenate.  Rows past the valid count in
kernel OUTPUT are zeroed with sentinel partition (the jnp epilogues
mask them), so the two impls agree byte-for-byte on the whole buffer.

VMEM posture: the reduce is gridded in and out (rows travel transposed
through HBM, see its kernel), so it runs at any capacity, and it works
only on the tiles up to the last real row: a receive buffer sized for
skew holds mostly sentinel tiles, which cost a grid step each and no
DMA or compute. The merge keeps both sorted runs VMEM-resident, laid
out 128 lanes wide, which caps it at :func:`merge_rows_cap` rows per
fold (about 65k for 100 B rows); the device merge fold takes the jnp
path above the cap.

Impl resolution (``spark.shuffle.tpu.read.mergeImpl``) lives here too:
:func:`resolve_kernel_impl` is THE seam deciding jnp vs pallas per
backend — ``auto`` picks the blocked kernels exactly where they compile
natively (TPU), explicit ``pallas`` additionally runs interpret on CPU;
every caller (reader fold, manager plan decoration, microbench) resolves
through it so the report/doctor evidence names what actually ran.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparkucx_tpu.ops.partition import counts_from_sorted

_FLIP = np.int32(-0x80000000)   # two's-complement 0x8000_0000

# Rows per grid tile, both kernels: one MXU/VPU-shaped block (the
# one-hot selection matmuls are [_TILE, _TILE] x [_TILE, W]).  Also the
# sentinel-pad depth the merge wrapper appends so every window load
# `pl.ds(ia0, _TILE)` stays in bounds.
_TILE = 128

# Scoped-VMEM limit the merge asks Mosaic for (v5e has 128 MiB of VMEM;
# the default scoped limit is 16 MiB) and the share of it its resident
# runs may take — :func:`merge_rows_cap` turns that into rows.
_MERGE_VMEM_LIMIT = 96 << 20
_MERGE_VMEM_RUNS = 64 << 20


def blocked_compile_supported(backend: Optional[str] = None) -> bool:
    """Whether the blocked kernels COMPILE natively on ``backend``
    (default: the current jax backend) — the capability half of
    ``auto`` resolution: auto only volunteers pallas where Mosaic
    lowers it for real; interpret execution elsewhere stays an
    explicit opt-in (impl='pallas')."""
    b = backend if backend is not None else jax.default_backend()
    return b == "tpu"


def kernel_gate_reason(backend: Optional[str] = None) -> Optional[str]:
    """THE shared capability gate: None when the blocked pallas kernels
    can execute here (natively on TPU, interpret on CPU), else ONE
    uniform human-readable reason string.  Tests, the microbench
    harness and impl resolution all consult this single helper so
    every skip/fallback names the same evidence."""
    b = backend if backend is not None else jax.default_backend()
    if b in ("tpu", "cpu"):
        return None
    return (f"pallas blocked kernels need a TPU backend (native) or a "
            f"CPU backend (interpret); backend={b!r}")


def resolve_kernel_impl(requested: str,
                        backend: Optional[str] = None, *,
                        combine_dtype=None
                        ) -> Tuple[str, Optional[str]]:
    """Resolve ``spark.shuffle.tpu.read.mergeImpl`` to the impl that
    will actually run -> ``(impl, fallback_reason)``.

    * ``jnp``    — always honored, never a fallback.
    * ``auto``   — ``pallas`` exactly where the blocked kernels compile
      natively (:func:`blocked_compile_supported`), ``jnp`` elsewhere
      (NOT a fallback: auto never advertised pallas off-chip).
    * ``pallas`` — honored wherever :func:`kernel_gate_reason` clears
      (TPU native, CPU interpret); otherwise resolves ``jnp`` with a
      reason.

    Either pallas-advertising path additionally requires a 4-byte
    combine value dtype (:func:`pallas_reduce_supported`) when a
    combine rides the read; a subword schema resolves ``jnp`` with
    reason ``'subword_dtype'``.  ``fallback_reason`` is non-None only
    when pallas was advertised/asked and SILENTLY degraded — exactly
    the event the ``kernel_fallback`` doctor rule counts.  Pure
    function: counters/logging belong to the callers (reader fold,
    manager plan decoration)."""
    if requested == "jnp":
        return "jnp", None
    if requested not in ("auto", "pallas"):
        raise ValueError(
            f"unknown kernel impl {requested!r}; want auto|jnp|pallas")

    def _dtype_gated() -> bool:
        return (combine_dtype is not None
                and not pallas_reduce_supported(np.dtype(combine_dtype)))

    if requested == "auto":
        if not blocked_compile_supported(backend):
            return "jnp", None
        if _dtype_gated():
            return "jnp", "subword_dtype"
        return "pallas", None
    # requested == "pallas"
    if kernel_gate_reason(backend) is not None:
        return "jnp", "backend_unsupported"
    if _dtype_gated():
        return "jnp", "subword_dtype"
    return "pallas", None


def _resolve_interpret(interpret) -> bool:
    """None -> interpret iff the default backend is CPU (trace-time
    resolution, the ragged_a2a idiom — pin explicitly when tracing for
    a backend other than the host's)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


# -- exact one-hot gathers -------------------------------------------------

# Every selection matmul runs at full f32 precision: Mosaic's default
# f32 matmul rounds its operands to bf16 (8 mantissa bits), which turned
# the 16-bit halves below into wrong keys on the chip (a word id
# 0xFFFFF came back as 0x100000 — PR 21's first four-chip run) while the
# CPU interpreter, exact in f32, passed every test.
_EXACT = jax.lax.Precision.HIGHEST

def _exact_gather(oh_f32: jnp.ndarray, mat_i32: jnp.ndarray) -> jnp.ndarray:
    """``oh_f32 @ mat_i32`` with EXACT int32 ring semantics on the MXU:
    split each int32 into (v >> 16, v & 0xffff) — both halves exactly
    representable in f32 — matmul each half, recombine with int32
    wraparound.  With a one-hot row this is an exact row gather (one
    product, zero error); with a multi-one row it is an exact mod-2^32
    segment sum (lo partials <= _TILE * 0xffff < 2^24 stay integral in
    f32, hi partials likewise), the int32 ring the combine contract
    specifies.  [S, T] f32 x [T, W] int32 -> [S, W] int32."""
    hi = (mat_i32 >> 16).astype(jnp.float32)
    lo = (mat_i32 & 0xFFFF).astype(jnp.float32)
    ghi = jax.lax.dot(oh_f32, hi,
                      preferred_element_type=jnp.float32,
                      precision=_EXACT).astype(jnp.int32)
    glo = jax.lax.dot(oh_f32, lo,
                      preferred_element_type=jnp.float32,
                      precision=_EXACT).astype(jnp.int32)
    return (ghi << 16) + glo


def _small_gather(oh_f32: jnp.ndarray, col_i32: jnp.ndarray) -> jnp.ndarray:
    """One-hot gather of SMALL non-negative int32 (partition ids): a
    single f32 matmul is already exact below 2^24."""
    g = jax.lax.dot(oh_f32, col_i32.astype(jnp.float32),
                    preferred_element_type=jnp.float32, precision=_EXACT)
    return g.astype(jnp.int32)


def _lt3(p_a, h_a, l_a, p_b, h_b, l_b):
    """Strict composite (partition, key_hi signed, key_lo flipped) '<'
    with numpy broadcasting; ``l_*`` pre-flipped (lo ^ _FLIP) so a
    signed compare realizes unsigned lo order."""
    return (p_a < p_b) | ((p_a == p_b) & (
        (h_a < h_b) | ((h_a == h_b) & (l_a < l_b))))


# -- merge -----------------------------------------------------------------

def _merge_path_kernel(a_ref, ap_ref, b_ref, bp_ref, o_ref, op_ref, *,
                       ca: int, cb: int, tile: int):
    """One output tile of the blocked merge-path merge.

    ``a_ref``/``b_ref`` are the FULL sorted runs plus ``tile`` sentinel
    pad rows each (zero rows, sentinel partition — byte-identical to
    the transport's own invalid rows, so a pad selected in place of a
    real sentinel is indistinguishable); ``o_ref`` is this grid step's
    [tile, W] output block at diagonal ``d0 = t * tile``.

    Step 1 binary-searches the merge-path split ``ia0`` of diagonal
    ``d0`` (smallest i with ``b[d0-i-1] < a[i]`` — ties take A), a
    scalar while-loop of ~log2 dynamic VMEM loads.  Step 2 loads the
    two [tile] windows at (ia0, d0-ia0) — in bounds by the sentinel
    padding — and CROSS-RANKS them: rank(a_k) = k + |{j: b_j < a_k}|,
    rank(b_j) = j + |{k: a_k <= b_j}| (broadcast compares; the <=/<
    asymmetry IS the ties-take-A discipline, making the 2*tile local
    ranks a permutation).  The merge-path property guarantees the
    window pair covers every output of this tile, so slot s of the
    block is the unique window element with local rank s — materialized
    by exact one-hot matmul selection, no scatter."""
    t = pl.program_id(0)
    d0 = t * tile

    def _key_a(i):
        return ap_ref[i, 0], a_ref[i, 1], a_ref[i, 0] ^ _FLIP

    def _key_b(j):
        return bp_ref[j, 0], b_ref[j, 1], b_ref[j, 0] ^ _FLIP

    lo0 = jnp.maximum(jnp.int32(0), d0 - cb)
    hi0 = jnp.minimum(d0, jnp.int32(ca))

    def _cond(c):
        lo, hi = c
        return lo < hi

    def _body(c):
        lo, hi = c
        mid = (lo + hi) // 2
        pa, ha, la = _key_a(mid)
        pb, hb, lb = _key_b(d0 - mid - 1)
        b_lt_a = _lt3(pb, hb, lb, pa, ha, la)
        return (jnp.where(b_lt_a, lo, mid + 1),
                jnp.where(b_lt_a, mid, hi))

    ia0, _ = jax.lax.while_loop(_cond, _body, (lo0, hi0))
    ib0 = d0 - ia0

    wa = a_ref[pl.ds(ia0, tile), :]                    # [tile, W]
    wb = b_ref[pl.ds(ib0, tile), :]
    pa = ap_ref[pl.ds(ia0, tile), :]                   # [tile, 1]
    pb = bp_ref[pl.ds(ib0, tile), :]
    ha, la = wa[:, 1:2], wa[:, 0:1] ^ _FLIP
    hb, lb = wb[:, 1:2], wb[:, 0:1] ^ _FLIP

    # b_lt_a[k, j] = wb[j] < wa[k]  (cols = b index via row-oriented b)
    b_lt_a = _lt3(jnp.reshape(pb, (1, tile)), jnp.reshape(hb, (1, tile)),
                  jnp.reshape(lb, (1, tile)), pa, ha, la)
    rank_a = (jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
              + jnp.sum(b_lt_a.astype(jnp.int32), axis=1, keepdims=True))
    # rank_b[j] = j + |{k: a_k <= b_j}| = j + tile - |{k: b_j < a_k}|
    # (computed directly in row orientation: axis-0 sum of b_lt_a)
    rank_b_row = (jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) + tile
                  - jnp.sum(b_lt_a.astype(jnp.int32), axis=0,
                            keepdims=True))

    slots = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    oh_a = (jnp.reshape(rank_a, (1, tile)) == slots).astype(jnp.float32)
    oh_b = (rank_b_row == slots).astype(jnp.float32)
    o_ref[:] = _exact_gather(oh_a, wa) + _exact_gather(oh_b, wb)
    op_ref[:] = _small_gather(oh_a, pa) + _small_gather(oh_b, pb)


def _merge_pallas(a_rows, a_part, b_rows, b_part, num_parts: int,
                  interpret: bool):
    ca, W = a_rows.shape
    cb = b_rows.shape[0]
    n = ca + cb
    tile = _TILE
    nt = max(1, -(-n // tile))
    pad_rows = jnp.zeros((tile, W), jnp.int32)
    pad_part = jnp.full((tile, 1), num_parts, jnp.int32)
    ap = jnp.concatenate([a_rows, pad_rows])
    app = jnp.concatenate([a_part.reshape(ca, 1), pad_part])
    bp = jnp.concatenate([b_rows, pad_rows])
    bpp = jnp.concatenate([b_part.reshape(cb, 1), pad_part])
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_MERGE_VMEM_LIMIT)
    rows, part2 = pl.pallas_call(
        functools.partial(_merge_path_kernel, ca=ca, cb=cb, tile=tile),
        grid=(nt,),
        out_shape=(jax.ShapeDtypeStruct((nt * tile, W), jnp.int32),
                   jax.ShapeDtypeStruct((nt * tile, 1), jnp.int32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
        out_specs=(pl.BlockSpec((tile, W), lambda t: (t, 0)),
                   pl.BlockSpec((tile, 1), lambda t: (t, 0))),
        interpret=interpret,
        **kw,
    )(ap, app, bp, bpp)
    return rows[:n], part2[:n]


def merge_rows_cap(width: int) -> int:
    """Most rows (both runs together) one pallas merge may hold: the
    runs and their partition columns stay VMEM-resident, and VMEM pads
    every row of each to whole 128-lane words. Above it the fold merges
    with the jnp path (reader.resolve_merge_impl)."""
    lanes = -(-int(width) // 128) * 128 + 128      # rows + partition col
    return (_MERGE_VMEM_RUNS // (4 * lanes) // _TILE - 2) * _TILE


def merge_rows(
    a_rows: jnp.ndarray, a_part: jnp.ndarray,
    b_rows: jnp.ndarray, b_part: jnp.ndarray,
    num_parts: int, *, impl: str = "jnp", interpret=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge two partition-major key-sorted buffers into one.

    a_rows/b_rows — [ca, W] / [cb, W] int32 transport rows, each sorted
    by (partition, signed int64 key) with invalid rows LAST.
    a_part/b_part — [ca] / [cb] int32 partition ids, SENTINEL
    ``num_parts`` on invalid rows (sorted with their rows).

    Returns (rows [ca+cb, W], part [ca+cb], pcounts [num_parts]):
    merged partition-major key-sorted rows, sentinels last; pcounts[r]
    counts only real partitions.  Valid rows are bit-exact across
    impls; rows past the valid total are sentinel-partition zeros."""
    if impl == "jnp":
        from sparkucx_tpu.ops.aggregate import keysort_rows
        cat = jnp.concatenate([a_rows, b_rows])
        pcat = jnp.concatenate([a_part, b_part])
        cap = cat.shape[0]
        spart, srows, pcounts = keysort_rows(
            cat, pcat, jnp.int32(cap), num_parts)
        return srows, spart, pcounts
    if impl != "pallas":
        raise ValueError(f"unknown merge impl {impl!r}; want jnp|pallas")
    rows, part2 = _merge_pallas(a_rows, a_part, b_rows, b_part,
                                num_parts, _resolve_interpret(interpret))
    part = part2.reshape(-1)
    return rows, part, counts_from_sorted(part, num_parts)


# -- segment reduce --------------------------------------------------------

def _segreduce_blocked_kernel(live_ref, x_ref, o_ref, n_ref, state_ref,
                              acc_ref, rep_ref, *, sum_words: int,
                              float_acc: bool, num_parts: int, tile: int,
                              width: int, wire_words: int, w_in: int):
    """One input tile of the tiled segment-reduce run-scan.

    TPU grid iterations run sequentially, so the OPEN segment (a run of
    equal (partition, key) crossing the tile edge) carries across steps
    in scratch: ``state_ref`` SMEM [optr, prev_part, prev_hi, prev_lo,
    rep_part], ``acc_ref`` the open segment's running sum (int32 ring /
    f32 — the oracle's dtype ladder), ``rep_ref`` its representative
    row.  Per tile: boundary flags against the previous row ->
    inclusive local segment ids (triangular-matmul cumsum, rows
    continuing the carry get id 0) -> per-segment partial sums by
    one-hot matmul (split-16 exact for ints) -> CLOSED segments (all
    but the last) emitted at the head of this tile's own output block,
    the rest of the block marked with the sentinel partition; the last
    LIVE tile also places the still-open segment in its block's next
    slot and stamps n_out.

    ``live_ref`` (scalar prefetch) holds the live tiles: those up to
    the last row with a real partition.  The tiles past them hold only
    sentinels, so their steps run no body, and the index maps repeat
    the last live block for them, so they move nothing in or out; their
    output blocks stay unwritten and the wrapper counts them empty.
    With no live tile, ``_init`` leaves n_out 0.

    Every store is a whole-block write, and the output is gridded like
    the input, so VMEM holds a few small blocks whatever the capacity;
    the wrapper compacts the per-tile heads into one dense prefix by
    one gather, its index built with no search
    (``_compact_src``: each block's step to the next block's offset
    scattered to its dense end, then a prefix sum; the offset rises
    with the block because a tile emits fewer rows than its block
    holds).

    Rows travel TRANSPOSED through HBM — ``x_ref`` is a [S_in, tile]
    block of (row lanes + partition) x rows, ``o_ref`` a [S_out, 2 tile]
    one — and turn in VMEM: a [rows, W] operand of a Mosaic kernel is
    laid out 128 lanes wide in HBM, which made a 9.7M-row receive
    buffer of 3-lane rows need 16 GB.

    ``wire_words`` > 0 is the int8-dequant-FUSED mode: the tile arrives
    in the narrowed wire format ([2 exact key lanes | packed int8 |
    f32 scale] — tiling over ``plan.wire_row_words`` lanes, not the
    logical width) and is dequantized here, in-register, before the
    scan — byte-extraction arithmetic instead of int8 bitcasts so the
    prologue stays reshape-free for Mosaic."""
    t = pl.program_id(0)
    live = live_ref[0]
    acc_zero = jnp.zeros((1, sum_words),
                         jnp.float32 if float_acc else jnp.int32)

    @pl.when(t == 0)
    def _init():
        state_ref[0, 0] = jnp.int32(-1)          # optr: open output slot
        state_ref[0, 1] = jnp.int32(num_parts)   # prev row (part, hi, lo)
        state_ref[0, 2] = jnp.int32(0)
        state_ref[0, 3] = jnp.int32(0)
        state_ref[0, 4] = jnp.int32(num_parts)   # open rep's partition
        acc_ref[:] = acc_zero
        rep_ref[:] = jnp.zeros((1, width), jnp.int32)
        n_ref[0, 0] = jnp.int32(0)               # no live tile: n_out 0

    @pl.when(t < live)
    def _tile():
        xs = x_ref[:].T                          # [tile, S_in]
        raw = xs[:, :w_in]                       # [tile, W_in]
        prt = xs[:, w_in:w_in + 1]               # [tile, 1]
        if wire_words > 0:
            # fused dequant prologue: wire cols = [key lo, key hi,
            # packed int8 x qw, f32 scale]; rebuild the full-width f32
            # row in int32 bit-pattern lanes (wire_unpack_rows semantics:
            # val = int8 * row scale)
            qw = -(-wire_words // 4)
            scale = jax.lax.bitcast_convert_type(raw[:, 2 + qw:3 + qw],
                                                 jnp.float32)
            cols = []
            for j in range(wire_words):
                w8 = (raw[:, 2 + j // 4:3 + j // 4] >> (8 * (j % 4))) & 0xFF
                signed = (w8 ^ 0x80) - 0x80          # sign-extend int8
                cols.append(signed.astype(jnp.float32) * scale)
            vals = jax.lax.bitcast_convert_type(
                jnp.concatenate(cols, axis=1), jnp.int32)
            rows = jnp.concatenate([raw[:, :2], vals], axis=1)
        else:
            rows = raw

        optr = state_ref[0, 0]
        open_ = optr >= 0
        hi, lo = rows[:, 1:2], rows[:, 0:1]
        valid = prt < num_parts
        prev_p = jnp.concatenate([state_ref[0, 1].reshape(1, 1), prt[:-1]])
        prev_h = jnp.concatenate([state_ref[0, 2].reshape(1, 1), hi[:-1]])
        prev_l = jnp.concatenate([state_ref[0, 3].reshape(1, 1), lo[:-1]])
        is_new = valid & ((prt != prev_p) | (hi != prev_h) | (lo != prev_l))

        # inclusive cumsum by triangular matmul: sid 0 = carry continuation
        tril = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
                ).astype(jnp.float32)
        sid = jax.lax.dot(tril, is_new.astype(jnp.float32),
                          preferred_element_type=jnp.float32,
                          precision=_EXACT).astype(jnp.int32)
        nnew = sid[tile - 1, 0]

        # per-segment partial sums, sids 0..tile: oh[s, i] = (sid_i == s)
        sid_row = jnp.reshape(sid, (1, tile))
        valid_row = jnp.reshape(valid, (1, tile))
        oh_sum = ((sid_row == jax.lax.broadcasted_iota(
            jnp.int32, (tile + 1, tile), 0)) & valid_row).astype(jnp.float32)
        lanes = rows[:, 2:2 + sum_words]
        if float_acc:
            fl = jax.lax.bitcast_convert_type(lanes, jnp.float32)
            fl = jnp.where(valid, fl, jnp.float32(0))
            sums = jax.lax.dot(oh_sum, fl, preferred_element_type=jnp.float32,
                               precision=_EXACT)
        else:
            sums = _exact_gather(oh_sum, jnp.where(valid, lanes, 0))

        # closed segments this tile: sids [shift, nnew) at slots optr+shift..
        shift = jnp.where(open_, 0, 1)
        total0 = acc_ref[:] + sums[0:1]              # carry + continuation
        sums_sel = jnp.where(open_, sums[0:tile], sums[1:tile + 1])
        sums_sel = jnp.concatenate(
            [jnp.where(open_, total0, sums_sel[0:1]), sums_sel[1:]])

        # representative rows: emit row r <- the is_new row of sid r+shift
        rvals = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0) + shift
        oh_rep = ((sid_row == rvals)
                  & jnp.reshape(is_new, (1, tile))).astype(jnp.float32)
        reps = _exact_gather(oh_rep, rows)
        rparts = _small_gather(oh_rep, prt)
        row0 = jnp.where(open_, rep_ref[:], reps[0:1])
        part0 = jnp.where(open_, state_ref[0, 4].reshape(1, 1), rparts[0:1])
        reps = jnp.concatenate([row0, reps[1:]])
        rparts = jnp.concatenate([part0, rparts[1:]])
        words = sums_sel if not float_acc else \
            jax.lax.bitcast_convert_type(sums_sel, jnp.int32)
        # carried lanes only when the row has any: Mosaic refuses the
        # zero-width slice a keys-plus-sums row would otherwise take
        carried = width > 2 + sum_words
        emit = jnp.concatenate(
            [reps[:, :2], words] + ([reps[:, 2 + sum_words:]] if carried
                                    else []), axis=1)

        nclosed = nnew - shift

        # roll the scratch forward: the LAST segment stays open
        optr2 = optr + nnew
        oh_last = ((sid_row == nnew)
                   & jnp.reshape(is_new, (1, tile))).astype(jnp.float32)
        # sums[0] is provably zero when no segment is open (a valid row can
        # only get sid 0 by continuing a previous run), so the nnew == 0 arm
        # is correct in every open/closed state
        acc_ref[:] = jnp.where(nnew == 0, acc_ref[:] + sums[0:1],
                               _pick_row(sums, nnew, float_acc))
        rep_ref[:] = jnp.where(nnew > 0, _exact_gather(oh_last, rows),
                               rep_ref[:])
        state_ref[0, 4] = jnp.where(nnew > 0,
                                    _small_gather(oh_last, prt)[0, 0],
                                    state_ref[0, 4])
        state_ref[0, 0] = optr2
        state_ref[0, 1] = prt[tile - 1, 0]
        state_ref[0, 2] = rows[tile - 1, 1]
        state_ref[0, 3] = rows[tile - 1, 0]
        n_ref[0, 0] = optr2 + 1

        # this tile's block: closed heads, then (last live tile) the
        # open segment
        blk = o_ref.shape[1]
        slot = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        out_rows = jnp.concatenate(
            [emit, jnp.zeros((blk - tile, width), jnp.int32)])
        out_part = jnp.where(
            slot < nclosed,
            jnp.concatenate([rparts,
                             jnp.zeros((blk - tile, 1), jnp.int32)]),
            jnp.int32(num_parts))
        acc = acc_ref[:]
        w = acc if not float_acc else \
            jax.lax.bitcast_convert_type(acc, jnp.int32)
        rep = rep_ref[:]
        flush_row = jnp.concatenate(
            [rep[:, :2], w] + ([rep[:, 2 + sum_words:]] if carried
                               else []), axis=1)
        at_flush = (slot == jnp.maximum(nclosed, 0)) \
            & (t == live - 1) & (optr2 >= 0)
        out_rows = jnp.where(at_flush, flush_row, out_rows)
        out_part = jnp.where(at_flush, state_ref[0, 4], out_part)
        s_out = o_ref.shape[0]
        o_ref[:] = jnp.concatenate(
            [out_rows, out_part,
             jnp.zeros((blk, s_out - width - 1), jnp.int32)], axis=1).T


def _pick_row(sums: jnp.ndarray, idx, float_acc: bool) -> jnp.ndarray:
    """Dynamic row select from the [tile+1, SW] segment-sum matrix by
    one-hot matmul (static-shape friendly for Mosaic; exact either
    way: single product per output)."""
    s = sums.shape[0]
    oh = (jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
          == idx).astype(jnp.float32)
    if float_acc:
        return jax.lax.dot(oh, sums, preferred_element_type=jnp.float32,
                           precision=_EXACT)
    return _exact_gather(oh, sums)


def _segreduce_pallas(rows, part, num_parts: int, sum_words: int,
                      float_acc: bool, interpret: bool,
                      width: Optional[int] = None,
                      wire_words: int = 0):
    cap, w_in = rows.shape
    width = w_in if width is None else width
    tile = _TILE
    nt = max(1, reduce_tiles(cap))
    cap_pad = nt * tile
    s_in = -(-(w_in + 1) // 8) * 8     # row lanes + partition, sublanes
    s_out = -(-(width + 1) // 8) * 8
    blk = 2 * tile                     # closed heads + the final flush slot
    # [S_in, cap_pad]: rows and their partition, transposed (the kernel
    # docstring says why); padding rows carry the sentinel partition
    x = jnp.concatenate([rows, part.reshape(cap, 1).astype(jnp.int32),
                         jnp.zeros((cap, s_in - w_in - 1), jnp.int32)],
                        axis=1)
    pad = jnp.zeros((cap_pad - cap, s_in), jnp.int32).at[:, w_in].set(
        num_parts)
    xt = jnp.concatenate([x, pad]).T
    # the live extent: tiles past the last real row hold only sentinels,
    # so the kernel skips them (the kernel docstring says how); one
    # reduction of the partition lane, whatever the rows' order
    extent = jnp.max(jnp.where(part.reshape(cap) < num_parts,
                               jnp.arange(1, cap + 1, dtype=jnp.int32), 0),
                     initial=0)
    live_tiles = reduce_tiles(extent).astype(jnp.int32)

    def _clamped(t, live_ref):
        # a dead step repeats the last live block: no DMA in or out
        return 0, jnp.minimum(t, jnp.maximum(live_ref[0] - 1, 0))

    acc_dt = jnp.float32 if float_acc else jnp.int32
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    out_t, n = pl.pallas_call(
        functools.partial(
            _segreduce_blocked_kernel, sum_words=sum_words,
            float_acc=float_acc, num_parts=num_parts, tile=tile,
            width=width, wire_words=wire_words, w_in=w_in),
        out_shape=(jax.ShapeDtypeStruct((s_out, nt * blk), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nt,),
            in_specs=[pl.BlockSpec((s_in, tile), _clamped)],
            # n_out is a scalar: Mosaic stores scalars to SMEM only
            out_specs=(pl.BlockSpec((s_out, blk), _clamped),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[pltpu.SMEM((1, 8), jnp.int32),
                            pltpu.VMEM((1, sum_words), acc_dt),
                            pltpu.VMEM((1, width), jnp.int32)]),
        interpret=interpret,
        # the name its op carries in the compiled program, so a
        # profiler trace gives the kernel's own device time
        name="segreduce_blocked",
        **kw,
    )(live_tiles.reshape(1), xt)
    # compact the per-tile heads (each block's live rows are a prefix)
    # into one dense prefix of ``cap`` rows by one gather; its index is
    # a prefix sum of per-block steps scattered to the blocks' dense
    # ends, no search: the block offset it builds rises strictly, as a
    # tile emits fewer than ``blk`` rows. The kernel never writes the
    # blocks past the live tiles, so their counts are set to 0: what
    # they hold is not rows
    part2 = out_t[width]
    counts = (part2.reshape(nt, blk) < num_parts).sum(
        axis=1, dtype=jnp.int32)
    counts = jnp.where(jnp.arange(nt) < live_tiles, counts, 0)
    src = _compact_src(counts, cap, blk)
    out = jnp.take(out_t[:width + 1], src, axis=1).T     # [cap, width + 1]
    return out[:, :width], out[:, width:], n


def reduce_tiles(rows):
    """Grid tiles the blocked segment-reduce spends on ``rows`` rows (an
    int or an integer array): the whole grid of a buffer's capacity, or
    its live tiles of the rows that reached it."""
    return -(-rows // _TILE)


def _compact_src(counts: jnp.ndarray, cap: int, blk: int) -> jnp.ndarray:
    """Source column in the kernel's gridded output of each of ``cap``
    dense slots: tile b's ``counts[b]`` live rows start at column
    ``b * blk`` and land at dense slots from ``start[b]`` (the exclusive
    prefix sum), so slot j of block b reads ``j + off[b]`` with
    ``off[b] = b * blk - start[b]``, the sum of ``blk - counts[b']``
    over the blocks before b.  Each block adds that step at its dense
    end ``cum[b]``; ``cum`` never falls, so the running sum at slot j
    holds the steps of exactly the blocks before j's own.  One
    scatter-add of ``nt`` steps (empty blocks share an end with a
    neighbour and their steps add up; an end at ``cap`` drops) and one
    prefix sum, where a search of every slot is a loop of cap-length
    gathers.  ``off`` rises strictly with b (a tile emits at most
    ``tile + 1 < blk`` rows), so live slots read distinct columns.
    Slots past the live total read garbage that ``_mask_past_n``
    clears."""
    nt = counts.shape[0]
    step = jnp.zeros((cap,), jnp.int32).at[jnp.cumsum(counts)].add(
        blk - counts, mode="drop")
    src = jnp.cumsum(step) + jnp.arange(cap, dtype=jnp.int32)
    return jnp.minimum(src, nt * blk - 1)


def _mask_past_n(rows_out, part2, n, num_parts: int):
    """Kernel emits leave garbage past the compacted total (dense block
    stores overrun by design); restore the combine contract — zero rows,
    sentinel partition — in one fused epilogue."""
    cap = rows_out.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < n
    rows_out = jnp.where(live[:, None], rows_out, 0)
    part = jnp.where(live, part2.reshape(-1), num_parts)
    return rows_out, part


def pallas_reduce_supported(val_dtype) -> bool:
    """The pallas segment-reduce accumulates whole int32 transport words
    in registers, so only 4-byte value dtypes (float32/int32/uint32)
    ride it; sub-word schemas (int8/16, float16) keep the jnp path —
    their lanes pack several values per word and the in-register ring
    arithmetic would carry across element boundaries."""
    return np.dtype(val_dtype).itemsize == 4


def _drop_sentinel_group(n: jnp.ndarray, part: jnp.ndarray,
                         num_parts: int) -> jnp.ndarray:
    """``combine_rows`` counts the sentinel rows (part == num_parts,
    zeroed lanes) as one extra group whenever the buffer is padded; its
    compacted row is all-zero and lands LAST among the live rows (the
    flag sort keeps end rows in (part, key) order and the sentinel part
    sorts after every real one), so correcting n is a subtraction —
    rows/pcounts are already right.  Keeps the jnp oracle's n in
    agreement with the blocked kernels, which never count sentinels."""
    has_pad = (part >= num_parts).any().astype(jnp.int32)
    return jnp.maximum(n - has_pad, 0)


def segment_reduce_rows(
    rows: jnp.ndarray, part: jnp.ndarray, num_parts: int,
    val_words: int, val_dtype, op: str = "sum", sum_words: int = 0,
    compaction: str = "stable", *, impl: str = "jnp", interpret=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One row per distinct (partition, key): sum the leading
    ``sum_words`` value words (0 = the whole value row), carry the rest.

    ``rows``/``part`` follow the :func:`merge_rows` output contract —
    the pallas path REQUIRES sorted input (it is a tiled run scan); the
    jnp path (``ops/aggregate.combine_rows``) sorts internally, so it
    accepts any order and is the oracle on every backend.

    Returns (rows_out [cap, W], pcounts [num_parts], n_out [1])."""
    if op != "sum":
        raise ValueError(f"unknown combiner {op!r}")
    vdt = np.dtype(val_dtype)
    if impl == "jnp":
        from sparkucx_tpu.ops.aggregate import combine_rows
        ro, pc, n = combine_rows(rows, part, jnp.int32(rows.shape[0]),
                                 num_parts, val_words, vdt, op,
                                 sum_words=sum_words,
                                 compaction=compaction)
        return ro, pc, _drop_sentinel_group(n, part, num_parts)
    if impl != "pallas":
        raise ValueError(f"unknown reduce impl {impl!r}; want jnp|pallas")
    if not pallas_reduce_supported(vdt):
        raise ValueError(
            f"pallas segment-reduce needs a 4-byte value dtype, got "
            f"{vdt} — use impl='jnp' (pallas_reduce_supported gates)")
    sw = sum_words if sum_words > 0 else val_words
    rows_out, part2, n = _segreduce_pallas(
        rows, part, num_parts, sw,
        float_acc=np.issubdtype(vdt, np.floating),
        interpret=_resolve_interpret(interpret))
    n = n.reshape(1)
    rows_out, part_m = _mask_past_n(rows_out, part2, n[0], num_parts)
    return rows_out, counts_from_sorted(part_m, num_parts), n


def segment_reduce_wire_rows(
    rows: jnp.ndarray, part: jnp.ndarray, num_parts: int,
    width: int, wire_words: int, sum_words: int = 0,
    *, impl: str = "jnp", interpret=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The int8-dequant-FUSED segment reduce: input rows still in the
    ``a2a.wire=int8`` wire format ([2 exact key lanes | packed int8 |
    f32 scale] = ``alltoall.int8_wire_words`` lanes — the NARROWED
    ``plan.wire_row_words`` width the kernel tiles over), output rows
    at the full logical ``width`` with f32 sums over the leading
    ``sum_words`` dequantized lanes (0 = all of them) and dequantized
    representative lanes carried.

    jnp path: ``wire_unpack_rows`` + ``combine_rows`` — already ONE
    XLA program under jit, and the parity oracle for the fused kernel
    (identical dequant math, so valid lanes agree bit-for-bit).
    Pallas path: the blocked reduce with its in-kernel dequant
    prologue — the EQuARX fusion, no separate dequant program.

    The wire tier only quantizes float32 value lanes, so the fused
    reduce is f32-accumulate by construction; the wire format must
    cover the whole value row (``width == 2 + wire_words`` — true for
    every combine plan the manager decorates, asserted here so a
    drifted schema fails loud).  Sorted-input contract and returns as
    :func:`segment_reduce_rows`."""
    from sparkucx_tpu.shuffle.alltoall import int8_wire_words, \
        wire_unpack_rows
    if wire_words <= 0:
        raise ValueError("fused dequant reduce needs wire_words > 0 "
                         "(a2a.wire=int8 plans only)")
    if width != 2 + wire_words:
        raise ValueError(
            f"fused dequant reduce needs the wire tier to cover the "
            f"whole value row (width == 2 + wire_words), got width="
            f"{width}, wire_words={wire_words}")
    ww = int8_wire_words(wire_words)
    if rows.shape[1] != 2 + ww - 1 + 1:
        raise ValueError(
            f"wire rows must be plan.wire_row_words = {2 + ww} lanes "
            f"wide (2 exact key lanes + packed int8 + scale), got "
            f"{rows.shape[1]}")
    sw = sum_words if sum_words > 0 else wire_words
    if impl == "jnp":
        from sparkucx_tpu.ops.aggregate import combine_rows
        full = wire_unpack_rows(rows, width, wire_words)
        ro, pc, n = combine_rows(full, part, jnp.int32(full.shape[0]),
                                 num_parts, wire_words,
                                 np.dtype(np.float32), "sum",
                                 sum_words=sum_words)
        return ro, pc, _drop_sentinel_group(n, part, num_parts)
    if impl != "pallas":
        raise ValueError(f"unknown reduce impl {impl!r}; want jnp|pallas")
    rows_out, part2, n = _segreduce_pallas(
        rows, part, num_parts, sw, float_acc=True,
        interpret=_resolve_interpret(interpret), width=width,
        wire_words=wire_words)
    n = n.reshape(1)
    rows_out, part_m = _mask_past_n(rows_out, part2, n[0], num_parts)
    return rows_out, counts_from_sorted(part_m, num_parts), n


def merge_reduce_rows(
    a_rows: jnp.ndarray, a_part: jnp.ndarray,
    b_rows: jnp.ndarray, b_part: jnp.ndarray,
    num_parts: int, val_words: int, val_dtype, op: str = "sum",
    sum_words: int = 0, compaction: str = "stable",
    *, impl: str = "jnp", interpret=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge two combined buffers AND re-reduce by key — one fold step
    of the device combine (a key spanning both inputs has one row in
    each; the reduce restores one row total, summed/carried lanes per
    the :func:`segment_reduce_rows` split).

    jnp path: one ``combine_rows`` over the concatenation (its grouping
    sort does the merge for free). Pallas path: blocked merge-path
    merge, then the tiled segment reduce over the merged run.

    Returns (rows_out [ca+cb, W], pcounts [num_parts], n_out [1])."""
    if impl == "jnp":
        from sparkucx_tpu.ops.aggregate import combine_rows
        cat = jnp.concatenate([a_rows, b_rows])
        pcat = jnp.concatenate([a_part, b_part])
        ro, pc, n = combine_rows(cat, pcat, jnp.int32(cat.shape[0]),
                                 num_parts, val_words,
                                 np.dtype(val_dtype),
                                 op, sum_words=sum_words,
                                 compaction=compaction)
        return ro, pc, _drop_sentinel_group(n, pcat, num_parts)
    rows, part, _ = merge_rows(a_rows, a_part, b_rows, b_part,
                               num_parts, impl=impl,
                               interpret=interpret)
    return segment_reduce_rows(rows, part, num_parts, val_words,
                               val_dtype, op, sum_words=sum_words,
                               compaction=compaction, impl=impl,
                               interpret=interpret)


__all__ = ["merge_rows", "segment_reduce_rows", "segment_reduce_wire_rows",
           "merge_reduce_rows",
           "blocked_compile_supported", "kernel_gate_reason",
           "resolve_kernel_impl", "pallas_reduce_supported",
           "reduce_tiles"]
