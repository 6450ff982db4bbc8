"""Device-side combine-by-key — the aggregation half of the reduce side.

The reference's reduce side hands fetched blocks to Spark's STOCK
deserialize -> aggregate -> sort pipeline on the executor CPU
(ref: compat/spark_2_4/UcxShuffleReader.scala:80-144; SURVEY.md §3.4
"deserialize → aggregate → sort (stock)"). The TPU build moves the
aggregation INTO the compiled exchange step, on both sides:

* map-side combine: rows are summed per (partition, key) BEFORE the
  all-to-all, so the wire carries one row per distinct key per mapper —
  Spark's map-side combine, but on the accelerator and fused with the
  destination sort it needs anyway.
* reduce-side combine: received segments are merged per key AFTER the
  all-to-all, so device-to-host transfers carry one row per distinct key
  (for aggregation workloads like WordCount this shrinks D2H by the
  duplication factor).

Everything is sort + prefix-sum — no scatter (XLA:TPU serializes colliding
scatters; see ops/partition.counts_from_sorted). The grouping
sort is BY (partition, key), which is strictly finer than the
partition-major exchange sort, so combining replaces that sort instead of
adding one — and its output is key-sorted within each partition, which is
the reference pipeline's trailing "sort" step for free.

Two formulations of the same order, chosen at trace time from the row's
shape (:func:`combine_form`):

* ``carried`` — narrow rows (at most :data:`CARRIED_MAX_OPERANDS` int32
  sort operands: the three key columns plus one 4-byte value word, i.e.
  WordCount's 3-word row). The grouping sort and the end-row compaction
  are each ONE 3-key ``lax.sort`` that carries the row's columns as
  operands; both share one sort signature, and nothing is gathered.
  A cap-length 1-D gather costs 0.6-0.7 s on a v5e at 38.9M rows, while
  a carried operand is nearly free (the benchmark's WordCount cell).
* ``perm`` — wider rows (TeraSort's 25 words, text WordCount's carried
  varbytes). XLA:TPU compile time grows with a sort's operand count, so
  they sort a permutation (:func:`~sparkucx_tpu.ops.partition.
  lexsort_perm`'s one (key, index) signature) and gather the rows with it.

Key ordering: rows carry int64 keys as two int32 words [lo, hi]
(shuffle/reader.py transport format). Lexicographic (hi signed, lo
unsigned) compare equals signed int64 compare; the low word is flipped by
0x8000_0000 so lax.sort's signed int32 compare orders it as unsigned.

Numerics: segment sums are computed as prefix-sum differences (inclusive
prefix sums carried to segment-end rows, then first-differenced).
Integers accumulate exactly (int32 lanes wrap mod 2^32, so differences
stay exact; the store back to a narrower declared dtype wraps, matching
a cast). Floats accumulate in float32; very long prefixes can lose
low-order bits versus a per-segment tree sum — the documented trade for
a scatter-free, gather-free one-pass formulation.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkucx_tpu.ops.partition import counts_from_sorted, lexsort_perm

COMBINERS = ("sum",)
# plain numpy, not jnp: a module-level jnp scalar would initialize the
# backend at import time AND become a closed-over device constant (the
# lifted-parameter fastpath hazard — see reader.step_body)
_FLIP = np.int32(-0x80000000)   # two's-complement 0x8000_0000
# The most int32 operands a combine sort carries before the combine
# takes the ``perm`` form. 4 = three key columns + one value word (the
# WordCount row). The carried 1-chip WordCount exchange at 38.9M rows
# takes 62 s cold to its first result on one v5e, compile included
# (the perm form 43 s); 4-operand sorts compile twice as long stable.
CARRIED_MAX_OPERANDS = 4
# the compaction packs its end-row flag above the partition id
_FLAG_SHIFT = 30


def check_combinable(val_tail, val_dtype, op: str) -> None:
    """Raise unless the declared value schema supports device combining."""
    if op not in COMBINERS:
        raise ValueError(f"unknown combiner {op!r}; want one of {COMBINERS}")
    if val_dtype is None:
        raise ValueError("combine needs valued rows (keys-only shuffle)")
    vdt = np.dtype(val_dtype)
    numeric = np.issubdtype(vdt, np.integer) or np.issubdtype(vdt, np.floating)
    if not numeric or vdt.itemsize > 4:
        raise ValueError(
            f"combine supports numeric value dtypes up to 4 bytes "
            f"(int8/16/32, float16/32), got {vdt}")
    nbytes = int(np.prod(val_tail, dtype=np.int64)) * vdt.itemsize
    if nbytes % 4:
        raise ValueError(
            f"combine needs the value row to fill whole transport words; "
            f"{val_tail} x {vdt} = {nbytes} B (pad the trailing dim)")


def keysort_rows(
    rows: jnp.ndarray,
    part: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_parts: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort transport rows by (partition, signed int64 key), padding last.

    Returns (spart [cap], rows_sorted [cap, W], pcounts [num_parts]) —
    partition-major, key-sorted within each partition; rows with EQUAL
    (partition, key) keep their input order (the contract promises no
    tie order — Spark's sortByKey does not either — but a stable order
    costs nothing here). The ``ordered`` read path's whole
    device cost, and the head of :func:`combine_rows`' ``perm`` form.

    The sort orders a permutation over the three key columns
    (:func:`~sparkucx_tpu.ops.partition.lexsort_perm`) and gathers the
    rows once: carrying every row column through the sort network made
    XLA:TPU compile for minutes per column."""
    cap, W = rows.shape
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid
    pkey = jnp.where(valid, part.astype(jnp.int32), jnp.int32(num_parts))
    perm = lexsort_perm((pkey, jnp.where(valid, rows[:, 1], 0),
                         jnp.where(valid, rows[:, 0] ^ _FLIP, 0)))
    spart = jnp.take(pkey, perm)
    srows = jnp.take(rows, perm, axis=0)
    return spart, srows, counts_from_sorted(spart, num_parts)


def _words_to_vals(words: jnp.ndarray, vdt: np.dtype) -> jnp.ndarray:
    """Reinterpret [cap, vw] int32 transport words as the value dtype."""
    cap, vw = words.shape
    if vdt.itemsize == 4:
        return jax.lax.bitcast_convert_type(words, vdt)
    # smaller lanes: bitcast adds a trailing axis of 4/itemsize
    out = jax.lax.bitcast_convert_type(words, vdt)
    return out.reshape(cap, vw * (4 // vdt.itemsize))


def _vals_to_words(vals: jnp.ndarray, vdt: np.dtype, vw: int) -> jnp.ndarray:
    """Inverse of _words_to_vals."""
    cap = vals.shape[0]
    if vdt.itemsize == 4:
        return jax.lax.bitcast_convert_type(vals, jnp.int32)
    ratio = 4 // vdt.itemsize
    return jax.lax.bitcast_convert_type(
        vals.reshape(cap, vw, ratio), jnp.int32)


def combine_form(num_parts: int, val_words_n: int, val_dtype,
                 sum_words: int = 0) -> str:
    """``carried`` or ``perm``: how :func:`combine_rows` orders rows of
    this shape (module docstring). ``carried`` when both of its sorts
    hold at most :data:`CARRIED_MAX_OPERANDS` operands — the grouping
    sort three keys plus the value words, the compaction three keys plus
    the prefix-sum lanes and carried words — and the end-row flag packs
    above the partition id (``num_parts < 2^30``)."""
    if sum_words <= 0:
        sum_words = val_words_n
    lanes = sum_words * (4 // np.dtype(val_dtype).itemsize) \
        + val_words_n - sum_words
    narrow = 3 + max(val_words_n, lanes) <= CARRIED_MAX_OPERANDS
    return "carried" if narrow and num_parts < (1 << _FLAG_SHIFT) else "perm"


def _sort3(keys, operands, stable: bool):
    """ONE sort on three int32 key columns carrying ``operands`` — the
    carried form's single sort signature."""
    return jax.lax.sort(tuple(keys) + tuple(operands), num_keys=3,
                        is_stable=stable)


def combine_rows(
    rows: jnp.ndarray,
    part: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_parts: int,
    val_words_n: int,
    val_dtype,
    op: str = "sum",
    sum_words: int = 0,
    compaction: str = "stable",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Group rows by (partition, int64 key) and combine values per group.

    rows       — [cap, W] int32 transport rows (cols 0,1 = key lo,hi; the
                 next ``val_words_n`` cols are the bit-packed value).
    part       — [cap] int32 partition id per row (from the partitioner).
                 A caller that marks rows with the sentinel id
                 ``num_parts`` passes them as valid rows (num_valid = cap):
                 they sort and combine past every real partition and are
                 not counted in pcounts.
    num_valid  — scalar count of real rows.
    num_parts  — static partition count R.
    val_words_n— value width in int32 words.
    val_dtype  — declared numeric dtype (validated by check_combinable).
    sum_words  — transport words (from the value's start) the combiner
                 SUMS; the remaining ``val_words_n - sum_words`` words are
                 CARRIED — one representative per key survives, byte-
                 identical. 0 means sum everything (the default). Carried
                 lanes hold per-key-constant payloads, e.g. the
                 length-prefixed word bytes of a text WordCount
                 (io/varlen.py pack_counted_varbytes): equal within a key
                 by construction, so any representative is THE value.
    compaction — the ``perm`` form's end-row compaction order's keys,
                 bit-identical results either way (property-tested):
                 ``stable``   — the flag alone; relies on stability to
                                keep the (part, key) order from the
                                grouping sort.
                 ``unstable`` — explicit keys (flag|part fused, key_hi,
                                key_lo) when num_parts < 2^30, else
                                (flag, part, key_hi, key_lo). End rows
                                are unique per (part, key), so explicit
                                keys restore the exact same order.
                 The ``carried`` form always sorts on the explicit keys
                 in one carried sort.

    Forms (module docstring): ``carried`` sorts twice with the columns as
    operands and gathers nothing; ``perm`` runs :func:`keysort_rows`
    (three LSD passes and a row gather) and a compaction of one more pass
    and four column gathers. On one v5e the 38.9M-row WordCount exchange
    takes 62 s cold to compile and run once as ``carried``, 43 s as
    ``perm``. Both give
    bit-identical outputs (property-tested): both keep equal (partition,
    key) rows in input order wherever that order shows in the result.

    Returns (rows_out [cap, W], pcounts [num_parts], n_out [1]):
    rows_out's first n_out rows are one row per distinct (partition, key),
    sorted by (partition, key) — partition-major AND key-sorted within
    each partition; pcounts[r] = distinct keys of partition r. Rows past
    n_out are zero."""
    vdt = np.dtype(val_dtype)
    if sum_words > val_words_n:
        # same check _decorated_plan applies — a silent clamp here would
        # sum carried payload bytes on a caller bug, corrupting records
        raise ValueError(
            f"sum_words={sum_words} > value width {val_words_n} words")
    if compaction not in ("stable", "unstable"):
        raise ValueError(
            f"unknown compaction {compaction!r}; want stable|unstable")
    if sum_words <= 0:
        sum_words = val_words_n
    form = combine_form(num_parts, val_words_n, vdt, sum_words)
    carry_n = val_words_n - sum_words
    cap, W = rows.shape
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid
    # the carried sorts keep equal (partition, key) rows in input order
    # only where that order shows in the result: float sums round by it
    # and a carried payload lane's representative is its last row. Integer
    # sums are exact in any order (int32 lanes wrap mod 2^32), and an
    # unstable sort compiles in about half the time on XLA:TPU.
    stable = bool(carry_n) or not np.issubdtype(vdt, np.integer)

    # ---- one grouping order: (partition, key_hi, key_lo-as-unsigned) ----
    if form == "carried":
        # padding takes the sentinel partition and a zero key, exactly
        # the keys keysort_rows orders by, so both forms give one order
        pkey = jnp.where(valid, part.astype(jnp.int32), jnp.int32(num_parts))
        out = _sort3((pkey, jnp.where(valid, rows[:, 1], 0),
                      jnp.where(valid, rows[:, 0] ^ _FLIP, 0)),
                     [rows[:, 2 + j] for j in range(val_words_n)], stable)
        spart, khi, klo = out[0], out[1], out[2] ^ _FLIP
        vwords = jnp.stack(out[3:], axis=1)               # [cap, vw]
    else:
        spart, srows, _ = keysort_rows(rows, part, num_valid, num_parts)
        klo, khi = srows[:, 0], srows[:, 1]
        vwords = srows[:, 2:2 + val_words_n]

    # ---- segment ENDS: last valid row, or row before a (part, key)
    # change. Ends (not starts) are the anchor because the inclusive
    # prefix sum AT an end row, differenced against the previous end's,
    # IS the segment sum — consecutive in sorted order, no index gather.
    key_eq = (klo == jnp.roll(klo, 1)) & (khi == jnp.roll(khi, 1))
    part_eq = spart == jnp.roll(spart, 1)
    is_start = valid & ~(key_eq & part_eq)
    is_start = is_start.at[0].set(num_valid > 0)
    n_out = is_start.sum().astype(jnp.int32)
    is_end = valid & (jnp.roll(is_start, -1) | (idx == num_valid - 1))

    # ---- inclusive prefix sums of the (masked) summed lanes -------------
    vals = _words_to_vals(vwords[:, :sum_words], vdt)
    acc_dt = jnp.float32 if np.issubdtype(vdt, np.floating) else jnp.int32
    acc = jnp.where(valid[:, None], vals.astype(acc_dt), 0)
    incl = jnp.cumsum(acc, axis=0)                        # [cap, m]
    carried = vwords[:, sum_words:]                       # [cap, carry_n]

    # ---- compact end rows to the front, in (partition, key) order -------
    # Every segment-end row (keys, partition, prefix-sum lanes, carried
    # payload words) moves to the front; the end row IS the carried
    # lanes' representative, no differencing. Dead (flag=1) rows land
    # past n_out, where every lane is masked to zero below.
    flag = jnp.where(is_end, 0, 1).astype(jnp.int32)
    lo_key = klo ^ jnp.int32(_FLIP)
    if form == "carried":
        # explicit keys: end rows are unique per (part, key), so this is
        # the grouping order; the flag packs above the partition id
        # (combine_form admits num_parts < 2^30 only), the prefix sums
        # travel as int32 bits so both sorts keep one signature
        m = incl.shape[1]
        lanes = jax.lax.bitcast_convert_type(incl, jnp.int32)
        out = _sort3(((flag << jnp.int32(_FLAG_SHIFT)) | spart, khi, lo_key),
                     [lanes[:, j] for j in range(m)]
                     + [carried[:, j] for j in range(carry_n)], stable)
        epart = out[0] & jnp.int32((1 << _FLAG_SHIFT) - 1)
        khi, klo = out[1], out[2] ^ _FLIP
        ends_incl = jax.lax.bitcast_convert_type(
            jnp.stack(out[3:3 + m], axis=1), incl.dtype)   # [cap, m]
        carried = jnp.stack(out[3 + m:], axis=1) if carry_n else carried
    else:
        if compaction == "unstable":
            keys = (((flag << jnp.int32(_FLAG_SHIFT)) | spart, khi, lo_key)
                    if num_parts < (1 << _FLAG_SHIFT)
                    else (flag, spart, khi, lo_key))
        else:
            keys = (flag,)
        perm = lexsort_perm(keys)
        epart = jnp.take(spart, perm)
        klo = jnp.take(klo, perm)
        khi = jnp.take(khi, perm)
        ends_incl = jnp.take(incl, perm, axis=0)          # [cap, m]
        if carry_n:
            carried = jnp.take(carried, perm, axis=0)     # [cap, carry_n]

    # ---- segment sums = first differences of end-row prefix sums --------
    live = idx < n_out
    prev = jnp.concatenate(
        [jnp.zeros((1, ends_incl.shape[1]), ends_incl.dtype),
         ends_incl[:-1]], axis=0)
    seg_sum = jnp.where(live[:, None], ends_incl - prev, 0).astype(vals.dtype)

    pieces = [jnp.stack([klo, khi], axis=1),
              _vals_to_words(seg_sum, vdt, sum_words)]
    if carry_n:
        pieces.append(carried)
    if W - 2 - val_words_n:
        pieces.append(jnp.zeros((cap, W - 2 - val_words_n), jnp.int32))
    rows_out = jnp.concatenate(pieces, axis=1)
    rows_out = jnp.where(live[:, None], rows_out, 0)

    out_part = jnp.where(live, epart, jnp.int32(num_parts))
    pcounts = counts_from_sorted(out_part, num_parts)
    return rows_out, pcounts, n_out.reshape(1)
