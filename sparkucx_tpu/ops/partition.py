"""Partitioning ops — map-side record routing, jit-compatible.

The reference inherits its map-side partitioning entirely from Spark's
SortShuffleManager (records hash-partitioned and sorted into per-reduce
runs in the data file, ref: CommonUcxShuffleManager.scala:22 and the
index-file layout consumed at OnOffsetsFetchCallback.java:44-52). Here the
same work is expressed as array ops that XLA fuses: a mixing hash, a
destination-grouping sort (see :func:`destination_sort` for the
per-method order contract), and segment counts — producing exactly the
destination-grouped send buffer + size row that
:func:`sparkucx_tpu.shuffle.alltoall.ragged_shuffle` consumes.

Everything is static-shape: callers pass padded row buffers with a validity
count; padding rows are routed to a sentinel destination that sorts last.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def hash32(keys: jnp.ndarray) -> jnp.ndarray:
    """Deterministic 32-bit avalanche hash (murmur3 finalizer) of int keys.

    Plays the role of Spark's key hash in HashPartitioner; must be identical
    across hosts/devices so every shard routes a key to the same reducer."""
    x = keys.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash_partition(keys: jnp.ndarray, num_partitions: int) -> jnp.ndarray:
    """keys -> reduce-partition id in [0, num_partitions)."""
    return (hash32(keys) % jnp.uint32(num_partitions)).astype(jnp.int32)


SORT_METHODS = ("auto", "argsort", "multisort", "multisort8", "counting")


def counts_from_sorted(sorted_key: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Bucket counts [num_bins] from an ASCENDING-sorted key vector, as
    searchsorted differences — (num_bins+1) binary searches, no scatter.

    This exists because ``jnp.bincount`` is a scatter-add, and XLA:TPU
    serializes scatters with potentially-colliding indices — measured at
    ~0.5 us per element on v5e, it turned a ~100 ms shuffle step into
    2.5 s. The hot paths all sort by destination anyway, so the histogram
    is free off the sorted form. Keys >= num_bins (padding sentinels) fall
    past the last edge and are not counted."""
    edges = jnp.searchsorted(
        sorted_key, jnp.arange(num_bins + 1, dtype=sorted_key.dtype),
        side="left").astype(jnp.int32)
    return edges[1:] - edges[:-1]


def lexsort_perm(keys) -> jnp.ndarray:
    """Stable lexicographic order of int32 key columns (most significant
    first), as a [cap] int32 permutation.

    Built as LSD passes that all share ONE sort signature — a stable
    1-key sort of (int32 key, int32 index) — and XLA:TPU compiles that
    sort once per program however many passes use it. Compile cost on
    the chip tracks the number of sort OPERANDS and stability: at 38.9M
    rows, compiled for a described v5e, this signature takes 40 s, a
    4-operand 3-key sort 117 s stable and 62 s unstable, and a 28-operand
    row-carrying sort of 100k rows over 5 minutes. Callers gather their
    rows with the permutation: the ordered read's :func:`~sparkucx_tpu.
    ops.aggregate.keysort_rows` for every width, and the combine for rows
    wider than 3 words (TeraSort's 25, text WordCount's varbytes). The
    combine's 3-word rows sort with their columns carried instead
    (:func:`~sparkucx_tpu.ops.aggregate.combine_form`): the WordCount
    exchange's first dispatch takes 62 s cold on one v5e, compile
    included, against 43 s in this permutation form."""
    keys = [k.astype(jnp.int32) for k in keys]
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    for i, k in enumerate(reversed(keys)):
        k = k if i == 0 else jnp.take(k, perm)
        _, perm = jax.lax.sort((k, perm), num_keys=1, is_stable=True)
    return perm


def _sentinel_key(dest: jnp.ndarray, num_valid: jnp.ndarray,
                  num_dests: int, cap: int) -> jnp.ndarray:
    """int32 grouping key: destination for real rows, the ``num_dests``
    sentinel for padding (valid rows are the prefix ``[:num_valid]``) —
    padding sorts past every real destination. Shared by the flat and
    strip sorts so the sentinel convention cannot drift."""
    idx = jnp.arange(cap, dtype=jnp.int32)
    return jnp.where(idx < num_valid, dest.astype(jnp.int32),
                     jnp.int32(num_dests))


def _int8_key_ok(num_dests: int) -> bool:
    """int8-key narrowing eligibility (the multisort8 lever): every key
    value INCLUDING the padding sentinel ``num_dests`` must fit int8."""
    return num_dests < 127


def destination_sort(
    rows: jnp.ndarray,
    dest: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_dests: int,
    method: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort padded rows by destination; padding sorts last.

    rows      — [cap, ...] record buffer (leading row axis).
    dest      — [cap] destination id per row (ignored for padding).
    num_valid — scalar count of real rows (rows[num_valid:] are padding).
    num_dests — static destination count.
    method    — hot-path formulation. All methods agree on the grouping
                contract — identical counts, identical per-destination row
                MULTISETS — but intra-destination ORDER is method-defined:
                argsort/counting preserve arrival order (stable),
                multisort is unstable (deterministic, but reordered) for
                a ~40% sort-cost win on TPU. The data plane only relies on
                the grouping, exactly like the reference, whose blocks
                arrive in network-delivery order:
        ``argsort``   — argsort the [cap] key then row-gather. The gather
                        moves whole padded lane tiles per row.
        ``multisort`` — one multi-operand ``lax.sort`` carrying every row
                        column through the sort network; no gather at all.
                        Needs 2-D rows.
        ``multisort8``— multisort with the key narrowed to int8 (sort
                        cost tracks provable key width). Eligible when
                        every key value incl. the padding sentinel fits
                        int8 (num_dests < 127) and rows are 2-D; falls
                        back to argsort otherwise. Same unstable
                        grouping contract as multisort.
        ``counting``  — counting sort: one-hot cumsum ranks (no comparison
                        sort), then a single row-gather via the inverse
                        permutation. O(cap x num_dests) scratch — only for
                        small destination counts.
        ``auto``      — argsort on TPU/GPU: the multisort's carried
                        columns are each a sort operand, and XLA:TPU's
                        compile time grows by tens of seconds per sort
                        operand (minutes for a 100 B row), while the
                        argsort's one sort signature compiles once
                        (:func:`lexsort_perm`). CPU -> counting for small
                        dest counts, else argsort. Override via
                        ``spark.shuffle.tpu.a2a.sortImpl``.

    Returns (sorted_rows [cap, ...], counts [num_dests]) where sorted_rows
    holds destination-grouped real rows first — the send-buffer invariant of
    the data plane — and counts is the local segment-size row (this map
    shard's row of the segment table)."""
    cap = rows.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    key = _sentinel_key(dest, num_valid, num_dests, cap)
    if method == "auto":
        if jax.default_backend() == "cpu" and num_dests <= 64:
            method = "counting"
        else:
            method = "argsort"
    if method == "counting" and num_dests > 64:
        method = "argsort"  # O(cap x D) scratch would dwarf the payload
    if method == "multisort8":
        # multisort with the key narrowed to int8: XLA:TPU sort cost
        # tracks PROVABLE key width, so an explicitly 1-byte
        # destination key is the next width lever.
        # Valid only when every key value (incl. the padding sentinel
        # num_dests) fits int8; conf-selectable for on-chip A/B
        # (a2a.sortImpl=multisort8).
        narrow = _int8_key_ok(num_dests) and rows.ndim == 2
        method = "multisort" if narrow else "argsort"
    else:
        narrow = False
    if method == "multisort" and rows.ndim != 2:
        method = "argsort"

    # counts come from the sorted key (or the counting ranks), NEVER from
    # jnp.bincount — see counts_from_sorted for the TPU scatter rationale
    if method == "argsort":
        order = lexsort_perm((key,))
        sorted_rows = jnp.take(rows, order, axis=0)
        counts = counts_from_sorted(jnp.take(key, order), num_dests)
    elif method == "multisort":
        if narrow:
            key = key.astype(jnp.int8)
        ops = (key,) + tuple(rows[:, i] for i in range(rows.shape[1]))
        # is_stable=False: measured on v5e at 2M x 10-int32 rows, the
        # stability machinery is ~40% of the whole sort (22.1 ms stable vs
        # 13.3 ms unstable — XLA:TPU's sort cost tracks effective key
        # width, and stability widens the key by an implicit index). The
        # shuffle contract never promises intra-partition arrival order —
        # the reference's blocks land in whatever order the network
        # delivers them (ref: reducer/OnBlocksFetchCallback.java:45-53) —
        # so the weaker (still deterministic) order is the honest one.
        out = jax.lax.sort(ops, num_keys=1, is_stable=False)
        sorted_rows = jnp.stack(out[1:], axis=1)
        counts = counts_from_sorted(out[0], num_dests)
    elif method == "counting":
        oh = (key[:, None] == jnp.arange(num_dests + 1,
                                         dtype=jnp.int32)[None, :])
        ranks = jnp.cumsum(oh.astype(jnp.int32), axis=0)
        rank = jnp.take_along_axis(ranks, key[:, None], axis=1)[:, 0] - 1
        counts_full = ranks[-1]                       # [num_dests + 1]
        counts = counts_full[:num_dests]
        start = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts_full)[:-1].astype(jnp.int32)])
        pos = jnp.take(start, key) + rank
        # pos is a permutation: tell the scatter so (unique + in-bounds
        # lets XLA skip the serializing collision path)
        inv = jnp.zeros((cap,), jnp.int32).at[pos].set(
            idx, unique_indices=True, mode="promise_in_bounds")
        sorted_rows = jnp.take(rows, inv, axis=0)
    else:
        raise ValueError(
            f"unknown sort method {method!r}; want one of {SORT_METHODS}")
    return sorted_rows, counts.astype(jnp.int32)


def destination_sort_strips(
    rows: jnp.ndarray,
    dest: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_dests: int,
    strips: int,
    key_impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Destination-group in S INDEPENDENT strips — one batched sort.

    Sort-network depth scales ~log^2(n), so S sorts of n/S rows cost
    ~log^2(n/S) each, and XLA batches them into ONE vectorized network
    (``lax.sort`` over the trailing axis of [S, n/S] operands): at 2M
    rows the depth ratio alone is 441/225 ~ 2x. The price is that each
    destination's rows land as S runs instead of one — but the receive
    layout already serves MULTI-RUN partitions (one run per sender,
    reader._RunIndex), so strips simply ride that contract as S virtual
    senders. The reference's reducers likewise assemble a partition from
    many per-mapper blocks, never from one contiguous range
    (ref: reducer/OnBlocksFetchCallback.java:36-43).

    Valid rows are a prefix (rows[:num_valid]), so strips fill front to
    back: full strips, then at most one partial, then empty ones — which
    is exactly the layout ``_RunIndex(align_chunk=strip_rows)`` indexes
    (every non-empty strip occupies one strip_rows-sized region; empty
    trailing strips contribute nothing).

    ``key_impl`` — 'multisort8' narrows the carried key to int8 when
    every value (incl. the sentinel) fits, same lever as
    :func:`destination_sort`; any other value keeps int32.

    Returns (sorted_rows [S*strip_rows, W], counts [S, num_dests],
    strip_rows). Padding sorts to each strip's tail."""
    cap = rows.shape[0]
    if rows.ndim != 2:
        raise ValueError("strip sort needs 2-D rows (multisort form)")
    S = max(1, min(int(strips), cap))
    M = -(-cap // S)
    pad = S * M - cap
    W = rows.shape[1]
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, W), rows.dtype)])
        dest = jnp.concatenate(
            [dest, jnp.zeros((pad,), dest.dtype)])
    key = _sentinel_key(dest, num_valid, num_dests, S * M)
    if key_impl == "multisort8" and _int8_key_ok(num_dests):
        key = key.astype(jnp.int8)
    k2 = key.reshape(S, M)
    r3 = rows.reshape(S, M, W)
    ops = (k2,) + tuple(r3[..., j] for j in range(W))
    out = jax.lax.sort(ops, dimension=-1, num_keys=1, is_stable=False)
    sorted_rows = jnp.stack(out[1:], axis=-1).reshape(S * M, W)
    counts = jax.vmap(
        lambda sk: counts_from_sorted(sk, num_dests))(
            out[0].astype(jnp.int32))
    return sorted_rows, counts.astype(jnp.int32), M



def _aligned_multisort(rows: jnp.ndarray, real_key2: jnp.ndarray,
                       dummy_key2: jnp.ndarray) -> jnp.ndarray:
    """Shared core of the aligned sorts: extend ``rows`` with zero dummy
    rows, multisort by the doubled keys (real = 2k, dummy = 2k+1 — so
    dummies land at their group's tail), return the sorted rows. The
    subtle chunk-alignment machinery (armed dummy blocks, sentinel
    placement) lives in the two thin wrappers that compute the keys."""
    pad_rows = dummy_key2.shape[0]
    rows_ext = jnp.concatenate(
        [rows, jnp.zeros((pad_rows,) + rows.shape[1:], rows.dtype)])
    k2 = jnp.concatenate([real_key2, dummy_key2])
    ops = (k2,) + tuple(rows_ext[:, i] for i in range(rows.shape[1]))
    out = jax.lax.sort(ops, num_keys=1, is_stable=False)
    return jnp.stack(out[1:], axis=1)


def destination_sort_aligned(
    rows: jnp.ndarray,
    dest: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_dests: int,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Destination-grouped send buffer with every segment padded to a
    CHUNK-row multiple — the layout the Pallas remote-DMA transport
    requires (ops/pallas/ragged_a2a.py: Mosaic DMA slices must be
    128-lane aligned, so segments start and end on chunk boundaries).

    The alignment is created BY THE SORT, not by a scatter/gather
    afterwards (round-2: a [2M]-row gather costs ~55 ms on v5e): the
    buffer is extended with ``num_dests * chunk`` dummy rows whose
    destinations are computed from a cheap key-only pre-sort's histogram
    (1-operand sort ≈ 1.2 ms at 2M rows), such that destination j gets
    exactly ``(-counts[j]) % chunk`` dummies; one multisort over
    ``(dest, is_dummy)`` then lands every segment chunk-aligned with its
    dummies at the segment tail.

    Returns (sorted_rows [cap + num_dests*chunk, ...], counts [D] REAL
    rows per destination, aligned_off [D] chunk-aligned segment starts).
    Dummy rows are ZERO. Unused dummies (and padding) sort past the last
    segment. Always the multisort formulation (the dummy-placement trick
    rides the carried sort network; 2-D rows required) — there is no
    argsort/counting variant of the aligned layout."""
    cap = rows.shape[0]
    if rows.ndim != 2:
        raise ValueError("aligned sort needs 2-D rows (multisort form)")
    pad_rows = num_dests * chunk
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid
    key = jnp.where(valid, dest.astype(jnp.int32), jnp.int32(num_dests))

    # real counts BEFORE the grouping sort, via a cheap key-only sort
    (skey,) = jax.lax.sort((key,), num_keys=1, is_stable=False)
    counts = counts_from_sorted(skey, num_dests)
    pad_per = (-counts) % chunk                           # [D]

    # dummy block j holds `chunk` candidate slots for destination j; the
    # first pad_per[j] are armed, the rest go to the sentinel
    slot = jnp.arange(pad_rows, dtype=jnp.int32)
    blk = slot // chunk
    within = slot % chunk
    dummy_dest = jnp.where(within < pad_per[blk], blk,
                           jnp.int32(num_dests))

    # one grouping sort over (dest, is_dummy): real rows precede their
    # destination's dummies; sentinel rows (padding + unused dummies)
    # sort last either way
    sorted_rows = _aligned_multisort(rows, key * 2, dummy_dest * 2 + 1)

    aligned_sizes = counts + pad_per                      # chunk multiples
    aligned_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(aligned_sizes)[:-1].astype(jnp.int32)])
    return sorted_rows, counts.astype(jnp.int32), aligned_off


def partition_major_sort_aligned(
    rows: jnp.ndarray,
    part: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_parts: int,
    dev_bounds,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partition-major send buffer with DEVICE segments padded to CHUNK
    multiples — :func:`destination_sort_aligned`'s layout, but keeping
    rows sorted by global reduce-partition id INSIDE each device segment
    (the no-receive-side-regrouping invariant of the partition-major
    design, shuffle/reader.py step_body) so the Pallas transport's
    aligned segments still deliver partition-sorted runs.

    ``dev_bounds`` — static [P+1] numpy partition-range boundaries
    (reader._device_bounds): device d owns partitions
    [dev_bounds[d], dev_bounds[d+1]).

    Sort key: real row -> part*2; dummy row of device d ->
    (last partition of d)*2 + 1 — dummies land at their device segment's
    tail, after every real row, before the next device's partitions.
    Returns (sorted_rows [cap + P*chunk, ...], rcounts [R] REAL rows per
    partition, dev_counts [P] REAL rows per device)."""
    import numpy as np
    cap = rows.shape[0]
    if rows.ndim != 2:
        raise ValueError("aligned sort needs 2-D rows (multisort form)")
    bounds = np.asarray(dev_bounds)
    P = bounds.shape[0] - 1
    pad_rows = P * chunk
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid
    pkey = jnp.where(valid, part.astype(jnp.int32), jnp.int32(num_parts))

    # per-partition histogram from a key-only pre-sort (cheap: 1 operand)
    (skey,) = jax.lax.sort((pkey,), num_keys=1, is_stable=False)
    rcounts = counts_from_sorted(skey, num_parts)
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(rcounts).astype(jnp.int32)])
    dev_counts = jnp.take(cum, jnp.asarray(bounds[1:])) \
        - jnp.take(cum, jnp.asarray(bounds[:-1]))        # [P]
    pad_per = (-dev_counts) % chunk

    # dummy block d: first pad_per[d] slots armed with key
    # (last partition of d)*2 + 1; rest go to the global sentinel
    last_part = np.maximum(bounds[1:] - 1, bounds[:-1])  # [P] static
    slot = jnp.arange(pad_rows, dtype=jnp.int32)
    blk = slot // chunk
    within = slot % chunk
    sentinel = jnp.int32(2 * num_parts + 1)
    dummy_key = jnp.where(within < pad_per[blk],
                          jnp.asarray(last_part, jnp.int32)[blk] * 2 + 1,
                          sentinel)

    sorted_rows = _aligned_multisort(
        rows, jnp.where(valid, pkey * 2, sentinel), dummy_key)
    return sorted_rows, rcounts.astype(jnp.int32), \
        dev_counts.astype(jnp.int32)


def partition_and_pack(
    keys: jnp.ndarray,
    rows: jnp.ndarray,
    num_valid: jnp.ndarray,
    num_partitions: int,
    part_to_dest: jnp.ndarray,
    num_devices: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused map-side pipeline: hash -> route -> destination sort.

    ``part_to_dest`` — [num_partitions] int32 map from reduce partition to
    owning device (the MapOutputTracker role: which executor owns which
    reduce partition, ref: UcxShuffleReader.scala:40-41). ``num_devices``
    is the static device count P.

    Returns (send_rows [cap, ...], dest_counts [P], parts_sorted [cap]) —
    the last carries each row's reduce-partition id in send order so the
    receiver can bucket received rows into its local partitions."""
    part = hash_partition(keys, num_partitions)
    dest = jnp.take(part_to_dest, part)
    cap = rows.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < num_valid
    sort_key = jnp.where(valid, dest, jnp.int32(num_devices))
    order = jnp.argsort(sort_key, stable=True)
    send_rows = jnp.take(rows, order, axis=0)
    parts_sorted = jnp.take(jnp.where(valid, part, -1), order)
    counts = counts_from_sorted(jnp.take(sort_key, order), num_devices)
    return send_rows, counts.astype(jnp.int32), parts_sorted


def range_partition_words(key_lo: jnp.ndarray, key_hi: jnp.ndarray,
                          bounds) -> jnp.ndarray:
    """Device twin of :func:`range_partition` for int64 keys split into
    transport words (lo, hi int32 — shuffle/reader.py format), x64-free.

    ``bounds`` — host-side sorted int64 split points (tuple/ndarray,
    static). partition = searchsorted(bounds, key, side='right') =
    #(b <= key), computed as a broadcast signed-64 compare over the
    (hi, lo-as-unsigned) word pairs. O(n x R) compares — the fused
    one-pass form; fine for the few-thousand-partition range."""
    import numpy as np
    b = np.asarray(bounds, dtype=np.int64)
    w = b.view(np.int32).reshape(-1, 2)         # little-endian [R-1, 2]
    b_lo = jnp.asarray(w[:, 0])[None, :]
    b_hi = jnp.asarray(w[:, 1])[None, :]
    flip = jnp.int32(-0x80000000)               # unsigned compare of lo
    lo = (key_lo ^ flip)[:, None]
    hi = key_hi[:, None]
    ge = (hi > b_hi) | ((hi == b_hi) & (lo >= (b_lo ^ flip)))
    return ge.sum(axis=1).astype(jnp.int32)


def range_partition(keys, bounds):
    """keys -> partition via sorted split points (TeraSort-style range
    partitioner: partition r holds keys in [bounds[r-1], bounds[r]) so
    concatenating sorted partitions yields a globally sorted sequence).

    ``bounds`` — [R-1] ascending split points, typically sampled quantiles
    (the role of Spark's RangePartitioner sampling).

    numpy inputs stay in numpy: jnp would silently truncate int64 keys to
    int32 with x64 off, corrupting 64-bit sort keys host-side. The jnp
    path serves device-resident (int32-safe) routing."""
    import numpy as np
    if isinstance(keys, np.ndarray):
        return np.searchsorted(np.asarray(bounds), keys,
                               side="right").astype(np.int32)
    return jnp.searchsorted(bounds, keys, side="right").astype(jnp.int32)


def sample_bounds(keys, num_partitions: int):
    """Host-side quantile sampling for range partitioning."""
    import numpy as np
    qs = np.linspace(0, 1, num_partitions + 1)[1:-1]
    return np.quantile(np.asarray(keys), qs).astype(np.asarray(keys).dtype)


class ReservoirSampler:
    """Streaming uniform sample of a key stream — Spark's
    RangePartitioner sketch without ever materializing the dataset.

    Vectorized Algorithm R: the first ``capacity`` keys fill the
    reservoir; each later key replaces a uniformly-random slot with
    probability ``capacity / seen_so_far``. Feeding the reservoir to
    :func:`sample_bounds` yields split points statistically equivalent
    to sampling the whole stream, at O(capacity) memory — the
    external-memory terasort's sampling pass streams every ingest chunk
    through here instead of concatenating the dataset on the host (the
    round-1 toy's O(N) bound this class deletes)."""

    def __init__(self, capacity: int = 4096, seed: int = 0):
        import numpy as np
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self.seen = 0
        self._rng = np.random.default_rng(seed)
        self._buf = None          # allocated lazily with the key dtype

    def add(self, keys) -> None:
        """Fold one chunk of keys into the reservoir (1-D array)."""
        import numpy as np
        keys = np.asarray(keys)
        if keys.ndim != 1:
            raise ValueError("reservoir keys must be 1-D")
        if keys.size == 0:
            return
        if self._buf is None:
            self._buf = np.empty(self.capacity, dtype=keys.dtype)
        n = keys.shape[0]
        fill = min(self.capacity - self.seen, n) \
            if self.seen < self.capacity else 0
        if fill > 0:
            self._buf[self.seen:self.seen + fill] = keys[:fill]
        tail = keys[fill:]
        if tail.size:
            # item i of the tail is the (seen + fill + i + 1)-th of the
            # stream: accept with capacity/rank into a uniform slot
            ranks = self.seen + fill + 1 \
                + np.arange(tail.size, dtype=np.float64)
            accept = self._rng.random(tail.size) < (self.capacity / ranks)
            idx = np.flatnonzero(accept)
            if idx.size:
                slots = self._rng.integers(0, self.capacity,
                                           size=idx.size)
                # later duplicates win a slot, matching sequential
                # Algorithm R's last-write order
                self._buf[slots] = tail[idx]
        self.seen += n

    def sample(self):
        """The reservoir's current contents (filled prefix only)."""
        import numpy as np
        if self._buf is None:
            return np.zeros(0, dtype=np.int64)
        return self._buf[:min(self.seen, self.capacity)]

    def bounds(self, num_partitions: int):
        """Split points for :func:`range_partition` from the reservoir
        (the sample_bounds quantiles over the streamed sketch)."""
        if self.seen == 0:
            raise ValueError("cannot derive bounds from an empty stream")
        return sample_bounds(self.sample(), num_partitions)


def blocked_partition_map(num_partitions: int, num_devices: int):
    """Default reduce-partition -> device assignment: contiguous blocks,
    remainder spread over the first partitions (Spark's grouping of reduce
    partitions per executor).

    Returns NUMPY int32, not jnp: callers close over this table inside
    traced functions, and a concrete jnp array there becomes a lifted
    executable parameter that jax's C++ fastpath fails to re-supply on
    repeat calls of the same compiled fn (trace-time numpy inlines as a
    literal instead). jnp ops accept it directly."""
    import numpy as np
    base = num_partitions // num_devices
    rem = num_partitions % num_devices
    counts = [base + (1 if d < rem else 0) for d in range(num_devices)]
    out = []
    for d, c in enumerate(counts):
        out.extend([d] * c)
    return np.asarray(out, dtype=np.int32)
