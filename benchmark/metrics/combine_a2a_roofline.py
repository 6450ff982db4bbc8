"""combine_a2a_roofline: a combining read's all-to-all, as a share of the
ICI roofline, %.

The least bytes are what must leave the busiest chip when every chip's
pairs are fully combined before the wire. Map m is staged on chip
``m % chips`` (map tasks round-robin over executors); chips own the
reduce partitions in contiguous blocks (64 a chip of 256 on four). For
each source chip, count the distinct words among its pairs whose
partition another chip owns, times the record's bytes (12 B: int64 word,
int32 count). The count does not depend on what implements the exchange
or on how many waves feed it. At the published ICI rate that is the
least time; its share of the collective ops' device time per shuffle
(mean over chips) is the metric. Silent on one chip.

``yardstick.ici_bytes_out`` counts every record, uncombined: right for
``a2a_roofline``'s sort, not for a combine."""

import numpy as np


def owners(num_partitions: int, chips: int) -> np.ndarray:
    """[R] chip owning each partition: contiguous blocks, the remainder
    spread over the first chips."""
    base, rem = divmod(num_partitions, chips)
    return np.repeat(np.arange(chips),
                     [base + (1 if c < rem else 0) for c in range(chips)])


def _distinct(keys: np.ndarray) -> np.ndarray:
    """Sorted distinct keys; a count by value where ids are small."""
    if keys.size and 0 <= keys.min() and keys.max() < (1 << 27):
        return np.flatnonzero(np.bincount(keys))
    return np.unique(keys)


def distinct_words(inputs, config, chips: int) -> np.ndarray:
    """[chips, chips] int64: distinct words staged on chip c (row) whose
    partition chip d owns (column) — the rows c sends d under full
    map-side combining."""
    import yardstick
    R = inputs.num_partitions
    owner = owners(R, chips)
    out = np.zeros((chips, chips), dtype=np.int64)
    for c in range(chips):
        words = _distinct(np.concatenate(
            [k for m, (k, _) in enumerate(inputs.maps) if m % chips == c]))
        dest = owner[yardstick.partition_of(words, config, R,
                                            inputs.bounds)]
        out[c] = np.bincount(dest, minlength=chips)
    return out


def read(ctx):
    if ctx.chips < 2 or ctx.trace is None or ctx.trace.collective_s <= 0:
        return None
    n = sum(1 for s in ctx.shuffles if not s.error)
    if not n:
        return None
    import yardstick
    m = distinct_words(ctx.inputs, ctx.config, ctx.chips)
    out = int((m.sum(axis=1) - np.diag(m)).max()) \
        * yardstick.record_bytes(ctx.config)
    least_s = out * 8 / ctx.peak("ici_bits_per_s")
    return 100.0 * least_s / (ctx.trace.collective_s / n)
