"""chip_smoke.py's checkers on planted faults.

The chip smoke runs its phases only on a TPU, so its oracles are tested
here on tiny numpy results: each checker must pass a clean result and
raise on every fault it exists to catch."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

R = 4


class _Result:
    """The one method of a read result the checkers call."""

    def __init__(self, parts):
        self.parts = parts

    def partition(self, r):
        return self.parts[r]


# -- terasort ----------------------------------------------------------------
def _terasort_result(rng, n=200):
    """A correct ordered read: globally sorted rows split into R
    contiguous partitions, with the ingest's sorted keys and digest."""
    from sparkucx_tpu.shuffle.integrity import digest_sum
    keys = rng.integers(0, 1 << 40, size=n, dtype=np.int64)
    vals = rng.integers(-(1 << 30), 1 << 30, size=(n, 3)).astype(np.int32)
    want_digest = digest_sum(keys, vals)
    order = np.argsort(keys, kind="stable")
    keys, vals = keys[order], vals[order]
    edges = [n * i // R for i in range(R + 1)]
    parts = [(keys[a:b].copy(), vals[a:b].copy())
             for a, b in zip(edges[:-1], edges[1:])]
    return parts, keys.copy(), want_digest


def _drop_row(parts):
    k, v = parts[1]
    parts[1] = (k[1:], v[1:])


def _swap_rows(parts):
    k, v = parts[2]
    k[[0, 1]] = k[[1, 0]]
    v[[0, 1]] = v[[1, 0]]


def _alter_key(parts):
    k, _ = parts[R - 1]
    k[-1] += 1                       # still sorted, no longer the ingest's


def _alter_payload(parts):
    _, v = parts[0]
    v[3, 1] ^= 1


def test_check_terasort_passes_clean_result(rng):
    parts, want, digest = _terasort_result(rng)
    chip_smoke._check_terasort(_Result(parts), R, want, digest)


@pytest.mark.parametrize("plant,message", [
    (_drop_row, "rows out"),
    (_swap_rows, "not globally sorted"),
    (_alter_key, "sorted keys differ"),
    (_alter_payload, "row digest differs"),
])
def test_check_terasort_catches_planted_fault(rng, plant, message):
    parts, want, digest = _terasort_result(rng)
    plant(parts)
    with pytest.raises(AssertionError, match=message):
        chip_smoke._check_terasort(_Result(parts), R, want, digest)


# -- wordcount ---------------------------------------------------------------
def _wordcount_result(rng, n=500):
    """A correct combined read: each word once, in the partition of its
    id mod R, keys ascending, with its count."""
    words = rng.integers(0, 300, size=n)
    counts = np.bincount(words, minlength=chip_smoke.WORDS).astype(np.int64)
    uniq = np.flatnonzero(counts)
    parts = []
    for r in range(R):
        k = uniq[uniq % R == r].astype(np.int64)
        parts.append((k, counts[k].astype(np.int32).reshape(-1, 1)))
    return parts, counts


def _unsort(parts):
    k, v = parts[0]
    parts[0] = (k[::-1].copy(), v[::-1].copy())


def _duplicate_word(parts):
    # partition 0's first word again in partition 1, in sorted position
    w, c = parts[0][0][:1], parts[0][1][:1]
    k, v = parts[1]
    at = np.searchsorted(k, w[0])
    parts[1] = (np.insert(k, at, w), np.insert(v, at, c, axis=0))


def _out_of_range(parts):
    k, v = parts[R - 1]
    parts[R - 1] = (np.append(k, chip_smoke.WORDS),
                    np.append(v, [[1]], axis=0).astype(np.int32))


def _wrong_count(parts):
    _, v = parts[2]
    v[0, 0] += 1


def test_check_wordcount_passes_clean_result(rng):
    parts, counts = _wordcount_result(rng)
    chip_smoke._check_wordcount(_Result(parts), R, counts)


@pytest.mark.parametrize("plant,message", [
    (_unsort, "not unique and sorted"),
    (_duplicate_word, "appears twice"),
    (_out_of_range, "out of"),
    (_wrong_count, "counts differ"),
])
def test_check_wordcount_catches_planted_fault(rng, plant, message):
    parts, counts = _wordcount_result(rng)
    plant(parts)
    with pytest.raises(AssertionError, match=message):
        chip_smoke._check_wordcount(_Result(parts), R, counts)


# -- resolved impls ----------------------------------------------------------
def _phase_line():
    import jax
    line = {"phase": "wordcount", "kernel_fallback": 0.0}
    line.update(chip_smoke._expected_impls(1, jax))
    return line


def test_check_resolved_passes_advertised_impls():
    import jax
    chip_smoke._check_resolved(_phase_line(), 1, jax)


@pytest.mark.parametrize("fault,message", [
    ({"kernel_fallback": 2.0}, "kernel fell back 2 times"),
    ({"a2a_impl": "gather"}, "a2a_impl resolved to 'gather'"),
    ({"merge_impl": "interpret"}, "merge_impl resolved to 'interpret'"),
])
def test_check_resolved_catches_fallback_and_other_impl(fault, message):
    import jax
    line = dict(_phase_line(), **fault)
    with pytest.raises(AssertionError, match=message):
        chip_smoke._check_resolved(line, 1, jax)
