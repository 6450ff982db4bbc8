"""Faults planted under the harness, for the tests that see ``correct``
come out false.

Each fault wraps the shuffle service's ``read`` so that the result it
hands back is broken the way a faulty exchange would break it; the
harness above it runs unchanged."""

from __future__ import annotations

import os
import sys
from typing import Callable, Dict

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import yardstick  # noqa: E402


class BrokenResult:
    """A read result whose partitions come out of ``parts(r, keys,
    values)`` or, with ``replace``, out of ``replace`` alone."""

    def __init__(self, res, fix: Callable, replace: Dict = None):
        self._res = res
        self._fix = fix
        self._replace = replace
        self.num_partitions = res.num_partitions

    def partitions_ready(self):
        if self._replace is not None:
            yield from self._replace.items()
            return
        for r, (k, v) in self._res.partitions_ready():
            yield r, self._fix(r, k, v)


def _alter(r, k, v):
    if k.shape[0]:
        v = v.copy()
        v[0, 0] ^= 1
    return k, v


def _half(r, k, v):
    n = k.shape[0] // 2
    return k[:n], v[:n]


def unchanged_inputs(inputs: yardstick.MapOutputs) -> Dict:
    """The staged records as written, cut into as many partitions: what an
    exchange step that returns its input unchanged would deliver."""
    keys = np.concatenate([k for k, _ in inputs.maps])
    vals = np.concatenate([v for _, v in inputs.maps])
    out = {}
    for r, (a, b) in enumerate(yardstick.slices(keys.shape[0],
                                                inputs.num_partitions)):
        out[r] = (keys[a:b], vals[a:b])
    return out


def no_exchange(inputs: yardstick.MapOutputs, chips: int) -> Callable:
    """Each chip keeps only the records staged on it: the exchange between
    chips left out. Map m is staged on chip m % chips; partition r is
    owned by chip r // (R / chips)."""
    local = [np.concatenate([k for m, (k, _) in enumerate(inputs.maps)
                             if m % chips == c]) for c in range(chips)]
    per_chip = inputs.num_partitions // chips

    def fix(r, k, v):
        keep = np.isin(k, local[r // per_chip])
        return k[keep], v[keep]
    return fix


def plant(monkeypatch, fault: str, config: Dict, chips: int, seed: int,
          rows_per_chip: int) -> None:
    """Wrap ``ShuffleService.read`` with ``fault``: ``altered`` (one value
    of the first record of every partition changed where it is
    produced), ``half`` (half of every partition left out),
    ``unchanged`` (the step returns its input), ``no_exchange`` (the exchange between chips left out), ``control``
    (the reference one precision step down in the program's place)."""
    from sparkucx_tpu.service import ShuffleService
    real = ShuffleService.read
    inputs = yardstick.make_inputs(config, chips, seed, rows_per_chip)
    replace = None
    fix = {"altered": _alter, "half": _half}.get(fault)
    if fault == "unchanged":
        replace = unchanged_inputs(inputs)
    elif fault == "no_exchange":
        fix = no_exchange(inputs, chips)
    elif fault == "control":
        replace = yardstick.split_rows(yardstick.control(inputs, config))
    elif fix is None:
        raise ValueError(f"unknown fault {fault!r}")

    def read(self, handle, **kw):
        return BrokenResult(real(self, handle, **kw), fix, replace)
    monkeypatch.setattr(ShuffleService, "read", read)
