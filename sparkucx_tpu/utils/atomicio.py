"""Torn-write-proof file writes — ONE helper for every artifact.

The failure model is a process dying (SIGKILL, OOM, power) mid-write:
a plain ``open(path, "w")`` leaves a half-written file under the final
name, and every consumer downstream — a metrics scraper reading the
rolling dump, the compile cache deserializing a program, a restarting
node validating its recovery ledger — sees garbage with a valid name.
The discipline is the classic one (temp file in the SAME directory →
flush → fsync → atomic ``os.replace``), applied uniformly so no writer
re-invents a weaker version:

* ``utils/export.write_snapshot`` (metrics dumps, flight postmortems)
* spill sidecars + the per-shuffle commit manifest (shuffle/writer.py,
  shuffle/durable.py)
* the CLI's timeline/stats outputs (``__main__.py``)

The persistent XLA compile cache is jax-managed and already writes
temp+rename internally (audited: jax's ``_cache_write`` path); it needs
no wrapper here.

``fsync`` is on by default — rename-without-fsync is atomic against
*concurrent readers* but not against power loss (the rename can land
before the data blocks). Callers on hot paths that only need
reader-atomicity (the periodic metrics dump, written once a minute and
re-written forever) may pass ``fsync=False``.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any

__all__ = ["atomic_write_bytes", "atomic_write_text", "atomic_write_json",
           "fsync_dir"]


def _tmp_name(path: str) -> str:
    # pid + thread id: two writers racing the same final path (the
    # PeriodicDumper.stop() final dump overlapping a background dump)
    # must not truncate each other's temp file
    return f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"


def fsync_dir(path: str) -> None:
    """Best-effort fsync of a DIRECTORY so a rename itself is durable
    (POSIX: the rename lives in the directory's data). Never raises —
    some filesystems/sandboxes reject O_DIRECTORY opens."""
    try:
        fd = os.open(path or ".", os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:
        pass


def atomic_write_bytes(path: str, data: bytes, fsync: bool = True) -> str:
    """Write ``data`` to ``path`` via temp + (fsync) + atomic rename.
    Returns ``path``. A reader of ``path`` sees either the old complete
    content or the new complete content, never a torn prefix."""
    tmp = _tmp_name(path)
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    if fsync:
        fsync_dir(os.path.dirname(path))
    return path


def atomic_write_text(path: str, text: str, fsync: bool = True) -> str:
    return atomic_write_bytes(path, text.encode("utf-8"), fsync=fsync)


def atomic_write_json(path: str, doc: Any, indent: int = 1,
                      fsync: bool = True, **dump_kw) -> str:
    return atomic_write_text(
        path, json.dumps(doc, indent=indent, **dump_kw), fsync=fsync)
