"""The host allocator's policy for a process that serves rooms.

A served process makes and frees large blocks all day: a room's native
mirror (tens of MB for a 100,000-row room, built on a planner pool
thread), the rows a compaction rebuilds, the bytes of a flush's
broadcast updates.  glibc's defaults hand such memory back to the kernel
as it is freed: a block over a threshold that moves with the process's
history is mapped and unmapped on its own, the top of the heap is
trimmed, and a pool thread's heap is unmapped when it empties.  The next
load of the same rooms then faults every page in again, or does not,
by what the process happened to free before: on the chip's host a cold
load's plan phase took 0.04 or 0.075 s and its emit 0.06 or 0.09 s from
load to load and run to run (PERF.md 6, PR 34).

:func:`ensure_heap_kept` has the allocator keep what the process has
grown to: blocks under 32 MiB (the most glibc takes) come from the
heaps, no heap is trimmed under 1 GiB of free top, and no thread's heap
is unmapped (a top pad of a whole heap).  Memory the process has used is
used again without a fault; resident size stays at its high-water mark
instead of following the load.  Every engine calls it once a process.
An operator who sets one of glibc's own ``MALLOC_*_`` variables, or
``GLIBC_TUNABLES``, keeps what they set; another libc is left alone.
"""

from __future__ import annotations

import ctypes
import functools
import os

# <malloc.h>
_M_TRIM_THRESHOLD = -1
_M_TOP_PAD = -2
_M_MMAP_THRESHOLD = -3

_POLICY = (
    (_M_MMAP_THRESHOLD, 32 << 20),
    (_M_TRIM_THRESHOLD, 1 << 30),
    (_M_TOP_PAD, 64 << 20),
)
_OPERATOR_ENV = (
    "MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_", "MALLOC_TOP_PAD_",
    "GLIBC_TUNABLES",
)


def set_heap_kept() -> bool:
    """Apply the policy now; False where it is not this module's to set."""
    if any(os.environ.get(name) for name in _OPERATOR_ENV):
        return False
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    return all([mallopt(param, value) == 1 for param, value in _POLICY])


@functools.cache
def ensure_heap_kept() -> bool:
    """Runs once per process; whether the policy was applied."""
    return set_heap_kept()
