"""Host allocator policy for the codec's large per-call host buffers.

glibc's malloc serves every block at or above its mmap threshold with a
mapping of its own and unmaps it on free; the threshold adapts upward
only to 32 MiB.  Each codec call fetches a 32 MiB result from the device
(and a decode stacks 64 MiB of survivors besides), so with the default
policy every call writes into newly mapped pages: on a TPU v5e host a
fresh 32 MiB fill took ~35 ms against ~1.3 ms into pages already
faulted in, and a [4, 8 MiB] uint8 fetch 51 ms against ~9 ms (PERF.md,
section 6).  The runtime's own threads stage that fetch in a block of
their malloc arena, whose heaps hold 64 MiB each: once the first heap
is partly taken, the block needs a second heap, which glibc unmaps as
soon as it is empty again, so without a top pad as large as a heap the
staging block is new memory every call as well.

`retain_freed_large_blocks`, called where the RS, Clay and SHEC codecs are
built, raises the mmap threshold above those buffers, the trim
threshold with it, and the top pad to a whole heap, so a freed block
stays mapped and the next call's allocation of the same size reuses its
pages, in the main heap and in a thread's.  Every buffer is still its
own allocation: nothing is shared between calls, and no answer changes.
"""
from __future__ import annotations

import ctypes
import os

# mallopt parameters (glibc malloc.h)
_M_TRIM_THRESHOLD = -1
_M_TOP_PAD = -2
_M_MMAP_THRESHOLD = -3
_M_MMAP_MAX = -4

#: blocks below this come from the heap: well above the largest per-call
#: host buffer of the codec paths (a decode's 64 MiB survivor stack)
MMAP_THRESHOLD = 256 << 20
#: free memory the main heap keeps at its top before it returns pages to
#: the system
TRIM_THRESHOLD = 256 << 20
#: the size of a thread arena's heap on 64-bit glibc: a pad this large
#: keeps an emptied heap mapped (glibc deletes one only when the heap
#: before it could not hold the pad), so each thread keeps at most the
#: heaps its largest blocks took
TOP_PAD = 64 << 20

#: None until the first call; then whether the policy is in force
_applied: bool | None = None


def _glibc():
    """The process's C library when it is glibc, else None."""
    try:
        if not os.confstr("CS_GNU_LIBC_VERSION"):
            return None
    except (ValueError, OSError):
        return None
    libc = ctypes.CDLL(None)
    libc.mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    libc.mallopt.restype = ctypes.c_int
    return libc


def retain_freed_large_blocks() -> bool:
    """Apply the allocator policy once per process; True if it is in
    force.  A no-op returning False off glibc, or where glibc refuses
    both ways of keeping large blocks in the heap (mallopt returns 0 on
    error)."""
    global _applied
    if _applied is None:
        _applied = _apply()
    return _applied


def _apply() -> bool:
    libc = _glibc()
    if libc is None:
        return False
    # some glibc releases cap the threshold at 32 MiB and refuse more;
    # never mapping a block on its own keeps it in the heap just the same
    if (libc.mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD) != 1
            and libc.mallopt(_M_MMAP_MAX, 0) != 1):
        return False
    return (libc.mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD) == 1
            and libc.mallopt(_M_TOP_PAD, TOP_PAD) == 1)
