"""Host memory a request frees stays with the process.

glibc gives every thread that allocates an arena of its own, and the HTTP
server starts a thread a request. By default a freed block over a moving
threshold goes back to the kernel (a block of its own ``mmap`` is unmapped,
an arena's heap is trimmed or deleted), and the next request's
megabyte-sized host buffers (a fetched image, a PNG, a JSON body) fault
their pages in again. How much of that a request pays depends on the state
of the arena its thread is handed, which differs from process to process:
two per-process speeds of the host path with nothing on the device
differing (PERF.md section 6: 46 ms of a 2.09 s img2img request, PR 24 E;
10.5 ms of a 1.34 s expanded txt2img, PR 28).

:func:`retain_freed_memory` fixes the three thresholds instead of letting
them move: blocks up to 32 MiB come from an arena's heap and not from an
``mmap`` of their own, a heap is trimmed only beyond 1 GiB free, and 64 MiB
of padding keeps an emptied heap from being deleted. The price is that the
process keeps its high-water mark of host memory. The number of arenas is
left alone: one arena for all threads gives the same single speed, but
XLA's compile threads then queue on its lock (a cold SDXL compile took
337 s for 160; my chip runs, PR 28).

The package calls it when it is imported, before anything allocates much.
On a libc without ``mallopt`` it does nothing.
"""

from __future__ import annotations

import ctypes

#: glibc's malloc.h
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3


def retain_freed_memory() -> bool:
    """True when the libc took all three settings."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    return all([mallopt(_M_MMAP_THRESHOLD, 32 * 2 ** 20) == 1,
                mallopt(_M_TRIM_THRESHOLD, 2 ** 30) == 1,
                mallopt(_M_TOP_PAD, 64 * 2 ** 20) == 1])
