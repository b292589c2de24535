"""Recycled host destinations for the planned restore's B phase.

A freshly allocated destination takes a page fault and a kernel zero-fill
on the first write to each of its pages, inside the scatter-read; a buffer
whose pages are already mapped does not.  The patch rows a restore reads
into (:class:`~repro.core.restore.ArrayPatch`) are dropped with their
instance and needed again, at the same sizes, by the next cold start of
the function, so :class:`DestArena` keeps them mapped and hands them out
again.

Liveness is read from the block itself.  NumPy collapses every view's
``.base`` to the owning array, so a reshape, a dtype view, a memoryview or
a zero-copy alias that JAX keeps for a transfer each hold a reference to
the block; a block is free only when the arena's own reference is all that
remains.  An instance freed late (a reference cycle) just leaves its blocks
busy: the next restore misses and allocates fresh, as without the arena.
"""

from __future__ import annotations

import sys
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

#: a free block serves a request only if it is at most this many times the
#: size asked for, so a large block is not pinned by a small restore
FIT_SLACK = 2


class _Block:
    __slots__ = ("arr", "freed_at")

    def __init__(self, arr: np.ndarray):
        self.arr = arr
        self.freed_at: Optional[int] = None  # scan tick it was first seen free


def _refs(block: _Block) -> int:
    return sys.getrefcount(block.arr)


#: references a block held by the arena alone shows to :func:`_refs`
_FREE_REFS = _refs(_Block(np.empty(1, np.uint8)))


class DestArena:
    """Page-backed ``uint8`` blocks recycled across cold starts.

    :meth:`acquire` serves each size asked for from the smallest free block
    that fits (within :data:`FIT_SLACK`), as a prefix view, or allocates a
    fresh zeroed block exactly as a restore without the arena would.  Free
    blocks beyond :attr:`cap` bytes are released, least recently freed
    first; the registry sets the cap to one restore's worth
    (:meth:`fit`).  A recycled block holds the previous instance's bytes:
    the caller overwrites or clears every byte it relies on.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._blocks: List[_Block] = []  # guarded-by: _lock
        self._tick = 0                   # guarded-by: _lock
        self.cap = 0                     # guarded-by: _lock [writes]
        self.hits = 0                    # guarded-by: _lock [writes]
        self.hit_bytes = 0               # guarded-by: _lock [writes]
        self.misses = 0                  # guarded-by: _lock [writes]
        self.miss_bytes = 0              # guarded-by: _lock [writes]
        self.trims = 0                   # guarded-by: _lock [writes]
        self.trim_bytes = 0              # guarded-by: _lock [writes]

    def fit(self, nbytes: int) -> None:
        """Raise the cap on free bytes to at least ``nbytes``."""
        with self._lock:
            self.cap = max(self.cap, int(nbytes))

    def acquire(self, sizes: Sequence[int]) -> List[Tuple[np.ndarray, bool]]:
        """One ``uint8`` array of each size, and whether it was recycled."""
        out: List[Optional[Tuple[np.ndarray, bool]]] = [None] * len(sizes)
        with self._lock:
            free = self._collect()
            # largest first, so a large block goes to the large request
            for i in sorted(range(len(sizes)), key=lambda i: -sizes[i]):
                n = int(sizes[i])
                if n == 0:
                    out[i] = (np.zeros(0, dtype=np.uint8), False)
                    continue
                fits = [b for b in free
                        if n <= b.arr.size <= FIT_SLACK * n]
                if fits:
                    b = min(fits, key=lambda b: b.arr.size)
                    free.remove(b)
                    b.freed_at = None
                    self.hits += 1
                    self.hit_bytes += n
                    out[i] = (b.arr[:n], True)
                else:
                    b = _Block(np.zeros(n, dtype=np.uint8))
                    self._blocks.append(b)
                    self.misses += 1
                    self.miss_bytes += n
                    out[i] = (b.arr[:n], False)
            self._trim(free)
        return out  # type: ignore[return-value]

    def stats(self) -> dict:
        """Running totals, and the blocks held now: ``free_bytes`` counts
        those nothing views, after trimming them to the cap."""
        with self._lock:
            free = self._collect()
            self._trim(free)
            return {
                "blocks": len(self._blocks),
                "held_bytes": sum(b.arr.size for b in self._blocks),
                "free_bytes": sum(b.arr.size for b in free),
                "cap": self.cap,
                "hits": self.hits, "hit_bytes": self.hit_bytes,
                "misses": self.misses, "miss_bytes": self.miss_bytes,
                "trims": self.trims, "trim_bytes": self.trim_bytes,
            }

    def _collect(self) -> List[_Block]:  # holds-lock: _lock
        """The free blocks, oldest freed first; stamps newly freed ones."""
        self._tick += 1
        free = []
        for b in self._blocks:
            if _refs(b) > _FREE_REFS:
                b.freed_at = None
                continue
            if b.freed_at is None:
                b.freed_at = self._tick
            free.append(b)
        free.sort(key=lambda b: b.freed_at)
        return free

    def _trim(self, free: List[_Block]) -> None:  # holds-lock: _lock
        """Release the oldest free blocks until the rest fit the cap."""
        total = sum(b.arr.size for b in free)
        while free and total > self.cap:
            b = free.pop(0)
            total -= b.arr.size
            self._blocks.remove(b)
            self.trims += 1
            self.trim_bytes += b.arr.size
