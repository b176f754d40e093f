"""A seeded block of whole records, repeated without end, and what the
reference expects of each partition a session cuts from it."""
from __future__ import annotations

import numpy as np


class Repeated:
    """``block`` repeated; record ``j`` of the stream is record
    ``j % n`` of the block."""

    def __init__(self, block: bytes, rec_end: np.ndarray):
        self.block = block
        self.L = len(block)
        self.rec_end = np.asarray(rec_end, np.int64)
        self.nl = self.rec_end - 1          # position of each record's \n
        self.n = len(self.rec_end)

    def records_before(self, x: int) -> int:
        """Records whose ``\\n`` lies before stream position ``x``."""
        q, r = divmod(int(x), self.L)
        return q * self.n + int(np.searchsorted(self.nl, r, side="left"))

    def record_start(self, j: int) -> int:
        q, r = divmod(int(j), self.n)
        return q * self.L + (int(self.rec_end[r - 1]) if r else 0)

    def next_end(self, x: int) -> int:
        """The first record end (one past a ``\\n``) at or after ``x``."""
        return self.record_start(self.records_before(int(x) - 1) + 1)

    def bytes(self, a: int, b: int) -> bytes:
        out, pos = [], a
        while pos < b:
            off = pos % self.L
            take = min(b - pos, self.L - off)
            out.append(self.block[off:off + take])
            pos += take
        return b"".join(out)

    def partition(self, k: int, partition_bytes: int, total: int, final: bool):
        """The bytes of the records take ``k`` completes: those whose
        ``\\n`` lies in the take (in the last take, all that are left)."""
        j0 = self.records_before(k * partition_bytes)
        j1 = self.records_before(total if final else (k + 1) * partition_bytes)
        return self.bytes(self.record_start(j0), self.record_start(j1))
