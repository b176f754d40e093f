"""Share of the traced window in which no chip ran any operation:
1 - (union of every chip's op intervals) / window, in percent."""
from benchlib import mesh


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - mesh.any_chip_s(r.trace) / r.trace.window_s)
