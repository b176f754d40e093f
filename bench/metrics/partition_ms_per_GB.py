"""Device time of the §3.3 partition, per GB of source bytes in the traced
window: the radix kernel's jitted wrapper (``partition_tags``, with the
scatter and sort inside it) and the permutation gather, whose name stack
does not name it (``jit(step_one)/gather``) but whose source JAX recorded
in ``repro/core/partition.py``."""

PATTERNS = (r"jit\(partition_tags\)", r" @ repro/core/partition\.py")


def read(r):
    if r.trace is None or not r.source_bytes:
        return None
    s = r.trace.matching_s(PATTERNS)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None
