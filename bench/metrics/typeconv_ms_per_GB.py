"""Device time of type conversion, per GB of source bytes in the traced
window: the fused gather+convert kernels' jitted wrappers, and the
conditional around them (whose own op carries no name stack, but whose
source JAX recorded in ``repro/kernels/numparse``)."""

PATTERNS = (r"jit\(parse_(int|float|date)_column_fused\)",
            r" @ repro/kernels/numparse/")


def read(r):
    if r.trace is None or not r.source_bytes:
        return None
    s = r.trace.matching_s(PATTERNS)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None
