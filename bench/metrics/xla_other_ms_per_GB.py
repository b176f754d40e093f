"""Device busy time that the scan, partition and type-conversion metrics do
not claim (the composite scan, ids, validation, carry splice and extract),
per GB of source bytes in the traced window."""

CLAIMED_BY = ("scan_ms_per_GB", "partition_ms_per_GB", "typeconv_ms_per_GB")


def read(r):
    if r.trace is None or not r.source_bytes:
        return None
    claimed = [p for m in CLAIMED_BY for p in r.patterns(m)]
    s = r.trace.busy_s() - r.trace.matching_s(claimed)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None
