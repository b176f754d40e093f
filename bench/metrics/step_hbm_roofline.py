"""The whole parse step's share of its HBM roofline, in percent: the least
bytes the work needs over the chip's HBM bandwidth, over the device busy
time of the traced window.

The least bytes are the source bytes read once plus the typed columns
written once (values, validity, string offsets and bytes, at the schema's
dtypes), computed from the cell's shapes and the window's record count,
never from what the kernels move."""


def read(r):
    if r.trace is None or r.peaks is None or not r.out_bytes:
        return None
    busy = r.trace.busy_s()
    if busy <= 0:
        return None
    least = r.source_bytes + r.out_bytes
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / busy
