"""The whole parse step's share of the chips' HBM roofline, in percent: the
least bytes the work needs over the HBM bandwidth of all the cell's chips,
over the wall time in which any chip was busy in the traced window.

The least bytes are the source bytes read once plus the typed columns
written once (values, validity, string offsets and bytes, at the schema's
dtypes), reckoned as ``step_hbm_roofline`` reckons them, from the cell's
shapes and the window's record count, never from what the kernels move."""
from benchlib import mesh


def read(r):
    if r.trace is None or r.peaks is None or not r.out_bytes:
        return None
    busy = mesh.any_chip_s(r.trace)
    if busy <= 0:
        return None
    least = r.source_bytes + r.out_bytes
    return 100.0 * least / (r.cell.chips * r.peaks["hbm_bytes_per_s"]) / busy
