"""Host time of the ``DistributedParser`` driver's own work, per GB of
source bytes: the program's ``mesh.prepare`` (chunking and padding),
``mesh.put`` (the sharded transfer) and ``mesh.dispatch`` (the jitted
call) spans (``repro.core.spans``) inside the traced window, read from the
trace's host plane on the profiler's clock."""
from benchlib import mesh

SPANS = ("mesh.prepare", "mesh.put", "mesh.dispatch")


def read(r):
    if r.trace is None or not r.source_bytes:
        return None
    s = mesh.span_s(r.trace, SPANS)
    return 1e3 * s / (r.source_bytes / 1e9) if s > 0 else None
