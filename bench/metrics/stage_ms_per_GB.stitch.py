"""Wall time under the program's ``stage.stitch`` scope in which an op ran
on any chip, per GB of source bytes in the traced window: the cross-chip
collectives of ``DistributedParser`` (the all-gather of the DFA transition
summaries and its scan, the all-gather of the record counts, and the §4.3
psum, pmin and pmax)."""
from benchlib import mesh, scopes

PATTERNS = scopes.patterns("stitch")


def read(r):
    return mesh.ms_per_gb(r, PATTERNS)
