"""Wall time under the program's ``stage.halo`` scope in which an op ran on
any chip, per GB of source bytes in the traced window: each chip's
unterminated tail found, passed to the next chip by ``ppermute``, and
spliced in front of that chip's bytes (``DistributedParser``)."""
from benchlib import mesh, scopes

PATTERNS = scopes.patterns("halo")


def read(r):
    return mesh.ms_per_gb(r, PATTERNS)
