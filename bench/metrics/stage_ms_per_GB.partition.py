"""Device time under the program's ``stage.partition`` scope, per GB of
source bytes in the traced window: the §3.3 stable partition's
permutation (``backend.partition``; on a TPU the ``radix_partition``
kernel with its scatter and sort)."""
from benchlib import scopes

PATTERNS = scopes.patterns("partition")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
