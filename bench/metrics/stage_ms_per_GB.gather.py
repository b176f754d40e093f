"""Device time under the program's ``stage.gather`` scope, per GB of
source bytes in the traced window: the §3.3 permutation gather of the
symbols and their tags (``core/partition.py``'s ``apply_partition``)."""
from benchlib import scopes

PATTERNS = scopes.patterns("gather")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
