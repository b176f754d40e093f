"""Device time under the program's ``stage.tag`` scope, per GB of source
bytes in the traced window: tagging each symbol with its record and column
(``core/tagging.py``)."""
from benchlib import scopes

PATTERNS = scopes.patterns("tag")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
