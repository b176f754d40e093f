"""Device time under the program's ``stage.ids`` scope, per GB of source
bytes in the traced window: §3.2 record and column ids of every symbol
(``core/offsets.py``)."""
from benchlib import scopes

PATTERNS = scopes.patterns("ids")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
