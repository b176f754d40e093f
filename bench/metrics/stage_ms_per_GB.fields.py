"""Device time under the program's ``stage.fields`` scope, per GB of
source bytes in the traced window: the field index of the partitioned
symbols (``core/fields.py``)."""
from benchlib import scopes

PATTERNS = scopes.patterns("fields")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
