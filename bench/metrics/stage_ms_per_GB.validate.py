"""Device time under the program's ``stage.validate`` scope, per GB of
source bytes in the traced window: §4.3 validation (``core/validation.py``)."""
from benchlib import scopes

PATTERNS = scopes.patterns("validate")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
