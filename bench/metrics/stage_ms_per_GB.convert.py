"""Device time under the program's ``stage.convert`` scope, per GB of
source bytes in the traced window: type conversion of every selected
column (``backend.parse_field``; on a TPU the ``numparse_*`` kernels)."""
from benchlib import scopes

PATTERNS = scopes.patterns("convert")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
