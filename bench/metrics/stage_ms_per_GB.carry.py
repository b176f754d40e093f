"""Device time under the program's ``stage.carry`` scope, per GB of source
bytes in the traced window: the §4.4 carry (splicing the carry in front of
the fresh bytes, locating and extracting the next one) and the step's
per-partition scalars."""
from benchlib import scopes

PATTERNS = scopes.patterns("carry")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
