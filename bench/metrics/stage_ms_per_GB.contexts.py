"""Device time under the program's ``stage.contexts`` scope, per GB of
source bytes in the traced window: §3.1 context determination (the
``dfa_chunk_vectors`` kernel, the composite exclusive scan, the start
states) and the ``dfa_replay`` kernel with its §3.2 summaries."""
from benchlib import scopes

PATTERNS = scopes.patterns("contexts")


def read(r):
    return scopes.ms_per_gb(r, PATTERNS)
