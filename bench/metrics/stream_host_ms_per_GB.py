"""Host time of the stream engine's own work, per GB of source bytes, in
the window's ``parse_streams`` call (the newest one): from the program's
span ring (``repro.core.spans``), the ``stream.stage`` spans less their
``stream.pull`` children (the caller's source), the ``stream.dispatch``
spans, and the ``stream.drain`` spans less their ``stream.wait`` children
(the wait for the device)."""
from benchlib import scopes


def read(r):
    return scopes.host_ms_per_gb(r)
