"""GoFS staging (``gofs/store.py``, ``gofs/prefetch.py``,
``gopher/session.py``): bytes staged per instance, from the session's
``last_run_report["staged_bytes"]`` of each pass in the window."""
from chipbench import layers


def read(run):
    return layers.per_instance(run, "staged_bytes")
