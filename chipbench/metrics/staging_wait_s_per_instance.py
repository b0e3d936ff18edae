"""GoFS staging (``gofs/prefetch.py``): seconds per instance the pass's
caller spent blocked on the next staged chunk (the ``gofs.wait`` span),
from the profiler's trace."""
from chipbench import spans


def read(run):
    return spans.per_instance(spans.seconds(run, "gofs.wait"), run)
