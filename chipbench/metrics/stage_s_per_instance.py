"""GoFS staging (``gofs/prefetch.py``): seconds per instance spent in the
``gofs.stage`` span (slice read, row-wise transform and tile fill of one
chunk, on the prefetch pool), from the profiler's trace."""
from chipbench import spans


def read(run):
    return spans.per_instance(spans.seconds(run, "gofs.stage"), run)
