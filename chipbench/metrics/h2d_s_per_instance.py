"""Engine (``core/engine.py``): seconds per instance the caller spent
uploading staged tiles to the device (the ``engine.put`` span around each
chunk's or cache miss's uploads), from the profiler's trace."""
from chipbench import spans


def read(run):
    return spans.per_instance(spans.seconds(run, "engine.put"), run)
