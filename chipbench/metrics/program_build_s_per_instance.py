"""Engine (``core/engine.py``): seconds per instance spent building
programs (the ``engine.build`` span over a new runner's first call:
trace, lower, compile or persistent-cache load, enqueue), from the
profiler's trace."""
from chipbench import spans


def read(run):
    return spans.per_instance(spans.seconds(run, "engine.build"), run)
