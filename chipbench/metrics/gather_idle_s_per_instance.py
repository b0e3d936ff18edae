"""Engine (``core/engine.py``): device-idle seconds per instance under
the ``engine.gather`` span (results brought back to the host), from the
profiler's trace: the gather's time the chip does not hide."""
from chipbench import spans


def read(run):
    idle = spans.idle_seconds_under(run, ["engine.gather"])
    return spans.per_instance(idle, run)
