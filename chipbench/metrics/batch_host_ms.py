"""Service layer (``gopher/service.py``): device-idle milliseconds per
executed batch under the ``service.execute`` span (coalesce, plan, run,
deliver on the serve thread), from the profiler's trace: the host's part
of a batch."""
from chipbench import spans


def read(run):
    idle = spans.idle_seconds_under(run, ["service.execute"])
    return spans.per_batch_ms(idle, run)
