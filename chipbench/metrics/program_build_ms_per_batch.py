"""Engine (``core/engine.py``) under the service: milliseconds of the
``engine.build`` span per executed batch (``service.execute`` span), from
the profiler's trace."""
from chipbench import spans


def read(run):
    return spans.per_batch_ms(spans.seconds(run, "engine.build"), run)
