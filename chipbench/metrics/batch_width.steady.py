"""Service layer (``gopher/service.py``): queries delivered per executed
batch in the window, from the service's ``served`` and ``batches``
counters."""
from chipbench import layers


def read(run):
    return layers.batch_width(run)
