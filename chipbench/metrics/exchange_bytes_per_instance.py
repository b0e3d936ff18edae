"""Exchange (``core/comm.py``): boundary bytes one device sent per
instance over the window's passes, from the program's ``exchange_bytes``
counter (``last_run_report``, published as a ``jax.monitoring`` scalar
after each pass); 0 when the passes ran stacked."""
from chipbench import collectives

# listen from the cell's resolution on, before its run starts
WATCH = collectives.ExchangeWatch.get()


def read(run):
    return collectives.exchange_bytes_per_instance(run, WATCH)
