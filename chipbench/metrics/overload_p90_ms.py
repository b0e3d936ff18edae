"""Service layer under overload: the 90th percentile of latency from due
time, in ms.  Recorded, not judged: above the knee the queue grows all
through the run."""
from chipbench import layers


def read(run):
    return layers.p90_ms(run)
