"""Device (TPU): percent of the traced window in which no operation ran
on the chip, from the profiler's device trace."""
from chipbench import layers


def read(run):
    return layers.device_idle(run)
