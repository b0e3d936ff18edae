"""Service layer below the knee: the 90th percentile of latency from due
time, in ms.  Recorded, not judged: host stalls of one to two seconds,
which come and go from machine to machine, move it by up to 46% between
runs (PERF.md, section 4)."""
from chipbench import layers


def read(run):
    return layers.p90_ms(run)
