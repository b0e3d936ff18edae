"""Exchange: device seconds per instance of the collective operations
(HLO all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all), averaged over the chips, from the profiler's device trace."""
from chipbench import collectives, spans


def read(run):
    return spans.per_instance(collectives.collective_seconds(run), run)
