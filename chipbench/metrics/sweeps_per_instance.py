"""Engine (``core/engine.py``): local sweeps per instance, from each
pass's ``EngineResult.stats["local_sweeps"]`` in the window."""
from chipbench import layers


def read(run):
    v = layers.per_instance(run, "local_sweeps")
    return v or None
