"""Kernels (``kernels/semiring_spmm``, ``kernels/semiring_superstep``):
percent of the semiring kernels' HBM roofline.  Useful bytes come from
edges and vertices (``chipbench/roofline.py``); kernel time is the summed
device time of the trace's ops that ``KERNELS`` matches."""
from chipbench import layers

# the Pallas kernels carry no name= yet, and a device op's trace name is
# its HLO text, so the kernels are matched as the Mosaic custom calls:
# every tpu_custom_call of this program is a semiring kernel
KERNELS = r'custom_call_target="tpu_custom_call"'


def read(run):
    return layers.kernel_roofline(run, KERNELS)
