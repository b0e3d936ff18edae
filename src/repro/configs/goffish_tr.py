"""GoFFish TR dataset analogue (paper §VI-A) + reduced variants.

The paper's TR collection: internet traceroute graph, 19.4M vertices, 22.8M
edges, 146 instances over 12 days (2 h windows), partitioned over 12 hosts.
We define a scaled family of synthetic small-world collections with the same
shape characteristics (power-law-ish subgraph size distribution, ~1.17
edges/vertex, 7 vertex + 7 edge attributes) for CPU-runnable benchmarks, and
the full-size spec for the dry-run.
"""
from repro.configs.base import GraphConfig

# Full-size spec (dry-run / documentation only on this container).
TR_FULL = GraphConfig(
    name="goffish-tr-full",
    num_vertices=19_442_778,
    avg_degree=1.172,
    num_instances=146,
    num_partitions=256,  # one per mesh device on the single-pod mesh
    block_size=128,
    instances_per_slice=20,
    bins_per_partition=20,
    cache_slots=14,
)

# One-chip replica preserving the distributional shape.  B=128 is the TPU
# lane width: the fused superstep kernel's tile DMA needs it.  Dense tiles
# cost ~730 MB per instance at this B, so a 4-instance time pack (the
# streamed chunk) keeps two chunks in flight well inside 16 GB of HBM.
TR_SMALL = GraphConfig(
    name="goffish-tr-small",
    num_vertices=16_384,
    avg_degree=2.0,
    num_instances=48,
    num_partitions=8,
    block_size=128,
    instances_per_slice=4,
    bins_per_partition=20,
    cache_slots=14,
)

# Tiny config for tests.
TR_TINY = GraphConfig(
    name="goffish-tr-tiny",
    num_vertices=512,
    avg_degree=3.0,
    num_instances=6,
    num_partitions=4,
    block_size=32,
    instances_per_slice=2,
    bins_per_partition=2,
    cache_slots=4,
)

CONFIG = TR_SMALL
