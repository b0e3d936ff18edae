"""Multi-process GoFFish cluster runtime (paper §V deployment shape).

The paper's GoFFish runs on a commodity cluster: every worker hosts its
own GoFS partition slices and Gopher computes where the data lives.  This
package is that deployment layer for the blocked engine:

* :mod:`repro.cluster.runtime` — process bootstrap (``jax.distributed``
  when available, single-process no-op fallback) plus the rank-ordered
  TCP exchange every cross-process primitive rides on.
* :mod:`repro.cluster.staging` — shard-local staging: each process's
  :class:`~repro.gofs.prefetch.SlicePrefetcher` stages only its OWN
  partition shard of the collection (~1/num_processes of the bytes),
  with a cross-process consistency check on chunk boundaries.
* :mod:`repro.cluster.gather` — :class:`ClusterGather`, the real
  inter-process boundary exchange behind the ``_host_fold_*`` seam of
  ``repro.core.comm`` (bitwise-identical to the single-process fold).
* :mod:`repro.cluster.checkpoint` — periodic snapshots of long analytic
  runs (atomic-rename machinery from ``repro.train.checkpoint``) so a
  preempted worker resumes mid-collection bitwise-identically.
"""
from repro.cluster.checkpoint import AnalyticCheckpointer, ResumableRun
from repro.cluster.gather import ClusterGather
from repro.cluster.runtime import ClusterRuntime, init_cluster
from repro.cluster.staging import shard_stream

__all__ = [
    "AnalyticCheckpointer",
    "ClusterGather",
    "ClusterRuntime",
    "ResumableRun",
    "init_cluster",
    "shard_stream",
]
