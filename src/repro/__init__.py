"""repro: tree-based DBSCAN (FDBSCAN / FDBSCAN-DenseBox) for TPU pods.

JAX + Pallas reproduction and extension of Prokopenko, Lebrun-Grandie,
Arndt: "Fast tree-based algorithms for DBSCAN for low-dimensional data
on GPUs" (2021): LBVH-fused traversal backends (including a lane-tiled
Pallas traversal kernel), multi-device sharding, and a streaming index,
behind one auto-dispatching entry point. See README.md and docs/api.md.

Stable public surface — everything an application needs lives here:

  * :func:`dbscan`        — clustering with automatic backend selection
                            (tree walk, Pallas tiles, sharded multi-device,
                            or a one-shot streaming snapshot);
  * :func:`plan`          — backend decision + cached index build, for
                            amortizing eps/min_pts parameter sweeps;
  * :func:`stream_handle` — an online insert/query/snapshot handle over
                            the same cached index;
  * :mod:`neighbors`      — fixed-radius counts, k-nearest-neighbor
                            queries, and raw visitor traversals over the
                            shared tree index;
  * :class:`DBSCANResult` — the result record every backend returns.

Deeper layers (``repro.core.traversal``'s predicate/callback engine,
``repro.distributed``, ``repro.stream``) stay importable for power users;
see DESIGN.md.
"""
from .core import DBSCANResult, dbscan, plan, stream_handle
from .core import neighbors

__all__ = ["DBSCANResult", "dbscan", "plan", "stream_handle", "neighbors",
           "__version__"]

__version__ = "1.1.0"
