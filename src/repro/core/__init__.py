"""Core library: the paper's tree-based DBSCAN algorithms on TPU/JAX.

``dbscan`` is the unified auto-dispatching entry point (DESIGN.md §5): it
plans a backend (tree walk or Pallas tiles) per input and reuses cached
indexes across eps/min_pts sweeps. The per-algorithm implementations stay
importable via ``fdbscan`` and ``kernels.ops``.
"""
from .fdbscan import DBSCANResult
from .dispatch import dbscan, plan, Plan, stream_handle
from .baselines import dbscan_bruteforce_np, gdbscan
from . import (dispatch, fdbscan, grid, lbvh, morton, neighbors, traversal,
               unionfind, validate)

__all__ = ["DBSCANResult", "dbscan", "plan", "Plan", "stream_handle",
           "dbscan_bruteforce_np", "gdbscan", "dispatch", "fdbscan", "grid",
           "lbvh", "morton", "neighbors", "traversal", "unionfind",
           "validate"]
