"""Plain DBSCAN reference on the host.

Independent of the program under test: it imports nothing of ``repro``
and reads nothing the program made. It follows the textbook definition
(Ester et al. 1996, the query point counted in its own neighbourhood):

* a point is core when at least ``min_pts`` points lie within ``eps``
  of it;
* core points within ``eps`` of each other share a cluster, and the
  clusters are the connected components of that graph;
* a non-core point within ``eps`` of a core point is a border point and
  may join any adjacent cluster; every other point is noise.

Every neighbour pair is decided by ``sum((p - q)**2) <= eps**2``, with
the coordinates and every operation rounded to the precision the caller
names: float64 for the reference (exact on float32 coordinates, up to
one rounding of the sum), a lower one for the control. SciPy's k-d tree
only lists the candidate pairs, within a radius widened past any
rounding of that test, so it never decides one.
"""
from __future__ import annotations

from typing import NamedTuple

import ml_dtypes
import numpy as np

#: candidate pairs tested at once (bounds the reference's memory)
PAIR_BLOCK = 1 << 23

PRECISIONS = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}


class Reference(NamedTuple):
    """What DBSCAN fixes about a point set, in input order.

    core: (n,) bool core flags.
    comp: (n,) int component id of each core point (-1 elsewhere).
    border_pt / border_comp: every (non-core point, adjacent component)
        pair; a non-core point in no pair is noise.
    """
    core: np.ndarray
    comp: np.ndarray
    border_pt: np.ndarray
    border_comp: np.ndarray

    def labels(self) -> np.ndarray:
        """One valid labelling: components as labels, each border point in
        its smallest adjacent component, noise as -1."""
        big = np.iinfo(np.int64).max
        best = np.full(len(self.core), big)
        np.minimum.at(best, self.border_pt, self.border_comp)
        return np.where(self.core, self.comp,
                        np.where(best == big, -1, best))


def _rounded(a, dtype):
    return np.asarray(a).astype(dtype)


def neighbour_pairs(points, eps: float, precision: str = "float64"):
    """``(i, j)``: every unordered pair ``i < j`` within ``eps``, the test
    computed in ``precision``."""
    from scipy.spatial import cKDTree
    dtype = PRECISIONS[precision]
    x = _rounded(points, dtype)
    # the widened radius covers the rounding of the test in ``dtype``
    slack = 8 * float(ml_dtypes.finfo(dtype).eps) + 1e-9
    cand = cKDTree(x.astype(np.float64)).query_pairs(
        eps * (1 + slack), output_type="ndarray")
    axes = [np.ascontiguousarray(x[:, k]) for k in range(x.shape[1])]
    e2 = _rounded(_rounded(eps, dtype) * _rounded(eps, dtype), dtype)
    keep_i, keep_j = [], []
    for lo in range(0, len(cand), PAIR_BLOCK):
        i, j = cand[lo:lo + PAIR_BLOCK].T
        d2 = None
        for xk in axes:
            diff = _rounded(xk[i] - xk[j], dtype)
            sq = _rounded(diff * diff, dtype)
            d2 = sq if d2 is None else _rounded(d2 + sq, dtype)
        adj = d2 <= e2
        keep_i.append(i[adj])
        keep_j.append(j[adj])
    if not keep_i:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(keep_i), np.concatenate(keep_j)


def dbscan(points, eps: float, min_pts: int,
           precision: str = "float64") -> Reference:
    """The reference DBSCAN of ``points`` (n, d) at ``eps``/``min_pts``,
    its distance test computed in ``precision``."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components
    n = len(points)
    i, j = neighbour_pairs(points, eps, precision)
    counts = 1 + np.bincount(i, minlength=n) + np.bincount(j, minlength=n)
    core = counts >= min_pts
    cc = core[i] & core[j]
    graph = coo_matrix((np.ones(int(cc.sum()), np.int8), (i[cc], j[cc])),
                       shape=(n, n)).tocsr()
    comp = np.where(core, connected_components(
        graph, directed=True, connection="weak")[1], -1)
    # (non-core point, adjacent core point), in both orientations
    bi = np.concatenate([i[~core[i] & core[j]], j[core[i] & ~core[j]]])
    bj = np.concatenate([j[~core[i] & core[j]], i[core[i] & ~core[j]]])
    pairs = np.unique(np.stack([bi, comp[bj]], 1), axis=0)
    return Reference(core, comp, pairs[:, 0], pairs[:, 1])
