"""The float64 oracle (core.validate): windowed tiles decide exactly what
the full n x n matrix would, and check_dbscan rejects each broken axiom."""
import numpy as np
import pytest

from repro.core import dbscan_bruteforce_np
from repro.core.validate import (adjacency_blocks, check_dbscan,
                                 neighbor_counts, x_sorted)
from repro.data import pointclouds


def _full_adjacency(pts, eps):
    sq = (pts * pts).sum(-1)
    return sq[:, None] + sq[None, :] - 2.0 * (pts @ pts.T) <= eps * eps


def _grid_points(n, d, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 12, size=(n, d)).astype(np.float64)


@pytest.mark.parametrize("pts,eps", [
    (np.asarray(pointclouds.load("portotaxi_like", 700), np.float64), 0.02),
    (np.asarray(pointclouds.load("hacc_like", 600), np.float64), 0.05),
    (_grid_points(500, 2, 0), 1.0),        # exact boundary distances
    (_grid_points(400, 3, 1), 0.0),        # duplicates only
], ids=["taxi", "hacc", "grid-eps1", "grid-eps0"])
def test_windowed_tiles_match_full_matrix(pts, eps):
    srt, order = x_sorted(pts)
    assert (srt == pts[order]).all()
    got = np.zeros((len(srt), len(srt)), bool)
    for lo, hi, c0, c1, adj in adjacency_blocks(srt, eps, block=64):
        got[lo:hi, c0:c1] = adj
    assert (got == _full_adjacency(srt, eps)).all()
    assert (neighbor_counts(pts, eps, block=64)
            == _full_adjacency(pts, eps).sum(1)).all()


def _reference_case():
    """Blobs with several clusters, border points and noise."""
    pts = np.asarray(pointclouds.load("blobs", 600), np.float64)
    eps, mp = 0.05, 12
    labels, core = dbscan_bruteforce_np(pts, eps, mp)
    adj = _full_adjacency(pts, eps)
    border = ~core & (adj & core[None, :]).any(1)
    assert labels.max() >= 1 and border.any() and (labels == -1).any()
    return pts, eps, mp, labels, core, adj, border


@pytest.mark.parametrize("axiom", ["A1", "A2-noise", "A2-split", "A3",
                                   "A4", "A5"])
def test_check_dbscan_rejects_each_axiom(axiom):
    pts, eps, mp, labels, core, adj, border = _reference_case()
    check_dbscan(pts, eps, mp, labels, core)
    labels, core = labels.copy(), core.copy()
    cores = np.nonzero(core)[0]
    if axiom == "A1":
        core[cores[0]] = False
    elif axiom == "A2-noise":
        labels[cores[0]] = -1
    elif axiom == "A2-split":
        labels[cores[0]] = labels.max() + 1
    elif axiom == "A3":
        labels[labels == 1] = 0
    elif axiom == "A4":
        b = np.nonzero(border)[0][0]
        nbr = set(labels[adj[b] & core])
        labels[b] = next(l for l in range(labels.max() + 1) if l not in nbr)
    else:
        labels[np.nonzero(labels == -1)[0][0]] = 0
    with pytest.raises(AssertionError, match=axiom.split("-")[0]):
        check_dbscan(pts, eps, mp, labels, core)
