"""Quickstart: the stable top-level ``repro`` surface.

    PYTHONPATH=src python examples/quickstart.py

Everything here uses only the package root's exports (``repro.dbscan``,
``repro.plan``, ``repro.stream_handle``, ``repro.neighbors``,
``repro.DBSCANResult``) — the API contract DESIGN.md §8.1 documents.
"""
import jax
import numpy as np

import repro
from repro.core.validate import (check_component_identical, check_dbscan,
                                 same_partition)
from repro.core import dbscan_bruteforce_np
from repro.data import pointclouds


def main():
    pts = pointclouds.blobs(2000, k=6, seed=42)
    eps, min_pts = 0.04, 8

    algos = ["fdbscan", "fdbscan-densebox", "tiled"]
    if jax.default_backend() != "tpu":
        # the Pallas traversal kernel runs interpreted off TPU only; on a
        # TPU Mosaic refuses it (repro.kernels.traverse.TPU_UNSUPPORTED)
        algos.append("pallas-tree")
    for algo in algos:
        res = repro.dbscan(pts, eps, min_pts, algorithm=algo)
        assert isinstance(res, repro.DBSCANResult)
        noise = int((np.asarray(res.labels) == -1).sum())
        print(f"{algo:18s}: {res.n_clusters} clusters, {noise} noise pts, "
              f"{res.n_sweeps} union-find sweeps")
        # validate against the DBSCAN axioms (oracle-backed)
        check_dbscan(pts, eps, min_pts, res.labels, res.core_mask)

    # parameter sweeps reuse one cached eps-independent index via plan()
    p = repro.plan(pts, eps, min_pts, algorithm="fdbscan")
    res = repro.dbscan(pts, eps, min_pts, query_plan=p)
    print(f"{'planned (cached)':18s}: backend={res.backend}")

    # brute-force oracle agreement on the core partition
    ref_labels, ref_core = dbscan_bruteforce_np(pts, eps, min_pts)
    assert (np.asarray(res.core_mask) == ref_core).all()
    assert same_partition(np.asarray(res.labels)[ref_core],
                          ref_labels[ref_core])
    print("all backends agree with the brute-force oracle ✓")

    # --- streaming: online inserts + probe queries over a live index ---
    stream = repro.stream_handle(pts[:1500], eps, min_pts)
    stream.insert(pts[1500:1750])           # two micro-batches arrive...
    stream.insert(pts[1750:])
    probes = stream.query(pts[:5])          # read-only cluster assignment
    print(f"{'streaming':18s}: {stream.n_points} pts "
          f"({stream.n_delta} in the delta tree), probe labels "
          f"{probes.labels.tolist()}")
    snap = stream.snapshot()                # ≡ batch dbscan on the union
    batch = repro.dbscan(pts, eps, min_pts, algorithm="fdbscan")
    check_component_identical(snap.labels, snap.core_mask,
                              batch.labels, batch.core_mask)
    print("streaming snapshot matches batch dbscan ✓")

    # --- neighbor queries over the same shared index (DESIGN.md §8) ---
    counts = repro.neighbors.neighbor_count(pts, eps)
    nn = repro.neighbors.knn(pts, k=min_pts)
    kth = np.asarray(nn.distances)[:, -1]
    print(f"{'neighbors':18s}: mean |N_eps| = "
          f"{float(np.asarray(counts).mean()):.1f}, "
          f"median {min_pts}-NN radius = {float(np.median(kth)):.4f} "
          f"(eps = {eps})")


if __name__ == "__main__":
    main()
