"""Clustering CLI — the paper's algorithms as a runnable tool.

  PYTHONPATH=src python -m repro.launch.cluster --data hacc_like -n 20000 \
      --eps 0.03 --minpts 5 --algorithm fdbscan-densebox

``--trace``/``--metrics-json`` record the run's phase spans (plan/build/
traverse/sweep/border, DESIGN.md §12) and metrics snapshot — the batch
analogue of the serving loop's observability artifacts.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default="blobs",
                    help="dataset name (data/pointclouds.py) or .npy path")
    ap.add_argument("-n", type=int, default=10000)
    ap.add_argument("--eps", type=float, required=True)
    ap.add_argument("--minpts", type=int, required=True)
    ap.add_argument("--algorithm", default="auto",
                    choices=["auto", "fdbscan", "fdbscan-densebox", "tiled",
                             "pallas-tree", "gdbscan", "ring"])
    ap.add_argument("--star", action="store_true", help="DBSCAN* variant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", help="write labels .npy")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the metrics registry snapshot here at exit")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record phase spans; write Chrome trace JSON here")
    args = ap.parse_args(argv)

    prev_reg, prev_tr = obs_metrics.active(), obs_trace.active()
    reg = tracer = None
    if args.metrics_json:
        reg = obs_metrics.install(obs_metrics.Registry())
    if args.trace:
        tracer = obs_trace.install(sync=True)
    try:
        _run(args, reg, tracer)
    finally:
        obs_metrics.install(prev_reg) if prev_reg is not None \
            else obs_metrics.uninstall()
        obs_trace.install(prev_tr) if prev_tr is not None \
            else obs_trace.uninstall()


def _run(args, reg, tracer):
    from repro.data import pointclouds
    pts = pointclouds.load(args.data, args.n, seed=args.seed)
    print(f"[cluster] {args.data}: n={len(pts)} d={pts.shape[1]} "
          f"eps={args.eps} minpts={args.minpts} algo={args.algorithm}")

    t0 = time.time()
    if args.algorithm == "gdbscan":
        from repro.core import gdbscan
        res = gdbscan(pts, args.eps, args.minpts)
    elif args.algorithm == "ring":
        from repro.distributed.ring_dbscan import ring_dbscan
        res = ring_dbscan(pts, args.eps, args.minpts)
    else:
        from repro.core import dbscan
        res = dbscan(pts, args.eps, args.minpts, algorithm=args.algorithm,
                     star=args.star)
    dt = time.time() - t0
    labels = np.asarray(res.labels)
    n_noise = int((labels == -1).sum())
    sizes = np.bincount(labels[labels >= 0]) if res.n_clusters else []
    print(f"[cluster] {res.n_clusters} clusters, {n_noise} noise "
          f"({100*n_noise/len(pts):.1f}%), "
          f"core={int(np.asarray(res.core_mask).sum())}, "
          f"sweeps={res.n_sweeps}, {dt:.2f}s (incl. compile)")
    if len(sizes):
        print(f"[cluster] largest clusters: {sorted(sizes)[-5:][::-1]}")
    if args.out:
        np.save(args.out, labels)
        print(f"[cluster] labels -> {args.out}")
    if reg is not None and args.metrics_json:
        obs_metrics.validate_snapshot(reg.write_json(args.metrics_json))
        print(f"[cluster] metrics snapshot -> {args.metrics_json}")
    if tracer is not None and args.trace:
        doc = tracer.export(args.trace)
        print(f"[cluster] Chrome trace ({len(doc['traceEvents'])} events) "
              f"-> {args.trace}")


if __name__ == "__main__":
    from repro.launch import use_compile_cache
    use_compile_cache()
    main()
