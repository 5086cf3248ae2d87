"""Distributed DBSCAN: dense systolic ring vs sharded tree + eps-halo.

The quantity under test is the ISSUE-2 claim: per-shard BVH traversal with
eps-halo exchange does the clustering with a small fraction of the ring
pass's pairwise distance evaluations (>= 10x fewer at n=16384), at equal
labels. We report exact distance-evaluation counts (the paper's work
metric — measured by the traversal engine for the tree path, analytic
``(2 + sweeps) * n_pad^2`` for the dense ring, which evaluates every pair
in every phase rotation) plus wall clock for both, and emit
``BENCH_distributed.json``.

On CPU, multi-device execution needs ``XLA_FLAGS`` set before jax
import, so ``run()`` re-executes this module in a subprocess with 8
forced host devices; ``python -m benchmarks.bench_distributed`` does the
same. On an accelerator the measurement runs in-process on the real
devices: a chip belongs to the process that first touched it, so a child
could not reach it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

# Above this the dense ring's wall clock is minutes on CPU; its eval count
# stays analytic either way, so larger sizes skip the ring timing only.
RING_MAX_N = 16384
N_DEVICES = 8
EPS, MINPTS = 0.02, 10


def _inner(sizes, json_out):
    import jax
    import numpy as np
    from repro.data import pointclouds
    from repro.distributed.ring_dbscan import ring_dbscan, tree_dbscan_sharded
    from repro.core.validate import same_partition
    from .common import emit, time_once

    ndev = len(jax.devices())
    records = {}
    for n in sizes:
        pts = pointclouds.taxi_2d(n)
        n_pad = ((n + ndev - 1) // ndev) * ndev

        tree_cold, (tree_res, st) = time_once(
            tree_dbscan_sharded, pts, EPS, MINPTS, with_stats=True,
            label=f"dist/n{n}/tree_cold")
        tree_warm, (tree_res, st) = time_once(
            tree_dbscan_sharded, pts, EPS, MINPTS, with_stats=True,
            label=f"dist/n{n}/tree_warm")

        rec = {
            "n": n, "n_pad": n_pad, "ndev": ndev,
            "eps": EPS, "minpts": MINPTS,
            "tree_wall_s": tree_warm, "tree_wall_cold_s": tree_cold,
            "tree_distance_evals": st["distance_evals"],
            "tree_sweeps": st["n_sweeps"],
            "n_clusters": tree_res.n_clusters,
        }
        if n <= RING_MAX_N:
            rec["ring_wall_cold_s"], ring_res = time_once(
                ring_dbscan, pts, EPS, MINPTS,
                label=f"dist/n{n}/ring_cold")
            rec["ring_wall_s"], ring_res = time_once(  # warm, like the tree
                ring_dbscan, pts, EPS, MINPTS,
                label=f"dist/n{n}/ring_warm")
            rec["ring_sweeps"] = ring_res.n_sweeps
            assert same_partition(np.asarray(ring_res.labels),
                                  np.asarray(tree_res.labels))
            ring_evals = (2 + ring_res.n_sweeps) * n_pad * n_pad
        else:
            # analytic only: same sweep count as the tree path's fixpoint
            # (both run min-label sweeps to convergence over one protocol)
            rec["ring_wall_s"] = None
            rec["ring_sweeps"] = st["n_sweeps"]
            ring_evals = (2 + st["n_sweeps"]) * n_pad * n_pad
        rec["ring_distance_evals"] = ring_evals
        rec["evals_ratio_ring_over_tree"] = (
            ring_evals / max(st["distance_evals"], 1))
        records[f"n{n}"] = rec
        emit(f"distributed/n{n}/tree-sharded", rec["tree_wall_s"] * 1e6,
             f"evals={st['distance_evals']};sweeps={st['n_sweeps']}")
        emit(f"distributed/n{n}/ring-dense",
             (rec["ring_wall_s"] or 0.0) * 1e6,
             f"evals={ring_evals};ratio="
             f"{rec['evals_ratio_ring_over_tree']:.1f}x")
    if json_out:
        with open(json_out, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {json_out}")
    return records


def _evals_only(sizes) -> dict:
    """Deterministic work counters for the sharded tree path (no timing,
    no ring comparator) — the ``run.py --check`` regression gate."""
    from repro.data import pointclouds
    from repro.distributed.ring_dbscan import tree_dbscan_sharded
    out = {}
    for n in sizes:
        pts = pointclouds.taxi_2d(n)
        _, st = tree_dbscan_sharded(pts, EPS, MINPTS, with_stats=True)
        out[f"n{n}"] = {"tree_distance_evals": st["distance_evals"],
                        "tree_sweeps": st["n_sweeps"]}
    return out


def _on_cpu() -> bool:
    """Is this process's backend the CPU? (Initializes it: on a chip the
    caller then holds the chip, which is why it must measure in-process.)"""
    import jax
    return jax.default_backend() == "cpu"


def _spawn(args) -> subprocess.CompletedProcess:
    """Re-run this module under 8 forced host devices (CPU only)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count="
                         f"{N_DEVICES}",
               PYTHONPATH=os.path.join(repo, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, "-m",
                           "benchmarks.bench_distributed", *args],
                          env=env, cwd=repo, text=True, capture_output=True)


def measure_evals(sizes) -> dict:
    """:func:`_evals_only` — under 8 forced host devices on CPU, on the
    real devices otherwise."""
    if not _on_cpu():
        return _evals_only(sizes)
    r = _spawn(["--evals-only", "--sizes", ",".join(str(n) for n in sizes)])
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise RuntimeError("bench_distributed evals-only run failed")
    for line in r.stdout.splitlines():
        if line.startswith("EVALS_JSON="):
            return json.loads(line[len("EVALS_JSON="):])
    raise RuntimeError(f"no EVALS_JSON line in output:\n{r.stdout}")


def run(sizes=(4096, 16384), quick: bool = False,
        json_out: str = "BENCH_distributed.json"):
    """Measure under 8 forced host devices (CPU: a relayed subprocess) or
    on the real devices (accelerator: in-process)."""
    if quick:
        sizes = tuple(n for n in sizes if n <= 16384)
    if not _on_cpu():
        return _inner(sizes, json_out)
    args = ["--inner", "--sizes", ",".join(str(n) for n in sizes)]
    if json_out:
        args += ["--json", json_out]
    r = _spawn(args)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise RuntimeError("bench_distributed inner run failed")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--evals-only", action="store_true")
    ap.add_argument("--sizes", default="4096,16384")
    ap.add_argument("--json", default="BENCH_distributed.json")
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    if args.evals_only:
        print("EVALS_JSON=" + json.dumps(_evals_only(sizes)))
    elif args.inner:
        _inner(sizes, args.json)
    else:
        run(sizes, json_out=args.json)


if __name__ == "__main__":
    main()
