#!/usr/bin/env python3
"""Chip smoke test: the main DBSCAN paths on a TPU, checked for exactness.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded path on four chips

One chip runs, through the public entry points:

  * every branch of ``repro.dbscan(..., algorithm="auto")`` — the tile
    kernel (n <= 1024) and the tree backends on a 2-D and a 3-D regime at
    n = 2**16 — each checked against the blocked float64 oracle
    (``core.validate.check_dbscan``);
  * on the 2-D tree input, ``auto`` again (warm) and the other exact tree
    backend (``fdbscan`` or ``fdbscan-densebox``): component-identical;
  * a streaming handle over 2**16 taxi points that takes 1% inserts and
    1% deletes; its snapshot must be component-identical to batch
    ``dbscan`` on the survivors.

The tree inputs are 2**16 points: on a TPU v5e one ``auto`` call at
n = 2**21 did not finish within 400 s, so larger inputs do not fit the
smoke's time limit (the tree walk's speed on the chip is open work). For
the same reason plain ``fdbscan`` on the 3-D input (280 s there, cold)
runs only under ``--chips 4``, as the sharded path's reference.

``--chips 4`` runs only the sharded path (``mesh=``) on the 3-D halo
regime at n = 2**16 and compares its labels, bit for bit, with
single-chip ``fdbscan``.

Lines starting ``info:`` are information (sizes, backends, wall times,
memory), not benchmark records. The last line of standard output is one
JSON object naming the device. Any failed check raises, so the script
exits non-zero without that line; it also refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.core.validate import (check_component_identical,  # noqa: E402
                                 check_dbscan)
from repro.data import pointclouds  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402

# (dataset, n, eps, min_pts) for each auto-dispatch branch
BRANCHES = [
    ("blobs", 1000, 0.05, 8),                  # n <= 1024: tile kernel
    ("portotaxi_like", 1 << 16, 0.01, 50),     # 2-D tree
    ("hacc_like", 1 << 16, 0.03, 5),           # 3-D tree
]
STREAM = ("portotaxi_like", 1 << 16, 0.01, 50)
SHARDED = ("hacc_like", 1 << 16, 0.03, 5)


_T0 = time.perf_counter()


def info(msg: str) -> None:
    print(f"info: [{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    res = fn(*args, **kw)
    jax.block_until_ready((res.labels, res.core_mask))
    return res, time.perf_counter() - t0


def check_branch(name: str, n: int, eps: float, min_pts: int) -> str:
    """One auto-dispatch call, validated against the oracle; returns the
    backend auto chose."""
    pts = pointclouds.load(name, n)
    res, wall = _timed(repro.dbscan, pts, eps, min_pts, algorithm="auto")
    t0 = time.perf_counter()
    check_dbscan(pts, eps, min_pts, res.labels, res.core_mask)
    info(f"auto {name} n={n} eps={eps} min_pts={min_pts}: "
         f"backend={res.backend} clusters={res.n_clusters} cold_s={wall} "
         f"oracle_s={time.perf_counter() - t0} ok")
    return res.backend


def check_tile_kernel_compiled(n: int = 1000, d: int = 2) -> None:
    """The tiled backend's program on this device holds the Mosaic kernel
    (``tpu_custom_call``), not the Pallas interpreter's plain HLO."""
    from repro.kernels import ops
    pts = jax.ShapeDtypeStruct((n, d), np.float32)
    hlo = ops._tiled_phases.lower(pts, 0.05, 8, None, 128).compile()
    assert "tpu_custom_call" in hlo.as_text(), "tile kernel not compiled"
    info(f"tiled program n={n} d={d}: tpu_custom_call present")


def check_real_size(name: str, n: int, eps: float, min_pts: int) -> None:
    """auto (warm: :func:`check_branch` ran it first), then every other
    exact tree backend on the same point set: all component-identical.
    The backend auto chose is not run again under its own name: that is
    the same cached plan and the same programs."""
    pts = pointclouds.load(name, n)
    res, warm = _timed(repro.dbscan, pts, eps, min_pts, algorithm="auto")
    info(f"real {name} n={n} eps={eps} min_pts={min_pts}: "
         f"auto backend={res.backend} clusters={res.n_clusters} "
         f"sweeps={res.n_sweeps} warm_s={warm}")
    for algo in ("fdbscan", "fdbscan-densebox"):
        if algo == res.backend:
            continue
        other, wall = _timed(repro.dbscan, pts, eps, min_pts, algorithm=algo)
        check_component_identical(res.labels, res.core_mask,
                                  other.labels, other.core_mask)
        info(f"real {name} {algo}: clusters={other.n_clusters} "
             f"sweeps={other.n_sweeps} cold_s={wall} "
             "component-identical to auto")
    info(f"real {name}: peak_bytes_in_use={peak_bytes()}")


def check_stream(name: str, n: int, eps: float, min_pts: int,
                 seed: int = 0) -> None:
    """Bootstrap on n points, insert 1% more, delete 1%; the snapshot
    must match batch dbscan on exactly the survivors."""
    k = max(1, n // 100)
    pts = pointclouds.load(name, n + k)
    h = repro.stream_handle(pts[:n], eps, min_pts)
    h.insert(pts[n:])
    rng = np.random.default_rng(seed)
    gone = rng.choice(n + k, size=k, replace=False)
    assert h.delete(gone) == k
    alive = np.setdiff1d(np.arange(n + k), gone)
    snap = h.snapshot()
    # the plain index: the same program shapes as the handle's own
    ref = repro.dbscan(pts[alive], eps, min_pts, algorithm="fdbscan")
    check_component_identical(snap.labels, snap.core_mask,
                              ref.labels, ref.core_mask)
    info(f"stream {name} n={n} +{k} inserts -{k} deletes: "
         f"clusters={snap.n_clusters}, component-identical to batch "
         f"({ref.backend}) on {len(alive)} survivors")


def check_sharded(name: str, n: int, eps: float, min_pts: int,
                  ndev: int) -> None:
    """The ``mesh=`` entry point on ``ndev`` devices: labels bit-identical
    to single-device fdbscan, each device holding its own slab."""
    from repro.distributed.ring_dbscan import tree_dbscan_sharded
    pts = pointclouds.load(name, n)
    mesh = jax.make_mesh((ndev,), ("data",))
    res, cold = _timed(repro.dbscan, pts, eps, min_pts, mesh=mesh)
    assert res.backend == "sharded", res.backend
    t0 = time.perf_counter()
    again, st = tree_dbscan_sharded(pts, eps, min_pts, mesh=mesh,
                                    with_stats=True)
    warm = time.perf_counter() - t0
    ref, single = _timed(repro.dbscan, pts, eps, min_pts,
                         algorithm="fdbscan")
    for other in (again, ref):
        assert np.array_equal(np.asarray(res.labels),
                              np.asarray(other.labels)), "labels differ"
        assert np.array_equal(np.asarray(res.core_mask),
                              np.asarray(other.core_mask)), "core differs"
    rows = st["shard_rows"]
    assert len(rows) == ndev and set(rows.values()) == {st["n_pad"] // ndev}, \
        rows
    info(f"sharded {name} n={n} eps={eps} min_pts={min_pts} on {ndev} "
         f"devices: clusters={res.n_clusters} sweeps={st['n_sweeps']} "
         f"cold_s={cold} warm_s={warm}; fdbscan on one device "
         f"{single}s; labels bit-identical")
    info(f"sharded distance_evals={st['distance_evals']} "
         f"ring_distance_evals={st['ring_distance_evals']} "
         f"shard_rows={rows}")
    for dev in jax.devices()[:ndev]:
        stats = dev.memory_stats() or {}
        info(f"{dev}: bytes_in_use={stats.get('bytes_in_use')} "
             f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: no TPU (JAX found {dev.platform}); this smoke test "
              "runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} but JAX found {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    info(f"compile cache: {use_compile_cache()}")
    info(f"device {dev.platform} {dev.device_kind} x{len(devices)}")

    if args.chips == 4:
        check_sharded(*SHARDED, ndev=4)
    else:
        backends = [check_branch(*b) for b in BRANCHES]
        assert backends[0] == "tiled", backends
        check_tile_kernel_compiled()
        check_real_size(*BRANCHES[1])
        check_stream(*STREAM)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
