"""Unified backend dispatch for DBSCAN (DESIGN.md §5).

One entry point — ``dbscan(points, eps, min_pts, algorithm="auto")`` —
serving the backends:

  * ``fdbscan``          — singleton-segment BVH (Morton order); the index
                           is eps-independent, so it is cached per point set
                           and reused verbatim across ``eps``/``min_pts``
                           sweeps (benchmarks/bench_eps.py's workload).
  * ``fdbscan-densebox`` — mixed dense-cell/loose-point BVH; the eps-grid
                           build doubles as the density probe that drives
                           the auto heuristic, so choosing this backend
                           costs no extra work.
  * ``tiled``            — the Pallas tile backend (kernels/ops.py):
                           n^2 streamed distance tiles beat a divergent
                           tree walk when the point count is small.
  * ``pallas-tree``      — the same tree algorithms with every traversal
                           run as the lane-tiled Pallas kernel
                           (kernels/traverse.py; DESIGN.md §9);
                           bit-identical labels. Explicit only, and off
                           TPU only: Mosaic refuses the kernel's
                           per-lane index gathers, so a TPU request
                           raises instead of running the emulator.
  * ``sharded``          — the multi-device tree path (DESIGN.md §6):
                           shard-local LBVH traversal + eps-halo exchange
                           (distributed/ring_dbscan.tree_dbscan_sharded).
                           Auto-selected whenever a mesh is passed; its
                           per-shard index is built inside the collective
                           program, so the plan itself carries no index.

``plan()`` performs the (cacheable) decision + index build; ``dbscan()``
executes a plan. Plans are memoized in a small LRU keyed by point-set
content hash + parameters, with the eps-independent fdbscan index shared
across all eps/min_pts entries of the same point set. Sharded plans are
index-free and mesh-determined, so they skip the content hash and the LRU
entirely (the compiled collective programs are cached per mesh/shape in
``repro.distributed.ring_dbscan._sharded_programs``).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, NamedTuple

import numpy as np
import jax.numpy as jnp

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from . import fdbscan, grid, lbvh, tune
from .validate import check_points

# Below this size the n^2 tile sweep is cheaper than divergent traversal
# (one 128x128 tile row per query block), and it keeps the CPU
# interpret-mode path exercised in tests.
TILED_MAX_POINTS = 1024
# Minimum fraction of points inside dense cells for the DenseBox index to
# pay for its grid pass (paper Fig. 6: sparse/high-minpts regimes have ~0).
DENSE_FRACTION_MIN = 0.05

_CACHE_MAX = 32
_plan_cache: "OrderedDict[Any, Any]" = OrderedDict()

ALGORITHMS = ("auto", "fdbscan", "fdbscan-densebox", "tiled", "sharded",
              "stream", "pallas-tree")


def _accel() -> bool:
    """Does jit target the TPU? (split out so tests can pin it)."""
    import jax
    return jax.default_backend() == "tpu"


def _attach_tune(p: "Plan", eps: float, min_pts: int) -> "Plan":
    """Resolve a pallas-tree plan's tuner state (core.tune; DESIGN.md §9).

    The decision rides in the plan LRU alongside the eps-independent
    index, so repeat runs reuse it (including the depth-rank calibration
    the first run performs). ``REPRO_TUNE=search`` configs are
    additionally cached under the bucketed :func:`core.tune.stats_key`,
    sharing one measured search across equal-shaped plans.
    """
    if p.backend != "pallas-tree" or p.tree is None:
        return p
    m = tune.mode()
    if m == "search":
        skey = ("tune-config", tune.stats_key(p.segs, eps, min_pts))
        hit = _cache_get(skey)
        if hit is None:
            with obs_trace.span("tune.search"):
                hit = _cache_put(
                    skey, tune.search(p.segs, p.tree, eps, min_pts))
            obs_metrics.inc("tune_searches_total")
        cfg, info = hit
        state = tune.TuneState(cfg)
        state.info = dict(info)
    else:
        state = tune.TuneState(tune.config_for(p.segs, p.tree, eps,
                                               min_pts, m))
    stats = dict(p.stats)
    stats["tuned_config"] = state.describe()
    return p._replace(tune=state, stats=stats)


class Plan(NamedTuple):
    """A resolved backend choice plus the (reusable) index that drove it.

    backend: one of "fdbscan", "fdbscan-densebox", "pallas-tree",
        "tiled", "sharded", "stream".
    segs / tree: the segment index and its LBVH (None for the index-free
        tiled/sharded backends, and tree is None below two segments).
    stats: occupancy/size stats behind the choice; ``stats["reason"]``
        states why this backend won; pallas-tree plans also record
        ``stats["tuned_config"]``.
    tune: the plan's ``core.tune.TuneState`` (pallas-tree only) — the
        per-phase engine/lane-tile/unroll/reorder decision plus the
        lazily-calibrated walk-depth oracle. Cached with the plan, so
        repeat runs reuse both the index *and* the calibration.
    """
    backend: str
    segs: grid.Segments | None
    tree: lbvh.Tree | None
    stats: dict
    tune: Any = None


def _mesh_ndev(mesh, axis: str) -> int:
    """Devices along ``axis`` (1 when the mesh lacks it — a mesh without a
    data axis never routes auto dispatch to the sharded backend)."""
    if mesh is None:
        import jax
        return len(jax.devices())
    from repro.distributed.sharding import _axis_size
    return _axis_size(mesh, axis)


def clear_cache() -> None:
    _plan_cache.clear()


def cache_info() -> dict:
    return {"entries": len(_plan_cache), "max": _CACHE_MAX}


def _points_key(points) -> str:
    arr = np.ascontiguousarray(np.asarray(points))
    h = hashlib.sha1(arr.tobytes())
    h.update(repr((arr.shape, str(arr.dtype))).encode())
    return h.hexdigest()


def _cache_get(key):
    if key in _plan_cache:
        _plan_cache.move_to_end(key)
        return _plan_cache[key]
    return None


def _cache_put(key, val):
    _plan_cache[key] = val
    _plan_cache.move_to_end(key)
    while len(_plan_cache) > _CACHE_MAX:
        _plan_cache.popitem(last=False)
    return val


def _tree_of(segs: grid.Segments):
    if segs.n_segments < 2 or segs.n_points < 2:
        return None
    return lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)


def _fdbscan_plan(points, pkey: str, stats: dict) -> Plan:
    """Plain-FDBSCAN plan; the index is eps-independent and shared across
    every (eps, min_pts) plan for the same point set."""
    base_key = (pkey, "fdbscan-index")
    cached = _cache_get(base_key)
    if cached is None:
        with obs_trace.span("build", index="fdbscan") as sp:
            segs = grid.build_segments_fdbscan(points)
            tree = _tree_of(segs)
            sp.watch(segs, tree)
        obs_metrics.inc("dispatch_index_builds_total", index="fdbscan")
        cached = _cache_put(base_key, (segs, tree))
    segs, tree = cached
    return Plan("fdbscan", segs, tree, stats)


def plan(points, eps: float, min_pts: int,
         algorithm: str = "auto", mesh=None, axis: str = "data") -> Plan:
    """Choose a backend and build (or fetch) its index.

    Instrumented (DESIGN.md §12): with a collector installed, planning is
    bracketed by a ``plan`` span (index builds get a nested ``build``
    span) and reports plan/cache-hit counters per backend; with none
    installed every instrumentation point is a no-op and the result is
    bit-identical.

    The densebox grid build is reused as the density probe: its dense-point
    fraction decides densebox-vs-plain, and on a densebox decision the very
    same segments become the index (no duplicated work). An active ``mesh``
    routes to the sharded multi-device tree path (whose per-shard index is
    built inside the collective program — nothing to cache here beyond the
    decision).

    Args:
        points: (n, d) point array (any array-like; converted to jnp).
        eps: DBSCAN radius (non-negative).
        min_pts: DBSCAN density threshold (the query point counts).
        algorithm: one of :data:`ALGORITHMS`; ``"auto"`` probes and picks.
        mesh: optional ``jax.sharding.Mesh``; with a ``axis`` data axis of
            size > 1 it routes auto dispatch to the sharded backend.
        axis: the mesh axis points are sharded over (default ``"data"``).

    Returns:
        A :class:`Plan` — resolved backend name, the (cacheable) index
        (``segs``/``tree``, ``None`` for index-free backends), and the
        stats dict that drove the decision (``stats["reason"]`` says why).

    Raises:
        ValueError: unknown ``algorithm``; negative ``eps``; malformed
            ``points`` (empty, non-numeric, NaN/Inf coordinates — see
            :func:`repro.core.validate.check_points`); ``mesh=`` combined
            with a single-device algorithm; a sharded request whose mesh
            lacks ``axis``; or a stream request with d ∉ {2, 3}.
        NotImplementedError: ``algorithm="pallas-tree"`` on a TPU (the
            kernel has no compiled TPU form; see
            ``repro.kernels.traverse.TPU_UNSUPPORTED``).
    """
    with obs_trace.span("plan", algorithm=algorithm) as sp:
        p = _plan_impl(points, eps, min_pts, algorithm, mesh, axis)
        sp.watch(p.segs, p.tree)
    obs_metrics.inc("dispatch_plans_total", backend=p.backend)
    return p


def _plan_impl(points, eps: float, min_pts: int, algorithm: str,
               mesh, axis: str) -> Plan:
    """The planning decision body; :func:`plan` wraps it in the span +
    counter instrumentation."""
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    if eps < 0:
        raise ValueError(f"eps must be non-negative; got {eps}"
                         " (a negative eps would be squared away silently)")
    if mesh is not None and algorithm not in ("auto", "sharded"):
        raise ValueError(
            f"mesh= is incompatible with algorithm={algorithm!r}: the "
            f"{algorithm} backend is single-device and would silently "
            "ignore it (use algorithm='sharded' or 'auto' to shard)")
    check_points(points)
    points = jnp.asarray(points)
    n, d = points.shape
    if mesh is not None and axis not in mesh.axis_names:
        if algorithm == "sharded":
            raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
        mesh = None  # a mesh without the data axis cannot shard points
    if (algorithm == "sharded"
            or (algorithm == "auto" and mesh is not None
                and _mesh_ndev(mesh, axis) > 1)):
        # sharded plans carry no index and depend only on the mesh, so no
        # point-content hash (an O(n) host transfer) and no cache needed
        return Plan("sharded", None, None,
                    {"n": n, "d": d, "ndev": _mesh_ndev(mesh, axis),
                     "mesh": mesh, "axis": axis,
                     "reason": ("explicit" if algorithm == "sharded"
                                else "mesh active: shard-local trees")})
    pkey = _points_key(points)
    key = (pkey, float(eps), int(min_pts), algorithm)
    hit = _cache_get(key)
    if hit is not None:
        obs_metrics.inc("dispatch_plan_cache_hits_total")
        return hit
    obs_metrics.inc("dispatch_plan_cache_misses_total")

    stats: dict = {"n": n, "d": d}
    if algorithm == "stream":
        # the streaming handle wraps the plain fdbscan index, which is
        # eps-independent — every (eps, min_pts) stream plan for the same
        # point set shares one cached index build
        if d not in (2, 3):
            raise ValueError(f"streaming index needs d in (2, 3); got {d}")
        stats["reason"] = "explicit: streaming two-level index"
        return _cache_put(key,
                          _fdbscan_plan(points, pkey, stats)._replace(
                              backend="stream"))
    if algorithm == "tiled" or (algorithm == "auto" and n <= TILED_MAX_POINTS):
        stats["reason"] = ("explicit" if algorithm == "tiled"
                           else f"n <= {TILED_MAX_POINTS}: Pallas tiles win")
        return _cache_put(key, Plan("tiled", None, None, stats))

    if algorithm == "pallas-tree":
        # the Pallas traversal kernel over the plain (eps-independent,
        # cached) fdbscan index
        if _accel():
            from repro.kernels.traverse import TPU_UNSUPPORTED
            raise NotImplementedError(TPU_UNSUPPORTED)
        stats["reason"] = "explicit: Pallas traversal kernel"
        return _cache_put(key, _attach_tune(
            _fdbscan_plan(points, pkey, stats)._replace(
                backend="pallas-tree"), eps, min_pts))

    if algorithm == "fdbscan" or d not in (2, 3):
        stats["reason"] = ("explicit" if algorithm == "fdbscan"
                           else "no eps-grid for this dimensionality")
        return _cache_put(key, _fdbscan_plan(points, pkey, stats))

    # eps-grid build: density probe and (potentially) the index itself
    with obs_trace.span("build", index="densebox") as sp:
        segs = grid.build_segments_densebox(points, eps, min_pts)
        sp.watch(segs)
    obs_metrics.inc("dispatch_index_builds_total", index="densebox")
    dense_frac = float(np.asarray(segs.dense_pt).mean())
    stats.update(dense_fraction=dense_frac, n_segments=segs.n_segments)
    if algorithm == "fdbscan-densebox" or dense_frac >= DENSE_FRACTION_MIN:
        stats["reason"] = ("explicit" if algorithm == "fdbscan-densebox"
                           else f"dense_fraction >= {DENSE_FRACTION_MIN}")
        return _cache_put(key, Plan("fdbscan-densebox", segs,
                                    _tree_of(segs), stats))
    stats["reason"] = f"dense_fraction < {DENSE_FRACTION_MIN}: plain tree"
    return _cache_put(key, _fdbscan_plan(points, pkey, stats))


def dbscan(points, eps: float, min_pts: int, *, algorithm: str = "auto",
           star: bool = False, frontier: bool = True, mesh=None,
           axis: str = "data",
           query_plan: Plan | None = None) -> fdbscan.DBSCANResult:
    """DBSCAN with automatic backend selection (the unified entry point).

    ``query_plan`` short-circuits planning entirely — pass the result of a
    previous :func:`plan` call *for the same point set* to amortize the
    index build across runs (the plan's index, not ``points``, is what a
    tree backend clusters). ``mesh`` (a jax Mesh with a data axis) routes
    auto dispatch to the sharded multi-device tree path.

    Args:
        points: (n, d) point array.
        eps: DBSCAN radius (non-negative).
        min_pts: DBSCAN density threshold (the query point counts, so a
            point with ``min_pts - 1`` neighbors is core).
        algorithm: backend request, see :func:`plan`.
        star: DBSCAN* variant — no border points, non-core points are
            noise (not supported by the sharded backend).
        frontier: restrict label sweeps to the changed-point frontier
            (exact, default True); only meaningful for the single-device
            tree backends.
        mesh / axis: multi-device routing, see :func:`plan`.
        query_plan: a previous :func:`plan` result for the same points.

    Returns:
        A :class:`repro.core.fdbscan.DBSCANResult`; ``labels[i] == -1``
        marks noise, ``backend`` names the backend that actually ran.

    Raises:
        ValueError: invalid parameters (see :func:`plan`), or
            ``frontier``/``star`` combined with a backend that would
            silently ignore them.
        NotImplementedError: ``star=True`` on the sharded backend.
    """
    check_points(points)    # before jnp.asarray: non-numeric dtypes must
    points = jnp.asarray(points)    # raise ValueError, not jax TypeError
    p = query_plan if query_plan is not None else plan(points, eps, min_pts,
                                                       algorithm, mesh=mesh,
                                                       axis=axis)
    if p.backend in ("tiled", "stream", "sharded") and frontier is not True:
        raise ValueError(
            f"frontier={frontier!r} is incompatible with the {p.backend} "
            "backend: frontier restriction only applies to the single-"
            "device tree-sweep backends and would silently be ignored "
            "(drop the kwarg, or pick "
            "algorithm='fdbscan'/'fdbscan-densebox')")
    with obs_trace.span("dbscan", backend=p.backend,
                        n=points.shape[0]) as sp:
        res = _execute(p, points, eps, min_pts, star=star,
                       frontier=frontier, mesh=mesh, axis=axis)
        sp.watch(res.labels, res.core_mask)
    obs_metrics.inc("dbscan_runs_total", backend=p.backend)
    obs_metrics.observe("dbscan_sweeps", res.n_sweeps, backend=p.backend)
    return res


def _execute(p: Plan, points, eps: float, min_pts: int, *, star: bool,
             frontier: bool, mesh, axis: str) -> fdbscan.DBSCANResult:
    """Run a resolved plan; :func:`dbscan` wraps it in the span +
    counter instrumentation."""
    if p.backend == "sharded":
        from repro.distributed.ring_dbscan import tree_dbscan_sharded
        if star:
            raise NotImplementedError("sharded backend has no DBSCAN* mode")
        res = tree_dbscan_sharded(points, eps, min_pts,
                                  mesh=p.stats.get("mesh", mesh),
                                  axis=p.stats.get("axis", axis))
        return res._replace(backend="sharded")
    if p.backend == "stream":
        # one-shot execution of a stream plan: bootstrap a handle over the
        # plan's (cached, eps-independent) index and materialize labels
        from repro.stream import StreamingDBSCAN
        h = StreamingDBSCAN(points, eps, min_pts, index=(p.segs, p.tree))
        return h.snapshot(star=star)
    if p.backend == "tiled":
        from repro.kernels import ops
        return ops.dbscan_tiled(points, eps, min_pts, star=star)
    if p.tune is not None:
        # Record the decision in the metrics snapshot (DESIGN.md §12):
        # an info-style gauge whose labels carry the per-phase choice.
        desc = p.tune.describe()
        for ph in ("first_pass", "sweep", "border"):
            c = desc[ph]
            obs_metrics.set_gauge(
                "tuned_config_info", 1.0, phase=ph, engine=c["engine"],
                lane_tile=str(c["lane_tile"]), unroll=str(c["unroll"]),
                reorder=c["reorder"], source=desc["source"])
    return fdbscan.cluster_from_index(p.segs, p.tree, eps, min_pts,
                                      star=star, frontier=frontier,
                                      backend=p.backend, tune=p.tune)


def stream_handle(points, eps: float, min_pts: int, *,
                  window: int | None = None,
                  wal=None, checkpoint_path: str | None = None,
                  checkpoint_every: int = 0, **kwargs):
    """Build a :class:`repro.stream.StreamingDBSCAN` handle over ``points``.

    Goes through :func:`plan`, so the handle's main tree is the *cached*
    eps-independent fdbscan index — building handles (or running batch
    ``dbscan``) for several ``eps``/``min_pts`` values over the same point
    set shares one index build.

    The durability options make the handle crash-safe (DESIGN.md §10):
    with ``wal`` every insert is durably logged before it is applied, and
    with ``checkpoint_path`` (+ ``checkpoint_every``) the full state is
    atomically serialized every K index merges.  After a crash,
    ``StreamingDBSCAN.restore(checkpoint_path, wal=wal)`` rebuilds the
    handle from the last checkpoint plus a WAL replay — this is what
    ``launch/serve.py --restore`` runs.

    Args:
        points: (n, d) initial points, d in (2, 3), n >= 2.
        eps: DBSCAN radius (non-negative).
        min_pts: DBSCAN density threshold.
        window: optional sliding-window size — every insert auto-expires
            points whose insert id falls below ``n_points - window``
            (insert-order watermark; see ``StreamingDBSCAN.expire``).
        wal: optional write-ahead-log path (or a prebuilt
            ``repro.stream.durability.WriteAheadLog``).
        checkpoint_path: optional checkpoint file for
            :meth:`StreamingDBSCAN.checkpoint` and the auto policy.
        checkpoint_every: auto-checkpoint after every K merges (0 = off).
        **kwargs: passed to the handle (e.g. ``merge_ratio``, the
            delta/main size ratio that triggers a full index merge, or
            ``buffer_max``/``growth``, the tiered-compaction knobs).

    Returns:
        A live ``StreamingDBSCAN`` handle exposing ``insert`` /
        ``delete`` / ``expire`` / ``query`` / ``snapshot`` / ``merge`` /
        ``compact`` / ``checkpoint`` (DESIGN.md §7, §10, §11); after any
        interleaving of inserts, deletes, expiries, merges and
        compactions, ``snapshot()`` is component-identical to batch
        :func:`dbscan` on exactly the surviving points.

    Raises:
        ValueError: malformed ``points`` (empty, NaN/Inf, d outside
            (2, 3)), negative ``eps``, or inserts that change
            dimensionality (raised by the handle).
        repro.stream.durability.WALError: ``wal`` names a file with
            leftover records from a crashed run (restore it instead).
    """
    from repro.stream import StreamingDBSCAN
    points = jnp.asarray(points)
    p = plan(points, eps, min_pts, algorithm="stream")
    return StreamingDBSCAN(points, eps, min_pts,
                           index=(p.segs, p.tree), window=window, wal=wal,
                           checkpoint_path=checkpoint_path,
                           checkpoint_every=checkpoint_every, **kwargs)


def tenant_handles(points, tenants: dict) -> dict:
    """Build one streaming handle per tenant over ONE shared index build.

    ``tenants`` maps tenant name -> kwargs for :func:`stream_handle`
    (``eps`` and ``min_pts`` required; durability/window/compaction
    options per tenant).  The eps-independent part of the bootstrap —
    the Morton sort + LBVH over ``points`` — is cached under the point
    set's content hash, so N tenants cost one index build plus N
    eps-dependent clusterings; ``dispatch_index_builds_total`` moves by
    exactly one however many tenants share the point set.  This is the
    serving plane's multi-tenant entry point
    (:func:`repro.serve.tenants.build_views`).
    """
    if not tenants:
        raise ValueError("tenant_handles needs at least one tenant")
    points = jnp.asarray(points)
    handles = {}
    with obs_trace.span("plan.tenants", n_tenants=len(tenants)):
        for name, kw in tenants.items():
            kw = dict(kw)
            try:
                eps = kw.pop("eps")
                min_pts = kw.pop("min_pts")
            except KeyError as e:
                raise ValueError(f"tenant {name!r}: missing {e} in spec")
            handles[name] = stream_handle(points, eps, min_pts, **kw)
    return handles
