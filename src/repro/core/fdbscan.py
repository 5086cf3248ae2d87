"""FDBSCAN and FDBSCAN-DenseBox — the paper's two tree-based algorithms.

Two bulk phases over a segment BVH (DESIGN.md §1, §3):

  fused first pass (DESIGN.md §4): ONE traversal computes the neighbor
      count *and* a min-neighbor-label candidate, collapsing core-point
      preprocessing and the first main-phase sweep — the paper's claim that
      clustering costs stay within ~2x of neighbor determination hinges on
      exactly this fusion. The candidate is validated against the core mask
      after the pass (a candidate gathered from a non-core neighbor is
      discarded), so the hook only ever merges genuine core-core pairs.

  main: min-label propagation sweeps fused into the traversal (hook) +
      pointer jumping (DESIGN.md §3 explains why this replaces the GPU's
      atomic-CAS union-find), iterated to a fixpoint. Sweeps restrict
      their gathers to the *frontier* — the points whose label changed
      last sweep (ECL-CC-style active-set restriction; DESIGN.md §4).
      Because labels decrease monotonically under a min hook, the
      restriction is exact, so the first no-change sweep certifies the
      fixpoint with no separate verification pass. Border points are
      assigned in one final gather and never propagate labels — this
      removes the paper's critical section (no cluster bridging by
      construction).

Memory is O(n + m): neighbor lists are never materialized.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from . import grid, lbvh, traversal, unionfind
from .validate import check_points

INT_MAX = traversal.INT_MAX

# Frontier id vectors are padded to the next power of two (floor below) so
# the jitted traversal sees a bounded number of distinct shapes per run.
_PAD_MIN = 64


class DBSCANResult(NamedTuple):
    """The result record every DBSCAN backend returns.

    labels: (n,) int32 cluster id in [0, n_clusters), or -1 for noise,
        in the caller's original point order. Cluster ids are compact and
        deterministic (derived from each component's smallest original
        index), so equal inputs give byte-equal labels across runs.
    core_mask: (n,) bool — the point has >= min_pts neighbors within eps
        (itself included).
    n_clusters: number of distinct non-noise labels.
    n_sweeps: main-phase label sweeps until fixpoint, including the fused
        first pass (DESIGN.md §4).
    n_traversals: total tree walks this run (``n_sweeps + 1`` for the
        tree backends with border assignment; -1 where not applicable,
        e.g. the tiled backend).
    backend: the resolved backend name that produced this result.
    """
    labels: jax.Array
    core_mask: jax.Array
    n_clusters: int
    n_sweeps: int
    n_traversals: int = -1
    backend: str = ""


def _unify_dense(labels, segs: grid.Segments):
    """Equalize labels within dense segments (paper: one UNION per cell)."""
    m = segs.n_segments
    seg_min = jax.ops.segment_min(labels, segs.seg_of_point, num_segments=m)
    dense_lab = seg_min[segs.seg_of_point]
    return jnp.where(segs.dense_pt, jnp.minimum(labels, dense_lab), labels)


@partial(jax.jit, static_argnames=("min_pts",))
def _preprocess(tree, segs, eps, min_pts: int):
    """Standalone core-point determination with early exit at min_pts.

    Kept as the unfused reference (tests compare it against the fused first
    pass); the production path is ``_fused_first_pass``.
    """
    # Dense members are core by construction; only loose points traverse.
    counts = traversal.count_neighbors(tree, segs, eps, cap=min_pts,
                                       query_active=~segs.dense_pt)
    core = segs.dense_pt | (counts >= min_pts)
    return core


@partial(jax.jit, static_argnames=("traverse_fn",))
def _fused_first_pass_jit(tree, segs, eps, min_pts, depth_rank=None,
                          traverse_fn=traversal.traverse):
    n = segs.n_points
    idx = jnp.arange(n, dtype=jnp.int32)
    # Candidate labels as if every point were core: own index, unified
    # within dense cells. Every gathered value is therefore a sorted index
    # whose core status can be checked once counts are known.
    vals0 = _unify_dense(idx, segs)
    # hits excludes the query itself: |N_eps(q)| >= min_pts <=> hits >= mp-1,
    # so the count may saturate at min_pts - 1 (re-arming the dense
    # short-circuit for saturated lanes — the fused early exit).
    tr = traversal.fused_count_minlabel(tree, segs, eps, vals0,
                                        cap=min_pts - 1,
                                        traverse_fn=traverse_fn,
                                        depth_rank=depth_rank)
    core = segs.dense_pt | (tr.hits >= min_pts - 1)
    # Validate the candidate: vals0 maps loose points to themselves and
    # dense points to a dense (hence core) member, so core[cand] holds iff
    # the contributing neighbor is core — a sound hook (DESIGN.md §4).
    cand = tr.acc
    cand_ok = core[jnp.clip(cand, 0, n - 1)]
    labels0 = jnp.where(core, jnp.where(cand_ok, cand, vals0),
                        jnp.int32(INT_MAX))
    labels0 = jnp.where(core, _unify_dense(labels0, segs), labels0)
    labels0 = jnp.where(core, unionfind.jump_to_fixpoint(
        jnp.where(core, labels0, idx)), labels0)
    # A core query with a valid candidate has absorbed the min over *every*
    # neighbor's initial value; in the next sweep it only needs to gather
    # from points whose label changed since init (DESIGN.md §4).
    absorbed = cand_ok & core
    return core, labels0, vals0, absorbed, tr


def _fused_first_pass(tree, segs, eps, min_pts: int,
                      traverse_fn=traversal.traverse, depth_rank=None):
    """(core, labels0, vals0, absorbed, trace) from a single traversal.

    ``traverse_fn`` selects the walk's execution engine — default the
    vmapped reference engine; the ``pallas-tree`` backend passes a
    ``repro.kernels.traverse.traverse`` configuration (bit-identical
    results). ``depth_rank`` is the kernel's optional lane-scheduling
    oracle (``core.tune``); it never changes results.
    """
    return _fused_first_pass_jit(tree, segs, eps,
                                 jnp.asarray(min_pts, jnp.int32),
                                 depth_rank,
                                 traverse_fn=traverse_fn)


def _pad_size(k: int) -> int:
    """Pad length with quarter-power-of-two granularity: bounded distinct
    jit shapes (~4 per octave) without the up-to-2x lane waste of pure
    power-of-two buckets."""
    size = _PAD_MIN
    while size < k:
        size *= 2
    if size > _PAD_MIN:
        quarter = size // 4
        size = -(-k // quarter) * quarter
    return max(size, _PAD_MIN)


def _compact_ids(mask_np: np.ndarray) -> jax.Array:
    """Active sorted-point ids, padded with -1 to a bucketed length."""
    idx = np.flatnonzero(mask_np).astype(np.int32)
    out = np.full(_pad_size(len(idx)), -1, np.int32)
    out[:len(idx)] = idx
    return jnp.asarray(out)


def _engine_name(traverse_fn) -> str:
    """Metric label for the walk's execution engine."""
    return "reference" if traverse_fn is traversal.traverse else "pallas"


def _record_trace(phase: str, engine: str, tr) -> None:
    """Fold a traversal Trace's work counters into the active metrics
    registry (DESIGN.md §12).  Reading the counters forces a device sync,
    so this is gated on an installed registry — with none, the traversal
    result is never touched and timing is unperturbed."""
    if obs_metrics.active() is None:
        return
    obs_metrics.inc("traversal_evals_total", float(jnp.sum(tr.evals)),
                    phase=phase, engine=engine)
    obs_metrics.inc("traversal_iters_total", float(jnp.sum(tr.iters)),
                    phase=phase, engine=engine)


def _gather_minlabel(tree, segs, eps, labels, gather_mask, ids,
                     node_mask=None, traverse_fn=traversal.traverse,
                     depth_rank=None):
    """One (possibly compacted/pruned) min-label sweep, full-width output."""
    kw = {} if depth_rank is None else {"depth_rank": depth_rank}
    tr = traverse_fn(tree, segs,
                     traversal.intersects(traversal.sphere(eps), ids=ids),
                     traversal.MinLabelVisitor(labels, gather_mask),
                     node_mask=node_mask, **kw)
    n = segs.n_points
    safe = jnp.where(ids >= 0, ids, jnp.int32(n))  # padding -> dropped
    gathered = jnp.full(n, INT_MAX, jnp.int32).at[safe].set(
        jnp.where(ids >= 0, tr.acc, INT_MAX), mode="drop")
    return gathered, tr


@jax.jit
def _post_sweep(tree, segs, labels, core, ids, acc):
    """Scatter-back + hook + dense unification + pointer jumping + change
    detection + next sweep's node flags, fused into one dispatch (the host
    loop's per-sweep cost is dominated by dispatch overhead otherwise)."""
    n = labels.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    safe = jnp.where(ids >= 0, ids, jnp.int32(n))  # padding -> dropped
    gathered = jnp.full(n, INT_MAX, jnp.int32).at[safe].set(
        jnp.where(ids >= 0, acc, INT_MAX), mode="drop")
    new = unionfind.hook(labels, gathered, mask=core)
    new = _unify_dense(jnp.where(core, new, labels), segs)
    new = jnp.where(core, unionfind.jump_to_fixpoint(
        jnp.where(core, new, idx)), new)
    changed = (new != labels) & core
    return new, changed, _frontier_node_mask(tree, segs, changed)


@jax.jit
def _frontier_node_mask(tree, segs, changed):
    """Per-node 'subtree holds a changed point' flag for descent pruning."""
    seg_changed = jax.ops.segment_max(changed.astype(jnp.int32),
                                      segs.seg_of_point,
                                      num_segments=segs.n_segments).astype(bool)
    return lbvh.propagate_leaf_flags(tree, seg_changed)


# A pair within eps spans at most ceil(eps / cell_edge) cells per axis;
# cell_edge >= eps/sqrt(d) (d <= 3), so radius 2 always covers.
_CELL_DILATE = 2


def _cell_keys(pts, eps: float) -> np.ndarray:
    """int64 eps-grid cell key per (sorted) point, for the frontier filter."""
    cells, _ = grid._cell_coords(jnp.asarray(pts), eps)
    c = np.asarray(cells).astype(np.int64)
    if c.shape[1] == 2:
        return (c[:, 0] << 21) | c[:, 1]
    return (c[:, 0] << 42) | (c[:, 1] << 21) | c[:, 2]


def _near_changed(keys: np.ndarray, d: int, changed_np: np.ndarray
                  ) -> np.ndarray:
    """Points whose eps-cell is within the dilation radius of a changed
    point's cell — a sound superset of 'has a changed point within eps'."""
    changed_keys = np.unique(keys[changed_np])
    r = range(-_CELL_DILATE, _CELL_DILATE + 1)
    # arithmetic (not bitwise) composition: offsets have negative components
    if d == 2:
        offs = [(dx << 21) + dy for dx in r for dy in r]
    else:
        offs = [(dx << 42) + (dy << 21) + dz
                for dx in r for dy in r for dz in r]
    dilated = (changed_keys[:, None] + np.asarray(offs, np.int64)).ravel()
    return np.isin(keys, dilated)


def _sweep_to_fixpoint(tree, segs, eps, core, labels0, *,
                       frontier: bool = True, collect_stats: bool = False,
                       fused_init=None, traverse_fn=traversal.traverse,
                       tune=None):
    """Hook+jump sweeps until the core-core components stabilize.

    Frontier restriction (DESIGN.md §4): labels only ever decrease and the
    hook is a monotone min, so a point already holds everything it gathered
    in earlier sweeps — gathering over *only the points whose label changed
    last sweep* is exact, not a heuristic. Each frontier sweep therefore
    (a) masks the gather to changed points and (b) prunes tree descent into
    subtrees containing no changed point, so lanes far from any change die
    within a few box tests. Dense-cell unification marks every member of a
    changed cell as changed, which flags the cell's subtree — points that
    neighbor such a cell re-discover it through the unpruned walk. Labels
    and sweep counts are identical to full sweeps; only the work shrinks.

    Returns (labels, sweeps, stats) with per-sweep frontier sizes and
    loop-trip totals.
    """
    n = segs.n_points
    d = segs.pts.shape[1]
    core_np = np.asarray(core)
    n_core = int(core_np.sum())
    # Query-side restriction only pays once the frontier is genuinely
    # small; above this the cell filter is host overhead for nothing.
    small = max(_PAD_MIN, n_core // 4)
    labels = labels0
    ids_core = _compact_ids(core_np)  # default: every core point gathers
    ids = ids_core
    gather_mask = core            # sweep 1 is full: nothing gathered yet
    # every gather mask is a subset of core, so subtrees holding only
    # non-core points (noise regions) are prunable from sweep one on
    node_mask_core = _frontier_node_mask(tree, segs, core)
    node_mask = node_mask_core
    # eps <= 0 is degenerate (no grid): skip the cell filter, keep the
    # (still exact) gather-mask + node-mask frontier restriction
    cell_keys = _cell_keys(segs.pts, eps) if frontier and eps > 0 else None
    dual = None
    gather_wide = None            # wide lanes' gather mask (split sweep 1)
    if frontier and fused_init is not None:
        # Split first sweep: queries that absorbed every initial value in
        # the fused pass gather changed-since-init points only (narrow);
        # the validation-rejected minority gathers the full core set
        # (wide). One walk, per-lane mask choice — exact either way.
        vals0, absorbed = fused_init
        changed0 = core & (labels0 != vals0)
        changed0_np = np.asarray(changed0)
        wide_np = core_np & ~np.asarray(absorbed)
        if cell_keys is not None and int(changed0_np.sum()) <= small:
            near0 = (_near_changed(cell_keys, d, changed0_np)
                     if changed0_np.any() else np.zeros(n, bool))
            active_np = wide_np | (core_np & near0)
            ids = _compact_ids(active_np)
            ids_np = np.asarray(ids)
            lane_wide = jnp.asarray(
                np.where(ids_np >= 0, wide_np[np.maximum(ids_np, 0)], False))
            gather_mask = changed0
            gather_wide = core
            dual = dict(wide_lanes=lane_wide,
                        node_mask_wide=node_mask_core)
            node_mask = _frontier_node_mask(tree, segs, changed0)
    sweeps = 0
    stats = {"frontier_per_sweep": [], "active_per_sweep": [],
             "iters_per_sweep": [], "evals_per_sweep": []}
    while True:
        # Per-sweep engine resolution (core.tune): the compacted lane
        # count shrinks as the frontier drains, and small batches run the
        # reference engine. The padded id length is a host-known shape,
        # so no device sync is added.
        sweep_fn, rank_kw = traverse_fn, {}
        if tune is not None:
            from . import tune as tune_mod
            cfg = tune.phase("sweep", n_lanes=int(ids.shape[0]))
            sweep_fn = tune_mod.engine_fn(cfg)
            rank = tune.rank_for(cfg)
            if rank is not None:
                rank_kw = {"depth_rank": rank}
        engine = _engine_name(sweep_fn)
        with obs_trace.span("sweep", i=sweeps + 1, engine=engine) as sp:
            tr = sweep_fn(
                tree, segs,
                traversal.intersects(traversal.sphere(eps), ids=ids),
                traversal.MinLabelVisitor(labels, gather_mask,
                                          mask_wide=gather_wide),
                node_mask=node_mask, **(dual or {}), **rank_kw)
            dual = None           # only the first sweep may be split
            gather_wide = None
            new, changed, changed_flags = _post_sweep(tree, segs, labels,
                                                      core, ids, tr.acc)
            sp.watch(new, changed)
        _record_trace("sweep", engine, tr)
        sweeps += 1
        if collect_stats:
            stats["frontier_per_sweep"].append(int(jnp.sum(gather_mask)))
            stats["active_per_sweep"].append(int(jnp.sum(ids >= 0)))
            stats["iters_per_sweep"].append(int(jnp.sum(tr.iters)))
            stats["evals_per_sweep"].append(int(jnp.sum(tr.evals)))
        labels = new
        changed_np = np.asarray(changed)
        n_changed = int(changed_np.sum())
        if n_changed == 0:
            break
        if frontier:
            # gather only from changed points; prune unchanged subtrees;
            # and, once the frontier is small, re-traverse only queries
            # whose eps-cell neighborhood holds a changed point (anyone
            # else provably cannot improve)
            gather_mask = changed
            node_mask = changed_flags
            if cell_keys is not None and n_changed <= small:
                ids = _compact_ids(core_np & _near_changed(cell_keys, d,
                                                           changed_np))
            else:
                ids = ids_core
    return labels, sweeps, stats


def _main_phase(tree, segs, eps, core, *, frontier: bool = True):
    """Seed-compatible entry: (labels, sweeps) from a core mask."""
    n = segs.n_points
    labels0 = jnp.where(core, jnp.arange(n, dtype=jnp.int32),
                        jnp.int32(INT_MAX))
    labels0 = jnp.where(core, _unify_dense(labels0, segs), labels0)
    labels, sweeps, _ = _sweep_to_fixpoint(tree, segs, eps, core, labels0,
                                           frontier=frontier)
    return labels, sweeps


def _assign_borders(tree, segs, eps, core, core_labels,
                    traverse_fn=traversal.traverse, tune=None):
    """Borders take the min adjacent core root; isolated non-core -> noise.

    Traverses a compacted non-core query set (usually a small minority),
    pruning subtrees that hold no core point (nothing to gather there).
    """
    ids = _compact_ids(np.asarray(~core))
    depth_rank = None
    if tune is not None:
        from . import tune as tune_mod
        cfg = tune.phase("border", n_lanes=int(ids.shape[0]),
                         n=int(segs.n_points))
        traverse_fn = tune_mod.engine_fn(cfg)
        depth_rank = tune.rank_for(cfg)
    vals = jnp.where(core, core_labels, jnp.int32(INT_MAX))
    gathered, tr = _gather_minlabel(tree, segs, eps, vals, core, ids,
                                    node_mask=_frontier_node_mask(tree, segs,
                                                                  core),
                                    traverse_fn=traverse_fn,
                                    depth_rank=depth_rank)
    _record_trace("border", _engine_name(traverse_fn), tr)
    labels = jnp.where(core, core_labels, gathered)
    return jnp.where(labels == INT_MAX, jnp.int32(-1), labels)


def _finalize(labels_sorted, order, n):
    """Map sorted-space representative labels to compact original-order ids."""
    out = jnp.full(n, -1, jnp.int32).at[order].set(labels_sorted)
    # representative (sorted index) -> original index for determinism
    rep_orig = jnp.where(out >= 0, order[jnp.clip(out, 0, n - 1)], -1)
    uniq, inv = jnp.unique(rep_orig, return_inverse=True, size=n + 1,
                           fill_value=-2)
    has_noise = jnp.any(rep_orig == -1)
    compact = inv - jnp.where(has_noise, 1, 0)
    compact = jnp.where(rep_orig == -1, -1, compact)
    n_clusters = int(jnp.sum(uniq >= 0))
    return compact.astype(jnp.int32), n_clusters


def cluster_from_index(segs: grid.Segments, tree, eps: float, min_pts: int,
                       *, star: bool = False, frontier: bool = True,
                       backend: str = "", with_stats: bool = False,
                       tune=None):
    """Run the clustering phases over a prebuilt (segments, tree) index.

    ``tree`` may be None when ``segs.n_segments == 1`` (single dense cell).
    This is the entry the dispatcher (repro.core.dispatch) reuses so an
    index cached across ``eps``/``min_pts`` sweeps skips the build.
    ``backend="pallas-tree"`` runs every traversal through the Pallas
    kernel engine (``repro.kernels.traverse``; DESIGN.md §9) — labels,
    core masks, and sweep counts are bit-identical to the reference
    engine, only the walk's lowering changes. ``tune`` is an optional
    ``core.tune.TuneState`` selecting per-phase engine/lane-tile/unroll/
    reordering (the dispatcher attaches the plan's state; ``None`` with
    the pallas backend derives one from the ``REPRO_TUNE`` mode); tuning
    changes the schedule only, never the results.
    """
    n = segs.n_points
    stats: dict = {}
    # the walk's execution engine, resolved once for every phase below
    traverse_fn = traversal.traverse
    if backend == "pallas-tree":
        from repro.kernels import traverse as pallas_traverse
        from . import tune as tune_mod
        traverse_fn = pallas_traverse.traverse
        if tune is None and tree is not None:
            tune = tune_mod.TuneState(
                tune_mod.config_for(segs, tree, eps, min_pts))
    else:
        tune = None
    if n == 1:
        noise = min_pts > 1
        res = DBSCANResult(labels=jnp.array([-1 if noise else 0], jnp.int32),
                           core_mask=jnp.array([not noise]),
                           n_clusters=0 if noise else 1, n_sweeps=0,
                           n_traversals=0, backend=backend)
        return (res, stats) if with_stats else res

    if segs.n_segments == 1:
        # Everything inside one dense cell: one cluster, all core, 0 sweeps.
        res = DBSCANResult(labels=jnp.zeros(n, jnp.int32),
                           core_mask=jnp.ones(n, bool),
                           n_clusters=1, n_sweeps=0, n_traversals=0,
                           backend=backend)
        return (res, stats) if with_stats else res

    # Fused first pass: neighbor count + hooked labels in ONE traversal
    # (the seed spent two: a count pass and the first min-label sweep).
    fp_fn, fp_rank = traverse_fn, None
    if tune is not None:
        fp_cfg = tune.phase("first_pass")
        fp_fn = tune_mod.engine_fn(fp_cfg)
        fp_rank = tune.rank_for(fp_cfg)
    engine = _engine_name(fp_fn)
    with obs_trace.span("traverse", phase="first_pass", engine=engine) as sp:
        core, labels0, vals0, absorbed, first = _fused_first_pass(
            tree, segs, eps, min_pts, traverse_fn=fp_fn,
            depth_rank=fp_rank)
        sp.watch(core, labels0)
    _record_trace("first_pass", engine, first)
    if tune is not None:
        # The pass's per-query loop-trip counts are the depth oracle for
        # every later reorder="depth" traversal over this plan (free: the
        # kernel returns iters anyway).
        tune.calibrate(first.iters)
    core_labels, loop_sweeps, sweep_stats = _sweep_to_fixpoint(
        tree, segs, eps, core, labels0, frontier=frontier,
        collect_stats=with_stats, fused_init=(vals0, absorbed),
        traverse_fn=traverse_fn, tune=tune)
    n_sweeps = 1 + loop_sweeps          # the fused pass is sweep #1
    n_traversals = n_sweeps

    if star:
        labels_sorted = jnp.where(core, core_labels, jnp.int32(-1))
    else:
        with obs_trace.span("border", engine=engine) as sp:
            labels_sorted = _assign_borders(tree, segs, eps, core,
                                            core_labels,
                                            traverse_fn=traverse_fn,
                                            tune=tune)
            sp.watch(labels_sorted)
        n_traversals += 1

    with obs_trace.span("finalize") as sp:
        labels, n_clusters = _finalize(labels_sorted, segs.order, n)
        core_mask = jnp.zeros(n, bool).at[segs.order].set(core)
        sp.watch(labels, core_mask)
    res = DBSCANResult(labels=labels, core_mask=core_mask,
                       n_clusters=n_clusters, n_sweeps=n_sweeps,
                       n_traversals=n_traversals, backend=backend)
    if with_stats:
        stats = dict(sweep_stats)
        stats["first_pass_iters"] = int(jnp.sum(first.iters))
        stats["first_pass_evals"] = int(jnp.sum(first.evals))
        return res, stats
    return res


def dbscan(points, eps: float, min_pts: int, *, algorithm: str = "auto",
           star: bool = False, frontier: bool = True,
           mesh=None) -> DBSCANResult:
    """DBSCAN via the paper's tree-based algorithms.

    algorithm: "fdbscan" | "fdbscan-densebox" build the named tree index
    directly; "auto", "tiled", "sharded", "stream" and "pallas-tree" go
    through the unified dispatcher (repro.core.dispatch), which probes the
    eps-grid occupancy and may pick the Pallas tile backend, the multi-device
    sharded tree path (when a ``mesh`` is active), the Pallas traversal
    kernel (DESIGN.md §9), or a one-shot streaming snapshot (DESIGN.md §7;
    use ``dispatch.stream_handle`` to keep the handle for inserts).
    star=True implements DBSCAN* (no border points; non-core -> noise).
    frontier=False forces full (unrestricted) sweeps.
    """
    points = jnp.asarray(points)
    if algorithm in ("auto", "tiled", "sharded", "stream", "pallas-tree"):
        from . import dispatch
        return dispatch.dbscan(points, eps, min_pts, algorithm=algorithm,
                               star=star, frontier=frontier, mesh=mesh)
    if eps < 0:
        raise ValueError(f"eps must be non-negative; got {eps}"
                         " (a negative eps would be squared away silently)")
    check_points(points)    # the dispatch route validates inside plan()
    n, d = points.shape
    if algorithm == "fdbscan-densebox":
        segs = grid.build_segments_densebox(points, eps, min_pts)
    elif algorithm == "fdbscan":
        segs = grid.build_segments_fdbscan(points)
    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")

    tree = None
    if segs.n_segments > 1 and n > 1:
        tree = lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)
    return cluster_from_index(segs, tree, eps, min_pts, star=star,
                              frontier=frontier, backend=algorithm)
