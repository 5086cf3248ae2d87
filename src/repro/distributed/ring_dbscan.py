"""Distributed DBSCAN over device shards (beyond-paper).

The paper's §6 lists distribution as future work; this module carries two
multi-device strategies over the same outer protocol (global Morton sort,
contiguous slabs over the mesh's data axis, all-gather + pointer-jumping
label fixpoint):

* ``ring_dbscan`` — the dense *ring systolic* baseline: every phase rotates
  full point blocks and runs an O(n^2/P) pairwise tile per step. None of
  the tree machinery reaches it; it survives as the small-n fallback and
  the comparator for ``BENCH_distributed.json``.

* ``tree_dbscan_sharded`` — the tree-based path (DESIGN.md §6): each shard
  Morton-resorts its slab locally and builds a singleton-segment LBVH over
  it *inside* the jitted collective program; queries (not primitives)
  travel the ring, and at each stop only the **eps-halo** — traveling
  points within ``eps`` of the resident slab's AABB — traverses the local
  tree (``sharding.halo_mask``). Everything else dies before the root box
  test, so per-shard work collapses from the dense n^2/P tile to the
  sequential tree bound plus a boundary-slab term, while the label fixpoint
  (all-gather + pointer jumping) is unchanged.

Union-find across shards: labels are global (Morton-sorted) indices; after
each hook sweep, labels are all-gathered (n x int32 — tiny next to the
distance work) and pointer jumping runs locally to a fixpoint. Sweeps
repeat until a global psum reports no change.

The ring's per-tile epilogues default to the pure-jnp oracle (portable: CPU
tests run it under shard_map); on TPU the Pallas kernels in repro.kernels
slot in via ``use_pallas=True`` (same contract, validated against the same
refs).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import grid, lbvh, morton, traversal
from repro.core.fdbscan import DBSCANResult, _finalize
from repro.distributed import sharding

INT_MAX = jnp.iinfo(jnp.int32).max


def _vary(x, axis, enabled=True):
    """Mark a loop-carry init as device-varying (shard_map VMA typing)."""
    return lax.pcast(x, (axis,), to="varying") if enabled else x


def _shard_map(fn, mesh, in_specs, out_specs, check_vma=True):
    # check_vma=False is required when pl.pallas_call runs inside the body
    # (its out_shape ShapeDtypeStructs carry no varying-axes typing).
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def _replicated(mesh, *xs):
    """Replicate sharded program outputs over the mesh, so host code can
    slice and scatter them (an ``Explicit`` mesh types them sharded)."""
    return jax.reshard(xs, NamedSharding(mesh, P()))


def _count_tile(q, r, eps):
    d2 = jnp.sum((q[:, None, :] - r[None, :, :]) ** 2, -1)
    return jnp.sum(d2 <= eps * eps, axis=1).astype(jnp.int32)


def _minlabel_tile(q, r, labels_r, mask_r, eps):
    d2 = jnp.sum((q[:, None, :] - r[None, :, :]) ** 2, -1)
    ok = (d2 <= eps * eps) & mask_r[None, :]
    return jnp.min(jnp.where(ok, labels_r[None, :], INT_MAX), axis=1)


def _pallas_count(q, r, eps):
    from repro.kernels import pairwise_count
    return pairwise_count(q, r, eps)


def _pallas_minlabel(q, r, labels_r, mask_r, eps):
    from repro.kernels import pairwise_minlabel
    return pairwise_minlabel(q, r, labels_r, mask_r, eps)[0]


def ring_dbscan(points, eps: float, min_pts: int, mesh=None,
                axis: str = "data", use_pallas: bool = False,
                max_jump: int = 32) -> DBSCANResult:
    points = jnp.asarray(points, jnp.float32)
    n, d = points.shape
    if mesh is None:
        ndev = len(jax.devices())
        mesh = jax.make_mesh((ndev,), (axis,))
    ndev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    pts_sorted, order, _ = morton.morton_sort(points)
    n_pad = ((n + ndev - 1) // ndev) * ndev
    pts_pad = jnp.pad(pts_sorted, ((0, n_pad - n), (0, 0)),
                      constant_values=1e30)  # sentinels never match
    n_loc = n_pad // ndev
    count_tile = _pallas_count if use_pallas else _count_tile
    minlabel_tile = _pallas_minlabel if use_pallas else _minlabel_tile
    check_vma = not use_pallas
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]

    def kernel(local_pts):
        me = lax.axis_index(axis)
        offset = me.astype(jnp.int32) * n_loc
        gid = offset + jnp.arange(n_loc, dtype=jnp.int32)
        valid = gid < n

        # ---- phase 1 (preprocessing): ring neighbor count ----------------
        def count_body(i, carry):
            counts, block = carry
            counts = counts + count_tile(local_pts, block, eps)
            return counts, lax.ppermute(block, axis, perm)

        counts, _ = lax.fori_loop(
            0, ndev, count_body,
            (_vary(jnp.zeros(n_loc, jnp.int32), axis, check_vma), local_pts))
        core = (counts >= min_pts) & valid

        # ---- phase 2 (main): ring hook sweeps + global pointer jumping ---
        labels = jnp.where(core, gid, INT_MAX)

        def jump(labels):
            # collectives live in the body (not cond): the carry holds the
            # already-psum'd global change flag.
            def body(state):
                l, _ = state
                table = lax.all_gather(l, axis, tiled=True)   # (n_pad,)
                safe = jnp.where(l == INT_MAX, 0, l)
                nl = jnp.where(l == INT_MAX, l, table[safe])
                changed = lax.psum(jnp.any(nl != l).astype(jnp.int32), axis)
                return nl, _vary(changed > 0, axis, check_vma)

            labels, _ = lax.while_loop(lambda s: s[1], body,
                                       (labels, _vary(jnp.bool_(True), axis, check_vma)))
            return labels

        def sweep_body(state):
            labels, _, n_sw = state

            def ring(i, carry):
                best, blk_pts, blk_lab, blk_core = carry
                got = minlabel_tile(local_pts, blk_pts, blk_lab, blk_core, eps)
                best = jnp.minimum(best, got)
                return (best,
                        lax.ppermute(blk_pts, axis, perm),
                        lax.ppermute(blk_lab, axis, perm),
                        lax.ppermute(blk_core, axis, perm))

            best, _, _, _ = lax.fori_loop(
                0, ndev, ring,
                (_vary(jnp.full(n_loc, INT_MAX, jnp.int32), axis, check_vma),
                 local_pts, labels, core))
            new = jnp.where(core, jnp.minimum(labels, best), labels)
            new = jump(new)
            changed = lax.psum(jnp.any(new != labels).astype(jnp.int32), axis)
            return new, _vary(changed > 0, axis, check_vma), n_sw + 1

        labels, _, n_sweeps = lax.while_loop(
            lambda s: s[1], sweep_body,
            (labels, _vary(jnp.bool_(True), axis, check_vma),
             _vary(jnp.int32(0), axis, check_vma)))

        # ---- borders: one more ring pass over core roots ------------------
        def bring(i, carry):
            best, blk_pts, blk_lab, blk_core = carry
            got = minlabel_tile(local_pts, blk_pts, blk_lab, blk_core, eps)
            return (jnp.minimum(best, got),
                    lax.ppermute(blk_pts, axis, perm),
                    lax.ppermute(blk_lab, axis, perm),
                    lax.ppermute(blk_core, axis, perm))

        broot = jnp.where(core, labels, INT_MAX)
        best, _, _, _ = lax.fori_loop(
            0, ndev, bring,
            (_vary(jnp.full(n_loc, INT_MAX, jnp.int32), axis, check_vma),
             local_pts, broot, core))
        labels = jnp.where(core, labels, jnp.where(valid, best, INT_MAX))
        labels = jnp.where(labels == INT_MAX, jnp.int32(-1), labels)
        return labels, core, jnp.reshape(n_sweeps, (1,))

    fn = _shard_map(kernel, mesh, in_specs=P(axis),
                    out_specs=(P(axis), P(axis), P(axis)),
                    check_vma=check_vma)
    labels_pad, core_pad, sweeps_dev = _replicated(mesh,
                                                   *jax.jit(fn)(pts_pad))
    labels_sorted = labels_pad[:n]   # -1 noise, else global sorted index
    core_sorted = core_pad[:n]
    labels, n_clusters = _finalize(labels_sorted, order, n)
    core_mask = jnp.zeros(n, bool).at[order].set(core_sorted)
    return DBSCANResult(labels=labels, core_mask=core_mask,
                        n_clusters=n_clusters,
                        n_sweeps=int(sweeps_dev[0]), backend="ring")



@lru_cache(maxsize=16)
def _sharded_programs(mesh, axis: str, n: int, n_pad: int, eps: float,
                      min_pts: int):
    """Compile (build, sweep, border) collective programs for one config.

    The host sweep loop calls the sweep program once per sweep; caching by
    (mesh, n, eps, min_pts) keeps repeat runs — parameter sweeps, property
    tests — from retracing three shard_map programs per call.
    """
    ndev = sharding._axis_size(mesh, axis)
    n_loc = n_pad // ndev
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]

    def rotate(*xs):
        return tuple(lax.ppermute(x, axis, perm) for x in xs)

    def slab_ids():
        me = lax.axis_index(axis)
        gid = me.astype(jnp.int32) * n_loc + jnp.arange(n_loc,
                                                        dtype=jnp.int32)
        return gid, gid < n

    def halo_ids(idx, blk_pts, blk_on):
        # the eps-dilated boundary slab: a traveling query farther than
        # eps from the resident AABB cannot match any resident point
        active = blk_on & sharding.halo_mask(blk_pts, idx["lo"], idx["hi"],
                                             eps)
        return jnp.where(active, 0, jnp.int32(-1)), active

    def total(evals):
        """This shard's distance-eval count, as a (1,) block: the host
        adds the per-device counts in int64, where the mesh-wide total
        cannot overflow."""
        return jnp.sum(evals)[None]

    def jump(labels):
        # all-gather + pointer jumping (labels are global sorted indices;
        # chains strictly decrease, so this terminates)
        def body(state):
            l, _ = state
            table = lax.all_gather(l, axis, tiled=True)   # (n_pad,)
            safe = jnp.where(l == INT_MAX, 0, l)
            nl = jnp.where(l == INT_MAX, l, table[safe])
            changed = lax.psum(jnp.any(nl != l).astype(jnp.int32), axis)
            return nl, changed > 0

        labels, _ = lax.while_loop(lambda s: s[1], body,
                                   (labels, jnp.bool_(True)))
        return labels

    def build_kernel(local_pts):
        """Per-shard index build + the traveling-query count phase."""
        gid, valid = slab_ids()

        lo, hi = sharding.shard_bounds(local_pts, valid)
        codes = morton.morton_encode(local_pts, lo=lo, hi=hi)
        codes = jnp.where(valid, codes, jnp.uint32(0xFFFFFFFF))
        lorder = jnp.argsort(codes)       # local sorted order of the slab
        lpts = local_pts[lorder]
        segs = grid.singleton_segments(lpts, lorder, codes[lorder])
        tree = lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)
        idx = {"tree": tree, "segs": segs, "lorder": lorder,
               "lvalid": valid[lorder], "lo": lo, "hi": hi}
        zero_i = jnp.zeros(n_loc, jnp.int32)

        def count_body(i, carry):
            blk_pts, blk_on, blk_cnt, blk_ev = carry
            qids, active = halo_ids(idx, blk_pts, blk_on)
            # the traveling slab is an external-predicate batch against the
            # resident tree; only halo lanes (qids >= 0) traverse
            tr = traversal.traverse_impl(
                tree, segs,
                traversal.intersects(traversal.sphere(eps), ids=qids,
                                     pts=blk_pts),
                traversal.CountVisitor(cap=min_pts))
            blk_cnt = blk_cnt + jnp.where(active, tr.acc, 0)
            return rotate(blk_pts, blk_on, blk_cnt, blk_ev + tr.evals)

        _, _, counts, evals = lax.fori_loop(
            0, ndev, count_body, (local_pts, valid, zero_i, zero_i))
        core = (counts >= min_pts) & valid
        labels0 = jnp.where(core, gid, INT_MAX)
        return idx, core, labels0, total(evals)

    def minlabel_rotation(local_pts, idx, point_vals, gather_mask, blk_on,
                          acc0):
        """Rotate ``(queries, acc)`` around the full ring, gathering the
        min of ``point_vals`` over masked resident neighbors at each halo
        stop. Returns (best, evals) home-aligned — shared by the sweep and
        border phases (same protocol, different values/queries)."""
        def ring_step(i, carry):
            blk_pts, on, blk_acc, blk_ev = carry
            qids, active = halo_ids(idx, blk_pts, on)
            # seed the carry with the traveling partial min: a query chains
            # its running answer across successive shard visits this way
            tr = traversal.traverse_impl(
                idx["tree"], idx["segs"],
                traversal.intersects(traversal.sphere(eps), ids=qids,
                                     pts=blk_pts),
                traversal.MinLabelVisitor(point_vals, gather_mask),
                carry=traversal.AccHits(acc=blk_acc,
                                        hits=jnp.zeros_like(blk_acc)))
            blk_acc = jnp.where(active, tr.acc, blk_acc)
            return rotate(blk_pts, on, blk_acc, blk_ev + tr.evals)

        _, _, best, evals = lax.fori_loop(
            0, ndev, ring_step,
            (local_pts, blk_on, acc0, jnp.zeros(n_loc, jnp.int32)))
        return best, evals

    def sweep_kernel(local_pts, idx, core, labels):
        """One traveling min-label sweep + pointer jumping + change psum."""
        gather_core = core[idx["lorder"]] & idx["lvalid"]
        _, valid = slab_ids()
        best, evals = minlabel_rotation(local_pts, idx,
                                        labels[idx["lorder"]], gather_core,
                                        valid & core, labels)
        new = jnp.where(core, jnp.minimum(labels, best), labels)
        new = jump(new)
        changed = lax.psum(jnp.any(new != labels).astype(jnp.int32), axis)
        return new, changed > 0, total(evals)

    def border_kernel(local_pts, idx, core, labels):
        """Borders: one rotation of the non-core queries over core roots."""
        root_l = jnp.where(core[idx["lorder"]], labels[idx["lorder"]],
                           INT_MAX)
        gather_core = core[idx["lorder"]] & idx["lvalid"]
        _, valid = slab_ids()
        best, evals = minlabel_rotation(local_pts, idx, root_l, gather_core,
                                        valid & ~core,
                                        jnp.full(n_loc, INT_MAX, jnp.int32))
        labels = jnp.where(core, labels, jnp.where(valid, best, INT_MAX))
        return (jnp.where(labels == INT_MAX, jnp.int32(-1), labels),
                total(evals))

    # check_vma=False: the traversal engine's loop carries mix replicated
    # constants with device-varying state; its while_loops carry no
    # collectives, so the replication checker's complaint is spurious here.
    # Per-point state and eval counts stay sharded over the data axis; the
    # psum'd change flag comes out replicated, so the host loop reads it
    # without touching a sharded array.
    def smap(fn, n_in, out_specs):
        return jax.jit(_shard_map(fn, mesh, in_specs=(P(axis),) * n_in,
                                  out_specs=out_specs, check_vma=False))
    return (smap(build_kernel, 1, P(axis)),
            smap(sweep_kernel, 4, (P(axis), P(), P(axis))),
            smap(border_kernel, 4, P(axis)))


def tree_dbscan_sharded(points, eps: float, min_pts: int, mesh=None,
                        axis: str = "data",
                        with_stats: bool = False):
    """Shard-local LBVH traversal + eps-halo exchange (DESIGN.md §6).

    Protocol per phase (count / sweep / border): the shard's slab of the
    globally Morton-sorted array travels the ring as *external queries*;
    at each of the ``ndev`` stops, the traveling points inside the resident
    shard's eps-dilated AABB (``sharding.halo_mask`` — the halo) traverse
    the resident tree, and the per-query partial result (count or running
    min label) travels on with the block. After a full rotation the block
    is home carrying its global answer. Exchanged points are *queries*, not
    tree primitives: no shard ever rebuilds its index for foreign points,
    and exactness needs no assumption that spatial neighbors land on
    Morton-adjacent shards (a query visits every shard and is simply inert
    wherever it is outside the halo).

    Per-visit neighbor counts saturate at ``min_pts``; the home-shard sum
    of the saturated per-visit counts crosses ``min_pts`` iff the true
    global count does, so the early exit survives distribution.

    The sweep fixpoint is driven from the host (one jitted collective
    program per sweep, like the single-device host loop): nesting the
    traversal's data-divergent ``while_loop`` inside a device-synchronized
    ``while_loop`` that carries collectives deadlocks the CPU backend's
    rendezvous, and a host loop also hands back per-sweep work stats for
    free. The per-shard index is built once and threaded through sharded
    outputs, so sweeps rebuild nothing.

    Returns a :class:`DBSCANResult` (labels/core identical to single-device
    ``dbscan``); with ``with_stats=True``, also a dict with the exact
    distance-evaluation count (the paper's work metric) and ring-equivalent
    comparators.
    """
    if eps < 0:
        raise ValueError(f"eps must be non-negative; got {eps}"
                         " (a negative eps would be squared away silently)")
    points = jnp.asarray(points)
    if not jnp.issubdtype(points.dtype, jnp.floating):
        points = points.astype(jnp.float32)
    n, d = points.shape
    if mesh is None:
        ndev = len(jax.devices())
        mesh = jax.make_mesh((ndev,), (axis,))
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    ndev = sharding._axis_size(mesh, axis)

    pts_sorted, order, _ = morton.morton_sort(points)
    n_pad = ((n + ndev - 1) // ndev) * ndev
    n_loc = n_pad // ndev
    if n_loc < 2:
        # a shard-local LBVH needs >= 2 primitives; inputs this tiny take
        # the dense ring (whose cost is trivial at this scale) — stats keep
        # the normal shape, with the ring's work on both sides of the ratio
        res = ring_dbscan(points, eps, min_pts, mesh=mesh, axis=axis)
        ring_evals = (2 + res.n_sweeps) * n_pad * n_pad
        return (res, {"distance_evals": ring_evals,
                      "ring_distance_evals": ring_evals, "ndev": ndev,
                      "n_pad": n_pad,
                      "n_sweeps": res.n_sweeps}) if with_stats else res
    pts_pad = jnp.pad(pts_sorted, ((0, n_pad - n), (0, 0)),
                      constant_values=1e30)  # sentinels never match
    # each device receives only its own slab of the sorted points
    pts_pad = jax.device_put(pts_pad, NamedSharding(mesh, P(axis)))
    build_fn, sweep_fn, border_fn = _sharded_programs(
        mesh, axis, n, n_pad, float(eps), int(min_pts))

    def host_total(evals):
        return int(np.sum(jax.device_get(evals), dtype=np.int64))

    idx, core_pad, labels_pad, evals = build_fn(pts_pad)
    total_evals = host_total(evals)
    n_sweeps = 0
    while True:
        labels_pad, changed, evals = sweep_fn(pts_pad, idx, core_pad,
                                              labels_pad)
        n_sweeps += 1
        total_evals += host_total(evals)
        if not bool(changed):
            break
    labels_pad, evals = border_fn(pts_pad, idx, core_pad, labels_pad)
    total_evals += host_total(evals)

    labels_pad, core_pad = _replicated(mesh, labels_pad, core_pad)
    labels_sorted = labels_pad[:n]
    core_sorted = core_pad[:n]
    labels, n_clusters = _finalize(labels_sorted, order, n)
    core_mask = jnp.zeros(n, bool).at[order].set(core_sorted)
    res = DBSCANResult(labels=labels, core_mask=core_mask,
                       n_clusters=n_clusters, n_sweeps=n_sweeps,
                       backend="sharded")
    if not with_stats:
        return res
    # ring comparator: every dense phase is a full n_pad^2 pairwise pass
    # (count + n_sweeps sweep rotations + border)
    stats = {
        "distance_evals": total_evals,
        "ring_distance_evals": (2 + n_sweeps) * n_pad * n_pad,
        "ndev": ndev, "n_pad": n_pad, "n_sweeps": n_sweeps,
        # resident slab rows per device: the points really are split
        "shard_rows": {str(sh.device): int(sh.data.shape[0])
                       for sh in pts_pad.addressable_shards},
    }
    return res, stats
