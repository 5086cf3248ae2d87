"""The fused single-pass engine (DESIGN.md §4) vs the separate-pass path.

Covers: fused count+minlabel == separate count / minlabel traversals;
frontier-restricted sweeps are label-identical and do bounded work vs full
sweeps; the unrolled loop body is result-invariant; the per-run traversal
budget is `n_sweeps + 1`.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dbscan, dbscan_bruteforce_np, fdbscan, grid, lbvh, traversal
from repro.core.validate import check_dbscan, same_partition

from conftest import separated_points

INT_MAX = traversal.INT_MAX


def _index(pts, algo="fdbscan", eps=0.1, mp=5):
    pts = jnp.asarray(pts)
    if algo == "fdbscan-densebox":
        segs = grid.build_segments_densebox(pts, eps, mp)
    else:
        segs = grid.build_segments_fdbscan(pts)
    tree = lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)
    return segs, tree


def test_fused_matches_separate_passes_fdbscan():
    # Singleton segments: no dense short-circuit anywhere, so the fused
    # pass must agree elementwise with the two separate traversals.
    pts = separated_points(200, 2, eps=0.1, seed=1)
    segs, tree = _index(pts)
    n = segs.n_points
    vals = jnp.arange(n, dtype=jnp.int32)
    fused = traversal.fused_count_minlabel(tree, segs, 0.1, vals)
    counts, evals = traversal.count_neighbors_with_work(tree, segs, 0.1,
                                                        cap=INT_MAX)
    minlab, matched = traversal.minlabel_sweep(tree, segs, 0.1, vals,
                                               gather_mask=jnp.ones(n, bool),
                                               query_active=jnp.ones(n, bool))
    np.testing.assert_array_equal(np.asarray(fused.hits) + 1,
                                  np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(fused.acc), np.asarray(minlab))
    np.testing.assert_array_equal(np.asarray(fused.evals), np.asarray(evals))


@pytest.mark.parametrize("algo", ["fdbscan", "fdbscan-densebox"])
@pytest.mark.parametrize("mp", [2, 5, 20])
def test_fused_core_matches_preprocess(algo, mp):
    pts = separated_points(300, 2, eps=0.08, seed=mp)
    segs, tree = _index(pts, algo, eps=0.08, mp=mp)
    core_fused = fdbscan._fused_first_pass(tree, segs, 0.08, mp)[0]
    core_ref = fdbscan._preprocess(tree, segs, 0.08, mp)
    np.testing.assert_array_equal(np.asarray(core_fused),
                                  np.asarray(core_ref))


@pytest.mark.parametrize("algo", ["fdbscan", "fdbscan-densebox"])
def test_frontier_identical_labels_and_bounded_work(algo):
    pts = separated_points(400, 2, eps=0.06, seed=7)
    segs, tree = _index(pts, algo, eps=0.06, mp=5)
    res_f, st_f = fdbscan.cluster_from_index(segs, tree, 0.06, 5,
                                             with_stats=True)
    res_u, st_u = fdbscan.cluster_from_index(segs, tree, 0.06, 5,
                                             frontier=False, with_stats=True)
    np.testing.assert_array_equal(np.asarray(res_f.labels),
                                  np.asarray(res_u.labels))
    np.testing.assert_array_equal(np.asarray(res_f.core_mask),
                                  np.asarray(res_u.core_mask))
    # gather-mask frontier is exact: same fixpoint in the same sweep count
    assert res_f.n_sweeps == res_u.n_sweeps
    # ... with no more (strictly less, past sweep one) traversal work
    assert sum(st_f["evals_per_sweep"]) <= sum(st_u["evals_per_sweep"])
    assert sum(st_f["iters_per_sweep"]) <= sum(st_u["iters_per_sweep"])
    # restricted sweeps never gather from more points than the full set
    assert all(f <= st_u["frontier_per_sweep"][0]
               for f in st_f["frontier_per_sweep"])


def test_frontier_matches_oracle_end_to_end():
    pts = separated_points(350, 2, eps=0.07, seed=11)
    ref_labels, ref_core = dbscan_bruteforce_np(pts, 0.07, 4)
    for frontier in (True, False):
        res = dbscan(pts, 0.07, 4, algorithm="fdbscan", frontier=frontier)
        assert (np.asarray(res.core_mask) == ref_core).all()
        assert same_partition(np.asarray(res.labels)[ref_core],
                              ref_labels[ref_core])
        check_dbscan(pts, 0.07, 4, res.labels, res.core_mask)


def _visitor(kind, n, cap=6):
    vals = jnp.arange(n, dtype=jnp.int32)
    mask = jnp.ones(n, bool)
    return {"count": traversal.CountVisitor(cap=cap),
            "minlabel": traversal.MinLabelVisitor(vals, mask),
            "count_minlabel": traversal.CountMinLabelVisitor(vals, mask,
                                                             cap=cap),
            }[kind]


@pytest.mark.parametrize("kind", ["count", "minlabel", "count_minlabel"])
def test_unroll_invariance(kind):
    pts = separated_points(150, 2, eps=0.12, seed=3)
    segs, tree = _index(pts, "fdbscan-densebox", eps=0.12, mp=4)
    n = segs.n_points
    pred = traversal.intersects(traversal.sphere(0.12))
    outs = [traversal.traverse(tree, segs, pred, _visitor(kind, n),
                               unroll=u) for u in (1, 4, 7)]
    for other in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0].acc),
                                      np.asarray(other.acc))
        np.testing.assert_array_equal(np.asarray(outs[0].hits),
                                      np.asarray(other.hits))
        np.testing.assert_array_equal(np.asarray(outs[0].evals),
                                      np.asarray(other.evals))
    # unrolling shrinks loop trips ~unroll-fold
    assert int(outs[1].iters.sum()) < int(outs[0].iters.sum())


@pytest.mark.parametrize("kind", ["count", "minlabel", "count_minlabel",
                                  "nearest"])
def test_external_queries_match_resident(kind):
    # an external predicate batch at the resident coordinates must see the
    # same neighborhoods (modulo self-identity, which externals lack)
    pts = separated_points(160, 2, eps=0.1, seed=8)
    segs, tree = _index(pts)
    n = segs.n_points
    if kind == "nearest":
        cb = traversal.KNNVisitor(4)
        res = traversal.traverse(tree, segs, traversal.nearest(4), cb)
        ext = traversal.traverse(tree, segs,
                                 traversal.nearest(4, pts=segs.pts), cb)
        np.testing.assert_array_equal(np.asarray(res.carry.ids),
                                      np.asarray(ext.carry.ids))
        np.testing.assert_array_equal(np.asarray(res.carry.d2),
                                      np.asarray(ext.carry.d2))
        return
    cb = _visitor(kind, n, cap=traversal.INT_MAX)
    res = traversal.traverse(tree, segs,
                             traversal.intersects(traversal.sphere(0.1)), cb)
    ext = traversal.traverse(
        tree, segs,
        traversal.intersects(traversal.sphere(0.1), pts=segs.pts), cb,
        carry=(None if kind == "count"
               else traversal.AccHits(acc=jnp.arange(n, dtype=jnp.int32),
                                      hits=jnp.zeros(n, jnp.int32))))
    np.testing.assert_array_equal(np.asarray(res.acc), np.asarray(ext.acc))
    # externals have no self to exclude: exactly one extra hit per lane
    np.testing.assert_array_equal(np.asarray(res.hits) + 1,
                                  np.asarray(ext.hits))


@pytest.mark.parametrize("cap", [1, 3, 7])
def test_count_early_exit_saturates_exactly(cap):
    pts = separated_points(180, 2, eps=0.15, seed=cap)
    segs, tree = _index(pts)
    full = traversal.count_neighbors(tree, segs, 0.15, cap=INT_MAX)
    capped = traversal.count_neighbors(tree, segs, 0.15, cap=cap)
    np.testing.assert_array_equal(np.asarray(capped),
                                  np.minimum(np.asarray(full), cap))


def test_node_mask_all_true_is_noop():
    pts = separated_points(120, 2, eps=0.1, seed=9)
    segs, tree = _index(pts)
    n = segs.n_points
    pred = traversal.intersects(traversal.sphere(0.1))
    cb = _visitor("minlabel", n)
    a = traversal.traverse(tree, segs, pred, cb)
    b = traversal.traverse(tree, segs, pred, cb,
                           node_mask=jnp.ones(2 * segs.n_segments - 1, bool))
    np.testing.assert_array_equal(np.asarray(a.acc), np.asarray(b.acc))
    np.testing.assert_array_equal(np.asarray(a.hits), np.asarray(b.hits))


@pytest.mark.parametrize("algo", ["fdbscan", "fdbscan-densebox"])
def test_traversal_budget_is_sweeps_plus_one(algo):
    # The paper-fusion acceptance bound: seed spent n_sweeps + 2 walks.
    pts = separated_points(250, 2, eps=0.07, seed=2)
    res = dbscan(pts, 0.07, 5, algorithm=algo)
    assert res.n_traversals == res.n_sweeps + 1
    star = dbscan(pts, 0.07, 5, algorithm=algo, star=True)
    assert star.n_traversals == star.n_sweeps  # no border gather


def test_minpts2_uses_fused_pass():
    # minpts == 2 is no longer special-cased: the fused count covers it.
    pts = separated_points(200, 2, eps=0.05, seed=4)
    res = dbscan(pts, 0.05, 2, algorithm="fdbscan")
    check_dbscan(pts, 0.05, 2, res.labels, res.core_mask)
    assert res.n_traversals == res.n_sweeps + 1


@pytest.mark.parametrize("kind", ["count", "minlabel", "count_minlabel",
                                  "nearest", "frontier", "external"])
def test_lane_compaction_is_exact(kind, monkeypatch):
    # Compacting the live lanes into narrower batches mid-walk moves lanes
    # but never changes one: every per-lane output matches the walk that
    # keeps all lanes at full width, work counters included.
    pts = separated_points(300, 2, eps=0.1, seed=12)
    segs, tree = _index(pts, "fdbscan-densebox", eps=0.1, mp=4)
    n = segs.n_points
    sphere = traversal.sphere(0.1)
    pred = traversal.intersects(sphere)
    cb = _visitor(kind if kind in ("count", "count_minlabel")
                  else "minlabel", n, cap=traversal.INT_MAX)
    if kind == "nearest":
        cb, pred = traversal.KNNVisitor(4), traversal.nearest(4)
    elif kind == "frontier":
        ids = np.where(np.arange(n) % 3 == 0, np.arange(n), -1)
        pred = traversal.intersects(sphere, ids=jnp.asarray(ids, jnp.int32))
    elif kind == "external":
        pred = traversal.intersects(sphere, pts=segs.pts[::-1] + 0.01)

    def walk(min_lanes):
        monkeypatch.setattr(traversal, "COMPACT_MIN_LANES", min_lanes)
        return traversal.traverse_impl(tree, segs, pred, cb)

    assert len(traversal.compact_widths(n)) == 1
    full = walk(traversal.COMPACT_MIN_LANES)
    compacted = walk(8)
    assert traversal.compact_widths(n) == [n, n // 4, n // 16]
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(compacted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(full.evals.sum()) > 0


@pytest.mark.parametrize("algo", ["fdbscan", "fdbscan-densebox"])
def test_lane_compaction_keeps_labels(algo, monkeypatch):
    pts = separated_points(400, 2, eps=0.06, seed=13)
    segs, tree = _index(pts, algo, eps=0.06, mp=5)
    ref = fdbscan.cluster_from_index(segs, tree, 0.06, 5)
    monkeypatch.setattr(traversal, "COMPACT_MIN_LANES", 8)
    jax.clear_caches()
    got = fdbscan.cluster_from_index(segs, tree, 0.06, 5)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(ref.labels),
                                  np.asarray(got.labels))
    np.testing.assert_array_equal(np.asarray(ref.core_mask),
                                  np.asarray(got.core_mask))
    assert ref.n_sweeps == got.n_sweeps
