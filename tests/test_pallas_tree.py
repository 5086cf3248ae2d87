"""The Pallas traversal kernel backend (kernels/traverse.py, DESIGN.md §9).

Every test pins the kernel (interpret mode on CPU) against the vmapped
reference engine on identical inputs — acc/hits/evals must be *equal*,
not close: both engines trace the same ``traversal.make_step`` op
sequence, so any drift is a bug in the lane tiling, the padding, or the
visitor inlining, never float noise.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import dbscan, dispatch, grid, lbvh, traversal
from repro.data import pointclouds
from repro.kernels import traverse as kt

EPS, MINPTS = 0.05, 8


@pytest.fixture(scope="module")
def index():
    pts = jnp.asarray(pointclouds.load("portotaxi_like", 600))
    segs = grid.build_segments_densebox(pts, EPS, MINPTS)
    tree = lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)
    return segs, tree


def _assert_trace_equal(ref, pal, iters_too=False):
    np.testing.assert_array_equal(np.asarray(ref.acc), np.asarray(pal.acc))
    np.testing.assert_array_equal(np.asarray(ref.hits), np.asarray(pal.hits))
    np.testing.assert_array_equal(np.asarray(ref.evals),
                                  np.asarray(pal.evals))
    if iters_too:
        np.testing.assert_array_equal(np.asarray(ref.iters),
                                      np.asarray(pal.iters))


def test_count_visitor_matches_engine(index):
    segs, tree = index
    pred = traversal.intersects(traversal.sphere(EPS))
    cb = traversal.CountVisitor(cap=MINPTS)
    _assert_trace_equal(traversal.traverse(tree, segs, pred, cb),
                        kt.traverse(tree, segs, pred, cb))


def test_iters_counter_matches_engine_at_same_unroll(index):
    # at matching unroll the per-lane loop-trip counters are identical —
    # the counter surface benchmarks/run.py --check gates
    segs, tree = index
    pred = traversal.intersects(traversal.sphere(EPS))
    cb = traversal.CountVisitor(cap=MINPTS)
    _assert_trace_equal(traversal.traverse(tree, segs, pred, cb, unroll=4),
                        kt.traverse(tree, segs, pred, cb, unroll=4),
                        iters_too=True)


def test_minlabel_with_node_mask_and_compacted_ids(index):
    segs, tree = index
    n = segs.n_points
    vals = jnp.arange(n, dtype=jnp.int32)
    mask = jnp.asarray(np.arange(n) % 2 == 0)
    # compacted active-lane batch with -1 padding (the frontier shape)
    ids = np.full(256, -1, np.int32)
    ids[:200] = np.random.default_rng(0).choice(n, 200, replace=False)
    ids = jnp.asarray(ids)
    nm = lbvh.propagate_leaf_flags(
        tree, jnp.asarray(np.arange(segs.n_segments) % 3 != 0))
    pred = traversal.intersects(traversal.sphere(EPS), ids=ids)
    cb = traversal.MinLabelVisitor(vals, mask)
    _assert_trace_equal(
        traversal.traverse(tree, segs, pred, cb, node_mask=nm),
        kt.traverse(tree, segs, pred, cb, node_mask=nm))


def test_dual_mask_wide_lanes(index):
    # the split first sweep: per-lane choice of gather mask AND node mask
    segs, tree = index
    n = segs.n_points
    vals = jnp.arange(n, dtype=jnp.int32)
    narrow = jnp.asarray(np.arange(n) % 4 == 0)
    wide_m = jnp.ones(n, bool)
    nm_n = lbvh.propagate_leaf_flags(
        tree, jnp.asarray(np.arange(segs.n_segments) % 2 == 0))
    nm_w = jnp.ones(2 * segs.n_segments - 1, bool)
    lanes_wide = jnp.asarray(np.arange(n) % 5 == 0)
    pred = traversal.intersects(traversal.sphere(EPS))
    cb = traversal.MinLabelVisitor(vals, narrow, mask_wide=wide_m)
    kw = dict(node_mask=nm_n, node_mask_wide=nm_w, wide_lanes=lanes_wide)
    _assert_trace_equal(traversal.traverse(tree, segs, pred, cb, **kw),
                        kt.traverse(tree, segs, pred, cb, **kw))


def test_minlabel_float_vals(index):
    # the gathered values' dtype rides the carry: float32 vals must flow
    # through the kernel's acc output unchanged
    segs, tree = index
    n = segs.n_points
    vals = jnp.asarray(np.random.default_rng(2).uniform(0, 1, n)
                       .astype(np.float32))
    cb = traversal.MinLabelVisitor(vals, jnp.ones(n, bool))
    pred = traversal.intersects(traversal.sphere(EPS))
    ref = traversal.traverse(tree, segs, pred, cb)
    pal = kt.traverse(tree, segs, pred, cb)
    assert pal.acc.dtype == ref.acc.dtype == jnp.float32
    _assert_trace_equal(ref, pal)


def test_countminlabel_fused_pass(index):
    segs, tree = index
    n = segs.n_points
    vals = jnp.arange(n, dtype=jnp.int32)
    cb = traversal.CountMinLabelVisitor(vals, jnp.ones(n, bool),
                                        cap=MINPTS - 1)
    pred = traversal.intersects(traversal.sphere(EPS))
    _assert_trace_equal(traversal.traverse(tree, segs, pred, cb),
                        kt.traverse(tree, segs, pred, cb))


def test_external_queries_and_seeded_carry(index):
    # external predicate batch + chained carry (the stream/halo shape)
    segs, tree = index
    rng = np.random.default_rng(1)
    qpts = jnp.asarray(rng.uniform(0, 1, (137, 2)).astype(np.float32))
    n = segs.n_points
    vals = jnp.arange(n, dtype=jnp.int32)
    cb = traversal.MinLabelVisitor(vals, jnp.ones(n, bool))
    pred = traversal.intersects(traversal.sphere(3 * EPS), pts=qpts)
    ref1 = traversal.traverse(tree, segs, pred, cb)
    pal1 = kt.traverse(tree, segs, pred, cb)
    _assert_trace_equal(ref1, pal1)
    # chain: seed the second walk with the first walk's carry
    ref2 = traversal.traverse(tree, segs, pred, cb, carry=ref1.carry)
    pal2 = kt.traverse(tree, segs, pred, cb, carry=pal1.carry)
    _assert_trace_equal(ref2, pal2)


def test_use_range_mask(index):
    segs, tree = index
    pred = traversal.intersects(traversal.sphere(EPS))
    cb = traversal.CountVisitor(cap=traversal.INT_MAX)
    _assert_trace_equal(
        traversal.traverse(tree, segs, pred, cb, use_range_mask=True),
        kt.traverse(tree, segs, pred, cb, use_range_mask=True))


def test_nearest_predicate_falls_back_to_engine(index):
    # k-NN is not fusible: the kernel path must hand off transparently
    segs, tree = index
    pred = traversal.nearest(4)
    cb = traversal.KNNVisitor(4)
    ref = traversal.traverse(tree, segs, pred, cb)
    pal = kt.traverse(tree, segs, pred, cb)
    assert not kt.fusible(pred, cb)
    np.testing.assert_array_equal(np.asarray(ref.carry.ids),
                                  np.asarray(pal.carry.ids))
    np.testing.assert_array_equal(np.asarray(ref.carry.d2),
                                  np.asarray(pal.carry.d2))


def test_custom_visitor_falls_back_to_engine(index):
    segs, tree = index

    class SumD2(traversal.Visitor):
        def init_carry(self, ids, external, segs):
            z = jnp.zeros(ids.shape, jnp.int32)
            return traversal.AccHits(acc=z, hits=z)

        def visit(self, carry, j, d2, hit, ctx):
            return traversal.AccHits(
                acc=carry.acc + jnp.where(hit, j, 0),
                hits=carry.hits + jnp.where(hit, 1, 0)), hit

    import jax
    jax.tree_util.register_pytree_node(
        SumD2, lambda v: ((), None), lambda aux, ch: SumD2())
    pred = traversal.intersects(traversal.sphere(EPS))
    assert not kt.fusible(pred, SumD2())
    _assert_trace_equal(traversal.traverse(tree, segs, pred, SumD2()),
                        kt.traverse(tree, segs, pred, SumD2()))


def test_dispatch_explicit_backend():
    pts = pointclouds.load("blobs", 500)
    p = dispatch.plan(pts, EPS, MINPTS, algorithm="pallas-tree")
    assert p.backend == "pallas-tree"
    assert p.tree is not None           # rides the cached fdbscan index
    a = dbscan(pts, EPS, MINPTS, algorithm="fdbscan")
    b = dbscan(pts, EPS, MINPTS, algorithm="pallas-tree")
    assert b.backend == "pallas-tree"
    np.testing.assert_array_equal(np.asarray(a.labels), np.asarray(b.labels))
    np.testing.assert_array_equal(np.asarray(a.core_mask),
                                  np.asarray(b.core_mask))
    assert (a.n_clusters, a.n_sweeps) == (b.n_clusters, b.n_sweeps)


def test_dispatch_auto_upgrades_on_accelerator(monkeypatch):
    # on TPU the kernel has no compiled form (kt.TPU_UNSUPPORTED), so auto
    # dispatch no longer upgrades a tree decision there: pin the probe
    # (CPU CI) and check that the plan and the labels stay the tree's
    pts = pointclouds.load("blobs", 2000)   # > TILED_MAX_POINTS
    dispatch.clear_cache()
    ref = dispatch.dbscan(pts, EPS, MINPTS, algorithm="auto")  # CPU: tree
    assert ref.backend != "pallas-tree"
    monkeypatch.setattr(dispatch, "_accel", lambda: True)
    dispatch.clear_cache()
    p = dispatch.plan(pts, EPS, MINPTS, algorithm="auto")
    assert p.backend == ref.backend
    assert "pallas" not in p.stats["reason"]
    res = dispatch.dbscan(pts, EPS, MINPTS, query_plan=p)
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(ref.labels))
    np.testing.assert_array_equal(np.asarray(res.core_mask),
                                  np.asarray(ref.core_mask))
    dispatch.clear_cache()


def test_auto_upgrade_respects_vmem_budget(monkeypatch):
    # an explicit pallas-tree request on TPU raises, naming the compiler's
    # refusal, instead of running the kernel in interpret mode; off TPU
    # the same request runs the (interpreted) kernel
    dispatch.clear_cache()
    pts = pointclouds.load("blobs", 2000)
    assert dispatch.plan(pts, EPS, MINPTS,
                         algorithm="pallas-tree").backend == "pallas-tree"
    monkeypatch.setattr(dispatch, "_accel", lambda: True)
    dispatch.clear_cache()
    with pytest.raises(NotImplementedError, match="Mosaic refuses"):
        dispatch.plan(pts, EPS, MINPTS, algorithm="pallas-tree")
    with pytest.raises(NotImplementedError, match="Mosaic refuses"):
        dispatch.dbscan(pts, EPS, MINPTS, algorithm="pallas-tree")
    dispatch.clear_cache()


def test_dispatch_rejects_mesh():
    import jax
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="mesh"):
        dispatch.plan(pointclouds.load("blobs", 300), EPS, MINPTS,
                      algorithm="pallas-tree", mesh=mesh)


def test_lane_tile_boundaries(index):
    # lane counts straddling the tile size: padding lanes must stay inert
    segs, tree = index
    cb = traversal.CountVisitor(cap=traversal.INT_MAX)
    for k in (1, kt.LANE_TILE - 1, kt.LANE_TILE, kt.LANE_TILE + 1):
        ids = jnp.arange(k, dtype=jnp.int32)
        pred = traversal.intersects(traversal.sphere(EPS), ids=ids)
        _assert_trace_equal(traversal.traverse(tree, segs, pred, cb),
                            kt.traverse(tree, segs, pred, cb))
