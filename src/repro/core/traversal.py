"""Predicate/callback BVH traversal engine fused with visitor epilogues.

This is the heart of FDBSCAN, redesigned the way the paper's framework
(ArborX) exposes it: a *generic* fused-traversal engine

    ``traverse(tree, segs, predicates, callback, carry) -> Trace``

where a **predicate batch** describes the queries and their geometry —
``intersects(sphere(eps))`` for fixed-radius search, ``nearest(k)`` for
distance-bounded k-nearest-neighbor search — and a **callback** is a
JAX-traceable visitor consuming matched neighbors *on the fly* over an
arbitrary accumulator pytree (the ``carry``); neighbor lists are never
materialized (the paper's O(n)-memory claim; DESIGN.md §3, §8).

The DBSCAN epilogues that used to be a closed ``mode=`` string enum are
now just visitor instances over this engine (DESIGN.md §8):

  * :class:`CountVisitor`         — |N_eps(q)| with early exit at ``cap``;
  * :class:`MinLabelVisitor`      — min gathered label over masked
                                    neighbors (hook sweeps, border gather);
  * :class:`CountMinLabelVisitor` — the fused first pass: count *and*
                                    min-label candidate in one walk;
  * :class:`KNNVisitor`           — the k-best (dist2, id) list that powers
                                    ``repro.neighbors.knn``.

Custom workloads implement the same four hooks (``init_carry`` /
``visit`` / ``done`` / ``segment_done``) — see DESIGN.md §8 for the
contract and why the K-unrolled dead-guarding survives arbitrary
callbacks.

GPU -> TPU mapping (unchanged by the redesign):
  * one CUDA thread per query  ->  one vmap lane per query; the vmapped
    ``lax.while_loop`` lowers to a single masked loop (lanes that finish go
    inert), the TPU analogue of a warp of independent traversals;
  * per-thread traversal stack  ->  precomputed ropes (``Tree.miss``), O(1)
    state per lane;
  * early exit  ->  the callback's ``done(carry)`` hook feeds the loop-mask
    condition;
  * the paper's "hide leaves j < i" mask  ->  a range test on
    ``Tree.range_r`` (skip subtrees whose max primitive index is below the
    query's own), via ``use_range_mask``.

Fused single-pass engine (DESIGN.md §4):
  * Each ``while_loop`` trip executes ``unroll`` work units (box tests or
    member distances) instead of one, amortizing the loop-carried overhead.
    Sub-steps are dead-guarded — every state select is masked by the lane's
    liveness — so lanes freeze exactly where the one-unit engine would,
    for *any* callback.
  * Queries are addressed by the predicate batch's explicit ``ids`` vector,
    so frontier sweeps can traverse a *compacted* active subset
    (ECL-CC-style active-set restriction) instead of masking inert
    full-width lanes.

External queries (DESIGN.md §6): ``intersects(sphere(eps), pts=...)``
decouples the query set from the tree's primitives — a lane traverses for
an arbitrary point that is not (necessarily) resident in the index. The
sharded distributed path runs eps-halo points received from other shards
as external queries against the local tree; the stream index chains one
query batch across its two trees by threading the carry. External lanes
have no resident identity, so self-exclusion and the dense/query-rank
shortcuts (which assume lane i <=> resident point i) are disabled.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import morton
from .lbvh import Tree, box_dist2 as _box_dist2
from .grid import Segments

INT_MAX = jnp.iinfo(jnp.int32).max

def default_unroll() -> int:
    """Work units per while_loop trip when the caller names none.

    On lockstep accelerators (TPU/GPU) 4 amortizes the loop-carried
    overhead (cond evaluation + state select per trip) without inflating
    tail waste: a lane overshoots by at most unroll-1 dead-guarded
    sub-steps. On CPU the while_loop is cheap and the masked sub-steps are
    pure overhead, so the default stays 1 there. Asked at trace time, so
    importing the package initializes no backend.
    """
    return 4 if jax.default_backend() in ("tpu", "gpu") else 1


# --------------------------------------------------------------------- #
# predicates                                                            #
# --------------------------------------------------------------------- #

class Sphere(NamedTuple):
    """Search geometry: a ball of radius ``r`` around each query point."""
    r: Any


def sphere(r) -> Sphere:
    """The eps-ball geometry for :func:`intersects` predicates."""
    return Sphere(r)


class Intersects(NamedTuple):
    """A batch of fixed-radius queries (ArborX's ``intersects(sphere)``).

    geometry: the shared :class:`Sphere` (its radius is a traced value —
        eps sweeps reuse one compiled program).
    ids: int32 sorted-order point indices; ``-1`` marks an inert (padding)
        lane. ``None`` traverses every resident point.
    pts: optional (k, d) *external* query coordinates (DESIGN.md §6). When
        given, lane i traverses for ``pts[i]`` instead of a tree point and
        ``ids`` only carries the inert-lane marker (-1 inert, anything
        else active).
    """
    geometry: Sphere
    ids: Any = None
    pts: Any = None


def intersects(geometry, ids=None, pts=None) -> Intersects:
    """Fixed-radius predicate batch: ``intersects(sphere(eps))``."""
    if not isinstance(geometry, Sphere):
        geometry = Sphere(geometry)
    return Intersects(geometry, ids, pts)


class Nearest:
    """A batch of k-nearest-neighbor queries (ArborX's ``nearest(k)``).

    Traversal is *distance-bounded*: a lane's box tests and member tests
    prune against ``min(r^2, worst-so-far)`` where worst-so-far is the
    callback's current k-th best distance (``worst_d2`` hook), so the
    search ball shrinks as better neighbors are found. ``r`` optionally
    caps the search radius (``None`` = unbounded). ``k`` is static (it
    sizes the carry); ``ids``/``pts`` work as in :class:`Intersects`.
    """

    def __init__(self, k: int, r=None, ids=None, pts=None):
        self.k = int(k)
        self.r = r
        self.ids = ids
        self.pts = pts

    def tree_flatten(self):
        return (self.r, self.ids, self.pts), self.k

    @classmethod
    def tree_unflatten(cls, k, children):
        r, ids, pts = children
        return cls(k, r=r, ids=ids, pts=pts)


jax.tree_util.register_pytree_node_class(Nearest)


def nearest(k: int, r=None, ids=None, pts=None) -> Nearest:
    """k-NN predicate batch: ``nearest(k)``, optionally radius-capped."""
    return Nearest(k, r=r, ids=ids, pts=pts)


# --------------------------------------------------------------------- #
# callback protocol                                                     #
# --------------------------------------------------------------------- #

class QueryCtx(NamedTuple):
    """Per-lane engine context handed to every callback hook.

    self_id: the lane's own sorted point index (-1 for external lanes —
             self-exclusion tests are vacuously false there).
    dense:   the query point lives in a dense segment (core by
             construction under DenseBox).
    rank:    the query's segment rank (``use_range_mask`` support).
    wide:    this lane uses the callback's *wide* gather mask (the split
             first sweep, DESIGN.md §4).
    """
    self_id: jax.Array
    dense: jax.Array
    rank: jax.Array
    wide: jax.Array


class AccHits(NamedTuple):
    """The standard DBSCAN carry: a scalar accumulator + a match counter.

    acc:  the visitor's accumulator — saturated neighbor count (incl.
          self) for :class:`CountVisitor`; min gathered value for the
          min-label visitors. Seeding ``acc`` via an explicit ``carry``
          chains a traveling query across trees/shards (DESIGN.md §6, §7).
    hits: matched neighbors *excluding* the query itself (mask-filtered by
          the min-label visitors; partial when a pass early-exits or a
          dense short-circuit fires).
    """
    acc: jax.Array
    hits: jax.Array


class Trace(NamedTuple):
    """Traversal outputs: the final callback carry + engine work counters.

    carry: the callback's accumulator pytree, one entry per lane.
    evals: member distance evaluations — the paper's work metric.
    iters: while_loop trips taken (after unrolling); the loop-overhead
           metric that ``unroll`` amortizes. Their sum is the walk's
           live lane-trips.
    stages: (S, 2) int32, one row per stage of the lane schedule
           (:func:`stage_widths`): the stage's lane width and its
           while_loop trips. Width times trips, summed, is the walk's
           issued lane-trips. None from engines without stages.

    ``acc``/``hits`` forward into an :class:`AccHits` carry so the DBSCAN
    epilogues read like the pre-redesign engine's outputs.
    """
    carry: Any
    evals: jax.Array
    iters: jax.Array
    stages: Any = None

    @property
    def acc(self):
        return self.carry.acc

    @property
    def hits(self):
        return self.carry.hits


class Visitor:
    """Base callback: visits every predicate match of every live lane.

    Hooks (all JAX-traceable, called per lane inside the vmapped loop):

      init_carry(ids, external, segs) -> carry
          Build the batch-wide initial accumulator pytree (leading dim =
          lane count). Only used when ``traverse`` gets ``carry=None``;
          callers chain multi-tree queries by passing the previous tree's
          carry instead.
      visit(carry, j, d2, hit, ctx) -> (carry, matched)
          Consume one member: ``j`` is the sorted point index, ``d2`` the
          squared distance, ``hit`` whether the predicate matched (the
          hook runs unconditionally — dead lanes/misses must be masked
          with ``jnp.where``, which keeps the K-unroll dead-guarding
          intact). ``matched`` reports whether the visitor *accepted* the
          neighbor (drives the dense-segment short-circuit).
      done(carry, ctx) -> bool
          Lane early-exit: a True lane stops traversing (feeds the
          while-loop mask — the engine never asks again).
      segment_done(carry, matched, seg_dense, ctx) -> bool
          After a visit: may the rest of the current segment be skipped?
          (The dense-cell short-circuit: all members of a dense segment
          share one label and core status, so one accepted hit can stand
          for the whole cell — paper §4.2.)

    Subclasses must be registered as pytrees whose leaves are the arrays
    the hooks close over (labels, masks, caps...) so the jitted engine
    caches on visitor *structure*, not identity.
    """

    def init_carry(self, ids, external: bool, segs: Segments):
        """Build the batch-wide initial accumulator pytree.

        Args:
            ids: (L,) int32 lane id vector (-1 marks inert padding).
            external: the batch queries points not resident in the tree.
            segs: the segment index being traversed.

        Returns:
            The carry pytree; every leaf's leading dim is the lane count.
        """
        raise NotImplementedError

    def visit(self, carry, j, d2, hit, ctx):
        """Consume one candidate member (called for every work unit).

        Args:
            carry: the lane's current accumulator pytree.
            j: sorted point index of the candidate member.
            d2: squared distance query→member.
            hit: whether the predicate matched — the hook runs
                unconditionally; misses and dead lanes must be masked
                with ``jnp.where`` (never branched on), which is what
                keeps the K-unroll dead-guarding intact.
            ctx: the per-lane :class:`QueryCtx`.

        Returns:
            ``(carry, matched)`` — the updated accumulator and whether
            the visitor *accepted* the neighbor (drives the dense-segment
            short-circuit via :meth:`segment_done`).
        """
        raise NotImplementedError

    def done(self, carry, ctx):
        """Lane early-exit: a True lane stops traversing (feeds the
        while-loop mask — the engine never asks it again). Default:
        never exit early.

        Returns:
            bool (per lane).
        """
        return jnp.bool_(False)

    def segment_done(self, carry, matched, seg_dense, ctx):
        """May the rest of the current segment be skipped after a visit?

        The dense-cell short-circuit (paper §4.2): all members of a dense
        segment share one label and core status, so one accepted hit can
        stand for the whole cell. Default: never skip.

        Args:
            carry: the accumulator *after* the visit.
            matched: did the visitor accept the member just visited?
            seg_dense: is the current segment a dense cell?
            ctx: the per-lane :class:`QueryCtx`.

        Returns:
            bool (per lane) — True skips the segment's remaining members.
        """
        return jnp.bool_(False)


@jax.tree_util.register_pytree_node_class
class CountVisitor(Visitor):
    """acc = |N_eps(q)| (incl. self) saturated at ``cap``; the lane dies
    once ``acc`` reaches ``cap`` (the paper's min_pts early exit). hits
    counts matches excluding the query itself."""

    def __init__(self, cap=INT_MAX):
        self.cap = cap

    def tree_flatten(self):
        return (self.cap,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def init_carry(self, ids, external, segs):
        z = jnp.zeros(ids.shape, jnp.int32)
        return AccHits(acc=z, hits=z)

    def visit(self, carry, j, d2, hit, ctx):
        acc = jnp.minimum(carry.acc + jnp.where(hit, 1, 0), self.cap)
        hits = carry.hits + jnp.where(hit & (j != ctx.self_id), 1, 0)
        return AccHits(acc=acc, hits=hits), hit

    def done(self, carry, ctx):
        return carry.acc >= self.cap


@jax.tree_util.register_pytree_node_class
class MinLabelVisitor(Visitor):
    """acc = min(vals[j]) over neighbors j with mask[j] (init: the query's
    own value); entering a *dense* segment stops at the first accepted
    member (all members share one label — the paper's dense-cell
    short-circuit). ``mask_wide`` + the engine's ``wide_lanes`` run the
    split first sweep's narrow/wide gather choice per lane."""

    def __init__(self, vals, mask, mask_wide=None):
        self.vals = vals
        self.mask = mask
        self.mask_wide = mask_wide

    def tree_flatten(self):
        return (self.vals, self.mask, self.mask_wide), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def init_carry(self, ids, external, segs):
        hits = jnp.zeros(ids.shape, jnp.int32)
        if external:
            return AccHits(acc=jnp.full(ids.shape, INT_MAX, jnp.int32),
                           hits=hits)
        safe = jnp.maximum(ids, jnp.int32(0))
        return AccHits(acc=self.vals[safe], hits=hits)

    def _accept(self, j, hit, ctx):
        if self.mask_wide is not None:
            return hit & jnp.where(ctx.wide, self.mask_wide[j], self.mask[j])
        return hit & self.mask[j]

    def visit(self, carry, j, d2, hit, ctx):
        ok = self._accept(j, hit, ctx)
        acc = jnp.where(ok, jnp.minimum(carry.acc, self.vals[j]), carry.acc)
        hits = carry.hits + jnp.where(ok & (j != ctx.self_id), 1, 0)
        return AccHits(acc=acc, hits=hits), ok

    def segment_done(self, carry, matched, seg_dense, ctx):
        return matched & seg_dense


@jax.tree_util.register_pytree_node_class
class CountMinLabelVisitor(MinLabelVisitor):
    """The fused first pass (DESIGN.md §4) — acc as in
    :class:`MinLabelVisitor` *and* hits = neighbor count saturated at
    ``cap`` in the same walk. The lane itself never exits early (the
    gather needs the full neighborhood), but the dense short-circuit
    fires for dense queries and for lanes whose count has saturated —
    one member hit still yields a dense cell's unified label, so the
    gather stays exact while the count work collapses to the paper's
    early-exit budget."""

    def __init__(self, vals, mask, cap=INT_MAX):
        super().__init__(vals, mask)
        self.cap = cap

    def tree_flatten(self):
        return (self.vals, self.mask, self.cap), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def visit(self, carry, j, d2, hit, ctx):
        ok = hit & self.mask[j]
        acc = jnp.where(ok, jnp.minimum(carry.acc, self.vals[j]), carry.acc)
        hits = jnp.minimum(
            carry.hits + jnp.where(ok & (j != ctx.self_id), 1, 0), self.cap)
        return AccHits(acc=acc, hits=hits), ok

    def segment_done(self, carry, matched, seg_dense, ctx):
        return matched & seg_dense & (ctx.dense | (carry.hits >= self.cap))


class KNNCarry(NamedTuple):
    """Per-lane k-best list, ascending by (d2, id); empty slots are
    (+inf, -1). ``ids`` are sorted-space point indices."""
    d2: jax.Array   # (k,) per lane
    ids: jax.Array  # (k,) per lane


@jax.tree_util.register_pytree_node_class
class KNNVisitor(Visitor):
    """Maintains the k nearest neighbors per lane under a shrinking
    distance bound (pairs with the :class:`Nearest` predicate).

    Selection is lexicographic on (d2, id) — exactly a stable argsort of
    the brute-force distance row — so ties at the k-th radius resolve
    deterministically to the smaller index, and tie *sets* match brute
    force. ``id_map`` remaps the engine's sorted point index before the
    comparison and the carry (pass ``segs.order`` to select/record by
    original index); ``None`` keeps sorted-space ids. ``worst_d2`` feeds
    the engine's per-lane pruning bound: subtrees (and members) farther
    than the current k-th best cannot improve the list. The query point
    itself is a neighbor at d2 = 0 (callers drop it if unwanted)."""

    def __init__(self, k: int, id_map=None):
        self.k = int(k)
        self.id_map = id_map

    def tree_flatten(self):
        return (self.id_map,), self.k

    @classmethod
    def tree_unflatten(cls, k, children):
        return cls(k, id_map=children[0])

    def init_carry(self, ids, external, segs):
        n = ids.shape[0]
        return KNNCarry(
            d2=jnp.full((n, self.k), jnp.inf, segs.pts.dtype),
            ids=jnp.full((n, self.k), -1, jnp.int32))

    def worst_d2(self, carry):
        return carry.d2[self.k - 1]

    def visit(self, carry, j, d2, hit, ctx):
        dd, ii = carry.d2, carry.ids
        jid = j if self.id_map is None else self.id_map[j].astype(jnp.int32)
        # slots strictly better than the candidate under (d2, id) order
        better = (dd < d2) | ((dd == d2) & (ii < jid))
        pos = jnp.sum(better.astype(jnp.int32))
        ar = jnp.arange(self.k, dtype=jnp.int32)
        d_sh, i_sh = jnp.roll(dd, 1), jnp.roll(ii, 1)
        nd = jnp.where(ar < pos, dd, jnp.where(ar == pos, d2, d_sh))
        ni = jnp.where(ar < pos, ii, jnp.where(ar == pos, jid, i_sh))
        take = hit & (pos < self.k)
        return KNNCarry(d2=jnp.where(take, nd, dd),
                        ids=jnp.where(take, ni, ii)), take


# --------------------------------------------------------------------- #
# the engine                                                            #
# --------------------------------------------------------------------- #

def lane_arrays(segs: Segments, predicates, use_range_mask: bool = False):
    """Resolve a predicate batch into per-lane query arrays.

    Returns ``(query_ids, q_arr, self_arr, dense_arr, rank_arr, external,
    r2, is_nearest)``: the lane id vector (-1 marks inert padding), the
    per-lane query coordinates, the engine context source arrays, whether
    the batch is external (DESIGN.md §6), the squared (initial) search
    radius, and whether the batch is distance-bounded k-NN.

    Shared by the vmapped reference engine (:func:`traverse_impl`) and the
    Pallas kernel backend (``repro.kernels.traverse``) so both resolve
    predicates identically.
    """
    n = segs.n_points
    pts = segs.pts
    is_nearest = isinstance(predicates, Nearest)
    if is_nearest:
        r2 = (jnp.asarray(jnp.inf, pts.dtype) if predicates.r is None
              else jnp.asarray(predicates.r, pts.dtype) ** 2)
    else:
        r2 = jnp.asarray(predicates.geometry.r, pts.dtype) ** 2
    query_ids, query_pts = predicates.ids, predicates.pts
    external = query_pts is not None
    if external:
        if use_range_mask:
            raise ValueError("use_range_mask needs tree-resident queries")
        if query_ids is None:
            query_ids = jnp.zeros(query_pts.shape[0], jnp.int32)
        q_arr = query_pts
        self_arr = jnp.full(query_ids.shape, -1, jnp.int32)   # never matches
        dense_arr = jnp.zeros(query_ids.shape, bool)
        rank_arr = jnp.zeros(query_ids.shape, jnp.int32)
    else:
        if query_ids is None:
            query_ids = jnp.arange(n, dtype=jnp.int32)
        safe = jnp.maximum(query_ids, jnp.int32(0))
        q_arr = pts[safe]
        self_arr = query_ids
        dense_arr = segs.dense_pt[safe]
        rank_arr = segs.seg_of_point[safe]
    return (query_ids, q_arr, self_arr, dense_arr, rank_arr, external, r2,
            is_nearest)


def lane_sort_key(reorder: str, query_ids, q_arr, external: bool,
                  depth_rank=None):
    """Per-lane sort key for divergence-aware lane reordering.

    The lane-tiled Pallas kernel retires a tile only when its *slowest*
    lane finishes, so wall clock is the sum of per-tile max walk depths.
    Sorting lanes so that similar-depth walks share a tile minimizes that
    sum without changing any per-lane result (the kernel applies the
    inverse permutation on exit — DESIGN.md §9). Policies:

      * ``"none"``   — no key (identity; today's launch order).
      * ``"morton"`` — the query points' Morton codes: lanes in a tile
        walk spatially-correlated subtrees (the ArborX pre-sort). The
        only option for external/halo batches, whose queries are not
        tree-resident.
      * ``"depth"``  — descending ``depth_rank[query_id]``, where
        ``depth_rank`` is the measured per-query loop-trip count of a
        prior pass over the same index (``Trace.iters`` of the fused
        first pass). Groups equal-depth walks directly instead of using
        locality as a proxy. Falls back to Morton for external batches
        and to identity when no rank is available (resident lanes are
        already Morton-ordered: ``segs.pts`` is Z-order sorted and
        compacted id vectors are ascending).

    Returns the key array, or ``None`` when reordering is the identity.
    Dead lanes (``query_ids < 0``) get the maximum key so they pack into
    all-dead tiles that retire immediately.
    """
    if reorder in (None, "none"):
        return None
    if reorder not in ("morton", "depth"):
        raise ValueError(
            f"reorder must be 'none', 'morton' or 'depth'; got {reorder!r}")
    live = query_ids >= 0
    if reorder == "depth" and not external:
        if depth_rank is None:
            return None
        safe = jnp.maximum(query_ids, jnp.int32(0))
        depth = depth_rank[safe].astype(jnp.int32)
        return jnp.where(live, -depth, jnp.int32(INT_MAX))
    codes = morton.morton_encode(q_arr)
    return jnp.where(live, codes, jnp.uint32(0xFFFFFFFF))


def make_step(tree: Tree, segs: Segments, callback, *, q, ctx: QueryCtx,
              lane_wide, r2, is_nearest: bool,
              node_mask=None, node_mask_wide=None,
              use_range_mask: bool = False):
    """Build the dead-guarded one-unit-of-work step for the rope walk.

    The returned ``(step, live_of)`` pair is *shape-polymorphic over a
    leading lane axis*: the reference engine instantiates it with scalar
    per-lane values under ``vmap``; the Pallas kernel backend
    (``repro.kernels.traverse``) instantiates it once per lane tile with
    ``(lane_tile,)``-shaped state. Both trace the exact same op sequence,
    which is what pins the kernel bit-identical to the interpreter-path
    engine.

    ``step`` maps ``(node, ptr, carry, evals) -> (node, ptr, carry,
    evals)`` where every state select is masked by the lane's liveness
    (the dead-guarding that makes K-unrolling exact); ``live_of(node,
    carry)`` is the lane's loop-mask condition.
    """
    m = segs.n_segments
    leaf_off = m - 1
    pts = segs.pts
    dual_nodes = node_mask_wide is not None

    def bound2(carry):
        """Per-lane squared search radius at this instant."""
        if is_nearest:
            return jnp.minimum(r2, callback.worst_d2(carry))
        return r2

    def live_of(node, carry):
        return (node >= 0) & ~callback.done(carry, ctx)

    def step(state):
        """One unit of work; a no-op for lanes that already finished."""
        node, ptr, carry, evals = state
        live = live_of(node, carry)
        node_safe = jnp.maximum(node, 0)
        is_member = live & (ptr >= 0)
        bnd = bound2(carry)

        # ---- member step: one distance test against sorted point ptr --
        j = jnp.where(is_member, ptr, 0)
        diff = q - pts[j]
        d2 = jnp.sum(diff * diff, axis=-1)
        hit = is_member & (d2 <= bnd)
        seg_id = jnp.where(node_safe >= leaf_off, node_safe - leaf_off, 0)
        carry_m, matched = callback.visit(carry, j, d2, hit, ctx)
        stop_seg = callback.segment_done(carry_m, matched,
                                         segs.dense_seg[seg_id], ctx)
        seg_done = (ptr + 1 >= segs.seg_end[seg_id]) | stop_seg
        member_next_node = jnp.where(seg_done, tree.miss[node_safe], node)
        member_next_ptr = jnp.where(seg_done, jnp.int32(-1), ptr + 1)

        # ---- node step: descend / skip -------------------------------
        is_leaf = node_safe >= leaf_off
        seg = jnp.where(is_leaf, node_safe - leaf_off, 0)
        bd2 = _box_dist2(q, tree.box_lo[node_safe], tree.box_hi[node_safe])
        overlap = bd2 <= bnd
        if use_range_mask:
            overlap = overlap & (tree.range_r[node_safe] >= ctx.rank)
        if node_mask is not None:
            if dual_nodes:
                overlap = overlap & jnp.where(lane_wide,
                                              node_mask_wide[node_safe],
                                              node_mask[node_safe])
            else:
                overlap = overlap & node_mask[node_safe]
        # internal: go left on overlap else rope; leaf: enter members on
        # overlap (empty segments skip straight to the rope).
        child = jnp.where(node_safe < leaf_off,
                          jnp.where(overlap, tree_left(tree, node_safe),
                                    tree.miss[node_safe]),
                          node)
        enter_members = is_leaf & overlap & (segs.seg_start[seg]
                                             < segs.seg_end[seg])
        node_next_node = jnp.where(is_leaf,
                                   jnp.where(enter_members, node,
                                             tree.miss[node_safe]),
                                   child)
        node_next_ptr = jnp.where(enter_members, segs.seg_start[seg],
                                  jnp.int32(-1))

        node_new = jnp.where(is_member, member_next_node, node_next_node)
        ptr_new = jnp.where(is_member, member_next_ptr, node_next_ptr)
        carry_new = jax.tree.map(
            lambda cm, c: jnp.where(is_member, cm, c), carry_m, carry)
        evals_new = evals + jnp.where(is_member, 1, 0)
        # freeze finished lanes so unrolled sub-steps are no-ops
        return (jnp.where(live, node_new, node),
                jnp.where(live, ptr_new, ptr),
                jax.tree.map(lambda cn, c: jnp.where(live, cn, c),
                             carry_new, carry),
                jnp.where(live, evals_new, evals))

    return step, live_of


def traverse_impl(tree: Tree, segs: Segments, predicates, callback,
                  carry=None,
                  node_mask: jax.Array | None = None,
                  node_mask_wide: jax.Array | None = None,
                  wide_lanes: jax.Array | None = None,
                  use_range_mask: bool = False,
                  unroll: int | None = None) -> Trace:
    """Run one fused traversal per predicate lane, driving ``callback``.

    predicates: an :func:`intersects` or :func:`nearest` batch. Its
        ``ids``/``pts`` select resident vs external queries and mark inert
        (-1) padding lanes; its geometry sets the (initial) search radius.
    callback: a :class:`Visitor`; its hooks consume matches on the fly
        over the ``carry`` accumulator pytree.
    carry: initial accumulator (leading dim = lane count). ``None`` asks
        the callback (``init_carry``). Passing the previous tree's final
        carry chains one query batch across several trees — the stream
        index's two-level reads and the sharded path's traveling halo
        queries (their running min rides the carry between shard visits).
    node_mask: optional (2m-1,) per-node flag; subtrees whose flag is
        False are pruned as if their boxes missed. Frontier sweeps pass
        the "subtree contains a changed point" flag (DESIGN.md §4) so
        lanes far from any change die within a few box tests.
    node_mask_wide / wide_lanes: optional second node mask selected per
        lane by the boolean ``wide_lanes``; lanes flagged wide also get
        ``ctx.wide`` so a dual-mask visitor switches its gather mask
        (the split first main sweep, DESIGN.md §4).
    unroll: work units per while-loop trip (``None``:
        :func:`default_unroll`).

    Lanes run under the schedule of :func:`stage_widths` (a pool with
    refill, then a tail by halves; DESIGN.md §4), which moves lanes but
    never changes a per-lane result.
    """
    if unroll is None:
        unroll = default_unroll()
    m = segs.n_segments
    leaf_off = m - 1
    root = jnp.int32(0 if m > 1 else leaf_off)  # m==1: the single leaf
    (query_ids, q_arr, self_arr, dense_arr, rank_arr, external, r2,
     is_nearest) = lane_arrays(segs, predicates, use_range_mask)
    if carry is None:
        carry = callback.init_carry(query_ids, external, segs)
    carry0 = carry
    if wide_lanes is None:
        wide_lanes = jnp.zeros_like(query_ids, dtype=bool)

    lanes = lanes0 = (q_arr, self_arr, dense_arr, rank_arr, wide_lanes)

    n_lanes = query_ids.shape[0]
    zeros = jnp.zeros(n_lanes, jnp.int32)
    # padding lanes (ids -1) start finished
    state = (jnp.where(query_ids >= 0, root, jnp.int32(-1)),
             jnp.full(n_lanes, -1, jnp.int32), carry, zeros, zeros)
    walk = (tree, segs, callback, r2, node_mask, node_mask_wide)
    flags = dict(is_nearest=is_nearest, use_range_mask=use_range_mask)

    def lane_live(state, *lane):
        node, ptr, carry, evals, iters = state
        return _lane_step(*walk, lane, **flags)[1](node, carry)

    live_all = jax.vmap(lane_live)

    def trip_all(state, *lanes):
        return _trip_all(*walk, state, lanes, unroll=unroll, **flags)

    def walk_while(cond_live, state, lanes):
        """Trip every lane of a batch until at most ``cond_live`` are
        live; returns the state and the number of trips."""
        def cond(s):
            return jnp.sum(live_all(s[0], *lanes)) > cond_live
        return lax.while_loop(
            cond, lambda s: (trip_all(s[0], *lanes), s[1] + 1),
            (state, jnp.int32(0)))

    out = state[2:]           # (carry, evals, iters): what the walk returns
    pos = jnp.arange(n_lanes, dtype=jnp.int32)
    widths = stage_widths(n_lanes)
    trips = []
    if n_lanes > POOL_LANES:
        width = widths[0]
        nxt = widths[1] if len(widths) > 1 else 0
        # lane positions waiting to walk, in input order (Morton order for
        # resident queries); padding lanes never wait
        waiting = query_ids >= 0
        n_waiting = jnp.sum(waiting, dtype=jnp.int32)
        queue = jnp.nonzero(waiting, size=n_lanes,
                            fill_value=n_lanes)[0].astype(jnp.int32)

        def refill(r):
            """Walk until at most 3/4 of the pool is live (once no lane
            waits: as many as the next stage holds), write the finished
            lanes back, and give their slots the next waiting lanes: the
            root node, no member pointer, zeroed counters, and the lane's
            rows of the initial carry and lane arrays."""
            st, lp, ps, out, head, _, t = r
            st, dt = walk_while(
                jnp.where(head < n_waiting, 3 * width // 4, nxt), st, lp)
            dead = ~live_all(st, *lp)
            out = jax.tree.map(
                lambda o, x: o.at[jnp.where(dead, ps, n_lanes)].set(
                    x, mode="drop"), out, st[2:])
            k = head + jnp.cumsum(dead, dtype=jnp.int32) - 1
            fill = dead & (k < n_waiting)
            src = jnp.where(fill, queue[jnp.minimum(k, n_lanes - 1)], 0)
            node, ptr, c, ev, it = st
            st = (jnp.where(fill, root, node), jnp.where(fill, -1, ptr),
                  jax.tree.map(lambda x, x0: _rows_where(fill, x0[src], x),
                               c, carry0),
                  jnp.where(fill, 0, ev), jnp.where(fill, 0, it))
            lp = jax.tree.map(lambda x, x0: _rows_where(fill, x0[src], x),
                              lp, lanes0)
            n_live = jnp.sum(~dead | fill, dtype=jnp.int32)
            return (st, lp, jnp.where(fill, src, ps), out,
                    head + jnp.sum(fill, dtype=jnp.int32), n_live, t + dt)

        # the pool starts with every slot finished and writing nowhere,
        # so the first refill fills it; it ends once no lane waits and
        # the next stage holds its live lanes
        pool, pool_lanes = jax.tree.map(lambda x: x[:width], (state, lanes))
        pool = (jnp.full(width, -1, jnp.int32),) + pool[1:]
        state, lanes, pos, out, _, _, t = lax.while_loop(
            lambda r: (r[4] < n_waiting) | (r[5] > nxt), refill,
            (pool, pool_lanes, jnp.full(width, n_lanes, jnp.int32), out,
             jnp.int32(0), jnp.int32(0), jnp.int32(0)))
        trips.append(t)
        if nxt:
            state, lanes, pos = _compact(live_all(state, *lanes), nxt,
                                         n_lanes, (state, lanes, pos))
        widths_tail = widths[1:]
    else:
        widths_tail = widths
    for k, width in enumerate(widths_tail):
        nxt = widths_tail[k + 1] if k + 1 < len(widths_tail) else 0
        state, t = walk_while(nxt, state, lanes)
        trips.append(t)
        # write this stage's lanes back (finished ones are final; the
        # still-live ones are overwritten by a later stage)
        out = jax.tree.map(lambda o, x: o.at[pos].set(x, mode="drop"),
                           out, state[2:])
        if nxt:
            state, lanes, pos = _compact(live_all(state, *lanes), nxt,
                                         n_lanes, (state, lanes, pos))
    carry, evals, iters = out
    stages = jnp.stack([jnp.asarray(widths, jnp.int32), jnp.stack(trips)],
                       axis=1)
    return Trace(carry=carry, evals=evals, iters=iters, stages=stages)


def _lane_step(tree, segs, callback, r2, node_mask, node_mask_wide, lane, *,
               is_nearest, use_range_mask):
    """:func:`make_step` for one lane, from its row of the lane arrays
    (query point, self id, dense flag, segment rank, wide flag)."""
    q, q_self, q_dense, q_rank, lane_wide = lane
    ctx = QueryCtx(self_id=q_self, dense=q_dense, rank=q_rank,
                   wide=lane_wide)
    return make_step(tree, segs, callback, q=q, ctx=ctx, lane_wide=lane_wide,
                     r2=r2, is_nearest=is_nearest, node_mask=node_mask,
                     node_mask_wide=node_mask_wide,
                     use_range_mask=use_range_mask)


@partial(jax.jit, inline=True,
         static_argnames=("is_nearest", "use_range_mask", "unroll"))
def _trip_all(tree, segs, callback, r2, node_mask, node_mask_wide, state,
              lanes, *, is_nearest, use_range_mask, unroll):
    """One while-loop trip of every lane of a batch: ``unroll``
    dead-guarded steps per lane (a finished lane stays frozen, as under a
    per-lane loop). Jitted and inlined, so a process traces it once per
    batch width and visitor structure, for every walk program and stage
    that share them: a warm process traces each walk program before its
    cache load."""
    def lane_trip(state, *lane):
        node, ptr, carry, evals, iters = state
        step, live_of = _lane_step(
            tree, segs, callback, r2, node_mask, node_mask_wide, lane,
            is_nearest=is_nearest, use_range_mask=use_range_mask)
        live = live_of(node, carry)
        inner = (node, ptr, carry, evals)
        for _ in range(unroll):
            inner = step(inner)
        return (*inner, iters + jnp.where(live, 1, 0))

    return jax.vmap(lane_trip)(state, *lanes)


def _compact(live, width: int, n_lanes: int, batch):
    """Move the (at most ``width``) live lanes of ``batch`` = (state,
    lanes, pos), in order, into a batch of ``width``; spare slots are
    finished and write nowhere (position ``n_lanes``)."""
    idx = jnp.nonzero(live, size=width, fill_value=0)[0]
    spare = jnp.arange(width) >= jnp.sum(live)
    state, lanes, pos = jax.tree.map(lambda x: x[idx], batch)
    state = (jnp.where(spare, -1, state[0]),) + state[1:]
    return state, lanes, jnp.where(spare, n_lanes, pos)


def _rows_where(mask, a, b):
    """``jnp.where`` with a per-row ``mask`` over arrays of any rank."""
    return jnp.where(mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)


# Lane scheduling (DESIGN.md §4): a batched walk costs every lane one trip
# for as long as its slowest lane walks. A walk wider than POOL_LANES runs
# at that width: once at most three quarters of its lanes are live, the
# finished ones are written back and their slots take the next waiting
# queries. Once none wait, the live lanes move into a batch half as wide
# whenever at most half are live, down to TAIL_MIN_LANES. Exact: lanes
# never interact.
POOL_LANES = 8192
TAIL_MIN_LANES = 512


def stage_widths(n_lanes: int) -> list:
    """Lane widths of the stages a walk of ``n_lanes`` lanes runs
    through: the pool first, for a walk wider than ``POOL_LANES``, then
    the tail's halvings."""
    width = min(n_lanes, POOL_LANES)
    widths = [width]
    while width // 2 >= TAIL_MIN_LANES:
        width //= 2
        widths.append(width)
    return widths


# The jitted entry point. Callers already inside a traced context (the
# sharded distributed kernel runs under shard_map) use ``traverse_impl``
# directly: a nested jit there would launch a separate per-device module
# whose collective-free body still participates in the host-device
# rendezvous machinery and can wedge the outer collectives. Predicates and
# callbacks are pytrees — their array leaves (labels, masks, caps, eps)
# are traced operands, their structure (visitor class, k) is the cache
# key — so parameter sweeps reuse one compiled program per visitor shape.
traverse = partial(jax.jit,
                   static_argnames=("use_range_mask", "unroll"))(traverse_impl)


def tree_left(tree: Tree, node):
    return tree.left[jnp.clip(node, 0, tree.left.shape[0] - 1)]


def _ids_from_mask(n: int, query_active) -> jax.Array:
    """Full-width id vector with inactive lanes marked -1 (no compaction)."""
    ids = jnp.arange(n, dtype=jnp.int32)
    if query_active is None:
        return ids
    return jnp.where(query_active, ids, jnp.int32(-1))


# --------------------------------------------------------------------- #
# DBSCAN epilogue helpers (visitor instances over the engine)           #
# --------------------------------------------------------------------- #

def count_neighbors(tree: Tree, segs: Segments, eps: float, cap: int,
                    query_active=None) -> jax.Array:
    """|N_eps(x)| per sorted point, saturated at ``cap`` (early exit)."""
    return count_neighbors_with_work(tree, segs, eps, cap, query_active)[0]


def count_neighbors_with_work(tree: Tree, segs: Segments, eps: float,
                              cap: int, query_active=None):
    """(counts, distance_evaluations) — the paper's work metric."""
    n = segs.n_points
    tr = traverse(tree, segs,
                  intersects(sphere(eps), ids=_ids_from_mask(n, query_active)),
                  CountVisitor(cap=cap))
    return tr.acc, tr.evals


def minlabel_sweep(tree: Tree, segs: Segments, eps: float, labels: jax.Array,
                   gather_mask: jax.Array, query_active: jax.Array):
    """Per active query: min(label) over neighbors with gather_mask.

    Returns (min_labels, matched_other_count); an inactive query returns
    its own ``labels`` value (no-op hook). ``labels`` must already be
    consistent within dense segments (the caller re-unifies after updates).
    """
    tr = traverse(tree, segs,
                  intersects(sphere(eps),
                             ids=_ids_from_mask(segs.n_points, query_active)),
                  MinLabelVisitor(labels, gather_mask))
    # inactive lanes carry no query identity inside the engine; restore
    # the own-value contract here where lane i <=> point i
    acc = jnp.where(query_active, tr.acc, labels)
    return acc, tr.hits


def fused_count_minlabel(tree: Tree, segs: Segments, eps: float,
                         point_vals: jax.Array, point_mask=None,
                         query_ids=None, cap: int | jax.Array = INT_MAX,
                         traverse_fn=None, depth_rank=None) -> Trace:
    """The fused first pass (DESIGN.md §4): one walk, two answers.

    Returns the full ``Trace``: ``acc`` is the min gathered value over all
    masked neighbors (candidate label — the caller validates it against the
    core mask once counts are known), ``hits`` the neighbor count excluding
    self, exact up to saturation at ``cap`` (pass ``min_pts - 1``; dense
    queries are core by construction and may undercount). ``traverse_fn``
    swaps the execution engine (the Pallas kernel backend passes
    ``repro.kernels.traverse.traverse``); the default is the vmapped
    reference engine.
    """
    if point_mask is None:
        point_mask = jnp.ones(segs.n_points, bool)
    if traverse_fn is None:   # the one place the engine default resolves
        traverse_fn = traverse
    # depth_rank is a kernel-only lane-scheduling hint (the reference
    # engine has no lane tiles to pack); forwarded only when present so
    # reference traverse_fn signatures stay unchanged.
    kw = {} if depth_rank is None else {"depth_rank": depth_rank}
    return traverse_fn(tree, segs, intersects(sphere(eps), ids=query_ids),
                       CountMinLabelVisitor(point_vals, point_mask, cap=cap),
                       **kw)


def border_gather(tree: Tree, segs: Segments, eps: float, root_labels,
                  core_mask, query_active):
    """Min core-neighbor root label per non-core query; INT_MAX if none."""
    sentinel = jnp.full_like(root_labels, INT_MAX)
    vals = jnp.where(core_mask, root_labels, sentinel)
    tr = traverse(tree, segs,
                  intersects(sphere(eps),
                             ids=_ids_from_mask(segs.n_points, query_active)),
                  MinLabelVisitor(vals, core_mask))
    # active lanes start from vals[q] (INT_MAX for non-core queries), so
    # acc == INT_MAX <=> no core neighbor (noise); inactive lanes return
    # their own vals[q] to keep the lane i <=> point i contract.
    acc = jnp.where(query_active, tr.acc, vals)
    return acc, tr.hits
