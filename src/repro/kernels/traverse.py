"""Pallas traversal kernel: the rope-based BVH walk as a lane-tiled kernel.

The engine in ``repro.core.traversal`` lowers the walk through a vmapped
``lax.while_loop`` — correct, but generic: XLA owns the memory placement
and the loop overhead. This module maps the same walk onto the hardware
the way the paper's ArborX kernels do on CUDA (DESIGN.md §9):

  * **lane tiling** — predicate lanes are tiled into blocks of
    ``LANE_TILE`` queries; the grid iterates over lane blocks the way a
    CUDA launch iterates over warps. Per-lane walk state (node cursor,
    member pointer, visitor carry, work counters) is a handful of
    ``(LANE_TILE,)`` vectors — the TPU analogue of the paper's O(1)
    per-thread state.
  * **index residency** — node AABBs, ropes, child links, and the segment
    tables ride in as whole-array VMEM block specs (``index_map`` pinned
    to block 0), so every box test and rope chase is a fast-memory gather;
    the engine's HBM-resident gathers become VMEM reads.
  * **inlined visitors** — the three hot DBSCAN callbacks
    (``CountVisitor``, ``MinLabelVisitor``, ``CountMinLabelVisitor``) are
    reconstructed *inside* the kernel from their array leaves and traced
    straight into the walk body: no callback dispatch survives lowering.
    Arbitrary user visitors (and ``nearest``/k-NN predicates) fall back to
    the interpreter-path engine — same semantics, generic lowering.
  * **K-unrolled dead-guarded walk** — each while-loop trip runs
    ``unroll`` work units per lane with every state select masked by the
    lane's liveness, exactly the reference engine's trip shape
    (DESIGN.md §4, §9 on why this is divergence-free in a lane-tiled
    kernel).

Bit-identity is by construction, not by luck: the kernel body calls the
*same* ``traversal.make_step`` the vmapped engine uses, so both trace the
identical op sequence over identical float32 arithmetic —
``tests/test_golden.py`` pins ``backend="pallas-tree"`` byte-equal to the
reference backends. The per-lane ``evals``/``iters`` work counters are
threaded out as kernel outputs so ``benchmarks/run.py --check`` gates the
kernel's traversal work exactly like the engine's.

On CPU (and GPU, where the TPU compiler params do not apply) the kernel
runs in Pallas interpret mode — the CI path that keeps the kernel body
exercised on every commit. On TPU it has no compiled form yet
(:data:`TPU_UNSUPPORTED`), and :func:`traverse` raises rather than run
the emulator there.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import morton, traversal
from repro.core.grid import Segments
from repro.core.lbvh import Tree
from .pairwise import resolve_interpret

INT_MAX = traversal.INT_MAX

#: Why the kernel cannot run on a TPU: the walk reads the index per lane
#: (``pts[ptr]``, ``box_lo[node]``, ropes, segment tables) with a
#: ``(LANE_TILE,)`` vector of data-dependent indices, and Mosaic lowers a
#: gather only when input, indices and output share one shape (a
#: within-vreg ``take_along_axis``).
TPU_UNSUPPORTED = (
    "the pallas-tree traversal kernel does not compile for TPU: Mosaic "
    "refuses its per-lane gathers from the VMEM-resident index "
    "(_gather_lowering_rule: 'Shape mismatch in input, indices and "
    "output'); use algorithm='fdbscan' or 'auto', which run the vmapped "
    "walk")

# Queries per kernel block. 128 matches the VPU lane count; each block's
# walk state is a few (128,) vectors, and a block retires when its slowest
# lane finishes (the warp-convergence analogue — see DESIGN.md §9).
LANE_TILE = 128

# The kernel is always lane-tiled, so the lockstep amortization argument
# of DESIGN.md §4 applies on every backend (the engine only defaults to 4
# on TPU/GPU because its *vmapped* loop is cheap on CPU).
PALLAS_UNROLL = 4

#: Visitor types whose hooks the kernel inlines; anything else falls back
#: to the interpreter-path engine.
FUSIBLE_VISITORS = (traversal.CountVisitor, traversal.MinLabelVisitor,
                    traversal.CountMinLabelVisitor)


class _Cfg(NamedTuple):
    """Static kernel specialization (part of the jit cache key)."""
    kind: str               # "count" | "minlabel" | "countminlabel"
    unroll: int
    use_range_mask: bool
    has_node_mask: bool
    dual_nodes: bool        # node_mask_wide present
    dual_gather: bool       # MinLabelVisitor.mask_wide present
    reorder: bool = False   # lane permutation by sort key (DESIGN.md §9)


def fusible(predicates, callback) -> bool:
    """Can this (predicate, callback) pair run as the Pallas kernel?

    True for ``intersects`` batches driving one of the three hot DBSCAN
    visitors (:data:`FUSIBLE_VISITORS`). ``nearest`` predicates and custom
    visitors are not fusible — :func:`traverse` transparently falls back
    to the interpreter-path engine for them.
    """
    return (isinstance(predicates, traversal.Intersects)
            and type(callback) in FUSIBLE_VISITORS)


def _walk_kernel(*refs, cfg: _Cfg):
    """The kernel body: one lane tile's full rope walk to quiescence."""
    it = iter(refs)
    # ---- lane-tiled state inputs -------------------------------------
    q = next(it)[...]
    qid = next(it)[...]
    self_id = next(it)[...]
    dense = next(it)[...] != 0
    rank = next(it)[...]
    wide = next(it)[...] != 0
    acc0 = next(it)[...]
    hits0 = next(it)[...]
    # ---- VMEM-resident index (whole-array block specs) ---------------
    pts = next(it)[...]
    seg_start = next(it)[...]
    seg_end = next(it)[...]
    dense_seg = next(it)[...] != 0
    left = next(it)[...]
    miss = next(it)[...]
    range_r = next(it)[...] if cfg.use_range_mask else None
    box_lo = next(it)[...]
    box_hi = next(it)[...]
    node_mask = (next(it)[...] != 0) if cfg.has_node_mask else None
    node_mask_wide = (next(it)[...] != 0) if cfg.dual_nodes else None
    if cfg.kind != "count":
        vals = next(it)[...]
        mask = next(it)[...] != 0
        mask_wide = (next(it)[...] != 0) if cfg.dual_gather else None
    # ---- scalars (SMEM) ----------------------------------------------
    r2 = next(it)[0, 0]
    cap = next(it)[0, 0]
    # ---- outputs ------------------------------------------------------
    acc_out, hits_out, evals_out, iters_out = refs[-4:]

    n_nodes = miss.shape[0]
    # Reassemble the index views the shared step closes over. Fields the
    # walk never touches stay None (the step only reads left/miss/range_r/
    # boxes and pts/seg_start/seg_end/dense_seg — see traversal.make_step).
    tree = Tree(left=left, right=None, parent=None, miss=miss,
                range_r=range_r if cfg.use_range_mask
                else jnp.zeros(n_nodes, jnp.int32),
                box_lo=box_lo, box_hi=box_hi)
    segs = Segments(pts=pts, order=None, seg_start=seg_start,
                    seg_end=seg_end, seg_of_point=None, dense_seg=dense_seg,
                    dense_pt=None, codes=None, prim_lo=None, prim_hi=None)
    # Inline the visitor: rebuild it from the kernel-resident leaves so
    # its visit/done/segment_done hooks trace into the walk body.
    if cfg.kind == "count":
        callback = traversal.CountVisitor(cap=cap)
    elif cfg.kind == "minlabel":
        callback = traversal.MinLabelVisitor(
            vals, mask, mask_wide if cfg.dual_gather else None)
    else:
        callback = traversal.CountMinLabelVisitor(vals, mask, cap=cap)

    ctx = traversal.QueryCtx(self_id=self_id, dense=dense, rank=rank,
                             wide=wide)
    step, live_of = traversal.make_step(
        tree, segs, callback, q=q, ctx=ctx, lane_wide=wide, r2=r2,
        is_nearest=False, node_mask=node_mask,
        node_mask_wide=node_mask_wide, use_range_mask=cfg.use_range_mask)

    lane_on = qid >= 0
    node0 = jnp.where(lane_on, jnp.int32(0), jnp.int32(-1))  # root = 0
    ptr0 = jnp.full_like(qid, -1)
    zeros = jnp.zeros_like(qid)
    carry0 = traversal.AccHits(acc=acc0, hits=hits0)

    def cond(state):
        node, ptr, carry, evals, iters = state
        return jnp.any(live_of(node, carry))

    def body(state):
        node, ptr, carry, evals, iters = state
        trip_live = live_of(node, carry)
        inner = (node, ptr, carry, evals)
        for _ in range(cfg.unroll):
            inner = step(inner)
        node, ptr, carry, evals = inner
        # per-lane trip counter: only lanes live at trip start advance,
        # so iters matches the vmapped engine's per-lane loop-trip count
        return (node, ptr, carry, evals,
                iters + jnp.where(trip_live, 1, 0))

    node, ptr, carry, evals, iters = lax.while_loop(
        cond, body, (node0, ptr0, carry0, zeros, zeros))
    acc_out[...] = carry.acc
    hits_out[...] = carry.hits
    evals_out[...] = evals
    iters_out[...] = iters


@functools.partial(jax.jit,
                   static_argnames=("cfg", "lane_tile", "interpret"))
def _run(cfg: _Cfg, lane_tile: int, interpret: bool,
         q, qid, self_id, dense, rank, wide, acc0, hits0, sort_key,
         pts, seg_start, seg_end, dense_seg, left, miss, range_r,
         box_lo, box_hi, node_mask, node_mask_wide, vals, mask, mask_wide,
         r2, cap):
    """Pad the lane axis, assemble block specs, and launch the kernel."""
    L = qid.shape[0]
    if cfg.reorder:
        # Permute lanes by sort_key so each tile walks correlated
        # subtrees; dead lanes carry the max key, packing them into
        # all-dead tiles that retire immediately. argsort is stable, so
        # equal keys keep lane order; the inverse permutation below makes
        # every per-lane output bit-identical to the unpermuted launch
        # (per-lane state never crosses lanes — DESIGN.md §9).
        perm = jnp.argsort(sort_key)
        inv = jnp.argsort(perm)
        q, qid, self_id, dense, rank, wide, acc0, hits0 = (
            x[perm] for x in (q, qid, self_id, dense, rank, wide,
                              acc0, hits0))
    Lp = -(-L // lane_tile) * lane_tile
    d = pts.shape[1]

    def pad(x, value):
        if x.shape[0] == Lp:
            return x
        width = ((0, Lp - x.shape[0]),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, width, constant_values=value)

    lane_inputs = [
        (pad(q, 0), pl.BlockSpec((lane_tile, d), lambda i: (i, 0))),
        (pad(qid, -1), None),           # -1: padding lanes are inert
        (pad(self_id, -1), None),
        (pad(dense.astype(jnp.int32), 0), None),
        (pad(rank, 0), None),
        (pad(wide.astype(jnp.int32), 0), None),
        (pad(acc0, 0), None),
        (pad(hits0, 0), None),
    ]
    lane_spec = pl.BlockSpec((lane_tile,), lambda i: (i,))

    def whole(x):
        """Whole-array VMEM residency: every block maps to block 0."""
        nd = x.ndim
        return pl.BlockSpec(x.shape, lambda i, _nd=nd: (0,) * _nd)

    full_inputs = [pts, seg_start, seg_end, dense_seg.astype(jnp.int32),
                   left, miss]
    if cfg.use_range_mask:
        full_inputs.append(range_r)
    full_inputs += [box_lo, box_hi]
    if cfg.has_node_mask:
        full_inputs.append(node_mask.astype(jnp.int32))
    if cfg.dual_nodes:
        full_inputs.append(node_mask_wide.astype(jnp.int32))
    if cfg.kind != "count":
        full_inputs.append(vals)
        full_inputs.append(mask.astype(jnp.int32))
        if cfg.dual_gather:
            full_inputs.append(mask_wide.astype(jnp.int32))

    scalar_inputs = [jnp.full((1, 1), r2, pts.dtype),
                     jnp.full((1, 1), cap, jnp.int32)]
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.MemorySpace.SMEM)

    operands = ([x for x, _ in lane_inputs] + full_inputs + scalar_inputs)
    in_specs = ([spec or lane_spec for _, spec in lane_inputs]
                + [whole(x) for x in full_inputs]
                + [scalar_spec] * 2)
    # acc inherits the carry's dtype (MinLabelVisitor gathers whatever
    # dtype its vals are); hits/evals/iters are engine-owned int32
    out_shape = ([jax.ShapeDtypeStruct((Lp,), acc0.dtype)]
                 + [jax.ShapeDtypeStruct((Lp,), jnp.int32)] * 3)
    out_specs = [lane_spec] * 4

    acc, hits, evals, iters = pl.pallas_call(
        functools.partial(_walk_kernel, cfg=cfg),
        grid=(Lp // lane_tile,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    outs = (acc[:L], hits[:L], evals[:L], iters[:L])
    if cfg.reorder:
        outs = tuple(x[inv] for x in outs)
    return outs


def traverse(tree: Tree, segs: Segments, predicates, callback, carry=None,
             node_mask=None, node_mask_wide=None, wide_lanes=None,
             use_range_mask: bool = False, unroll: int | None = None,
             lane_tile: int = LANE_TILE,
             interpret: bool | None = None, reorder: str = "none",
             depth_rank=None) -> traversal.Trace:
    """Drop-in Pallas replacement for :func:`repro.core.traversal.traverse`.

    Runs the rope-based BVH walk as a lane-tiled Pallas kernel when the
    (predicate, callback) pair is fusible (:func:`fusible`); anything else
    — ``nearest`` predicates, custom visitors, or an index too small to
    carry a tree — falls back to the interpreter-path engine with
    identical semantics.

    Args:
        tree: the LBVH over ``segs`` (``None`` falls back to the engine).
        segs: the segment index the tree was built over.
        predicates: an ``intersects``/``nearest`` batch (see
            ``repro.core.traversal``).
        callback: a :class:`~repro.core.traversal.Visitor`.
        carry: optional initial accumulator (chained multi-tree queries);
            ``None`` asks the callback's ``init_carry``.
        node_mask / node_mask_wide / wide_lanes: descent pruning and the
            split first sweep, exactly as in the reference engine.
        use_range_mask: the paper's "hide leaves j < i" subtree mask.
        unroll: work units per while-loop trip (default
            :data:`PALLAS_UNROLL`; the engine's backend-adaptive default
            does not apply — the kernel is always lane-tiled).
        lane_tile: queries per kernel block (default :data:`LANE_TILE`).
        interpret: Pallas interpret mode; default interpreted off TPU
            (the CPU CI path). A compiled launch raises
            ``NotImplementedError`` (:data:`TPU_UNSUPPORTED`).
        reorder: lane-permutation policy — ``"none"`` (default, today's
            launch order), ``"morton"`` (sort lanes by the query points'
            Morton code so a tile walks correlated subtrees), or
            ``"depth"`` (sort by descending ``depth_rank``, the measured
            per-query walk depth from a prior pass — the strongest
            divergence remedy; falls back to Morton for external batches
            and to identity when no rank is available). Results are
            bit-identical for every policy: per-lane walk state never
            crosses lanes, and the inverse permutation is applied to all
            per-lane outputs on exit (see :func:`repro.core.traversal.\
lane_sort_key` and DESIGN.md §9).
        depth_rank: optional ``(n_points,)`` int32 of per-query walk
            depth (e.g. ``Trace.iters`` from the fused first pass),
            indexed by sorted point id; used only by ``reorder="depth"``.

    Returns:
        A :class:`~repro.core.traversal.Trace` whose ``carry`` is an
        ``AccHits`` pytree and whose ``evals``/``iters`` are the kernel's
        per-lane work counters — bit-identical ``acc``/``hits``/``evals``
        to the reference engine on the same inputs.
    """
    if (tree is None or segs.n_segments < 2
            or not fusible(predicates, callback)):
        return traversal.traverse(
            tree, segs, predicates, callback, carry=carry,
            node_mask=node_mask, node_mask_wide=node_mask_wide,
            wide_lanes=wide_lanes, use_range_mask=use_range_mask,
            unroll=unroll)
    if unroll is None:
        unroll = PALLAS_UNROLL
    interpret = resolve_interpret(interpret)
    if not interpret:
        raise NotImplementedError(TPU_UNSUPPORTED)

    (query_ids, q_arr, self_arr, dense_arr, rank_arr, external, r2,
     _) = traversal.lane_arrays(segs, predicates, use_range_mask)
    if carry is None:
        carry = callback.init_carry(query_ids, external, segs)
    if wide_lanes is None:
        wide_lanes = jnp.zeros_like(query_ids, dtype=bool)

    kind = {traversal.CountVisitor: "count",
            traversal.MinLabelVisitor: "minlabel",
            traversal.CountMinLabelVisitor: "countminlabel"}[type(callback)]
    dual_gather = (kind == "minlabel"
                   and callback.mask_wide is not None)
    sort_key = traversal.lane_sort_key(reorder, query_ids, q_arr, external,
                                       depth_rank)
    cfg = _Cfg(kind=kind, unroll=int(unroll),
               use_range_mask=bool(use_range_mask),
               has_node_mask=node_mask is not None,
               dual_nodes=node_mask_wide is not None,
               dual_gather=dual_gather,
               reorder=sort_key is not None)

    cap = getattr(callback, "cap", INT_MAX)
    vals = getattr(callback, "vals", None)
    mask = getattr(callback, "mask", None)
    mask_wide = callback.mask_wide if dual_gather else None

    # Launch accounting (DESIGN.md §12): only outside jit tracing — the
    # pallas walk may also run nested inside a jitted first pass, where a
    # host-side counter bump would fire at trace time, not per run.
    from repro.obs import metrics as obs_metrics
    if (obs_metrics.active() is not None
            and not isinstance(segs.pts, jax.core.Tracer)):
        obs_metrics.inc("pallas_kernel_launches_total", kind=kind)
        obs_metrics.inc("pallas_kernel_lanes_total",
                        float(q_arr.shape[0]), kind=kind)

    acc, hits, evals, iters = _run(
        cfg, int(lane_tile), bool(interpret),
        q_arr, query_ids, self_arr, dense_arr, rank_arr, wide_lanes,
        carry.acc, carry.hits, sort_key,
        segs.pts, segs.seg_start, segs.seg_end, segs.dense_seg,
        tree.left, tree.miss, tree.range_r if cfg.use_range_mask else None,
        tree.box_lo, tree.box_hi, node_mask, node_mask_wide,
        vals, mask, mask_wide, r2, cap)
    return traversal.Trace(carry=traversal.AccHits(acc=acc, hits=hits),
                           evals=evals, iters=iters)
