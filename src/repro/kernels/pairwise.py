"""Pallas TPU tile kernels for the dense compute hot spot of DBSCAN.

The paper's DenseBox insight is that in dense regions most distance tests
are wasted. On a GPU the answer is to *skip* them (per-thread early exits,
linear cell scans). On a TPU, branches idle the vector units — the native
move (DESIGN.md §3) is to *batch* them: a 128x128 tile of squared
distances, fully resident in VMEM, computed in the difference form

    d2[j, i] = sum_k (q[i, k] - r[j, k])^2

with reference points on sublanes and queries on lanes. For d <= 3 the
MXU form |q|^2 + |r|^2 - 2<q, r> would contract over two or three
elements and lose the low bits to cancellation; the difference form is
exact float32 VPU work that decides every pair the way the tree walk and
the pure-jnp oracles do. The epilogues (neighbor counting for
core-point determination; min-label relaxation for the union-find hook)
fuse into the same tile so the n x n distance matrix is never
materialized — the kernel streams over reference tiles and keeps only
O(TQ) accumulators, preserving the paper's O(n)-memory on-the-fly
property.

Kernels (each has a pure-jnp oracle in ref.py and a jit wrapper below):
  * ``count_kernel``     — per query, # of reference points within eps
                           (saturating at a cap: the bulk analogue of the
                           paper's early exit at minpts).
  * ``minlabel_kernel``  — per query, min label over masked (core)
                           reference points within eps + matched count
                           (the fused hook of the main phase).

Grid layout: (n_q_tiles, n_r_tiles); the reference axis is the innermost
(sequential) dimension so output tiles are revisited and accumulated in
VMEM. Every block is 2-D, the layout Mosaic tiles natively: queries ride
in transposed as ``(d, TQ)`` row blocks, per-reference labels and masks
as ``(TR, 1)`` columns, and per-query outputs as lane-dense ``(1, TQ)``
rows. Padding uses +/-1e30 coordinates (distances overflow to +inf =>
never within eps), so no validity masks are needed in the hot loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max

# 128 matches the VPU lane count.
TILE_Q = 128
TILE_R = 128


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode: ``None`` means compiled on TPU and
    interpreted on every other backend (the CPU test path). The kernels
    carry TPU compiler params, so there is no compiled form elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _tile_hits(qt_ref, r_ref, eps2_ref):
    """(TR, TQ) within-eps mask of one (reference tile, query tile) pair."""
    qt = qt_ref[...]                                     # (d, TQ)
    r = r_ref[...]                                       # (TR, d)
    d2 = None
    for k in range(qt.shape[0]):
        diff = qt[k:k + 1, :] - r[:, k:k + 1]            # (TR, TQ)
        d2 = diff * diff if d2 is None else d2 + diff * diff
    return d2 <= eps2_ref[0, 0]


def count_kernel(qt_ref, r_ref, eps2_ref, out_ref, *, cap: int):
    """out[i] (+)= saturating count of r within eps of q_i."""
    hits = jnp.sum(_tile_hits(qt_ref, r_ref, eps2_ref).astype(jnp.int32),
                   axis=0, keepdims=True)                # (1, TQ)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Saturate: the paper terminates traversal at minpts; here extra hits
    # saturate instead of branching (dense tiles beat branches on TPU).
    out_ref[...] = jnp.minimum(out_ref[...] + hits, cap)


def minlabel_kernel(qt_ref, r_ref, lab_ref, mask_ref, eps2_ref,
                    out_lab_ref, out_cnt_ref):
    """Fused union-find hook tile: min core-neighbor label + matched count."""
    ok = _tile_hits(qt_ref, r_ref, eps2_ref) & (mask_ref[...] != 0)
    labs = jnp.where(ok, lab_ref[...], INT_MAX)          # (TR, TQ)
    tile_min = jnp.min(labs, axis=0, keepdims=True)      # (1, TQ)
    tile_cnt = jnp.sum(ok.astype(jnp.int32), axis=0, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_lab_ref[...] = jnp.full_like(out_lab_ref, INT_MAX)
        out_cnt_ref[...] = jnp.zeros_like(out_cnt_ref)

    out_lab_ref[...] = jnp.minimum(out_lab_ref[...], tile_min)
    out_cnt_ref[...] = out_cnt_ref[...] + tile_cnt


def _pad_to(x, mult, value):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=value)


def _operands(points_q, points_r, eps, tile_q, tile_r):
    """Padded, laid-out operands and specs shared by both kernels."""
    qt = _pad_to(points_q.astype(jnp.float32), tile_q, 1e30).T  # (d, nq)
    r = _pad_to(points_r.astype(jnp.float32), tile_r, -1e30)    # (nr, d)
    d = qt.shape[0]
    eps2 = jnp.full((1, 1), eps * eps, jnp.float32)
    grid = (qt.shape[1] // tile_q, r.shape[0] // tile_r)
    specs = (pl.BlockSpec((d, tile_q), lambda i, j: (0, i)),
             pl.BlockSpec((tile_r, d), lambda i, j: (j, 0)),
             pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                          memory_space=pltpu.MemorySpace.SMEM))
    return qt, r, eps2, grid, specs


def _row(n_pad, tile_q):
    return (pl.BlockSpec((1, tile_q), lambda i, j: (0, i)),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("cap", "tile_q", "tile_r",
                                             "interpret"))
def pairwise_count(points_q, points_r, eps, cap: int = INT_MAX,
                   tile_q: int = TILE_Q, tile_r: int = TILE_R,
                   interpret: bool | None = None):
    """Counts of reference points within eps per query (saturating at cap)."""
    nq = points_q.shape[0]
    qt, r, eps2, grid, (q_spec, r_spec, s_spec) = _operands(
        points_q, points_r, eps, tile_q, tile_r)
    out_spec, out_shape = _row(qt.shape[1], tile_q)
    out = pl.pallas_call(
        functools.partial(count_kernel, cap=cap),
        grid=grid,
        in_specs=[q_spec, r_spec, s_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret),
    )(qt, r, eps2)
    return out[0, :nq]


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_r", "interpret"))
def pairwise_minlabel(points_q, points_r, labels_r, mask_r, eps,
                      tile_q: int = TILE_Q, tile_r: int = TILE_R,
                      interpret: bool | None = None):
    """(min masked label within eps, matched count) per query point."""
    nq = points_q.shape[0]
    qt, r, eps2, grid, (q_spec, r_spec, s_spec) = _operands(
        points_q, points_r, eps, tile_q, tile_r)
    lab = _pad_to(labels_r.astype(jnp.int32), tile_r, INT_MAX)[:, None]
    mask = _pad_to(mask_r.astype(jnp.int32), tile_r, 0)[:, None]
    col_spec = pl.BlockSpec((tile_r, 1), lambda i, j: (j, 0))
    out_spec, out_shape = _row(qt.shape[1], tile_q)
    out_lab, out_cnt = pl.pallas_call(
        minlabel_kernel,
        grid=grid,
        in_specs=[q_spec, r_spec, col_spec, col_spec, s_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret),
    )(qt, r, lab, mask, eps2)
    return out_lab[0, :nq], out_cnt[0, :nq]
