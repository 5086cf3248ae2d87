"""jit'd wrappers over the Pallas tile kernels + the tiled DBSCAN backend.

``dbscan_tiled`` is the TPU-native dense backend (DESIGN.md §3): the whole
two-phase PDSDBSCAN framework of the paper, but with neighbor determination
done by streaming distance tiles instead of a tree walk. It is the
backend of choice when points/chip is small enough that n^2/chips tiles are
cheaper than divergent traversal (and it is what the distributed ring
version in repro.distributed.ring_dbscan runs per step). Memory stays O(n):
tiles live in VMEM only.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .pairwise import INT_MAX, pairwise_count, pairwise_minlabel
from . import ref as kernel_ref  # noqa: F401  (re-exported for benchmarks)


@partial(jax.jit, static_argnames=("min_pts", "interpret", "tile"))
def _tiled_phases(pts, eps, min_pts: int, interpret: bool | None,
                  tile: int):
    n = pts.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    # -- preprocessing: early-exit (saturating) neighbor count ------------
    counts = pairwise_count(pts, pts, eps, cap=min_pts,
                            tile_q=tile, tile_r=tile, interpret=interpret)
    core = counts >= min_pts

    # -- main phase: fused hook tiles + pointer jumping to fixpoint -------
    labels0 = jnp.where(core, idx, INT_MAX)

    def cond(state):
        return state[1]

    def body(state):
        labels, _ = state
        gathered, _ = pairwise_minlabel(pts, pts, jnp.where(core, labels, INT_MAX),
                                        core, eps, tile_q=tile, tile_r=tile,
                                        interpret=interpret)
        new = jnp.where(core, jnp.minimum(labels, gathered), labels)
        safe = jnp.where(core, new, idx)
        compressed = lax.while_loop(lambda l: jnp.any(l != l[l]),
                                    lambda l: l[l], safe)
        new = jnp.where(core, compressed, labels)
        return (new, jnp.any(new != labels))

    labels, _ = lax.while_loop(cond, body, (labels0, jnp.bool_(True)))

    # -- borders ----------------------------------------------------------
    blab, bcnt = pairwise_minlabel(pts, pts, jnp.where(core, labels, INT_MAX),
                                   core, eps, tile_q=tile, tile_r=tile,
                                   interpret=interpret)
    labels = jnp.where(core, labels, blab)
    return jnp.where(labels == INT_MAX, jnp.int32(-1), labels), core


def dbscan_tiled(points, eps: float, min_pts: int, *, star: bool = False,
                 interpret: bool | None = None, tile: int = 128):
    """Full DBSCAN on Pallas distance tiles (labels compacted, noise = -1).

    Unlike the paper's GPU preprocessing skip for minpts == 2, the tiled
    backend keeps the uniform count pass: a saturating count over dense
    tiles costs the same as the main sweep and keeps all lanes uniform.
    star=True implements DBSCAN* (non-core points become noise).
    """
    from repro.core.fdbscan import DBSCANResult, _finalize
    pts = jnp.asarray(points, jnp.float32)
    n = pts.shape[0]
    labels_rep, core = _tiled_phases(pts, eps, min_pts, interpret, tile)
    if star:
        labels_rep = jnp.where(core, labels_rep, jnp.int32(-1))
    labels, n_clusters = _finalize(labels_rep, jnp.arange(n, dtype=jnp.int32), n)
    return DBSCANResult(labels=labels, core_mask=core,
                        n_clusters=n_clusters, n_sweeps=-1,
                        n_traversals=0, backend="tiled")
