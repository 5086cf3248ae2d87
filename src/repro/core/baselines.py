"""Baselines the paper compares against, plus the ground-truth oracle.

* ``dbscan_bruteforce_np`` — textbook Ester et al. BFS DBSCAN in NumPy.
  Slow and obviously correct: the oracle for every property test.
* ``gdbscan`` — G-DBSCAN [Andrade et al. 2013] re-expressed in JAX: it
  *materializes the full adjacency* (the O(E) memory behaviour the paper
  criticizes — [32] measured 166x CUDA-DClust's footprint) and then runs a
  level-synchronous BFS. We reproduce it with a dense adjacency matrix, so
  its memory is Theta(n^2) bits regardless of eps — the memory benchmark
  (benchmarks/bench_memory.py) contrasts this against FDBSCAN's O(n).
* ``dbscan_tiled`` lives in repro.kernels.ops — the Pallas tile backend.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .fdbscan import DBSCANResult
from .validate import neighbor_counts


def dbscan_bruteforce_np(points, eps: float, min_pts: int):
    """Oracle DBSCAN (labels, core_mask); labels compacted, noise = -1.

    Core determination shares the blocked tiles of ``validate`` (O(n*block)
    memory, float64-exact); the BFS recomputes one adjacency row per pop —
    the oracle stays obviously correct yet never holds the n x n matrix.
    """
    pts = np.asarray(points, dtype=np.float64)
    n = pts.shape[0]
    e2 = eps * eps
    core = neighbor_counts(pts, eps) >= min_pts
    sq = (pts * pts).sum(-1)

    def row_adj(x):
        # same Gram form as validate.adjacency_blocks: one oracle, one
        # notion of adjacency
        return sq + sq[x] - 2.0 * (pts @ pts[x]) <= e2

    labels = np.full(n, -1, np.int64)
    cid = 0
    for s in range(n):
        if not core[s] or labels[s] != -1:
            continue
        stack = [s]
        labels[s] = cid
        while stack:
            x = stack.pop()
            if not core[x]:
                continue  # border: absorbed but does not expand
            for y in np.nonzero(row_adj(x))[0]:
                if labels[y] == -1:
                    labels[y] = cid
                    if core[y]:
                        stack.append(y)
        cid += 1
    return labels, core


@jax.jit
def _gdbscan_jit(pts, eps, min_pts):
    n = pts.shape[0]
    d2 = jnp.sum((pts[:, None, :] - pts[None, :, :]) ** 2, -1)
    adj = d2 <= eps * eps                       # the materialized graph
    core = jnp.sum(adj, 1) >= min_pts
    cc_adj = adj & core[:, None] & core[None, :]

    # Level-synchronous BFS from all sources at once == iterative min-label
    # frontier expansion over the core-core graph.
    labels = jnp.where(core, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))

    def cond(state):
        labels, changed = state
        return changed

    def body(state):
        labels, _ = state
        relaxed = jnp.min(jnp.where(cc_adj, labels[None, :], n), axis=1)
        new = jnp.where(core, jnp.minimum(labels, relaxed), labels)
        return new, jnp.any(new != labels)

    labels, _ = jax.lax.while_loop(cond, body, (labels, jnp.bool_(True)))

    # borders: min core-neighbor label
    bl = jnp.min(jnp.where(adj & core[None, :], labels[None, :], n), axis=1)
    labels = jnp.where(core, labels, jnp.where(bl < n, bl, -1))
    return labels, core


def gdbscan(points, eps: float, min_pts: int) -> DBSCANResult:
    pts = jnp.asarray(points)
    labels, core = _gdbscan_jit(pts, eps, min_pts)
    labels = np.asarray(labels)
    uniq = {}
    out = np.full(labels.shape, -1, np.int32)
    for i, l in enumerate(labels):
        if l >= 0:
            out[i] = uniq.setdefault(int(l), len(uniq))
    return DBSCANResult(labels=jnp.asarray(out), core_mask=core,
                        n_clusters=len(uniq), n_sweeps=0)
