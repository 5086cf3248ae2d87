"""DBSCAN-axiom checker: validates a labeling against first principles.

Border points may legitimately belong to any adjacent cluster (the paper
assigns "first encountered", we assign min-representative), so label arrays
cannot be compared naively. This checker accepts exactly the set of valid
DBSCAN labelings:

  A1  core_mask is correct: |N_eps(x)| >= minpts  <=>  core.
  A2  density-connected core points share a label (same component of the
      core-core eps-graph).
  A3  core points in different components have different labels.
  A4  a border point (non-core with >= 1 core neighbor) carries the label of
      at least one core neighbor.
  A5  noise (non-core, no core neighbor) is labeled -1; nothing else is.

All adjacency questions are answered from *blocked* row tiles (~2k rows at
a time) so the checker never materializes the n x n float64 distance
matrix — conformance runs at n >= 50k stay within O(n * block) memory.
Points are sorted by their first coordinate first, so each tile spans only
the columns within eps of its rows along that axis: exact, and at the
densities users run (n = 2**16) it cuts the work from n^2 to a few percent
of it. Component structure is recovered from the core-core edges of the
same tiles by SciPy's connected components.
"""
from __future__ import annotations

import numpy as np


def check_points(points, *, name: str = "points", allow_empty: bool = False,
                 dims: tuple = None, d: int = None) -> np.ndarray:
    """Validate a user-supplied point batch at the public surface.

    One shared gate for every entry point (``dispatch.plan``/``dbscan``,
    the streaming handle's ``insert``/``query``, ``neighbors.*``): a
    malformed batch must raise a clear ``ValueError`` *here*, not produce
    garbage Morton codes and silently wrong labels three layers down.

    Rejects: non-numeric / bool / complex dtypes, non-2-d shapes, empty
    point sets (unless ``allow_empty``), NaN/Inf coordinates, and a
    dimensionality outside ``dims`` (or different from ``d``).

    Args:
        points: any array-like the caller intends as an (n, d) batch.
        name: how to call the argument in error messages.
        allow_empty: permit n == 0 (e.g. an optional initial set).
        dims: allowed dimensionalities, e.g. ``(2, 3)``; None = any.
        d: exact required dimensionality (e.g. an index's own d).

    Returns:
        The batch as a host ``np.ndarray`` (no copy when the input
        already is one); callers do their own dtype conversion.

    Raises:
        ValueError: any of the rejections above, with the offending
            rows named for the NaN/Inf case.
    """
    try:
        arr = np.asarray(points)
    except (ValueError, TypeError) as e:
        raise ValueError(f"{name} is not a numeric array: {e}")
    if (arr.dtype == object or arr.dtype.kind not in "iuf"):
        raise ValueError(
            f"{name} must be a real-valued numeric array; got dtype "
            f"{arr.dtype} (bool/complex/object inputs would be cast to "
            "garbage coordinates silently)")
    if arr.ndim != 2:
        raise ValueError(f"{name} must have shape (n, d); got {arr.shape}")
    if arr.shape[0] == 0 and not allow_empty:
        raise ValueError(f"{name} is empty: got shape {arr.shape} "
                         "(an empty point set has no clustering)")
    if d is not None and arr.shape[1] != d:
        raise ValueError(f"{name} must be {d}-dimensional to match the "
                         f"index; got {arr.shape[1]}-d")
    if dims is not None and arr.shape[1] not in dims:
        raise ValueError(f"{name} must have d in {dims}; got shape "
                         f"{arr.shape}")
    if arr.dtype.kind == "f" and arr.size and not np.isfinite(arr).all():
        bad = np.flatnonzero(~np.isfinite(arr).all(axis=1))
        raise ValueError(
            f"{name} contains {len(bad)} row(s) with non-finite (NaN/Inf) "
            f"coordinates, first at rows {bad[:5].tolist()} — these would "
            "corrupt the Morton codes, not cluster as outliers")
    return arr

# Row-tile height for all blocked adjacency passes: block * window boolean
# cells live at once (at most ~2k * n bits), never the n^2 matrix.
ORACLE_BLOCK = 2048


def x_sorted(points):
    """``(pts, order)``: float64 points sorted by their first coordinate
    (stable), and the permutation that sorted them (``pts ==
    points[order]``) — the layout :func:`adjacency_blocks` windows over."""
    pts = np.asarray(points, np.float64)
    order = np.argsort(pts[:, 0], kind="stable") if len(pts) else \
        np.zeros(0, np.int64)
    return pts[order], order


def adjacency_blocks(pts, eps: float, block: int = ORACLE_BLOCK):
    """Yield ``(lo, hi, c0, c1, adj)`` tiles of the eps-adjacency matrix.

    ``pts`` must be sorted by its first coordinate (:func:`x_sorted`).
    ``adj`` is the boolean slice ``[lo:hi, c0:c1]``; every pair of a row
    in ``lo:hi`` with a column outside ``c0:c1`` is non-adjacent, because
    the window holds every point whose first coordinate lies within
    ``eps + pad`` of the rows'. Distances are float64 via the BLAS Gram
    form ``|a|^2 + |b|^2 - 2ab`` (a dgemm per tile). On the integer-grid
    property data every term is an exact float64 integer, so boundary
    decisions are exact; float data in the test-suite keeps a separation
    band around eps many orders above the ~1e-16 relative rounding of this
    form. ``pad`` exceeds that rounding, so the window drops only pairs
    the Gram form could never call adjacent: the tiles decide exactly what
    the full ``n x n`` matrix would. Shared by :func:`check_dbscan` and
    ``baselines.dbscan_bruteforce_np`` (through :func:`neighbor_counts`).
    """
    n = pts.shape[0]
    if n == 0:
        return
    e2 = eps * eps
    sq = (pts * pts).sum(-1)
    x = pts[:, 0]
    # |dx| > eps + pad  =>  d2 >= eps^2 + pad^2, and pad^2 = 1e-12 * scale^2
    # dominates the Gram form's rounding (~1e-15 * scale^2)
    pad = 1e-6 * (float(np.abs(pts).max()) + eps)
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        c0 = int(np.searchsorted(x, x[lo] - eps - pad, "left"))
        c1 = int(np.searchsorted(x, x[hi - 1] + eps + pad, "right"))
        d2 = (sq[lo:hi, None] + sq[None, c0:c1]
              - 2.0 * (pts[lo:hi] @ pts[c0:c1].T))
        yield lo, hi, c0, c1, d2 <= e2


def neighbor_counts(points, eps: float, block: int = ORACLE_BLOCK
                    ) -> np.ndarray:
    """|N_eps(x)| per point (self included), blocked, in input order."""
    pts, order = x_sorted(points)
    counts = np.zeros(pts.shape[0], np.int64)
    counts[order] = _sorted_counts(pts, eps, block)
    return counts


def _sorted_counts(pts, eps, block) -> np.ndarray:
    counts = np.zeros(pts.shape[0], np.int64)
    for lo, hi, _, _, adj in adjacency_blocks(pts, eps, block):
        counts[lo:hi] = adj.sum(1)
    return counts


def _core_components(pts, eps, core, block) -> np.ndarray:
    """Component id of each point in the core-core eps-graph (``pts``
    x-sorted, ids in that order; a non-core point is its own component).

    One tile pass extracts each core-core edge once (``i < j``); SciPy's
    connected components then labels the graph — a textbook algorithm,
    independent of the library's hook + jump loop it checks.
    """
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components
    n = pts.shape[0]
    srcs, dsts = [], []
    for lo, hi, c0, c1, adj in adjacency_blocks(pts, eps, block):
        rows = np.arange(lo, hi)[:, None]
        cols = np.arange(c0, c1)[None, :]
        r, c = np.nonzero(adj & core[None, c0:c1] & core[lo:hi, None]
                          & (rows < cols))
        srcs.append((r + lo).astype(np.int32))
        dsts.append((c + c0).astype(np.int32))
    src = np.concatenate(srcs) if srcs else np.zeros(0, np.int32)
    dst = np.concatenate(dsts) if dsts else np.zeros(0, np.int32)
    graph = coo_matrix((np.ones(len(src), np.int8), (src, dst)),
                       shape=(n, n)).tocsr()
    return connected_components(graph, directed=False)[1]


def check_dbscan(points, eps: float, min_pts: int, labels, core_mask,
                 block: int = ORACLE_BLOCK) -> None:
    pts, order = x_sorted(points)
    labels = np.asarray(labels)[order]
    core = np.asarray(core_mask)[order]

    ref_core = _sorted_counts(pts, eps, block) >= min_pts
    assert (core == ref_core).all(), (
        "A1 core mask mismatch at "
        f"{np.sort(order[np.nonzero(core != ref_core)[0]])[:10]}")

    comp = _core_components(pts, eps, ref_core, block)

    ci = np.nonzero(ref_core)[0]
    noise = ref_core & (labels < 0)
    assert not noise.any(), \
        f"A2 core point {order[_first(noise, order)]} labeled noise"
    # A2/A3: label partition == component partition on core points
    pairs = np.unique(np.stack([comp[ci], labels[ci]], 1), axis=0)
    reps, k = np.unique(pairs[:, 0], return_counts=True)
    split = reps[k > 1]
    assert not len(split), (f"A2 component {split[0]} split into labels "
                            f"{pairs[pairs[:, 0] == split[0], 1]}")
    labs, k = np.unique(pairs[:, 1], return_counts=True)
    merged = labs[k > 1]
    assert not len(merged), (
        f"A3 label {merged[0]} merges components "
        f"{pairs[pairs[:, 1] == merged[0], 0]}")

    # A4/A5 witnesses per non-core point, gathered from the same tiles
    has_core_nbr = np.zeros(len(pts), bool)
    label_ok = np.zeros(len(pts), bool)   # some core neighbor carries it
    for lo, hi, c0, c1, adj in adjacency_blocks(pts, eps, block):
        sub = adj & ref_core[None, c0:c1]
        has_core_nbr[lo:hi] = sub.any(1)
        label_ok[lo:hi] = (sub & (labels[None, c0:c1]
                                  == labels[lo:hi, None])).any(1)
    loose = ~ref_core & ~has_core_nbr & (labels != -1)
    assert not loose.any(), \
        f"A5 isolated point {order[_first(loose, order)]} not noise"
    bad = ~ref_core & has_core_nbr & ~label_ok
    assert not bad.any(), (
        f"A4 border {order[_first(bad, order)]} labeled "
        f"{labels[_first(bad, order)]} but no core neighbor carries that "
        "label")


def _first(mask, order) -> int:
    """Sorted-space index of the masked point with the smallest input
    index (error messages name the first offender in input order)."""
    idx = np.nonzero(mask)[0]
    return int(idx[np.argmin(order[idx])])


def check_component_identical(labels_a, core_a, labels_b, core_b) -> None:
    """Assert two DBSCAN results are *component-identical*: exact core
    mask, exact noise set, identical partition of the core points.

    This is the strongest comparison that is well-defined across backends
    — border points may legitimately attach to any adjacent cluster (see
    the module docstring), so full label arrays are never compared
    elementwise. The streaming subsystem's snapshot()-vs-batch contract
    (DESIGN.md §7) is stated in exactly these terms; the benchmark, the
    serving loop's ``--validate``, and the test suite all share this one
    definition.
    """
    ca, cb = np.asarray(core_a), np.asarray(core_b)
    assert (ca == cb).all(), "core mask differs"
    la, lb = np.asarray(labels_a), np.asarray(labels_b)
    assert ((la == -1) == (lb == -1)).all(), "noise set differs"
    assert same_partition(la[ca], lb[ca]), "core partition differs"


def same_partition(labels_a, labels_b) -> bool:
    """True iff two labelings induce the same partition (noise == noise)."""
    a = np.asarray(labels_a)
    b = np.asarray(labels_b)
    if ((a == -1) != (b == -1)).any():
        return False
    keep = a != -1
    pairs = np.unique(np.stack([a[keep], b[keep]], 1), axis=0)
    return (len(np.unique(pairs[:, 0])) == len(pairs)
            == len(np.unique(pairs[:, 1])))
