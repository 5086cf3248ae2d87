"""The comparison that decides ``correct``: one call's labels and core
mask against the reference (:mod:`benchlib.reference`).

Border points may join any adjacent cluster, and cluster ids are
arbitrary, so labels are not compared one by one. Three counts are:

* ``core_mismatch``: points whose core flag differs from the reference;
* ``cluster_mismatch``: points core on both sides that lie outside the
  one-to-one matching of the program's labels to the reference's
  components (matched greedily by overlap): 0 exactly when the two
  partitions of the core points agree;
* ``border_mismatch``: points non-core on both sides whose label is
  wrong: a label on a point with no core neighbour, noise on a point
  with one, or a label whose matched component is not adjacent to it.
"""
from __future__ import annotations

import numpy as np

from .reference import Reference

NUMBERS = ("core_mismatch", "cluster_mismatch", "border_mismatch")


def _match(lab: np.ndarray, comp: np.ndarray) -> dict:
    """Greedy one-to-one matching of labels to components by overlap;
    returns {label: component}."""
    keep = lab >= 0
    if not keep.any():
        return {}
    pairs, cnt = np.unique(np.stack([lab[keep], comp[keep]], 1), axis=0,
                           return_counts=True)
    used_l, used_c, out = set(), set(), {}
    for k in np.argsort(-cnt, kind="stable"):
        l, c = int(pairs[k, 0]), int(pairs[k, 1])
        if l not in used_l and c not in used_c:
            used_l.add(l)
            used_c.add(c)
            out[l] = c
    return out


def compare(ref: Reference, labels, core_mask) -> dict:
    """The three mismatch counts of one result against ``ref``; every
    point counts as a mismatch where the result is not one label and one
    core flag per point."""
    labels = np.asarray(labels).astype(np.int64)
    core = np.asarray(core_mask).astype(bool)
    n = len(ref.core)
    if labels.shape != (n,) or core.shape != (n,):
        return {k: n for k in NUMBERS}
    both = core & ref.core
    match = _match(labels[both], ref.comp[both])
    mapped = np.array([match.get(int(l), -2) for l in labels[both]],
                      np.int64) if both.any() else np.zeros(0, np.int64)
    cluster_bad = int((mapped != ref.comp[both]).sum())
    neither = ~core & ~ref.core
    has_border = np.zeros(n, bool)
    has_border[ref.border_pt] = True
    idx = np.flatnonzero(neither)
    lab = labels[idx]
    noise_ok = ~has_border[idx] & (lab == -1)
    comp_of = np.array([match.get(int(l), -2) for l in lab], np.int64)
    border_ok = has_border[idx] & (lab >= 0) & np.isin(
        idx * (n + 1) + comp_of, ref.border_pt * (n + 1) + ref.border_comp)
    return {"core_mismatch": int((core != ref.core).sum()),
            "cluster_mismatch": cluster_bad,
            "border_mismatch": int((~(noise_ok | border_ok)).sum())}
