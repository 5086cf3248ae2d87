"""The benchmark's own point generators.

Copies of the surrogate regimes in ``src/repro/data/pointclouds.py``
(PortoTaxi-like urban mixture, HACC-like halos), kept here so that a
change to the program cannot move the yardstick. Each generator takes
two seeds:

* ``structure_seed`` fixes the dataset's structure: cluster centres,
  weights and scales, or halo centres and masses;
* ``sample_seed`` draws the points from that structure.

Both come from the configuration's file. The run's ``--seed`` only
permutes the points (:func:`make_points`): every seed clusters the same
point set in another input order, so every seed compiles the same
shapes and gives the same clusters. The work is not quite the same:
labels propagate in input order, so the walks of the sweeps, and with
them a call's time, differ by a few percent from seed to seed.
"""
from __future__ import annotations

import numpy as np


def taxi_2d(n: int, structure_seed: int, sample_seed: int, *, k: int,
            weight_pareto: float, scale_lo: float, scale_hi: float
            ) -> np.ndarray:
    """PortoTaxi-like: a heavy-tailed mixture of ``k`` urban hot spots."""
    rs = np.random.default_rng(structure_seed)
    centers = rs.uniform(0, 1, size=(k, 2))
    weights = rs.pareto(weight_pareto, size=k) + 0.1
    weights /= weights.sum()
    scales = rs.uniform(scale_lo, scale_hi, size=k)
    rng = np.random.default_rng(sample_seed)
    which = rng.choice(k, size=n, p=weights)
    pts = centers[which] + rng.normal(size=(n, 2)) * scales[which, None]
    return pts.astype(np.float32)


def halos_3d(n: int, structure_seed: int, sample_seed: int, *,
             n_halos: int, mass_pareto: float, radius: float,
             background_frac: float) -> np.ndarray:
    """HACC-like: NFW-ish halos over a uniform background in the unit
    box."""
    rs = np.random.default_rng(structure_seed)
    centers = rs.uniform(0, 1, size=(n_halos, 3))
    mass = rs.pareto(mass_pareto, size=n_halos) + 0.05
    mass /= mass.sum()
    rng = np.random.default_rng(sample_seed)
    n_bg = int(n * background_frac)
    n_h = n - n_bg
    which = rng.choice(n_halos, size=n_h, p=mass)
    # radius ~ r^-1 density falloff by inverse-CDF sampling
    r = radius * np.sqrt(rng.uniform(1e-4, 1, size=n_h))
    direction = rng.normal(size=(n_h, 3))
    direction /= np.linalg.norm(direction, axis=1, keepdims=True)
    pts = centers[which] + direction * r[:, None]
    bg = rng.uniform(0, 1, size=(n_bg, 3))
    return np.concatenate([pts, bg]).astype(np.float32)


GENERATORS = {"taxi_2d": taxi_2d, "halos_3d": halos_3d}


def base_points(config: dict) -> np.ndarray:
    """The configuration's point set, in generation order."""
    data = config["data"]
    return GENERATORS[data["generator"]](
        config["n"], data["structure_seed"], data["sample_seed"],
        **data["params"])


def make_points(config: dict, seed: int) -> np.ndarray:
    """The run's input: the configuration's point set in an order drawn
    from ``seed`` (any integer)."""
    pts = base_points(config)
    perm = np.random.default_rng(seed % 2**64).permutation(len(pts))
    return np.ascontiguousarray(pts[perm])
