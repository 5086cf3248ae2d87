"""Slab geometry shared by the distributed DBSCAN paths (DESIGN.md §6).

:func:`shard_bounds` fits a shard's resident AABB and :func:`halo_mask`
is the eps-dilated membership test that decides which traveling queries
must traverse a remote shard's tree at all.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def shard_bounds(pts: jax.Array, valid: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """AABB (lo, hi) of the *valid* resident points of a shard's slab.

    Padding sentinels (coordinates ~1e30 on the trailing shard) are masked
    out so they cannot stretch the box; a shard with no valid point (tiny
    ``n`` on a wide mesh) reports the unit box — visiting queries may pass
    that halo test, but its tree holds only sentinel primitives, so they
    die at the root box test having evaluated nothing.
    """
    big = jnp.asarray(jnp.inf, pts.dtype)
    lo = jnp.min(jnp.where(valid[:, None], pts, big), axis=0)
    hi = jnp.max(jnp.where(valid[:, None], pts, -big), axis=0)
    any_valid = jnp.any(valid)
    lo = jnp.where(any_valid, lo, jnp.zeros_like(lo))
    hi = jnp.where(any_valid, hi, jnp.ones_like(hi))
    return lo, hi


def halo_mask(q_pts: jax.Array, lo: jax.Array, hi: jax.Array,
              eps) -> jax.Array:
    """Which of ``q_pts`` lie in the eps-dilated slab of the AABB [lo, hi].

    This is the halo-exchange membership test (DESIGN.md §6): a traveling
    query farther than ``eps`` from a shard's resident bounding box cannot
    be within ``eps`` of any resident point, so its lane is marked inert
    before the local tree traversal — it is *not* part of that shard's halo.
    """
    from repro.core.lbvh import box_dist2
    d2 = box_dist2(q_pts, lo[None, :], hi[None, :])
    return d2 <= jnp.asarray(eps, q_pts.dtype) ** 2


def _axis_size(mesh: Mesh, axis: Optional[str]) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
