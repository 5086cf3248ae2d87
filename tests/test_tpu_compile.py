"""Compile the chip's kernels and programs for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
lower and compile at real sizes against a ``v5e:2x2`` topology
description, so a kernel Mosaic refuses, or a sharded program that loses
its collectives, fails here instead of on the chip. Nothing runs, so they
say nothing about results or times.

The topology is described only inside the module-scoped fixture: only one
process may load the TPU library, so describing it at import would break
every other test worker.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import dispatch, grid, lbvh, traversal
from repro.data import pointclouds
from repro.kernels import ops, traverse as kt
from repro.kernels.pairwise import pairwise_count, pairwise_minlabel

N = 65536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache while these tests run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _tile_programs(n, d, on):
    pts = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=on)
    eps = jax.ShapeDtypeStruct((), jnp.float32, sharding=on)
    lab = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=on)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=on)
    return {
        "count": (lambda p, e: pairwise_count(p, p, e, cap=5,
                                              interpret=False), (pts, eps)),
        "minlabel": (lambda p, l, m, e: pairwise_minlabel(
            p, p, l, m, e, interpret=False), (pts, lab, mask, eps)),
        # the whole tiled backend: auto dispatch's n <= 1024 branch
        "tiled": (lambda p, e: ops._tiled_phases(p, e, 5, False, 128),
                  (pts, eps)),
    }


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("kernel,n", [("count", N), ("minlabel", N),
                                      ("tiled", 1024)])
def test_tile_kernels_compile(one_chip, kernel, n, d):
    fn, args = _tile_programs(n, d, one_chip)[kernel]
    assert "tpu_custom_call" in _compile(fn, *args)


def _walk_operands(kind, n=4096):
    """The traversal kernel's launch operands over a real index (captured
    from the CPU launch path, which runs the kernel interpreted)."""
    pts = jnp.asarray(pointclouds.load("portotaxi_like", n))
    segs = grid.build_segments_fdbscan(pts)
    tree = lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)
    vals, mask = jnp.arange(n, dtype=jnp.int32), jnp.ones(n, bool)
    cb = (traversal.CountVisitor(cap=5) if kind == "count"
          else traversal.MinLabelVisitor(vals, mask))
    got = {}

    class Captured(Exception):
        pass

    def capture(cfg, lane_tile, interpret, *arrays):
        got["launch"] = (cfg, lane_tile, arrays)
        raise Captured

    real, kt._run = kt._run, capture
    try:
        with pytest.raises(Captured):
            kt.traverse(tree, segs,
                        traversal.intersects(traversal.sphere(0.01)), cb)
    finally:
        kt._run = real
    return got["launch"]


@pytest.mark.parametrize("kind", ["count", "minlabel"])
def test_traversal_kernel_refused_by_mosaic(one_chip, kind):
    # the recorded reason pallas-tree is off on TPU (kt.TPU_UNSUPPORTED);
    # when this compile starts to pass, the kernel can come back
    cfg, lane_tile, arrays = _walk_operands(kind)
    shapes = [None if a is None else
              jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype,
                                   sharding=one_chip) for a in arrays]
    with pytest.raises(Exception, match="Shape mismatch in input, indices"):
        kt._run.lower(cfg, lane_tile, False, *shapes).compile()


def test_explicit_pallas_tree_raises_on_tpu_plan(monkeypatch):
    pts = pointclouds.load("blobs", 2000)
    monkeypatch.setattr(dispatch, "_accel", lambda: True)
    dispatch.clear_cache()
    with pytest.raises(NotImplementedError, match="Mosaic"):
        dispatch.plan(pts, 0.05, 8, algorithm="pallas-tree")
    assert dispatch.plan(pts, 0.05, 8).backend != "pallas-tree"
    dispatch.clear_cache()
    # a compiled launch of the kernel itself refuses too, never emulates
    segs = grid.build_segments_fdbscan(jnp.asarray(pts))
    tree = lbvh.build_tree(segs.codes, segs.prim_lo, segs.prim_hi)
    with pytest.raises(NotImplementedError, match="Mosaic"):
        kt.traverse(tree, segs,
                    traversal.intersects(traversal.sphere(0.05)),
                    traversal.CountVisitor(cap=8), interpret=False)


def test_sharded_sweep_program_has_collectives(topo):
    from repro.distributed.ring_dbscan import _sharded_programs
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    n = N
    build_fn, sweep_fn, _ = _sharded_programs(mesh, "data", n, n, 0.01, 5)
    rows = NamedSharding(mesh, P("data"))
    pts = jax.ShapeDtypeStruct((n, 3), jnp.float32, sharding=rows)
    idx, core, labels, _ = build_fn.lower(pts).out_info
    idx, core, labels = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rows),
        (idx, core, labels))
    compiled = sweep_fn.lower(pts, idx, core, labels).compile()
    hlo = compiled.as_text()
    for op in ("collective-permute", "all-gather", "all-reduce"):
        assert op in hlo, op
    # each device holds a quarter of the per-point state, not a replica
    args = jax.tree.leaves((pts, idx, core, labels))
    total = sum(a.size * a.dtype.itemsize for a in args)
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    assert per_dev * 4 <= total * 1.05, (per_dev, total)
