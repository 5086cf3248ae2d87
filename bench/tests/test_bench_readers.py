"""The span readers of the per-layer metrics on hand-made runs, and the
names of the walk's XLA programs that ``walk_device_s`` matches."""
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import devtrace, spec  # noqa: E402
from benchlib.runner import Call, Run  # noqa: E402

DEVICE = devtrace.Reduced(window_s=10.0, busy_s=9.5, module_s={}, gap_s={})


def _span(name, dur_s, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_s * 1e6,
            "args": dict(args, sync="blocked")}


def _calls(k):
    return [Call(start=float(i), end=float(i) + 1.0, n_sweeps=3,
                 backend="fdbscan-densebox") for i in range(k)]


def _run(spans, k=2, device=DEVICE):
    return Run(calls=_calls(k), spans=spans, device=device)


SPANS = [
    _span("plan", 0.5), _span("lbvh", 0.3, n_segments=100),
    _span("traverse", 4.0, phase="first_pass", engine="reference"),
    _span("traverse", 1.0, phase="other"),
    _span("frontier", 0.2, stage="setup", lanes=256),
    _span("sweep", 2.0, i=1, lanes=256), _span("sweep", 1.0, i=2, lanes=64),
    _span("frontier", 0.1, stage="next", n_changed=5, lanes=64),
    _span("frontier", 0.1, stage="next", n_changed=0, lanes=0),
    _span("border", 0.6), _span("finalize", 0.05),
    _span("jax.compile", 0.25, fun="jit(scan)", stage="compile",
          span="lbvh"),
    _span("jax.compile", 0.15, fun="jit(scan)", stage="lower",
          span="lbvh"),
]

# metric -> its reading of SPANS over two calls
EXPECTED = {
    "first_pass_s": 2.0,
    "border_s": 0.3,
    "frontier_s": 0.2,
    "lbvh_s": 0.15,
    "compile_s_per_call.batch": 0.2,
    "sweep_lanes_per_call": 160.0,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_sums_its_spans_per_call(metric):
    read = spec.reader(metric, ROOT)
    assert read(_run(SPANS)) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_is_silent_without_calls_or_device(metric):
    read = spec.reader(metric, ROOT)
    assert read(_run(SPANS, k=0)) is None
    assert read(_run(SPANS, device=None)) is None


# metric -> the spans it reads (compile spans: see the test after)
READS = {
    "first_pass_s": lambda e: (e["name"] == "traverse"
                               and e["args"].get("phase") == "first_pass"),
    "border_s": lambda e: e["name"] == "border",
    "frontier_s": lambda e: e["name"] == "frontier",
    "lbvh_s": lambda e: e["name"] == "lbvh",
    "sweep_lanes_per_call": lambda e: e["name"] == "sweep",
}


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_is_silent_without_its_spans(metric):
    read = spec.reader(metric, ROOT)
    assert read(_run([e for e in SPANS if not READS[metric](e)])) is None
    # a program whose sweep spans carry no lane count (as before they did)
    no_lanes = [dict(e, args={k: v for k, v in e["args"].items()
                              if k != "lanes"}) for e in SPANS]
    if metric == "sweep_lanes_per_call":
        assert read(_run(no_lanes)) is None


def test_compile_reader_tells_no_compiles_from_no_compile_spans(
        monkeypatch):
    read = spec.reader("compile_s_per_call.batch", ROOT)
    no_compiles = [s for s in SPANS if s["name"] != "jax.compile"]
    # a program that records compile spans, in a window that compiled
    # nothing, reads 0 ...
    assert read(_run(no_compiles)) == 0.0
    # ... and a program without compile spans reads nothing
    from repro.obs import trace
    monkeypatch.delattr(trace, "COMPILE_SPAN")
    assert read(_run(SPANS)) is None


def test_new_metrics_are_declared_for_both_cells():
    bench = spec.load(ROOT)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = by_name[name]
        assert m["workloads"] == ["porto2d.batch", "hacc3d-fof.batch"]
        assert m["moves"] == "cluster_points_per_s"
        assert m["source"] == "program_span"


def _module_name(lowered) -> str:
    """The XLA module's name, as the profiler's "XLA Modules" line
    carries it (before the runtime appends its ``(id)``)."""
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_walk_programs_carry_the_names_walk_device_s_matches():
    """A rename of a walk program must fail here, not empty
    ``walk_device_s`` on the chip."""
    import jax.numpy as jnp
    from repro.core import dispatch, fdbscan, traversal
    from repro.data import pointclouds
    read = spec.reader("walk_device_s", ROOT)
    modules = read.__globals__["WALK_MODULES"]

    pts = pointclouds.load("portotaxi_like", 1500)
    eps, min_pts = 0.02, 5
    dispatch.clear_cache()
    p = dispatch.plan(pts, eps, min_pts, algorithm="auto")
    dispatch.clear_cache()
    assert p.tree is not None
    first = fdbscan._fused_first_pass_jit.lower(
        p.tree, p.segs, eps, jnp.int32(min_pts), None,
        traverse_fn=traversal.traverse)
    n = p.segs.n_points
    ids = jnp.asarray(np.arange(n, dtype=np.int32))
    labels = jnp.arange(n, dtype=jnp.int32)
    core = jnp.ones(n, bool)
    walk = traversal.traverse.lower(
        p.tree, p.segs, traversal.intersects(traversal.sphere(eps), ids=ids),
        traversal.MinLabelVisitor(labels, core), node_mask=None)
    # the sweeps and the border pass run the one walk program
    names = [_module_name(first), _module_name(walk)]
    for m in modules:
        assert any(m in name for name in names), (m, names)
