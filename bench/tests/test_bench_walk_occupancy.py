"""The ``walk_occupancy`` reader on hand-made runs, and on the spans of
a real call of the program."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import devtrace, spec  # noqa: E402
from benchlib.runner import Call, Run  # noqa: E402

DEVICE = devtrace.Reduced(window_s=10.0, busy_s=9.5, module_s={}, gap_s={})


def _span(name, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": 1e6,
            "args": dict(args, sync="blocked")}


def _run(spans, k=2, device=DEVICE):
    calls = [Call(start=float(i), end=float(i) + 1.0, n_sweeps=3,
                  backend="fdbscan-densebox") for i in range(k)]
    return Run(calls=calls, spans=spans, device=device)


SPANS = [
    _span("plan"),
    _span("traverse", phase="first_pass", lane_trips=1000,
          live_lane_trips=800),
    _span("sweep", i=1, lanes=256, lane_trips=600, live_lane_trips=300),
    _span("sweep", i=2, lanes=64, lane_trips=200, live_lane_trips=100),
    _span("border", lane_trips=200, live_lane_trips=40),
    # a span that is no walk is never read, whatever it carries
    _span("finalize", lane_trips=10**6, live_lane_trips=0),
]


def test_reads_live_over_issued_lane_trips_of_the_walk_spans():
    read = spec.reader("walk_occupancy", ROOT)
    assert read(_run(SPANS)) == pytest.approx(100.0 * 1240 / 2000)


def test_is_silent_without_lane_trips_calls_or_device():
    read = spec.reader("walk_occupancy", ROOT)
    # the spans of a program that counts no lane-trips
    bare = [dict(e, args={k: v for k, v in e["args"].items()
                          if "lane_trips" not in k}) for e in SPANS]
    assert read(_run(bare)) is None
    assert read(_run(SPANS, k=0)) is None
    assert read(_run(SPANS, device=None)) is None


def test_is_declared_for_both_cells():
    bench = spec.load(ROOT)
    m = {m["name"]: m for m in bench["per_layer"]}["walk_occupancy"]
    assert m["workloads"] == ["porto2d.batch", "hacc3d-fof.batch"]
    assert m["moves"] == "cluster_points_per_s"
    assert m["layer"] == "traversal: vmapped walk"
    assert (m["unit"], m["better"]) == ("%", "higher")


def test_reads_the_programs_own_spans():
    """On a traced call of the program, the reading is the walks'
    live lane-trips over their issued ones, at most 100%."""
    from repro import obs
    from repro.core import dispatch, fdbscan
    from repro.data import pointclouds
    pts, eps, mp = pointclouds.load("portotaxi_like", 1500), 0.02, 5
    dispatch.clear_cache()
    p = dispatch.plan(pts, eps, mp, algorithm="auto")
    dispatch.clear_cache()
    with obs.instrumented(sync=True) as (reg, tracer):
        fdbscan.cluster_from_index(p.segs, p.tree, eps, mp,
                                   backend=p.backend)
    total = {kind: sum(
        reg.get("traversal_lane_trips_total", phase=ph,
                engine="reference", kind=kind).value
        for ph in ("first_pass", "sweep", "border"))
        for kind in ("issued", "live")}
    got = spec.reader("walk_occupancy", ROOT)(_run(tracer.events, k=1))
    assert 0 < got <= 100.0
    assert got == pytest.approx(100.0 * total["live"] / total["issued"])
