"""The trace reduction (benchlib.devtrace) on small traces: busy union,
idle share, per-program device time, and gaps named by host spans."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import devtrace  # noqa: E402

DATA = BENCH / "tests" / "data"


def _hand_trace():
    # device 0: ops overlap on [5, 10]; idle on [20, 30] and [40, 50]
    return devtrace.Trace(
        ops={0: [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)],
             1: [("a", 0, 25)]},
        modules={0: [("jit_walk", 0, 20), ("jit_post", 30, 40)],
                 1: [("jit_walk", 0, 25)]},
        host=[("bench.call", 0, 50), ("dbscan", 0, 50),
              ("sweep", 18, 35), ("other", 20, 30)])


def test_hand_trace_one_device():
    r = devtrace.reduce(_hand_trace(), "bench.call", 1)
    assert r.window_s == pytest.approx(50e-9)
    assert r.busy_s == pytest.approx(30e-9)
    assert r.idle_share == pytest.approx(0.4)
    assert r.module_s == pytest.approx({"jit_walk": 20e-9,
                                        "jit_post": 10e-9})
    # [20, 30] lies inside sweep (the innermost program span; "other" is
    # not one), [40, 50] only inside dbscan
    assert r.gap_s == pytest.approx({"sweep": 10e-9, "dbscan": 10e-9})
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_walk", pytest.approx(20e-9)]


def test_hand_trace_averages_devices_and_clips_to_window():
    t = _hand_trace()
    t = t._replace(host=[("bench.call", 5, 35)] + t.host[1:])
    r = devtrace.reduce(t, "bench.call", 2)
    # device 0 busy [5, 20] + [30, 35] = 20; device 1 busy [5, 25] = 20
    assert r.window_s == pytest.approx(30e-9)
    assert r.busy_s == pytest.approx(20e-9)
    assert r.module_s["jit_walk"] == pytest.approx((15 + 20) / 2 * 1e-9)


def test_no_window_or_no_device_reads_nothing():
    t = _hand_trace()
    assert devtrace.reduce(t, "missing", 1) is None
    assert devtrace.reduce(t._replace(ops={}), "bench.call", 1) is None


def test_module_name_drops_the_runtime_id():
    assert devtrace.module_name("jit_traverse_impl(123)") == \
        "jit_traverse_impl"
    assert devtrace.module_name("jit_f") == "jit_f"


def _busy_by_sweep(intervals):
    """Busy nanoseconds by a running maximum over start-sorted intervals
    (another formulation than the merge in devtrace.union)."""
    total, reach = 0, None
    for s, e in sorted(intervals):
        start = s if reach is None else max(s, reach)
        total += max(0, e - start)
        reach = e if reach is None else max(reach, e)
    return total


def test_recorded_chip_trace():
    doc = json.loads((DATA / "trace_porto2d_build.json").read_text())
    trace = devtrace.Trace.from_json(doc)
    r = devtrace.reduce(trace, "bench.call", 1)
    (lo, hi), = [(s, e) for n, s, e in trace.host if n == "bench.call"]
    ops = [(max(s, lo), min(e, hi)) for _, s, e in trace.ops[0]
           if e > lo and s < hi]
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    assert r.busy_s == pytest.approx(_busy_by_sweep(ops) / 1e9)
    # the index build is host-bound: tiny eager programs, long gaps
    assert 0.9 < r.idle_share < 1.0
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s)
    # gaps inside the build span are the build's; the rest lie in plan
    assert set(r.gap_s) == {"build", "plan"}
    build = [(s, e) for n, s, e in trace.host if n == "build"][0]
    assert r.gap_s["build"] < (build[1] - build[0]) / 1e9
    clipped = sum(min(e, hi) - max(s, lo) for _, s, e in trace.modules[0]
                  if e > lo and s < hi)
    assert sum(r.module_s.values()) == pytest.approx(clipped / 1e9)
    assert "jit__reduce_min" in r.module_s


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%while.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_traverse_impl(42)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 39000 }
    events { metadata_id: 3 offset_ps: 6000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "sweep" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
}
"""


def test_from_profile_reads_device_and_host_planes():
    import jax
    profile = jax.profiler.ProfileData.from_text_proto(XSPACE)
    trace = devtrace.from_profile(profile,
                                  devtrace.SPAN_NAMES + ("bench.call",))
    assert trace.ops == {0: [("%fusion.1", 1000, 1010),
                             ("%while.2", 1030, 1040)]}
    assert trace.modules == {0: [("jit_traverse_impl", 1000, 1040)]}
    assert sorted(trace.host) == [("bench.call", 1000, 1050),
                                  ("sweep", 1005, 1044)]
    r = devtrace.reduce(trace, "bench.call", 1)
    assert r.busy_s == pytest.approx(20e-9)
    assert r.gap_s == pytest.approx({"sweep": 20e-9, "none": 10e-9})
