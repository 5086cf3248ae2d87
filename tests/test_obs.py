"""Unit tests for the observability layer (DESIGN.md §12): registry
semantics, sketch accuracy and memory bounds, trace export and sync
marking, the disabled fast path, schema stability, artifact validation,
and a traced serve smoke run."""
import json
import math

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics, trace
from repro.obs import validate as obs_validate


# --------------------------------------------------------------------- #
# registry semantics                                                    #
# --------------------------------------------------------------------- #

def test_counter_monotone_and_labels():
    reg = metrics.Registry()
    fam = reg.counter("requests_total", labels=("kind",))
    fam.labels(kind="insert").inc()
    fam.labels(kind="insert").inc(2.5)
    fam.labels(kind="query").inc()
    assert fam.labels(kind="insert").value == 3.5
    assert fam.labels(kind="query").value == 1.0
    with pytest.raises(ValueError):
        fam.labels(kind="insert").inc(-1)
    # typo'd label names must raise, not fork a parallel series
    with pytest.raises(ValueError):
        fam.labels(kinds="insert")


def test_family_conflicts_raise():
    reg = metrics.Registry()
    reg.counter("x", labels=("a",))
    with pytest.raises(ValueError):
        reg.gauge("x", labels=("a",))          # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x", labels=("b",))        # label-set conflict


def test_registry_get_never_creates():
    reg = metrics.Registry()
    assert reg.get("absent") is None
    reg.counter("c", labels=("k",)).labels(k="v").inc()
    assert reg.get("c", k="v").value == 1.0
    assert reg.get("c", k="other") is None
    assert len(reg._families["c"]._children) == 1


# --------------------------------------------------------------------- #
# histogram sketch: accuracy, memory bound, zero bucket                 #
# --------------------------------------------------------------------- #

def test_histogram_quantiles_within_relative_accuracy():
    rng = np.random.default_rng(0)
    vals = np.exp(rng.normal(-7.0, 1.5, size=20_000))   # latency-like
    h = metrics.Histogram()
    for v in vals:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(vals, q))
        est = h.quantile(q)
        # DDSketch guarantee is rel error <= a on the value; allow 3a for
        # rank-interpolation differences vs numpy at finite sample size
        assert abs(est - exact) / exact <= 3 * metrics.REL_ACCURACY, \
            f"q={q}: {est} vs {exact}"
    assert h.count == len(vals)
    assert math.isclose(h.sum, float(vals.sum()), rel_tol=1e-9)
    assert h.min == float(vals.min()) and h.max == float(vals.max())


def test_histogram_memory_flat_in_sample_count():
    h = metrics.Histogram()
    lo, hi = 1e-4, 1e-1
    # memory is bounded by the data's dynamic range, never by the count:
    # the sketch can use at most one bucket per log-gamma step across
    # [lo, hi] (+1 for the boundary), however many samples arrive
    range_buckets = math.ceil(math.log(hi / lo) / h._log_gamma) + 1
    rng = np.random.default_rng(1)
    for v in rng.uniform(lo, hi, size=50_000):
        h.observe(float(v))
    assert h.bucket_count() <= range_buckets
    for v in rng.uniform(lo, hi, size=50_000):
        h.observe(float(v))
    assert h.bucket_count() <= range_buckets < 400
    assert h.count == 100_000


def test_histogram_bucket_cap_collapses():
    h = metrics.Histogram()
    # one observation per sketch bucket across a huge dynamic range:
    # blows straight past MAX_BUCKETS unless the lowest buckets collapse
    step = h._log_gamma * 1.01
    for i in range(metrics.MAX_BUCKETS + 200):
        h.observe(math.exp((i - 100) * step))
    assert h.bucket_count() <= metrics.MAX_BUCKETS
    assert h.count == metrics.MAX_BUCKETS + 200


def test_histogram_zero_bucket_and_empty():
    h = metrics.Histogram()
    assert math.isnan(h.quantile(0.5))
    for v in (0.0, -1.0, 0.0, 5.0):
        h.observe(v)
    assert h.quantile(0.25) == 0.0            # the three non-positives
    # the top quantile lands in 5.0's bucket (midpoint within rel error)
    assert abs(h.quantile(1.0) - 5.0) / 5.0 <= metrics.REL_ACCURACY
    with pytest.raises(ValueError):
        h.quantile(1.5)


# --------------------------------------------------------------------- #
# schema stability + validation                                         #
# --------------------------------------------------------------------- #

def test_snapshot_schema_pinned():
    # the exact document layout is a compatibility surface: CI tooling
    # and dashboards parse it, so a change here is a schema migration
    assert metrics.SCHEMA == "repro.obs/v1"
    assert trace.TRACE_SCHEMA == "repro.obs.trace/v1"
    reg = metrics.Registry()
    reg.counter("c", help="h", labels=("k",)).labels(k="v").inc(2)
    reg.gauge("g").labels().set(1.5)
    reg.histogram("lat", labels=("op",)).labels(op="q").observe(0.25)
    doc = reg.snapshot()
    metrics.validate_snapshot(doc)
    assert sorted(doc) == ["metrics", "schema"]
    assert [m["name"] for m in doc["metrics"]] == ["c", "g", "lat"]
    c, g, lat = doc["metrics"]
    assert sorted(c) == ["help", "kind", "label_names", "name", "series"]
    assert c["series"] == [{"labels": {"k": "v"}, "value": 2.0}]
    assert g["series"] == [{"labels": {}, "value": 1.5}]
    s = lat["series"][0]
    assert sorted(s) == ["count", "labels", "max", "min", "p50", "p95",
                         "p99", "sum"]
    assert s["count"] == 1 and s["sum"] == 0.25
    # round-trips through JSON unchanged
    assert json.loads(json.dumps(doc)) == doc


def test_validate_snapshot_rejections():
    good = {"schema": metrics.SCHEMA, "metrics": []}
    metrics.validate_snapshot(good)
    with pytest.raises(ValueError):
        metrics.validate_snapshot({"schema": "nope", "metrics": []})
    with pytest.raises(ValueError):
        metrics.validate_snapshot({"schema": metrics.SCHEMA,
                                   "metrics": {}})
    dup = {"schema": metrics.SCHEMA, "metrics": [
        {"name": "x", "kind": "counter", "label_names": [], "series": []},
        {"name": "x", "kind": "counter", "label_names": [], "series": []}]}
    with pytest.raises(ValueError):
        metrics.validate_snapshot(dup)
    bad_hist = {"schema": metrics.SCHEMA, "metrics": [
        {"name": "h", "kind": "histogram", "label_names": [],
         "series": [{"labels": {}, "count": 1}]}]}
    with pytest.raises(ValueError):
        metrics.validate_snapshot(bad_hist)


def test_validate_chrome_trace_rejections():
    tr = trace.Tracer(sync=False, annotate=False)
    with tr.span("a"):
        with tr.span("b", i=1):
            pass
    doc = tr.to_dict()
    trace.validate_chrome_trace(doc)
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"traceEvents": []})   # no schema tag
    bad = json.loads(json.dumps(doc))
    del bad["traceEvents"][0]["dur"]
    with pytest.raises(ValueError):
        trace.validate_chrome_trace(bad)


# --------------------------------------------------------------------- #
# tracer: nesting, sync marking, export                                 #
# --------------------------------------------------------------------- #

def test_trace_nesting_and_attrs(tmp_path):
    tr = trace.Tracer(sync=False, annotate=False)
    with tr.span("outer", backend="fdbscan"):
        with tr.span("inner", i=2):
            pass
    # children close (and record) before parents
    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    inner, outer = tr.events
    assert outer["args"]["backend"] == "fdbscan"
    assert inner["args"]["i"] == 2
    assert outer["dur"] >= inner["dur"]
    p = tmp_path / "t.json"
    doc = tr.export(str(p))
    trace.validate_chrome_trace(json.loads(p.read_text()))
    assert doc["otherData"]["dropped_events"] == 0


def test_trace_sync_marking():
    import jax.numpy as jnp
    tr = trace.Tracer(sync=True, annotate=False)
    with tr.span("synced") as sp:
        sp.watch(jnp.arange(8) * 2)
    with tr.span("unsynced"):
        pass
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["synced"]["args"]["sync"] == "blocked"
    assert by_name["unsynced"]["args"]["sync"] == "none"
    # no-sync tracer never blocks, even with watches registered
    tr2 = trace.Tracer(sync=False, annotate=False)
    with tr2.span("s") as sp:
        sp.watch(jnp.arange(4))
    assert tr2.events[0]["args"]["sync"] == "none"


def test_trace_event_cap():
    tr = trace.Tracer(sync=False, annotate=False, max_events=3)
    for i in range(5):
        with tr.span("s", i=i):
            pass
    assert len(tr.events) == 3
    assert tr.to_dict()["otherData"]["dropped_events"] == 2


# --------------------------------------------------------------------- #
# disabled fast path + scoped installation                              #
# --------------------------------------------------------------------- #

def test_disabled_fast_path_is_noop():
    assert metrics.active() is None and trace.active() is None
    # module helpers must not allocate registries as a side effect
    metrics.inc("nope")
    metrics.observe("nope", 1.0)
    metrics.set_gauge("nope", 1.0)
    assert metrics.active() is None
    # span() hands back the one shared no-op object
    assert trace.span("a") is trace.span("b")
    with trace.span("a") as sp:
        sp.watch(object())
    trace.watch(object())                     # outside any span: no-op


def test_instrumented_scopes_and_restores():
    outer_reg = metrics.install(metrics.Registry())
    try:
        with obs.instrumented(sync=True) as (reg, tr):
            assert metrics.active() is reg and trace.active() is tr
            assert reg is not outer_reg
            metrics.inc("inside")
            with trace.span("s"):
                pass
        assert metrics.active() is outer_reg
        assert trace.active() is None
        assert outer_reg.get("inside") is None
    finally:
        metrics.uninstall()


# --------------------------------------------------------------------- #
# validator CLI + traced serve smoke (artifact end-to-end)              #
# --------------------------------------------------------------------- #

def test_validator_cli(tmp_path):
    reg = metrics.Registry()
    reg.counter("c").labels().inc()
    mpath = tmp_path / "m.json"
    reg.write_json(str(mpath))
    tr = trace.Tracer(sync=False, annotate=False)
    with tr.span("phase"):
        pass
    tpath = tmp_path / "t.json"
    tr.export(str(tpath))
    assert obs_validate.main(["--metrics", str(mpath), "--trace",
                              str(tpath), "--require-span", "phase",
                              "--require-metric", "c"]) == 0
    assert obs_validate.main(["--trace", str(tpath),
                              "--require-span", "absent"]) == 1
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert obs_validate.main(["--metrics", str(bad)]) == 1


def test_serve_emits_valid_artifacts(tmp_path):
    from repro.launch import serve
    mpath, tpath = tmp_path / "m.json", tmp_path / "t.json"
    stats = serve.main([
        "--dataset", "blobs", "--n", "512", "--warm-frac", "0.5",
        "--eps", "0.05", "--min-pts", "8", "--batch", "64",
        "--steps", "4", "--insert-frac", "1.0", "--seed", "3",
        "--metrics-json", str(mpath), "--trace", str(tpath),
        "--trace-sync"])
    assert obs_validate.main([
        "--metrics", str(mpath), "--trace", str(tpath),
        "--require-span", "serve.request", "--require-span",
        "stream.insert", "--require-metric", "serve_insert_seconds"]) == 0
    # serving latency lives in bounded sketches, not unbounded lists
    assert stats["latency_sketch_buckets"] < metrics.MAX_BUCKETS
    assert stats["insert_p50_ms"] > 0
    # collectors installed by serve.main must not leak into the session
    assert metrics.active() is None and trace.active() is None


# --------------------------------------------------------------------- #
# span attributes set late, the wall-clock epoch, compile spans         #
# --------------------------------------------------------------------- #

def test_span_set_on_live_and_noop_span():
    tr = trace.Tracer(sync=False, annotate=False)
    with tr.span("frontier", stage="next") as sp:
        sp.set(n_changed=5, lanes=64)
    assert tr.events[0]["args"] == {"stage": "next", "n_changed": 5,
                                    "lanes": 64, "sync": "none"}
    assert trace.active() is None
    with trace.span("frontier") as sp:
        sp.set(n_changed=1)                   # the shared no-op


def test_export_records_the_wall_clock_epoch():
    import time
    before = time.time_ns()
    tr = trace.Tracer(sync=False, annotate=False)
    after = time.time_ns()
    with tr.span("s"):
        pass
    doc = tr.to_dict()
    trace.validate_chrome_trace(doc)
    assert before <= doc["otherData"]["epoch_unix_ns"] <= after
    # an event's wall-clock start: the epoch plus its ts (microseconds)
    start = doc["otherData"]["epoch_unix_ns"] + doc["traceEvents"][0][
        "ts"] * 1e3
    assert before <= start <= time.time_ns()


def test_compile_spans_name_the_function_and_the_enclosing_span():
    import jax
    import jax.numpy as jnp

    def fresh_program(x):
        return jnp.cumsum(x * 3) - 1

    x = jnp.arange(7.0)
    tr = trace.install(sync=False, annotate=False)
    try:
        with trace.span("plan"):
            jax.jit(fresh_program)(x).block_until_ready()
    finally:
        trace.uninstall()
    compiles = [e for e in tr.events if e["name"] == trace.COMPILE_SPAN
                and "fresh_program" in e["args"]["fun"]]
    assert sorted(e["args"]["stage"] for e in compiles) == ["compile",
                                                            "lower"]
    for e in compiles:
        assert e["args"]["span"] == "plan"
        assert e["dur"] >= 0
    plan = next(e for e in tr.events if e["name"] == "plan")
    assert all(plan["ts"] <= e["ts"] and e["ts"] + e["dur"]
               <= plan["ts"] + plan["dur"] + 1 for e in compiles)
    trace.validate_chrome_trace(tr.to_dict())

    # no tracer installed: the listener records nothing anywhere
    n_events = len(tr.events)

    def another_program(x):
        return jnp.cumprod(x) + 2
    jax.jit(another_program)(x).block_until_ready()
    assert len(tr.events) == n_events


def test_annotation_attributes_reach_a_profiler_capture(tmp_path):
    import glob
    import os

    import jax
    import jax.numpy as jnp
    tr = trace.Tracer(sync=False, annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("sweep", i=3, lanes=128, engine="reference"):
            jnp.arange(16).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    found = [dict(e.stats) for plane in profile.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "sweep"]
    assert found and found[0]["i"] == 3 and found[0]["lanes"] == 128
    assert found[0]["engine"] == "reference"


# --------------------------------------------------------------------- #
# the batch path's spans, and the registry's observer effect            #
# --------------------------------------------------------------------- #

def _auto_points():
    from repro.data import pointclouds
    # above the tiled cut-off, so auto builds a densebox tree and sweeps
    return pointclouds.load("portotaxi_like", 1500), 0.02, 5


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1)


def test_lbvh_nests_in_plan_and_frontier_in_dbscan():
    import repro
    from repro.core import dispatch
    pts, eps, mp = _auto_points()
    dispatch.clear_cache()
    with obs.instrumented(sync=True) as (_, tr):
        res = repro.dbscan(pts, eps, mp, algorithm="auto")
    dispatch.clear_cache()
    assert res.backend == "fdbscan-densebox"
    by = {}
    for e in tr.events:
        by.setdefault(e["name"], []).append(e)
    (plan,), (dbscan_span,), (lbvh_span,) = (by["plan"], by["dbscan"],
                                             by["lbvh"])
    assert _inside(lbvh_span, plan)
    assert lbvh_span["args"]["sync"] == "blocked"
    frontier = by["frontier"]
    assert all(_inside(e, dbscan_span) for e in frontier)
    setup, *nexts = frontier
    assert setup["args"]["stage"] == "setup"
    assert [e["args"]["stage"] for e in nexts] == ["next"] * len(nexts)
    # one "next" after every sweep; the last finds no change
    sweeps = by["sweep"]
    assert len(nexts) == len(sweeps) == res.n_sweeps - 1
    assert [e["args"]["n_changed"] > 0 for e in nexts] == \
        [True] * (len(nexts) - 1) + [False]
    # each sweep walks the lanes the frontier work before it chose
    lanes = [setup["args"]["lanes"]] + [e["args"]["lanes"] for e in nexts]
    assert [s["args"]["lanes"] for s in sweeps] == lanes[:-1]
    assert lanes[-1] == 0


def test_metrics_registry_adds_no_compiles():
    import jax
    import repro
    from repro.core import dispatch
    counts, phase = {}, [None]

    def listen(event, duration, **kwargs):
        if (event == "/jax/core/compile/backend_compile_duration"
                and phase[0] is not None):
            counts[phase[0]] = counts.get(phase[0], 0) + 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    pts, eps, mp = _auto_points()

    def call():
        dispatch.clear_cache()
        return repro.dbscan(pts, eps, mp, algorithm="auto")
    call()                                    # warm: every shape compiled
    try:
        phase[0] = "plain"
        plain = call()
        phase[0] = "registry"
        with obs.instrumented(sync=True) as (reg, _):
            traced = call()
    finally:
        phase[0] = None          # a listener cannot be removed: mute it
        dispatch.clear_cache()
    assert counts.get("plain", 0) == counts.get("registry", 0)
    np.testing.assert_array_equal(np.asarray(plain.labels),
                                  np.asarray(traced.labels))
    assert reg.get("traversal_evals_total", phase="sweep",
                   engine="reference").value > 0


def test_evals_counter_matches_the_walks_own_counts():
    from repro.core import dispatch, fdbscan
    pts, eps, mp = _auto_points()
    dispatch.clear_cache()
    p = dispatch.plan(pts, eps, mp, algorithm="auto")
    dispatch.clear_cache()
    _, stats = fdbscan.cluster_from_index(p.segs, p.tree, eps, mp,
                                          backend=p.backend, with_stats=True)
    with obs.instrumented(sync=False) as (reg, _):
        fdbscan.cluster_from_index(p.segs, p.tree, eps, mp,
                                   backend=p.backend)

    def counted(phase):
        return reg.get("traversal_evals_total", phase=phase,
                       engine="reference").value
    assert counted("first_pass") == stats["first_pass_evals"]
    assert counted("sweep") == sum(stats["evals_per_sweep"])
    assert counted("border") >= 0
    families = {m["name"] for m in reg.snapshot()["metrics"]}
    assert "traversal_iters_total" not in families


def test_lane_trips_reach_the_spans_and_the_registry():
    """Each walk's issued and live lane-trips land on its span and in
    ``traversal_lane_trips_total``; counting them compiles nothing."""
    import jax
    from repro.core import dispatch, fdbscan
    counts, phase = {}, [None]

    def listen(event, duration, **kwargs):
        if (event == "/jax/core/compile/backend_compile_duration"
                and phase[0] is not None):
            counts[phase[0]] = counts.get(phase[0], 0) + 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    pts, eps, mp = _auto_points()
    dispatch.clear_cache()
    p = dispatch.plan(pts, eps, mp, algorithm="auto")
    dispatch.clear_cache()

    def call(**kw):
        return fdbscan.cluster_from_index(p.segs, p.tree, eps, mp,
                                          backend=p.backend, **kw)
    _, stats = call(with_stats=True)          # warm: every shape compiled
    try:
        phase[0] = "plain"
        call()
        phase[0] = "counted"
        with obs.instrumented(sync=True) as (reg, tracer):
            call()
    finally:
        phase[0] = None          # a listener cannot be removed: mute it
    assert counts.get("plain", 0) == counts.get("counted", 0)

    def counted(phase, kind):
        return reg.get("traversal_lane_trips_total", phase=phase,
                       engine="reference", kind=kind).value
    assert counted("first_pass", "live") == stats["first_pass_iters"]
    assert counted("sweep", "live") == sum(stats["iters_per_sweep"])
    for ph in ("first_pass", "sweep", "border"):
        assert counted(ph, "issued") >= counted(ph, "live") > 0
    walks = [e for e in tracer.events
             if e["name"] in ("traverse", "sweep", "border")]
    assert {e["name"] for e in walks} == {"traverse", "sweep", "border"}
    for e in walks:
        assert e["args"]["lane_trips"] >= e["args"]["live_lane_trips"] > 0
    by_phase = {"traverse": "first_pass", "sweep": "sweep",
                "border": "border"}
    for name, ph in by_phase.items():
        for kind, key in (("issued", "lane_trips"),
                          ("live", "live_lane_trips")):
            assert sum(e["args"][key] for e in walks
                       if e["name"] == name) == counted(ph, kind)
