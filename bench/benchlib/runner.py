"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Every call clears the program's plan cache, so that it plans and builds
its index as a batch user's first call does, clusters the
configuration's whole point set with ``repro.dbscan(points, eps,
min_pts, algorithm="auto")`` at the configuration's eps and min_pts, and
blocks on its labels and core mask. The window starts calls while its
elapsed time is under ``--seconds`` and counts every call it started,
whole; ``cluster_points_per_s`` is the points of all its calls over the
time from the window's start to the end of its last call.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from . import compare, devtrace, points, reference, spec

#: the persistent compilation cache when the environment names none: a
#: fixed path inside the checkout (the path is part of the cache key)
CACHE_DIR = spec.ROOT / ".jax_cache"
#: one event per program compiled, or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: host annotation that brackets the traced call in the profiler trace
CALL_ANNOTATION = "bench.call"


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclass
class Call:
    """One call of the window."""
    start: float
    end: float
    n_sweeps: int
    backend: str
    compiles: int = 0


@dataclass
class Run:
    """What the per-layer readers read: the window's calls, the program's
    spans over the window (Chrome trace events), and the reduced device
    trace of the traced call (None without one)."""
    calls: list
    spans: list = field(default_factory=list)
    device: object = None


_compiles = [0]
_listening = [False]


def _count_compiles() -> None:
    """Count every program compiled or loaded from the cache (once per
    process: a monitoring listener cannot be removed)."""
    if _listening[0]:
        return
    from jax import monitoring

    def listen(event, duration, **kwargs):
        if event == COMPILE_EVENT:
            _compiles[0] += 1
    monitoring.register_event_duration_secs_listener(listen)
    _listening[0] = True


def use_compile_cache() -> None:
    """Keep JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else at :data:`CACHE_DIR`; cache every
    program, however small or quick to compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def accelerator(chips: int) -> list:
    """The devices, or :class:`NoAccelerator` without ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"needs {chips} TPU chip(s); JAX reports {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs


def _call(pts, eps: float, min_pts: int):
    import jax
    import repro
    from repro.core import dispatch
    start = time.perf_counter()
    dispatch.clear_cache()
    res = repro.dbscan(pts, eps, min_pts, algorithm="auto")
    jax.block_until_ready((res.labels, res.core_mask))
    end = time.perf_counter()
    return res, Call(start=start, end=end, n_sweeps=int(res.n_sweeps),
                     backend=res.backend)


def _profiled_call(pts, eps: float, min_pts: int, log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    opts.raise_error_on_start_failure = True
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(CALL_ANNOTATION):
            out = _call(pts, eps, min_pts)
    finally:
        jax.profiler.stop_trace()
    return out


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root=spec.ROOT, require_tpu: bool = True,
        out=None, err=None) -> int:
    """Run ``workload`` once; print the result line; return the exit
    code. ``t_start`` is the perf-counter time set-up is timed from.
    ``require_tpu=False`` skips the look for a chip (tests only)."""
    out = out or sys.stdout
    err = err or sys.stderr
    bench = spec.load(root)
    cell, config, _ = spec.cell(bench, workload, root)
    use_compile_cache()
    import jax
    devs = accelerator(cell["chips"]) if require_tpu else jax.devices()
    used = devs[:cell["chips"]]

    pts = points.make_points(config, seed)
    eps, min_pts = float(config["eps"]), int(config["min_pts"])
    _call(pts, eps, min_pts)            # warm-up: the window's one shape

    if trace:
        from repro.obs import trace as obs_trace
        _count_compiles()
        tracer = obs_trace.install(sync=True, annotate=True)
        trace_dir = tempfile.TemporaryDirectory()
    results, calls = [], []
    window_start = time.perf_counter()
    setup_s = window_start - t_start
    try:
        while not calls or time.perf_counter() - window_start < seconds:
            before = _compiles[0]
            if trace and not calls:
                res, call = _profiled_call(pts, eps, min_pts,
                                           trace_dir.name)
            else:
                res, call = _call(pts, eps, min_pts)
            call.compiles = _compiles[0] - before
            results.append(res)
            calls.append(call)
    finally:
        if trace:
            obs_trace.uninstall()
    window_s = calls[-1].end - window_start
    peak = _peak_bytes(used)

    # the program's state is freed before the reference runs
    answers = [(np.asarray(r.labels), np.asarray(r.core_mask))
               for r in results]
    del results
    from repro.core import dispatch
    dispatch.clear_cache()

    if trace:
        run_rec = Run(calls=calls, spans=list(tracer.events))
        run_rec.device = devtrace.reduce_dir(trace_dir.name,
                                             CALL_ANNOTATION, len(used))
        trace_dir.cleanup()
        metrics = {}
        for m in spec.metrics_of(bench, workload, trace=True):
            value = spec.reader(m["name"], root)(run_rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"cluster_points_per_s":
               len(calls) * len(pts) / window_s,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics_of(bench, workload, trace=False)}

    checks, failed = _check(pts, config, answers)
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    line = {"correct": failed == 0, "attempted": len(calls),
            "failed": failed, "metrics": metrics, "device": device}
    if trace:
        reduced = run_rec.device
        device["busy_s"] = reduced.busy_s if reduced else None
        device["window_s"] = reduced.window_s if reduced else None
        if reduced:
            line["breakdown"] = reduced.breakdown()
    # each call's seconds, in order: where a run reads slow, which call
    line["call_s"] = [c.end - c.start for c in calls]
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0


def _check(pts, config: dict, answers: list):
    """Compare every call's answer with the reference.

    Returns ``(checks, failed)``: for each number compared, the largest
    reading over the window's calls beside its limit, and how many calls
    read over a limit."""
    limits = config["limits"]
    ref = reference.dbscan(pts, float(config["eps"]), int(config["min_pts"]))
    worst = {name: 0 for name in compare.NUMBERS}
    failed = 0
    for labels, core in answers:
        got = compare.compare(ref, labels, core)
        failed += any(got[k] > limits[k] for k in compare.NUMBERS)
        for k in compare.NUMBERS:
            worst[k] = max(worst[k], got[k])
    return ({k: {"value": worst[k], "limit": limits[k]}
             for k in compare.NUMBERS}, failed)
