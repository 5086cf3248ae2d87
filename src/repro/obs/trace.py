"""Span tracer — nestable phase spans exported as Chrome trace-event JSON
(DESIGN.md §12).

``span("build")`` / ``span("sweep", i=k)`` bracket *host-side* calls:
the jitted programs underneath are opaque to the tracer by design (a
span entered inside a ``jit`` trace would fire at trace time, not run
time, and poison the cache — see the observer-effect contract).  Spans
nest via a per-thread stack and serialize as Chrome trace-event
*complete* events (``"ph": "X"``), so ``export(path)`` produces a file
that loads directly in Perfetto / ``chrome://tracing``.

Device-sync semantics: JAX dispatch is asynchronous, so a span that only
measures the Python call would report dispatch cost, not compute cost.
A span can therefore *watch* values (``sp.watch(arrays)`` or the
module-level :func:`watch`); in ``sync=True`` mode (the default) the
span close runs ``jax.block_until_ready`` over everything watched before
taking the end timestamp, and the event is explicitly marked
(``args["sync"] == "blocked"``) so the observer cost is visible in the
trace rather than silently attributed.  ``sync=False`` is the production
mode: watches are recorded as ``"none"`` and nothing ever blocks.

``jax.profiler`` shim (the paxml ``cuda_profile_hook`` shape): with
``annotate=True`` every span also enters a
``jax.profiler.TraceAnnotation``, so when a JAX profiler capture is
active (e.g. under :func:`profiler_session`) the same phase names appear
on the profiler timeline; without an active capture the annotation is a
cheap no-op.

Disabled-by-default: with no tracer installed, :func:`span` returns a
shared no-op context manager — one module-global load per call site.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

# Version tag of the exported document; carried in the trace metadata.
TRACE_SCHEMA = "repro.obs.trace/v1"

# Event-buffer cap: tracing is for runs a human inspects, not a flight
# recorder — past the cap new events are dropped and counted.
MAX_EVENTS = 200_000


class Span:
    """One phase bracket; use via ``with trace.span(name, **attrs):``."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_watched", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._watched: list = []
        self._ann = None

    def watch(self, *values) -> None:
        """Register values to ``block_until_ready`` at span close (sync
        mode); in no-sync mode the values are simply dropped."""
        if self._tracer.sync:
            self._watched.extend(values)

    def __enter__(self) -> "Span":
        self._tracer._stack().append(self)
        if self._tracer.annotate:
            self._ann = _enter_annotation(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # sync only a span whose body succeeded: a device fault surfacing
        # here must propagate, but must not replace the body's own error
        synced = bool(self._watched) and exc_type is None
        if synced:
            import jax
            jax.block_until_ready(jax.tree.leaves(self._watched))
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._record(self.name, self._t0, t1, self.attrs, synced)


class _NoopSpan:
    """The shared disabled-path span: every method is a no-op."""

    __slots__ = ()

    def watch(self, *values) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NOOP = _NoopSpan()


class Tracer:
    """Collects span events; ``export(path)`` writes Chrome trace JSON.

    sync: block on watched device values at span close (timing covers
        the compute, observer cost is explicit); False never blocks.
    annotate: mirror spans into ``jax.profiler.TraceAnnotation`` so an
        active profiler capture shows the same phase names.
    """

    def __init__(self, sync: bool = True, annotate: bool = True,
                 max_events: int = MAX_EVENTS):
        self.sync = bool(sync)
        self.annotate = bool(annotate)
        self.max_events = int(max_events)
        self.events: list[dict] = []
        self.n_dropped = 0
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def _record(self, name: str, t0: float, t1: float, attrs: dict,
                synced: bool) -> None:
        with self._lock:
            if len(self.events) >= self.max_events:
                self.n_dropped += 1
                return
            args = {k: _jsonable(v) for k, v in attrs.items()}
            args["sync"] = "blocked" if synced else "none"
            self.events.append({
                "name": name, "ph": "X", "cat": "repro",
                "ts": (t0 - self._epoch) * 1e6,
                "dur": (t1 - t0) * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident() % 2**31,
                "args": args,
            })

    def to_dict(self) -> dict:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {"schema": TRACE_SCHEMA,
                          "sync": "blocked" if self.sync else "none",
                          "dropped_events": self.n_dropped},
        }

    def export(self, path: str) -> dict:
        doc = self.to_dict()
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return int(v)           # 0-d numpy / jax scalars
    except Exception:
        return str(v)


# ---------------------------------------------------------------------- #
# the installed tracer (module-global; None = tracing off)               #
# ---------------------------------------------------------------------- #

_active: Tracer | None = None


def install(tracer: Tracer | None = None, *, sync: bool = True,
            annotate: bool = True) -> Tracer:
    """Install ``tracer`` (or a fresh ``Tracer(sync=, annotate=)``) as the
    process-wide span collector and return it."""
    global _active
    _active = tracer if tracer is not None else Tracer(sync=sync,
                                                       annotate=annotate)
    return _active


def uninstall() -> None:
    global _active
    _active = None


def active() -> Tracer | None:
    """The installed tracer, or None when tracing is off."""
    return _active


def span(name: str, **attrs):
    """A span context manager on the installed tracer, or the shared
    no-op when tracing is off (the disabled fast path)."""
    t = _active
    if t is None:
        return _NOOP
    return t.span(name, **attrs)


def watch(*values) -> None:
    """Register values on the innermost open span of this thread for
    device sync at span close.  No-op when tracing is off, when the
    tracer is in no-sync mode, or outside any span."""
    t = _active
    if t is None or not t.sync:
        return
    stack = t._stack()
    if stack:
        stack[-1].watch(*values)


# ---------------------------------------------------------------------- #
# jax.profiler shim                                                      #
# ---------------------------------------------------------------------- #

def _enter_annotation(name: str):
    """Enter a ``jax.profiler.TraceAnnotation(name)``; the annotation is
    visible only while a profiler capture is active."""
    from jax import profiler
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


@contextmanager
def profiler_session(log_dir: str):
    """Bracket a region with a JAX profiler capture (the
    ``cuda_profile_hook`` shape: arm the vendor profiler around exactly
    the region of interest). A capture that cannot start or stop raises:
    a missing trace must not pass for an empty one."""
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------- #
# trace validation (CI gates artifacts through this)                     #
# ---------------------------------------------------------------------- #

def validate_chrome_trace(doc: dict) -> None:
    """Raise ValueError unless ``doc`` is a loadable Chrome trace-event
    document of ours (JSON-object form with complete events)."""
    if not isinstance(doc, dict):
        raise ValueError(f"trace must be a dict; got {type(doc).__name__}")
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("trace 'traceEvents' must be a list")
    if doc.get("otherData", {}).get("schema") != TRACE_SCHEMA:
        raise ValueError(f"trace schema "
                         f"{doc.get('otherData', {}).get('schema')!r} "
                         f"!= {TRACE_SCHEMA!r}")
    for ev in events:
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"event missing {k!r}: {ev}")
        if ev["ph"] == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                raise ValueError(f"complete event needs dur >= 0: {ev}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            raise ValueError(f"event ts must be a non-negative number: {ev}")
