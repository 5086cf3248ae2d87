"""Reduction of a profiler trace to device busy time, idle gaps and
per-program device time.

A trace is reduced in two steps. :func:`from_profile` reads the
profiler's XSpace (``jax.profiler.ProfileData``) into a
:class:`Trace`: the operations each device ran, the XLA programs
(modules) it ran, and the host's annotations (the program's spans and
the harness's own), all on one clock in nanoseconds. :func:`reduce`
then works on plain intervals, and is what the tests check on a small
recorded trace:

* busy time is the union of a device's operation intervals inside the
  window, averaged over the devices used;
* the idle share is one minus busy time over the window;
* a program's device time is the sum of its module intervals inside the
  window;
* each idle gap is named by the innermost program span that encloses
  its midpoint (``"none"`` when no span does).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

#: the program's own spans, the names a gap can be given
SPAN_NAMES = ("plan", "build", "dbscan", "traverse", "sweep", "border",
              "finalize")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


class Trace(NamedTuple):
    """Events as ``(name, start_ns, end_ns)``.

    ops: per device index, the operations it ran.
    modules: per device index, the XLA programs it ran.
    host: the host's annotations (spans and harness markers).
    """
    ops: dict
    modules: dict
    host: list

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        def events(rows):
            return [(str(n), int(s), int(e)) for n, s, e in rows]
        return cls({int(k): events(v) for k, v in doc["ops"].items()},
                   {int(k): events(v) for k, v in doc["modules"].items()},
                   events(doc["host"]))


def module_name(name: str) -> str:
    """An XLA module's name without the ``(id)`` the runtime appends."""
    return _SUFFIX.sub("", name)


def from_profile(profile, host_names) -> Trace:
    """Read a ``jax.profiler.ProfileData``: device operations and modules
    from the ``/device:TPU:<i>`` planes, and host events whose names are
    in ``host_names`` from the host planes."""
    ops, modules, host = defaultdict(list), defaultdict(list), []
    host_names = set(host_names)
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Ops":
                    ops[dev].extend((e.name, int(e.start_ns), int(e.end_ns))
                                    for e in line.events)
                elif line.name == "XLA Modules":
                    modules[dev].extend((module_name(e.name),
                                         int(e.start_ns), int(e.end_ns))
                                        for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name in host_names)
    return Trace(dict(ops), dict(modules), host)


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(events, lo: int, hi: int) -> list:
    """``(start, end)`` of each event's part inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for _, s, e in events
            if e > lo and s < hi]


class Reduced(NamedTuple):
    """A traced window, reduced. Times in seconds."""
    window_s: float
    busy_s: float
    module_s: dict          # XLA program name -> device seconds
    gap_s: dict             # enclosing span name -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The programs that took the most device time, and the idle time
        by what the host was doing, each as ``[name, seconds]``."""
        def ranked(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": ranked(self.module_s),
                "idle_gaps": ranked(self.gap_s)}


def reduce(trace: Trace, window_name: str, n_devices: int) -> Reduced | None:
    """Reduce the window that the host event ``window_name`` brackets
    (its first occurrence), over devices ``0 .. n_devices - 1``.
    None when the trace holds no such window or no device operation."""
    marks = [(s, e) for n, s, e in trace.host if n == window_name]
    devices = [d for d in range(n_devices) if trace.ops.get(d)]
    if not marks or not devices:
        return None
    lo, hi = marks[0]
    busy_by_dev = [union(clip(trace.ops[d], lo, hi)) for d in devices]
    busy = sum(sum(e - s for s, e in b) for b in busy_by_dev) / len(devices)
    module_s = defaultdict(float)
    for d in devices:
        for name, s, e in trace.modules.get(d, ()):
            if e > lo and s < hi:
                module_s[name] += (min(e, hi) - max(s, lo)) / 1e9 / len(
                    devices)
    spans = [(n, s, e) for n, s, e in trace.host if n in SPAN_NAMES]
    gap_s = defaultdict(float)
    t = lo
    for s, e in busy_by_dev[0] + [(hi, hi)]:
        if s > t:
            mid = (t + s) / 2
            enclosing = [(e2 - s2, n) for n, s2, e2 in spans
                         if s2 <= mid <= e2]
            gap_s[min(enclosing)[1] if enclosing else "none"] += (s - t) / 1e9
        t = max(t, e)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9,
                   module_s=dict(module_s), gap_s=dict(gap_s))


def reduce_dir(log_dir: str, window_name: str, n_devices: int
               ) -> Reduced | None:
    """Read the profiler's output under ``log_dir`` and :func:`reduce`
    it; None when the profiler wrote nothing."""
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    profile = jax.profiler.ProfileData.from_file(files[0])
    trace = from_profile(profile, SPAN_NAMES + (window_name,))
    return reduce(trace, window_name, n_devices)
