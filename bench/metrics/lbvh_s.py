"""lbvh_s: seconds per call inside the program's ``lbvh`` spans: the
LBVH build of ``core/dispatch.py`` (Karras topology, box fitting,
ropes), nested in ``plan``, timed by the program's tracer in sync mode.

Read only beside a device trace of the same run: off the chip the span
times XLA's CPU backend, which is no measurement of the chip."""


def read(run):
    spans = [e for e in run.spans if e["name"] == "lbvh"]
    if not spans or not run.calls or run.device is None:
        return None
    return sum(e["dur"] for e in spans) / 1e6 / len(run.calls)
