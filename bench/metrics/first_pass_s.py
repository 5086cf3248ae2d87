"""first_pass_s: seconds per call inside the program's ``traverse``
spans of phase ``first_pass`` (the fused count-and-hook walk of
``core/fdbscan.py``), timed by the program's tracer in sync mode.

Read only beside a device trace of the same run: off the chip the span
times XLA's CPU backend, which is no measurement of the chip."""


def read(run):
    spans = [e for e in run.spans if e["name"] == "traverse"
             and e["args"].get("phase") == "first_pass"]
    if not spans or not run.calls or run.device is None:
        return None
    return sum(e["dur"] for e in spans) / 1e6 / len(run.calls)
