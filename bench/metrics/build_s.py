"""build_s: seconds per call inside the program's ``plan`` spans: the
point-set hash, the eps-grid build (the nested ``build`` span) and the
Morton sort and LBVH build of ``core/dispatch.py``, timed by the
program's tracer in sync mode. The ``build`` span alone leaves out the
densebox index's LBVH, which ``dispatch.plan`` builds after it."""


def read(run):
    spans = [e for e in run.spans if e["name"] == "plan"]
    if not spans or not run.calls:
        return None
    return sum(e["dur"] for e in spans) / 1e6 / len(run.calls)
