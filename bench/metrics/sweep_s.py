"""sweep_s: seconds per call inside the program's ``sweep`` spans (the
host-driven hook/jump sweeps of ``core/fdbscan.py``), timed by the
program's tracer in sync mode."""


def read(run):
    spans = [e for e in run.spans if e["name"] == "sweep"]
    if not spans or not run.calls:
        return None
    return sum(e["dur"] for e in spans) / 1e6 / len(run.calls)
