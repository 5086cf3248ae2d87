"""sweep_lanes_per_call: lanes walked per call by the label sweeps: the
sum of the ``lanes`` attribute (the walk's padded query count) of the
program's ``sweep`` spans, over the window's calls.

Reported with the other span metrics of the sweep loop, only beside a
device trace of the same run."""


def read(run):
    lanes = [e["args"]["lanes"] for e in run.spans
             if e["name"] == "sweep" and "lanes" in e["args"]]
    if not lanes or not run.calls or run.device is None:
        return None
    return sum(lanes) / len(run.calls)
