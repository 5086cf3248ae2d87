"""frontier_s: seconds per call inside the program's ``frontier`` spans:
the sweep loop's host work in ``core/fdbscan.py``, before the first
sweep (stage ``setup``) and after each sweep (stage ``next``: fetching
the change flags, picking the next walk's lanes and uploading them).

Read only beside a device trace of the same run: off the chip the span
times XLA's CPU backend, which is no measurement of the chip."""


def read(run):
    spans = [e for e in run.spans if e["name"] == "frontier"]
    if not spans or not run.calls or run.device is None:
        return None
    return sum(e["dur"] for e in spans) / 1e6 / len(run.calls)
