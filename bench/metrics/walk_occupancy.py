"""walk_occupancy: percent of the tree walk's issued lane-trips that a
live lane took: 100 * the sum of ``live_lane_trips`` over the sum of
``lane_trips``, over the program's ``traverse``, ``sweep`` and ``border``
spans of the window's calls. A lane-trip is one lane's share of one
while-loop trip of the vmapped walk (``core/traversal.py``): every lane
of the batch is issued one, and it is live while that lane still walks.

Silent where the spans carry no lane-trips (a program that does not
count them). Reported with the other span metrics of the walk, only
beside a device trace of the same run."""

WALK_SPANS = ("traverse", "sweep", "border")


def read(run):
    spans = [e["args"] for e in run.spans
             if e["name"] in WALK_SPANS and "lane_trips" in e["args"]]
    issued = sum(a["lane_trips"] for a in spans)
    if not issued or not run.calls or run.device is None:
        return None
    return 100.0 * sum(a["live_lane_trips"] for a in spans) / issued
