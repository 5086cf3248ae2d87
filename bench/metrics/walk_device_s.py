"""walk_device_s: device seconds of the traced call spent in the tree
walk's XLA programs: the fused first pass and ``traverse_impl`` (the
sweeps and the border pass), matched by module name in the profiler
trace. Silent when no module matches."""

WALK_MODULES = ("traverse_impl", "fused_first_pass")


def read(run):
    if run.device is None:
        return None
    walk = [s for name, s in run.device.module_s.items()
            if any(w in name for w in WALK_MODULES)]
    return sum(walk) if walk else None
