"""sweeps_per_call: label sweeps per call, fused first pass included, as
the program's result reports them (``DBSCANResult.n_sweeps``)."""


def read(run):
    if not run.calls:
        return None
    return sum(c.n_sweeps for c in run.calls) / len(run.calls)
