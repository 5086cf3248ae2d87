#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload porto2d.batch \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

For each seed of ``--seeds`` one call of the cell's timed path (plan,
index build and clustering of the whole point set) is compared with the
float64 reference: the program's readings. For each seed of
``--control-seeds`` the reference itself, computed in bfloat16 (the
precision below the configuration's float32), takes the program's place:
the control's readings, which have to fail a limit. On the same seeds
each fault of :data:`FAULTS` is planted in the reference's own answer:
a number that the control leaves at its lower reading takes its upper
reading from these. One JSON line per reading, then a summary line: the
largest program reading and the smallest control and fault readings of
each number, beside its limit. Needs a TPU, as a run does; the
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

#: the control's precision: the one below the configuration's float32
CONTROL_PRECISION = "bfloat16"


def _label_moved(labels, core, pick):
    """One point of ``pick`` (a mask) moved to a label of its own."""
    labels = labels.copy()
    labels[np.flatnonzero(pick)[0]] = labels.max() + 1
    return labels, core


def _core_flipped(labels, core):
    core = core.copy()
    core[0] = ~core[0]
    return labels, core


#: answers altered where they are produced: (labels, core) -> (labels, core)
FAULTS = {
    "core_label_moved": lambda lab, core: _label_moved(lab, core, core),
    "noncore_label_moved": lambda lab, core: _label_moved(lab, core, ~core),
    "core_flag_flipped": _core_flipped,
}


def readings(workload: str, seeds, control_seeds, *, root=None,
             require_tpu=True, out=sys.stdout) -> dict:
    """Print and return ``{"program": [...], "control": [...]}``, each a
    list of ``{number: reading}``. ``require_tpu=False`` skips the look
    for a chip (tests only)."""
    from benchlib import compare, points, reference, runner, spec
    root = root or spec.ROOT
    bench = spec.load(root)
    cell, config, _ = spec.cell(bench, workload, root)
    runner.use_compile_cache()
    if require_tpu:
        runner.accelerator(cell["chips"])
    eps, min_pts = float(config["eps"]), int(config["min_pts"])
    got = {"program": [], "control": [], "fault": []}
    for seed in dict.fromkeys(list(seeds) + list(control_seeds)):
        pts = points.make_points(config, seed)
        ref = reference.dbscan(pts, eps, min_pts)
        if seed in seeds:
            res, _ = runner._call(pts, eps, min_pts)
            got["program"].append(compare.compare(ref, res.labels,
                                                  res.core_mask))
            print(json.dumps({"kind": "program", "seed": seed,
                              **got["program"][-1]}), file=out, flush=True)
        if seed in control_seeds:
            ctl = reference.dbscan(pts, eps, min_pts,
                                   CONTROL_PRECISION)
            got["control"].append(compare.compare(ref, ctl.labels(),
                                                  ctl.core))
            print(json.dumps({"kind": "control", "seed": seed,
                              **got["control"][-1]}), file=out, flush=True)
            for name, fault in FAULTS.items():
                got["fault"].append(compare.compare(
                    ref, *fault(ref.labels(), ref.core)))
                print(json.dumps({"kind": "fault", "fault": name,
                                  "seed": seed, **got["fault"][-1]}),
                      file=out, flush=True)
    summary = {k: {"program_max": max((g[k] for g in got["program"]),
                                       default=None),
                   "control_min": min((g[k] for g in got["control"]),
                                      default=None),
                   "fault_min": min((g[k] for g in got["fault"]
                                     if g[k] > 0), default=None),
                   "limit": config["limits"][k]}
               for k in compare.NUMBERS}
    print(json.dumps({"workload": workload, "summary": summary}), file=out,
          flush=True)
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    from benchlib import runner
    try:
        readings(args.workload,
                 [int(s) for s in args.seeds.split(",") if s],
                 [int(s) for s in args.control_seeds.split(",") if s])
    except runner.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
