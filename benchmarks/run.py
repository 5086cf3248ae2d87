"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run``          — smoke sizes (CI-friendly)
``python -m benchmarks.run --full``   — paper-scale sizes (n=16384 etc.)
``python -m benchmarks.run --check``  — regression gate: re-measure the
    *deterministic* work counters (traversal loop trips, sharded distance
    evaluations, streaming repair/compaction work on the mixed
    insert/delete/window trace) and fail if any regresses more than
    ``CHECK_THRESHOLD``x
    against the committed ``BENCH_*.json`` trajectory files. Absolute
    wall-clock numbers are never gated (CI machines drift); counters
    cannot. The one wall-clock quantity that IS gated is the
    pallas-vs-reference end-to-end *ratio* from ``BENCH_traversal.json``:
    both engines are re-measured interleaved on the same machine through
    the obs layer (bench_phase_cost.wallclock), so the ratio is drift-free
    even though each absolute time is not. It is gated as a HARD limit —
    the pallas engine must win (ratio <= WALL_RATIO_LIMIT) on *every*
    scenario, and the committed ratios must themselves be <= 1.0; the
    old ratio-of-ratios comparison let a committed 2-of-3 loss pass
    indefinitely. The gate also writes the tuner's chosen per-scenario
    configs to ``tuner_configs.json`` for the CI artifact upload.

Output: ``name,us_per_call,derived`` CSV lines.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_THRESHOLD = 1.5
# Hard per-scenario ceiling for the pallas-vs-reference end-to-end wall
# ratio: the kernel must win (<= 1.0) with a small drift tolerance for
# shared-machine noise. A committed ratio above 1.0 fails outright.
WALL_RATIO_LIMIT = 1.05


def _check_ratio(failures: list, name: str, got: float, committed: float,
                 floor: float = 1.0):
    """``floor`` guards the divide: 1.0 for integer work counters (a
    committed 0 means "got must stay ~0"), a tiny epsilon for float
    ratios where flooring at 1 would silently mask regressions below 1."""
    ratio = got / max(committed, floor)
    status = "FAIL" if ratio > CHECK_THRESHOLD else "ok"
    print(f"check,{name},{committed},{got},{ratio:.3f},{status}")
    if ratio > CHECK_THRESHOLD:
        failures.append(f"{name}: {committed} -> {got} "
                        f"({ratio:.2f}x > {CHECK_THRESHOLD}x)")


def check() -> None:
    """The ``--check`` gate over the committed BENCH_*.json counters."""
    failures: list[str] = []
    print("check,name,committed,measured,ratio,status")

    trav_path = os.path.join(REPO, "BENCH_traversal.json")
    if os.path.exists(trav_path):
        with open(trav_path) as f:
            committed = json.load(f)
        from . import bench_phase_cost
        n = next(iter(committed.values()))["n"]
        # re-measure exactly the committed scenario set; a committed
        # scenario the suite no longer knows is a gate failure, not a
        # silent skip
        got = bench_phase_cost.counters(n=n, only=set(committed))
        for dset in committed:
            if dset not in got:
                failures.append(f"traversal/{dset}: committed in "
                                "BENCH_traversal.json but no longer "
                                "measurable (scenario renamed/removed?)")
                print(f"check,traversal/{dset},-,-,-,FAIL (unmeasured)")
                continue
            rec, ref = got[dset], committed[dset]
            if (rec["eps"], rec["minpts"]) != (ref["eps"], ref["minpts"]):
                failures.append(
                    f"traversal/{dset}: workload drifted (committed "
                    f"eps={ref['eps']}/minpts={ref['minpts']}, bench now "
                    f"uses eps={rec['eps']}/minpts={rec['minpts']}) — "
                    "regenerate BENCH_traversal.json")
                continue
            for key in ("loop_iters_before_fusion",
                        "loop_iters_after_fusion",
                        "pallas_loop_iters", "pallas_evals"):
                if key not in ref:
                    continue  # pre-kernel trajectory file
                _check_ratio(failures, f"traversal/{dset}/{key}",
                             rec[key], ref[key])
            _check_ratio(failures, f"traversal/{dset}/sweep_iters_total",
                         sum(rec["sweep_iters_per_sweep"]),
                         sum(ref["sweep_iters_per_sweep"]))
        # pallas-vs-reference wall clock, gated as a HARD limit: both
        # engines are re-measured interleaved (obs-layer histograms, same
        # machine) so the *ratio* is drift-free, and the pallas engine
        # must win every scenario. The committed ratio must itself be
        # <= 1.0; anything above means BENCH_traversal.json predates the
        # autotuner and must be regenerated (``make bench-tune``).
        wall_dsets = {d for d in committed
                      if "wall_ratio_pallas_over_ref" in committed[d]
                      and d in got}
        if wall_dsets:
            for dset in sorted(wall_dsets):
                ref_ratio = committed[dset]["wall_ratio_pallas_over_ref"]
                if ref_ratio > 1.0:
                    print(f"check,traversal/{dset}/wall_ratio_committed,"
                          f"{ref_ratio},-,-,FAIL")
                    failures.append(
                        f"traversal/{dset}: committed wall ratio "
                        f"{ref_ratio} > 1.0 — regenerate "
                        "BENCH_traversal.json with `make bench-tune`")
            wall = bench_phase_cost.wallclock(n=n, only=wall_dsets)
            tuner_configs = {}
            for dset in sorted(wall_dsets):
                got_ratio = wall[dset]["wall_ratio_pallas_over_ref"]
                status = "FAIL" if got_ratio > WALL_RATIO_LIMIT else "ok"
                print(f"check,traversal/{dset}/wall_ratio_pallas_over_ref,"
                      f"{WALL_RATIO_LIMIT},{got_ratio},"
                      f"{got_ratio / WALL_RATIO_LIMIT:.3f},{status}")
                if got_ratio > WALL_RATIO_LIMIT:
                    failures.append(
                        f"traversal/{dset}: pallas engine lost the wall "
                        f"race (ratio {got_ratio:.3f} > hard limit "
                        f"{WALL_RATIO_LIMIT})")
                tuner_configs[dset] = wall[dset].get("tuned_config")
            # artifact for CI: which configs the tuner actually chose
            with open(os.path.join(REPO, "tuner_configs.json"), "w") as f:
                json.dump(tuner_configs, f, indent=2, sort_keys=True)
                f.write("\n")
    else:
        print("check,traversal,-,-,-,skipped (no BENCH_traversal.json)")

    dist_path = os.path.join(REPO, "BENCH_distributed.json")
    if os.path.exists(dist_path):
        with open(dist_path) as f:
            committed = json.load(f)
        from . import bench_distributed
        # gate on the smallest committed size only: counters are exact at
        # any n, and CI shouldn't pay for the 16k+ collective programs
        key = min(committed, key=lambda k: committed[k]["n"])
        n = committed[key]["n"]
        if (committed[key]["eps"], committed[key]["minpts"]) != \
                (bench_distributed.EPS, bench_distributed.MINPTS):
            failures.append(
                f"distributed/n{n}: workload drifted (committed "
                f"eps={committed[key]['eps']}/minpts="
                f"{committed[key]['minpts']}, bench now uses "
                f"eps={bench_distributed.EPS}/minpts="
                f"{bench_distributed.MINPTS}) — regenerate "
                "BENCH_distributed.json")
        else:
            got = bench_distributed.measure_evals((n,))
            _check_ratio(failures, f"distributed/n{n}/tree_distance_evals",
                         got[f"n{n}"]["tree_distance_evals"],
                         committed[key]["tree_distance_evals"])
    else:
        print("check,distributed,-,-,-,skipped (no BENCH_distributed.json)")

    stream_path = os.path.join(REPO, "BENCH_stream.json")
    if os.path.exists(stream_path):
        with open(stream_path) as f:
            committed = json.load(f)
        if "mixed" not in committed:
            print("check,stream,-,-,-,skipped (pre-mixed BENCH_stream.json"
                  " — regenerate)")
        else:
            from . import bench_stream
            ref = committed["mixed"]
            drift = {k: ref[k] for k in ("n", "window", "batch", "seed",
                                         "buffer_max", "delete_every",
                                         "delete_frac")}
            if (drift != {k: bench_stream.MIXED[k] for k in drift}
                    or (ref["eps"], ref["minpts"]) != (bench_stream.EPS,
                                                       bench_stream.MINPTS)):
                failures.append(
                    "stream/mixed: workload drifted (committed "
                    f"{drift} eps={ref['eps']}/minpts={ref['minpts']}) — "
                    "regenerate BENCH_stream.json")
            else:
                # the dynamic trace is fully deterministic: the repair /
                # compaction work counters are exact, so gate them (and
                # the exact survivor counts) — never the wall clock
                got = bench_stream.mixed_workload()
                for key in ("repair_sweeps", "n_compactions", "n_merges",
                            "n_active", "n_tombstoned"):
                    _check_ratio(failures, f"stream/mixed/{key}",
                                 got[key], ref[key])
    else:
        print("check,stream,-,-,-,skipped (no BENCH_stream.json)")

    serve_path = os.path.join(REPO, "BENCH_serve.json")
    if os.path.exists(serve_path):
        with open(serve_path) as f:
            committed = json.load(f)
        from . import bench_serve
        ref = committed["snapshot_vs_handle_check"]
        if (ref["eps"], ref["minpts"], ref["n"]) != \
                (bench_serve.EPS, bench_serve.MINPTS, bench_serve.CHECK_N):
            failures.append(
                "serve/snapshot_vs_handle_check: workload drifted "
                f"(committed n={ref['n']} eps={ref['eps']}/minpts="
                f"{ref['minpts']}) — regenerate BENCH_serve.json")
        else:
            # steady-state jit stability is exact: zero new programs, gated
            # as an equality (committed 0 + threshold still pins got <= 1)
            rec = bench_serve.recompile_steadystate()
            _check_ratio(failures, "serve/recompiles/new_programs_steady",
                         rec["new_programs_steady"],
                         committed["recompiles"]["new_programs_steady"])
            # snapshot-vs-handle speedup: both engines re-measured
            # interleaved, gated as an inverted ratio-of-ratios (bigger
            # speedup is better, so a drop shows up as ratio > threshold)
            got = bench_serve.snapshot_vs_handle(n=ref["n"])
            _check_ratio(failures, "serve/snapshot_vs_handle/speedup",
                         1.0 / got["speedup"], 1.0 / ref["speedup"],
                         floor=1e-9)
    else:
        print("check,serve,-,-,-,skipped (no BENCH_serve.json)")

    if failures:
        print("# REGRESSION GATE FAILED:", file=sys.stderr)
        for f_ in failures:
            print(f"#   {f_}", file=sys.stderr)
        sys.exit(1)
    print("# regression gate passed")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="regression-gate the deterministic counters "
                         "against the committed BENCH_*.json files")
    ap.add_argument("--only", default=None,
                    help="comma list: minpts,eps,scaling,cosmo,memory,"
                         "phase,kernels,dist_evals,distributed,stream,"
                         "serve")
    args = ap.parse_args()
    from repro.launch import use_compile_cache
    use_compile_cache()
    if args.check:
        check()
        return
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    from . import (bench_cosmo, bench_distance_evals, bench_distributed,
                   bench_eps, bench_kernels, bench_memory, bench_minpts,
                   bench_phase_cost, bench_scaling, bench_serve,
                   bench_stream)
    suites = {
        "minpts": lambda: bench_minpts.run(n=16384 if args.full else 2048,
                                           quick=quick),
        "eps": lambda: bench_eps.run(n=16384 if args.full else 2048,
                                     quick=quick),
        "scaling": lambda: bench_scaling.run(
            sizes=(4096, 16384, 65536, 131072) if args.full
            else (1024, 2048), quick=quick),
        "cosmo": lambda: bench_cosmo.run(n=36000 if args.full else 4000,
                                         quick=quick),
        "memory": lambda: bench_memory.run(quick=quick),
        # the phase suite measures the paper's headline <=2x bound; below
        # n=4096 the subsampled scenarios leave the density regime the
        # claim is about, so quick mode keeps the larger size
        "phase": lambda: bench_phase_cost.run(n=16384 if args.full else 4096,
                                              quick=quick,
                                              json_out="BENCH_traversal.json"),
        "kernels": lambda: bench_kernels.run(quick=quick),
        "dist_evals": lambda: bench_distance_evals.run(
            n=16384 if args.full else 2048, quick=quick),
        # ring vs sharded tree (8 virtual devices, subprocess); 16384 stays
        # in quick mode — it is the acceptance size for the >=10x evals
        # claim recorded in BENCH_distributed.json
        "distributed": lambda: bench_distributed.run(
            sizes=(4096, 16384, 65536) if args.full else (4096, 16384),
            quick=quick),
        # streaming insert vs full recluster; 32768 is the acceptance size
        # for the >=5x wall-clock claim recorded in BENCH_stream.json
        "stream": lambda: bench_stream.run(n=32768 if args.full else 4096,
                                           quick=quick),
        # the serving plane: snapshot-vs-handle speedup (>= 50x), open-loop
        # multi-tenant aggregate throughput, and the zero-recompile witness
        "serve": lambda: bench_serve.run(quick=quick),
    }
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# suite: {name}", flush=True)
        fn()
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
