"""Generate docs/api.md from the live docstrings of the stable surface.

    PYTHONPATH=src python docs/gen_api.py            # (re)write docs/api.md
    PYTHONPATH=src python docs/gen_api.py --check    # CI: fail if stale

The reference is *generated*, never hand-edited: it covers everything in
``repro.__all__`` plus the extension surface DESIGN.md §8 documents (the
visitor contract and the predicate constructors). The CI docs job runs
``--check`` so the committed file can't drift from the docstrings.
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "api.md")

HEADER = """\
# repro API reference

*Generated from docstrings by `docs/gen_api.py` — do not edit by hand
(`PYTHONPATH=src python docs/gen_api.py` regenerates; CI checks it is
current).*

The stable public surface is what `repro.__all__` exports; everything
else (including the `repro.core.*` modules documented at the end for
extension authors) is importable but not part of the stability
contract. See [README.md](../README.md) for the quickstart and
[DESIGN.md](../DESIGN.md) for the architecture.
"""


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"


def _doc(obj) -> str:
    return inspect.getdoc(obj) or "*(no docstring)*"


def _entry(title: str, obj, kind: str = "function") -> str:
    lines = [f"### `{title}`", ""]
    if kind == "function":
        name = title.rsplit(".", 1)[-1]
        lines += ["```python", f"{name}{_signature(obj)}", "```", ""]
    lines += [_doc(obj), ""]
    return "\n".join(lines)


def _method_entries(cls, names, prefix: str) -> list[str]:
    out = []
    for name in names:
        member = inspect.getattr_static(cls, name)
        if isinstance(member, property):
            out.append(_entry(f"{prefix}.{name}", member.fget,
                              kind="property"))
        else:
            out.append(_entry(f"{prefix}.{name}", getattr(cls, name)))
    return out


def generate() -> str:
    import repro
    from repro import obs
    from repro.core import traversal
    from repro.core import neighbors
    from repro.core import tune
    from repro.kernels import traverse as pallas_traverse
    from repro.stream import StreamingDBSCAN, durability
    from repro import serve

    parts = [HEADER]

    parts.append("## Top level (`repro.__all__`)\n")
    parts.append(_entry("repro.dbscan", repro.dbscan))
    parts.append(_entry("repro.plan", repro.plan))
    parts.append(_entry("repro.stream_handle", repro.stream_handle))
    parts.append(_entry("repro.DBSCANResult", repro.DBSCANResult,
                        kind="class"))

    parts.append("## Streaming handle (`repro.stream_handle(...)`)\n")
    parts.append(_entry("StreamingDBSCAN", StreamingDBSCAN, kind="class"))
    parts.extend(_method_entries(
        StreamingDBSCAN,
        ["insert", "query", "snapshot", "merge", "checkpoint", "restore",
         "n_points", "n_main", "n_delta", "points"],
        "StreamingDBSCAN"))

    parts.append("## Durability (`repro.stream.durability`)\n")
    parts.append(_doc(durability) + "\n")
    parts.append(_entry("durability.save_checkpoint",
                        durability.save_checkpoint))
    parts.append(_entry("durability.load_checkpoint",
                        durability.load_checkpoint))
    parts.append(_entry("durability.scan_wal", durability.scan_wal))
    parts.append(_entry("durability.recover", durability.recover))
    parts.append(_entry("durability.WriteAheadLog", durability.WriteAheadLog,
                        kind="class"))
    parts.append(_entry("durability.CheckpointError",
                        durability.CheckpointError, kind="class"))
    parts.append(_entry("durability.WALError", durability.WALError,
                        kind="class"))

    parts.append("## Serving (`repro.serve`)\n")
    parts.append(_doc(serve) + "\n")
    parts.append(_entry("serve.Server", serve.Server, kind="class"))
    parts.extend(_method_entries(
        serve.Server,
        ["restore", "submit_query", "query", "submit_insert", "insert",
         "stats", "shutdown", "tenants"],
        "Server"))
    parts.append(_entry("serve.ServerConfig", serve.ServerConfig,
                        kind="class"))
    parts.append(_entry("serve.QueryReply", serve.QueryReply, kind="class"))
    parts.append(_entry("serve.InsertReply", serve.InsertReply,
                        kind="class"))
    parts.append(_entry("serve.TenantSpec", serve.TenantSpec, kind="class"))
    parts.append(_entry("serve.IndexSnapshot", serve.IndexSnapshot,
                        kind="class"))
    parts.extend(_method_entries(
        serve.IndexSnapshot, ["build", "query", "stats"], "IndexSnapshot"))
    parts.append(_entry("serve.freeze", serve.freeze))
    parts.append(_entry("serve.SnapshotStore", serve.SnapshotStore,
                        kind="class"))
    parts.extend(_method_entries(
        serve.SnapshotStore, ["current", "get", "publish", "version"],
        "SnapshotStore"))
    parts.append(_entry("serve.MicroBatcher", serve.MicroBatcher,
                        kind="class"))
    parts.append(_entry("serve.bucket_size", serve.bucket_size))
    parts.append(_entry("serve.AdmissionController",
                        serve.AdmissionController, kind="class"))
    parts.append(_entry("serve.Overloaded", serve.Overloaded, kind="class"))

    parts.append("## Neighbor queries (`repro.neighbors`)\n")
    parts.append(_doc(neighbors) + "\n")
    parts.append(_entry("repro.neighbors.neighbor_count",
                        neighbors.neighbor_count))
    parts.append(_entry("repro.neighbors.knn", neighbors.knn))
    parts.append(_entry("repro.neighbors.radius_visit",
                        neighbors.radius_visit))
    parts.append(_entry("repro.neighbors.KNNResult", neighbors.KNNResult,
                        kind="class"))

    parts.append("## Observability (`repro.obs`)\n")
    parts.append(_doc(obs) + "\n")
    parts.append(_entry("obs.instrumented", obs.instrumented))
    parts.append(_entry("obs.metrics.Registry", obs.metrics.Registry,
                        kind="class"))
    parts.extend(_method_entries(
        obs.metrics.Registry,
        ["counter", "gauge", "histogram", "get", "snapshot", "write_json"],
        "Registry"))
    parts.append(_entry("obs.metrics.Histogram", obs.metrics.Histogram,
                        kind="class"))
    for fn in (obs.metrics.install, obs.metrics.uninstall,
               obs.metrics.active, obs.metrics.inc, obs.metrics.set_gauge,
               obs.metrics.observe, obs.metrics.validate_snapshot):
        parts.append(_entry(f"obs.metrics.{fn.__name__}", fn))
    parts.append(_entry("obs.trace.Tracer", obs.trace.Tracer, kind="class"))
    for fn in (obs.trace.span, obs.trace.watch, obs.trace.install,
               obs.trace.uninstall, obs.trace.active,
               obs.trace.profiler_session, obs.trace.validate_chrome_trace):
        parts.append(_entry(f"obs.trace.{fn.__name__}", fn))

    parts.append("## Predicates (`repro.core.traversal`)\n")
    parts.append(
        "Predicate batches name the queries a traversal runs and their\n"
        "search geometry (DESIGN.md §8). They are pytrees: array leaves\n"
        "(radii, id vectors, external coordinates) are traced operands,\n"
        "so parameter sweeps reuse one compiled program.\n")
    parts.append(_entry("traversal.intersects", traversal.intersects))
    parts.append(_entry("traversal.sphere", traversal.sphere))
    parts.append(_entry("traversal.nearest", traversal.nearest))

    parts.append("## The visitor contract (`repro.core.traversal`)\n")
    parts.append(_entry("traversal.Visitor", traversal.Visitor,
                        kind="class"))
    parts.extend(_method_entries(
        traversal.Visitor,
        ["init_carry", "visit", "done", "segment_done"],
        "Visitor"))
    for cls in (traversal.CountVisitor, traversal.MinLabelVisitor,
                traversal.CountMinLabelVisitor, traversal.KNNVisitor):
        parts.append(_entry(f"traversal.{cls.__name__}", cls, kind="class"))

    parts.append("## Traversal engines\n")
    parts.append(_entry("repro.core.traversal.traverse",
                        traversal.traverse_impl))
    parts.append(_entry("repro.kernels.traverse.traverse",
                        pallas_traverse.traverse))
    parts.append(_entry("traversal.Trace", traversal.Trace, kind="class"))
    parts.append(_entry("traversal.QueryCtx", traversal.QueryCtx,
                        kind="class"))
    parts.append(_entry("traversal.AccHits", traversal.AccHits,
                        kind="class"))
    parts.append(_entry("traversal.lane_sort_key", traversal.lane_sort_key))

    parts.append("## Autotuning (`repro.core.tune`)\n")
    parts.append(_doc(tune) + "\n")
    parts.append(_entry("tune.PhaseConfig", tune.PhaseConfig, kind="class"))
    parts.append(_entry("tune.TunedConfig", tune.TunedConfig, kind="class"))
    parts.append(_entry("tune.TuneState", tune.TuneState, kind="class"))
    parts.extend(_method_entries(
        tune.TuneState, ["phase", "rank_for", "calibrate", "describe"],
        "TuneState"))
    for fn in (tune.mode, tune.engine_fn, tune.lane_tiles_within_budget,
               tune.stats_key, tune.heuristic, tune.search,
               tune.config_for):
        parts.append(_entry(f"tune.{fn.__name__}", fn))

    return "\n".join(parts).rstrip() + "\n"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) if docs/api.md is stale")
    args = ap.parse_args()
    content = generate()
    if args.check:
        on_disk = open(OUT).read() if os.path.exists(OUT) else ""
        if on_disk != content:
            print("docs/api.md is stale — regenerate with "
                  "`PYTHONPATH=src python docs/gen_api.py`",
                  file=sys.stderr)
            return 1
        print("docs/api.md is current")
        return 0
    with open(OUT, "w") as f:
        f.write(content)
    print(f"wrote {OUT} ({len(content.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
