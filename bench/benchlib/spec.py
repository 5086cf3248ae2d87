"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; everything that belongs to one of them, or to one per-layer
metric, lives in a file of its own:

* a configuration: the ``file`` its entry in ``configs`` names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: the reader ``bench/metrics/<name>.py``, whose
  ``read(run)`` returns the metric's value or None (nothing to read).

So a cell, a configuration or a metric is added by adding files and
entries, with no edit to the harness.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

#: the checkout's root: this file is ``<root>/bench/benchlib/spec.py``
ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = "bench"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the benchmark's
    rules."""


def load(root: Path = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` at ``root``, checked by
    :func:`validate`."""
    path = Path(root) / "BENCHMARK.json"
    if path.stat().st_size > 64 * 1024:
        raise SpecError("BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        bench = json.load(f)
    validate(bench, root)
    return bench


def _text(value, what: str) -> None:
    if not (isinstance(value, str) and 1 <= len(value) <= 200
            and "\n" not in value and "\t" not in value):
        raise SpecError(f"{what}: 1 to 200 characters on one line, no tab")


def _name(value, what: str) -> None:
    if not (isinstance(value, str) and NAME_RE.match(value)):
        raise SpecError(f"{what} {value!r} is not a valid name")


def _keys(entry: dict, allowed: set, what: str, optional=()) -> None:
    keys = set(entry)
    if not allowed <= keys or keys - allowed - set(optional):
        raise SpecError(f"{what} {entry.get('name')!r} has keys "
                        f"{sorted(keys)}; wants {sorted(allowed)}")


def validate(bench: dict, root: Path = ROOT) -> None:
    """Raise :class:`SpecError` where ``bench`` breaks the benchmark's
    static rules: keys, names, units, bounds, references between
    entries, and the files the entries name."""
    root = Path(root)
    if set(bench) != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(bench)} != "
                        f"{sorted(TOP_KEYS)}")
    cmd = bench["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise SpecError("command: a list of 1 to 32 strings")
    for word in cmd:
        _text(word, "command word")
    paths = bench["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        raise SpecError("paths: 1 to 16 directories")
    for p in paths:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise SpecError(f"path {p!r} is not a plain relative path")
    rs = bench["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        raise SpecError("run_seconds: a whole number from 1 to 51")

    seen: set = set()

    def unique(name: str, kind: str) -> None:
        if (kind, name) in seen:
            raise SpecError(f"two {kind}s are named {name!r}")
        seen.add((kind, name))

    configs = bench["configs"]
    if not 1 <= len(configs) <= 24:
        raise SpecError("configs: 1 to 24 entries")
    files = set()
    for c in configs:
        _keys(c, CONFIG_KEYS, "config")
        _name(c["name"], "config name")
        unique(c["name"], "config")
        _text(c["source"], f"config {c['name']} source")
        _text(c["why"], f"config {c['name']} why")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            raise SpecError(f"config file {c['file']} is outside paths")
        if c["file"] in files:
            raise SpecError(f"config file {c['file']} is used twice")
        files.add(c["file"])
        if not (root / c["file"]).is_file():
            raise SpecError(f"config file {c['file']} is missing")
        if len(c["reduced"]) > 16:
            raise SpecError(f"config {c['name']}: over 16 reduced keys")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")

    cells = bench["workloads"]
    if not 1 <= len(cells) <= 24:
        raise SpecError("workloads: 1 to 24 cells")
    config_names = {c["name"] for c in configs}
    pairs = set()
    for w in cells:
        _keys(w, WORKLOAD_KEYS, "workload")
        _name(w["name"], "workload name")
        unique(w["name"], "workload")
        _name(w["config"], "workload config")
        _name(w["traffic"], "workload traffic")
        _text(w["why"], f"workload {w['name']} why")
        if w["config"] not in config_names:
            raise SpecError(f"workload {w['name']}: no config "
                            f"{w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"config {w['config']} under traffic "
                            f"{w['traffic']} appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips must be 1 or 4")
        if not traffic_path(w["traffic"], root).is_file():
            raise SpecError(f"workload {w['name']}: no traffic file "
                            f"{traffic_path(w['traffic'], root)}")
    used = {w["config"] for w in cells}
    if used != config_names:
        raise SpecError(f"configs used by no cell: {config_names - used}")
    if sum(w["chips"] == 4 for w in cells) > max(1, len(cells) // 2):
        raise SpecError("over half of the cells ask for 4 chips")

    cell_names = {w["name"] for w in cells}
    e2e = bench["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        raise SpecError("end_to_end: 1 to 16 metrics")
    for m in e2e:
        _keys(m, E2E_KEYS, "end-to-end metric", optional=("workloads",))
        _metric(m, "metric", unique, cell_names)
        if m["source"] not in SOURCES_E2E:
            raise SpecError(f"{m['name']}: an end-to-end source is "
                            f"host_clock or device_trace")
        b = m["bound"]
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
            raise SpecError(f"{m['name']}: bound must lie in [0.01, 0.25]")
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        raise SpecError("end_to_end must hold setup_s")
    layer = bench["per_layer"]
    if not 1 <= len(layer) <= 128:
        raise SpecError("per_layer: 1 to 128 metrics")
    for m in layer:
        _keys(m, LAYER_KEYS, "per-layer metric", optional=("workloads",))
        _metric(m, "metric", unique, cell_names)
        _text(m["layer"], f"{m['name']} layer")
        if m["moves"] not in e2e_names:
            raise SpecError(f"{m['name']} moves unknown {m['moves']!r}")
        if not reader_path(m["name"], root).is_file():
            raise SpecError(f"{m['name']}: no reader "
                            f"{reader_path(m['name'], root)}")


def _metric(m: dict, what: str, unique, cell_names: set) -> None:
    _name(m["name"], what)
    unique(m["name"], "metric")
    if not (isinstance(m["unit"], str) and UNIT_RE.match(m["unit"])):
        raise SpecError(f"{m['name']}: unit {m['unit']!r} is not valid")
    if m["better"] not in ("lower", "higher"):
        raise SpecError(f"{m['name']}: better is lower or higher")
    if m["source"] not in SOURCES:
        raise SpecError(f"{m['name']}: unknown source {m['source']!r}")
    for w in m.get("workloads", ()):
        if w not in cell_names:
            raise SpecError(f"{m['name']}: unknown workload {w!r}")


def traffic_path(traffic: str, root: Path = ROOT) -> Path:
    return Path(root) / BENCH_DIR / "traffic" / f"{traffic}.json"


def reader_path(metric: str, root: Path = ROOT) -> Path:
    return Path(root) / BENCH_DIR / "metrics" / f"{metric}.py"


def cell(bench: dict, workload: str, root: Path = ROOT):
    """``(workload entry, configuration, traffic)`` of the cell named
    ``workload``; the configuration and the traffic read from their
    files."""
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise SpecError(f"no workload named {workload!r}")
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(Path(root) / entry["file"]) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"], root)) as f:
        traffic = json.load(f)
    return w, config, traffic


def metrics_of(bench: dict, workload: str, trace: bool) -> list:
    """The metrics a run of ``workload`` reports: the end-to-end ones
    untraced, the per-layer ones traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", (workload,))]


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of a per-layer metric's reader file."""
    path = reader_path(metric, root)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
