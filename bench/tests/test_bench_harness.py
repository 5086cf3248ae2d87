"""CPU tests of the benchmark harness: discovery by name, the rules on
BENCHMARK.json, the generators, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import points, spec  # noqa: E402


def _copy_benchmark(dst: Path) -> dict:
    """BENCHMARK.json and the benchmark's directory, as a checkout of the
    benchmark alone would hold them."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return json.loads((dst / "BENCHMARK.json").read_text())


def test_committed_benchmark_is_valid():
    bench = spec.load(ROOT)
    assert {w["name"] for w in bench["workloads"]} == {
        "porto2d.batch", "hacc3d-fof.batch"}
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"], ROOT))


def test_names_and_units_use_allowed_characters():
    bench = spec.load(ROOT)
    names = [c["name"] for c in bench["configs"]]
    names += [w[k] for w in bench["workloads"]
              for k in ("name", "config", "traffic")]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for name in names:
        assert spec.NAME_RE.match(name), name
        assert all(ch.isascii() for ch in name), name
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]) and len(m["unit"]) <= 16
        assert all(ch.isascii() for ch in m["unit"]), m["unit"]


@pytest.mark.parametrize("bad", [
    ("workloads", 0, "name", "porto 2d"),
    ("per_layer", 0, "unit", "seconds per call"),
    ("per_layer", 0, "unit", "µs"),
    ("end_to_end", 0, "bound", 0.3),
    ("configs", 0, "file", "elsewhere/porto2d.json"),
])
def test_rule_breaks_are_refused(bad):
    bench = spec.load(ROOT)
    group, i, key, value = bad
    bench[group][i][key] = value
    with pytest.raises(spec.SpecError):
        spec.validate(bench, ROOT)


def test_new_config_workload_and_metric_are_found_by_name(tmp_path):
    bench = _copy_benchmark(tmp_path)
    config = json.loads((BENCH / "configs" / "porto2d.json").read_text())
    config.update(name="porto2d-small", n=4096)
    (tmp_path / "bench" / "configs" / "porto2d-small.json").write_text(
        json.dumps(config))
    (tmp_path / "bench" / "traffic" / "plain.json").write_text(json.dumps(
        {"description": "the batch mix under another name"}))
    (tmp_path / "bench" / "metrics" / "calls_in_window.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    bench["configs"].append({
        "name": "porto2d-small", "source": "a test configuration",
        "file": "bench/configs/porto2d-small.json", "reduced": ["n"],
        "why": "found by name"})
    bench["workloads"].append({
        "name": "porto2d-small.plain", "config": "porto2d-small",
        "traffic": "plain", "chips": 1, "why": "found by name"})
    bench["per_layer"].append({
        "name": "calls_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "harness",
        "moves": "cluster_points_per_s",
        "workloads": ["porto2d-small.plain"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = spec.load(tmp_path)
    cell, cfg, traffic = spec.cell(loaded, "porto2d-small.plain", tmp_path)
    assert cfg["n"] == 4096 and traffic["description"].startswith("the batch")
    names = [m["name"] for m in spec.metrics_of(
        loaded, "porto2d-small.plain", trace=True)]
    assert names == ["calls_in_window"]
    read = spec.reader("calls_in_window", tmp_path)
    assert read(type("Run", (), {"calls": [1, 2, 3]})) == 3
    assert len(points.make_points(cfg, 5)) == 4096


@pytest.mark.parametrize("config", ["porto2d", "hacc3d-fof"])
def test_generators_are_deterministic_per_seed_pair(config):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["n"] = 2048
    a = points.make_points(cfg, 2**31 + 7)
    assert a.dtype == np.float32 and a.shape == (2048, cfg["d"])
    np.testing.assert_array_equal(a, points.make_points(cfg, 2**31 + 7))
    b = points.make_points(cfg, 3)
    assert not np.array_equal(a, b)
    # --seed only reorders the configuration's point set
    np.testing.assert_array_equal(np.unique(a, axis=0), np.unique(b, axis=0))
    for key in ("structure_seed", "sample_seed"):
        other = json.loads(json.dumps(cfg))
        other["data"][key] += 1
        assert not np.array_equal(np.unique(a, axis=0), np.unique(
            points.make_points(other, 3), axis=0))


def _cell_command(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/cell.py", "--workload", "porto2d.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cell_exits_nonzero_without_a_tpu():
    proc = _cell_command(ROOT)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_cell_exits_nonzero_with_only_the_benchmark(tmp_path):
    _copy_benchmark(tmp_path)
    proc = _cell_command(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_fof_eps_is_the_linking_length_at_its_n():
    """hacc3d-fof's eps is b = 0.168 of the mean interparticle spacing of
    its n particles in the unit box, so a change of n moves eps too."""
    cfg = json.loads((BENCH / "configs" / "hacc3d-fof.json").read_text())
    assert cfg["eps"] == pytest.approx(0.168 * cfg["n"] ** (-1 / 3),
                                       rel=1e-12)
