"""CPU tests of the comparison that decides ``correct``.

At a size a test run holds: the reference agrees with itself and with
the program; the control (the reference in bfloat16 in the program's
place) fails a limit; and a run driven with its timed path broken
underneath reports ``correct`` false, once for each fault a batch cell
can have."""
import io
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import control  # noqa: E402
from benchlib import compare, points, reference, runner  # noqa: E402

SMALL = {"porto2d": dict(n=4096, eps=0.02, min_pts=20),
         "hacc3d-fof": dict(n=4096, eps=0.168 * 4096 ** (-1 / 3),
                            min_pts=2)}


def _config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(SMALL[name])
    return cfg


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_and_reference_agrees_with_itself(name):
    cfg = _config(name)
    pts = points.make_points(cfg, 11)
    ref = reference.dbscan(pts, cfg["eps"], cfg["min_pts"])
    assert ref.core.any() and (ref.comp[ref.core] >= 0).all()
    own = compare.compare(ref, ref.labels(), ref.core)
    assert all(v == 0 for v in own.values())
    ctl = reference.dbscan(pts, cfg["eps"], cfg["min_pts"],
                           control.CONTROL_PRECISION)
    got = compare.compare(ref, ctl.labels(), ctl.core)
    assert any(got[k] > cfg["limits"][k] for k in compare.NUMBERS), got


def test_reference_matches_brute_force():
    cfg = _config("porto2d")
    cfg["n"] = 1500
    pts = points.make_points(cfg, 4).astype(np.float64)
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    adj = d2 <= cfg["eps"] ** 2
    core = adj.sum(1) >= cfg["min_pts"]
    ref = reference.dbscan(pts.astype(np.float32), cfg["eps"],
                           cfg["min_pts"])
    np.testing.assert_array_equal(ref.core, core)
    cc = adj & core[:, None] & core[None, :]
    comp = np.full(len(pts), -1)        # components by breadth-first search
    for seed in np.flatnonzero(core):
        if comp[seed] < 0:
            comp[seed] = seed
            frontier = [seed]
            while frontier:
                nxt = np.flatnonzero(cc[frontier].any(0) & (comp < 0))
                comp[nxt] = seed
                frontier = list(nxt)
    pairs = np.unique(np.stack([comp[core], ref.comp[core]], 1), axis=0)
    assert len(pairs) == len(np.unique(comp[core])) == len(
        np.unique(ref.comp[core]))
    border = ~core & (adj & core[None, :]).any(1)
    assert set(ref.border_pt.tolist()) == set(np.flatnonzero(border))


@pytest.fixture
def small_root(tmp_path, monkeypatch):
    """A checkout whose one cell is porto2d at n = 4096."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench" / "configs" / "porto2d.json").write_text(
        json.dumps(_config("porto2d")))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    # tests share one process: keep JAX's persistent cache as it was
    monkeypatch.setattr(runner, "use_compile_cache", lambda: None)
    return tmp_path


def _run(root, trace=False, workload="porto2d.batch"):
    out, err = io.StringIO(), io.StringIO()
    code = runner.run(workload, 123, 0.5, trace, t_start=0.0,
                      root=root, require_tpu=False, out=out, err=err)
    assert code == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")
    return line


def _altered_label(res):
    labels = np.asarray(res.labels).copy()
    core = np.flatnonzero(np.asarray(res.core_mask))
    labels[core[0]] = labels.max() + 1      # one core point moved out
    return res._replace(labels=labels)


def _flipped_core(res):
    core = np.asarray(res.core_mask).copy()
    core[0] = ~core[0]
    return res._replace(core_mask=core)


def _half_left_out(dbscan):
    def run(points, eps, min_pts, **kw):
        half = len(points) // 2
        res = dbscan(points[:half], eps, min_pts, **kw)
        pad = len(points) - half
        return res._replace(
            labels=np.concatenate([np.asarray(res.labels),
                                   np.full(pad, -1, np.int32)]),
            core_mask=np.concatenate([np.asarray(res.core_mask),
                                      np.zeros(pad, bool)]))
    return run


FAULTS = {
    "label_altered": lambda f: lambda *a, **k: _altered_label(f(*a, **k)),
    "core_flag_altered": lambda f: lambda *a, **k: _flipped_core(f(*a, **k)),
    "half_left_out": _half_left_out,
}


def test_control_readings_separate(small_root):
    got = control.readings("porto2d.batch", [5, 6], [5], root=small_root,
                           require_tpu=False, out=io.StringIO())
    limits = _config("porto2d")["limits"]
    for reading in got["program"]:
        assert all(reading[k] <= limits[k] for k in compare.NUMBERS)
    assert any(got["control"][0][k] > limits[k] for k in compare.NUMBERS)
    # each number is failed by one of the faults planted in the reference
    for k in compare.NUMBERS:
        assert any(f[k] > limits[k] for f in got["fault"]), k


def test_sound_run_is_correct(small_root):
    line = _run(small_root)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"cluster_points_per_s", "setup_s"}


def test_traced_run_reports_per_layer_metrics(small_root):
    line = _run(small_root, trace=True)
    assert line["correct"]
    # off the chip the profiler's trace has no TPU plane: the device
    # metrics are left out, the span and counter metrics are read
    assert set(line["metrics"]) == {"build_s", "compiles_per_call.batch",
                                    "sweeps_per_call", "sweep_s"}
    assert line["metrics"]["sweeps_per_call"]["value"] >= 1
    assert {"busy_s", "window_s"} <= set(line["device"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(small_root, monkeypatch, fault):
    import repro
    monkeypatch.setattr(repro, "dbscan", FAULTS[fault](repro.dbscan))
    line = _run(small_root)
    assert not line["correct"]
    assert line["failed"] == line["attempted"] >= 1
