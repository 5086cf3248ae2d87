"""chip_smoke.py's phases at tiny sizes on CPU, and its refusal to run
anywhere but on a TPU.

The phases are the script's own functions, so a wrong path, argument or
check shows up here before a chip run is spent on it.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu_backend(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out


@pytest.mark.parametrize("name,n,eps,min_pts,backend", [
    ("blobs", 1000, 0.05, 8, "tiled"),
    ("portotaxi_like", 2048, 0.03, 10, None),
    ("hacc_like", 2048, 0.08, 5, None),
])
def test_branch_phase(smoke, name, n, eps, min_pts, backend):
    got = smoke.check_branch(name, n, eps, min_pts)
    if backend is not None:
        assert got == backend
    else:
        assert got in ("fdbscan", "fdbscan-densebox")


@pytest.mark.parametrize("name,eps,min_pts", [("portotaxi_like", 0.01, 50),
                                              ("hacc_like", 0.03, 5)])
def test_real_size_phase(smoke, capsys, name, eps, min_pts):
    smoke.check_real_size(name, 4096, eps, min_pts)
    out = capsys.readouterr().out
    # the one exact tree backend auto did not choose; auto's own is the
    # same cached plan and is not run twice
    assert out.count("component-identical to auto") == 1


def test_stream_phase(smoke):
    smoke.check_stream("portotaxi_like", 2048, 0.03, 10)


def test_sharded_phase_on_four_host_devices():
    code = textwrap.dedent("""
    import os, sys
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    sys.path.insert(0, {repo!r})
    import chip_smoke
    chip_smoke.check_sharded('hacc_like', 2048, 0.03, 5, ndev=4)
    """).format(repo=REPO)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "labels bit-identical" in r.stdout
    rows = [line for line in r.stdout.splitlines() if "shard_rows" in line]
    assert rows and "512" in rows[0]


def test_last_line_is_the_device_record(smoke, monkeypatch, capsys):
    # main()'s last line on a (pretended) chip: phases stubbed, so only the
    # device check and the record's shape are exercised here
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(smoke.jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(smoke, "use_compile_cache", lambda: "cache")
    for phase in ("check_branch", "check_tile_kernel_compiled",
                  "check_real_size", "check_stream"):
        monkeypatch.setattr(smoke, phase, lambda *a, **k: "tiled")
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
