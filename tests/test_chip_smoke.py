"""chip_smoke.py on the CPU.

The script itself must refuse to run without a TPU; its phases — the same
functions the chip run calls — are driven here at a tiny size (kernels on
the XLA path, as ``kernel_impl="auto"`` resolves off-TPU), so a broken
phase or a failed answer check shows up before any chip time is spent.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(tmp_path):
    r = subprocess.run([sys.executable, str(SMOKE)], cwd=tmp_path,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""            # refused before any work


def test_one_chip_phases(smoke, tmp_path, capsys):
    idx = tmp_path / "idx"
    smoke.run(3000, 1, idx, 2000, 300)
    out = capsys.readouterr().out
    for line in ("host build:", "index saved", "random phase mix:",
                 "positive phase mix:", "frontend:", "wavefront build:"):
        assert line in out
    assert "differ from the host DFS" in out
    # a rerun on the same artifact loads instead of building
    smoke.host_build_saved(smoke.make_graph(3000), smoke.graph_meta(3000),
                           idx)
    assert "build skipped" in capsys.readouterr().out


def test_rebuilds_for_another_graph(smoke, tmp_path, capsys):
    idx = tmp_path / "idx"
    smoke.host_build_saved(smoke.make_graph(500), smoke.graph_meta(500), idx)
    smoke.host_build_saved(smoke.make_graph(600), smoke.graph_meta(600), idx)
    out = capsys.readouterr().out
    assert out.count("host build:") == 2 and "build skipped" not in out


def test_four_device_phases(tmp_path):
    """--chips 4's placements on four virtual CPU devices."""
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r}); "
            f"sys.path.insert(0, {str(ROOT / 'src')!r}); "
            "from pathlib import Path; import chip_smoke; "
            f"chip_smoke.run(3000, 4, Path({str(tmp_path / 'idx')!r}), "
            "1000, 200)")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    for placement in ("replicated", "sharded"):
        assert f"{placement} random sample: 0 of 200" in r.stdout
        assert f"{placement} positive sample: 0 of 200" in r.stdout
    assert r.stdout.count("slab shard on") == 8

