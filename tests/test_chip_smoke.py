"""``chip_smoke.py``'s phases at 48x80 on the CPU: the same code the chip
runs at 720p, with the kernel in interpret mode and the fleet on four
fake CPU devices. A rehearsal of the smoke, not a chip check."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMALL = dict(height=48, width=80)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_parity_phase(smoke, capsys):
    """Both kernel builds (plain and bbox) match the oracle over two
    chained batches."""
    smoke.parity(cams=2, frames=4, **SMALL)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if '"phase": "parity"' in ln]
    assert len(lines) == 4
    assert sum('"bbox": true' in ln for ln in lines) == 2


def test_serve_phase(smoke, tmp_path, monkeypatch):
    """Warm-up on the virtual clock, then both wall-clock passes serve."""
    from repro.launch import serve as launcher
    # tests keep the persistent compile cache off
    monkeypatch.setattr(launcher, "enable_compile_cache", lambda: None)
    smoke.serve(cams=2, frames=16, out_dir=tmp_path, **SMALL)
    assert sorted(p.name for p in tmp_path.glob("*.json")) == [
        "serve_wall_mock.json", "serve_wall_real.json",
        "serve_warmup_mock.json"]


def test_fleet_phase():
    """The sharded session on four fake devices matches the unsharded
    one, bit for bit (run in a child so this process keeps one device)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import chip_smoke as s; "
            "s.fleet(chips=4, cams=8, frames=4, height=48, width=80)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert '"pops_equal": true' in out.stdout
    assert '"devices_per_state_leaf": [4]' in out.stdout
