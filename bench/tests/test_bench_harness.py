"""The harness end to end at 48x80 on the CPU: the device check, each
driver through ``run_cell`` with the check swapped out, the metric
declarations, and faults planted under the timed path, each of which
has to turn ``correct`` false."""
from __future__ import annotations

import json
import subprocess
import sys

import numpy as np
import pytest

from tiny import ROOT, run_tiny  # noqa: F401  (sets JAX_PLATFORMS, paths)


def test_run_exits_nonzero_without_a_tpu(capsys):
    from bench import run as R
    rc = R.main(["--workload", "detrac24_540p.saturate", "--seed", "1",
                 "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_bare_checkout_fails(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program
    to run: the command exits non-zero and prints no result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "detrac24_540p.saturate", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["detrac24_540p.saturate",
                                      "detrac24_540p.serve"])
def test_driver_runs_and_is_correct(workload):
    res = run_tiny(workload, seconds=4.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    for m in res["metrics"].values():
        assert np.isfinite(m["value"]) and m["value"] > 0
    assert "setup_s" in res["metrics"]


def test_metric_declarations_are_consistent():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    reports = {c: {n for n, m in e2e.items()
                   if "workloads" not in m or c in m["workloads"]}
               for c in cells}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for c in m.get("workloads", cells):
            assert c in cells, (m["name"], c)
            assert m["moves"] in reports[c], (m["name"], c)
        assert (ROOT / "bench" / "layer_metrics" / f"{m['name']}.py").exists()
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2, c
        assert any(c in m.get("workloads", cells) for m in spec["per_layer"])
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for conf in spec["configs"]:
        assert (ROOT / conf["file"]).exists()


# -- faults under the timed path ---------------------------------------------

def _patch_step(monkeypatch, wrap):
    from repro.core.session import ShedSession
    orig = ShedSession.step

    def step(self, frames=None, **kw):
        return wrap(self, orig, frames, kw)
    monkeypatch.setattr(ShedSession, "step", step)


def test_fault_state_left_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(self, orig, frames, kw):
        keep = jax.tree.map(jnp.copy, self.state)
        res = orig(self, frames=frames, **kw)
        self.state = keep
        return res
    _patch_step(monkeypatch, wrap)
    assert not run_tiny("detrac24_540p.saturate")["correct"]


def test_fault_half_the_batch_left_out(monkeypatch):
    def wrap(self, orig, frames, kw):
        frames = np.array(frames)
        half = frames.shape[0] // 2
        frames[half:] = frames[:half]
        return orig(self, frames=frames, **kw)
    _patch_step(monkeypatch, wrap)
    assert not run_tiny("detrac24_540p.saturate")["correct"]


@pytest.mark.parametrize("workload", ["detrac24_540p.saturate",
                                      "detrac24_540p.serve"])
def test_fault_answer_altered(monkeypatch, workload):
    import dataclasses

    def wrap(self, orig, frames, kw):
        res = orig(self, frames=frames, **kw)
        dec = np.array(res.decisions)
        dec[0, 0] = 1 - min(int(dec[0, 0]), 1)
        return dataclasses.replace(res, decisions=dec)
    _patch_step(monkeypatch, wrap)
    assert not run_tiny(workload, seconds=4.0)["correct"]


FLEET = r'''
import dataclasses, sys
sys.path.insert(0, sys.argv[1])
from tiny import run_tiny
import numpy as np
from repro.core import fleet
if sys.argv[2] == "cut":
    orig = fleet.pop_topk
    def local_only(state, *, mesh, axis, k, rows=None):
        C = state.q_util.shape[0]
        rows = np.zeros((C,), bool); rows[: C // 4] = True
        import jax.numpy as jnp
        return orig(state, mesh=mesh, axis=axis, k=k, rows=jnp.asarray(rows))
    fleet.pop_topk = local_only
res = run_tiny("detrac24_540p.saturate", cameras=8, chips=4)
print("CORRECT", res["correct"], res["device"]["count"])
'''


@pytest.mark.parametrize("mode", ["sound", "cut"])
def test_fleet_exchange_between_chips(mode):
    """The saturate driver over a camera mesh of 4 virtual devices, as a
    four-chip configuration runs it: sound, and with the pop's merge
    across chips cut to the first chip's cameras."""
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT}:{ROOT / 'src'}")
    p = subprocess.run([sys.executable, "-c", FLEET,
                        str(ROOT / "bench" / "tests"), mode], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    want = "True" if mode == "sound" else "False"
    assert f"CORRECT {want} 4" in p.stdout, p.stdout[-2000:]
