"""The camera's uint8 frames on the session's device frames path.

A session's frames step hands the frames to the device in the
camera's dtype and converts them to float32 there, in the ingest's
first relayout (the Pallas kernel's planar tiles, the oracle's HSV
conversion). Every
uint8 value is exact in float32, so a session fed uint8 frames must
score, decide, queue and pop bit for bit like one fed the same frames
as float32: with and without a carried background, with a tick every
step, for the jnp oracle, the Pallas kernel (interpret mode) and the
camera-sharded fleet. The host-scored paths (the cascade and
``ingest``) keep converting on the host.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.cascade import Cascade, CallableScorer
from repro.core import Query, RED, open_session, train_utility_model
from repro.core import session as session_mod

REPO = Path(__file__).resolve().parent.parent
C, T, H, W, STEPS = 3, 4, 24, 40, 3


def _model():
    rng = np.random.default_rng(0)
    pfs = rng.random((40, 1, 8, 8)).astype(np.float32)
    return train_utility_model(pfs, rng.random(40) < 0.5, [RED])


def _session(C=C, **kw):
    rng = np.random.default_rng(0)
    return open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                        num_cameras=C, model=_model(),
                        train_utilities=rng.uniform(0, 1, 64)
                        .astype(np.float32),
                        queue_size=3, cdf_window=64, **kw)


def _windows(steps=STEPS, C=C, T=T, H=H, W=W):
    rng = np.random.default_rng(1)
    return [rng.integers(0, 256, (C, T, H, W, 3), dtype=np.uint8)
            for _ in range(steps)]


def _assert_same_state(a, b, where):
    for f in dataclasses.fields(a.state):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, f.name)),
            np.asarray(getattr(b.state, f.name)),
            err_msg=f"{where}: state.{f.name}")


def _assert_same_run(make, windows):
    """Step a uint8-fed and a float32-fed session through ``windows``
    (a latency report and a tick each step) and compare every output,
    the whole state after each step, and the pops at the end. The
    reported latency sets Eq. 19's drop rate to about a half."""
    a, b = make(), make()
    latency = 2.0 / (a.num_cameras * 10.0)
    shed = 0
    for s, win in enumerate(windows):
        for sess in (a, b):
            sess.report_backend_latency(latency)
        r1 = a.step(frames=win, tick=True)
        r2 = b.step(frames=win.astype(np.float32), tick=True)
        np.testing.assert_array_equal(r1.decisions, r2.decisions,
                                      err_msg=f"step {s}")
        np.testing.assert_array_equal(r1.pushed_seq, r2.pushed_seq)
        np.testing.assert_array_equal(r1.target_drop_rate,
                                      r2.target_drop_rate)
        for e1, e2 in zip(r1.evicted, r2.evicted):
            np.testing.assert_array_equal(e1, e2)
        _assert_same_state(a, b, f"step {s}")
        assert bool(np.asarray(a.state.bg_valid))
        shed += int((r1.decisions != session_mod.ADMIT).sum())
    assert shed > 0, "no frame was shed, so no shed decision was compared"
    p1, p2 = a.next_frames(2 * a.num_cameras), b.next_frames(
        2 * b.num_cameras)
    assert p1 == p2 and len(p1) > 0
    _assert_same_state(a, b, "after the pops")


@pytest.mark.parametrize("frame_shape", [None, (H, W)],
                         ids=["sized-by-first-window", "preallocated"])
def test_device_step_uint8_matches_float32_jnp(frame_shape):
    _assert_same_run(lambda: _session(impl="jnp",
                                      frame_shape=frame_shape),
                     _windows())


def test_device_step_uint8_matches_float32_pallas_interpret():
    _assert_same_run(lambda: _session(C=2, impl="pallas",
                                      interpret=True),
                     _windows(steps=2, C=2, T=2, H=16, W=32))


def test_fleet_step_uint8_matches_float32_one_device_mesh():
    """The shard_map program on a one-device camera mesh (the same
    program as on four chips, one shard)."""
    _assert_same_run(lambda: _session(shard_cameras=True, impl="jnp"),
                     _windows())


def test_fleet_step_uint8_matches_float32_on_four_devices():
    """Four fake CPU devices, in a child so this process keeps one: the
    uint8-fed fleet session equals the float32-fed unsharded one."""
    code = r"""
import jax, numpy as np
assert len(jax.devices()) == 4
import test_uint8_frames as t
from repro.core.fleet import fleet_mesh
wins = t._windows(C=8)
fl = t._session(C=8, mesh=fleet_mesh(4), impl="jnp")
ref = t._session(C=8, impl="jnp")
for s, win in enumerate(wins):
    for sess in (fl, ref):
        sess.report_backend_latency(2.0 / 80.0)
    r1 = fl.step(frames=win, tick=True)
    r2 = ref.step(frames=win.astype(np.float32), tick=True)
    assert np.array_equal(r1.decisions, r2.decisions), s
    t._assert_same_state(fl, ref, f"step {s}")
assert fl.next_frames(16) == ref.next_frames(16)
print("UINT8-FLEET-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"),
                                         str(REPO / "tests")])
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "UINT8-FLEET-OK" in out.stdout


@pytest.mark.parametrize("dtype", [np.int32, np.float16])
def test_device_step_other_dtypes_match_float32_jnp(dtype):
    """Integer and narrow float frames cross as they come and score as
    their float32 values do."""
    a, b = _session(impl="jnp"), _session(impl="jnp")
    for s, win in enumerate(_windows(steps=2)):
        r1 = a.step(frames=win.astype(dtype), tick=True)
        r2 = b.step(frames=win.astype(np.float32), tick=True)
        np.testing.assert_array_equal(r1.decisions, r2.decisions)
        _assert_same_state(a, b, f"step {s}")


def test_device_step_hands_the_camera_dtype_to_the_device(monkeypatch):
    """Frames cross in the dtype they come in; float64 becomes float32
    in the hand-off itself, as JAX holds no 64-bit arrays here."""
    seen = []
    flatten = session_mod._flatten_frames

    def spy(frames):
        seen.append(frames.dtype)
        return flatten(frames)

    monkeypatch.setattr(session_mod, "_flatten_frames", spy)
    s = _session(impl="jnp")
    win = _windows(steps=1)[0]
    for frames in (win, win.astype(np.float64), win.astype(np.float32)):
        s.step(frames=frames, tick=True)
    assert [str(d) for d in seen] == ["uint8", "float32", "float32"]


@pytest.mark.parametrize("path", ["cascade", "ingest"])
def test_host_scored_paths_still_convert_on_the_host(monkeypatch, path):
    """The cascade step and ``ingest()`` score float32 frames, as before:
    uint8 and float32 inputs give the same results, and the ingest and
    the stage-2 scorer only ever see float32."""
    seen = []
    pipeline = session_mod.ingest_pipeline

    def spy(rgb, *a, **k):
        seen.append(("ingest", rgb.dtype))
        return pipeline(rgb, *a, **k)

    monkeypatch.setattr(session_mod, "ingest_pipeline", spy)

    def score(frames, bboxes):
        seen.append(("scorer", frames.dtype))
        return frames.reshape(len(frames), -1).mean(axis=1) / 255.0

    if path == "cascade":
        _assert_same_run(lambda: _session(
            impl="jnp",
            cascade=Cascade(CallableScorer(score), gate_fraction=0.5)),
            _windows(steps=2))
        assert ("scorer", np.dtype(np.float32)) in seen
    else:
        a, b = _session(impl="jnp"), _session(impl="jnp")
        for s, win in enumerate(_windows(steps=2)):
            r1, r2 = a.ingest(win), b.ingest(win.astype(np.float32))
            np.testing.assert_array_equal(r1.pf, r2.pf)
            np.testing.assert_array_equal(r1.utility, r2.utility)
            _assert_same_state(a, b, f"ingest {s}")
    assert seen and all(str(d) == "float32" for _, d in seen), path
