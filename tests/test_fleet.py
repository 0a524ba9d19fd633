"""Fleet-scale sharded serving (repro.core.fleet): the camera axis of
ShedSession sharded over a device mesh.

Multi-device cases run on 8 fake CPU devices in subprocesses (the
test_distributed pattern, so the main pytest process keeps a single
device); the wiring cases run in-process on a 1-device mesh — the
shard_map program is identical, only the shard count differs.

Covered contracts:
  * shard_map step vs single-device device step: bit parity of
    decisions, thresholds and queue lanes on a seeded trace (utilities
    path, fused frames path, masked offer_batch path);
  * sharded checkpoint -> restore onto a DIFFERENT device count ->
    identical subsequent decisions (checkpoints are mesh-independent
    global arrays);
  * fleet psum aggregates == NumPy reductions over the per-camera
    lanes (exact for counts, float-tolerant for sums: psum adds
    per-shard partials in a different order).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def run_py(code: str, ndev: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={ndev}").strip()
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# In-process wiring tests (1-device mesh: same program, one shard)
# ---------------------------------------------------------------------------

def _sessions(C=12, W=256, seed=0, **fleet_kw):
    from repro.core import Query, open_session
    rng = np.random.default_rng(seed)
    hist = rng.uniform(0, 1, 300).astype(np.float32)
    kw = dict(num_cameras=C, train_utilities=hist, queue_size=4,
              queue_capacity=16, cdf_window=W)
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    ref = open_session(q, **kw)
    fl = open_session(q, shard_cameras=True, **fleet_kw, **kw)
    return ref, fl, rng


def test_single_shard_parity_in_process():
    """A 1-device camera mesh must reproduce the unsharded device step
    bit-for-bit (trace: mixed no-tick and tick steps)."""
    ref, fl, rng = _sessions()
    for s in range(6):
        lat = float(rng.uniform(0.7, 2.0) / 120.0)
        ref.report_backend_latency(lat)
        fl.report_backend_latency(lat)
        u = rng.uniform(0, 1, (12, 8)).astype(np.float32)
        tick = s % 2 == 1
        r1 = ref.step(utilities=u, tick=tick)
        r2 = fl.step(utilities=u, tick=tick)
        np.testing.assert_array_equal(r1.decisions, r2.decisions)
        np.testing.assert_array_equal(np.asarray(ref.state.threshold),
                                      np.asarray(fl.state.threshold))
        np.testing.assert_array_equal(np.asarray(ref.state.q_seq),
                                      np.asarray(fl.state.q_seq))
        np.testing.assert_array_equal(np.asarray(ref.state.q_util),
                                      np.asarray(fl.state.q_util))


def test_offer_batch_and_pop_parity_in_process():
    """The masked (offer_batch) fleet path and cross-shard pop agree
    with the unsharded device session."""
    ref, fl, rng = _sessions()
    items = list(range(9))
    us = rng.uniform(0, 1, 9).tolist()
    cams = [0, 1, 1, 2, 5, 5, 5, 11, 0]
    c1 = ref.offer_batch(items, us, cams=cams)
    c2 = fl.offer_batch(items, us, cams=cams)
    assert c1 == c2
    for _ in range(4):
        assert ref.next_frame() == fl.next_frame()


@pytest.mark.parametrize("sharded", [False, True])
def test_host_serve_is_rejected(sharded):
    """The NumPy serve path is gone: serve="host" (and any value but
    None or "device") raises, with or without camera sharding."""
    from repro.core import Query, open_session
    for serve in ("host", "numpy"):
        with pytest.raises(ValueError, match="NumPy serve path was removed"):
            open_session(Query.single("red"), num_cameras=4,
                         shard_cameras=sharded, serve=serve)


def test_serve_none_and_device_open_the_same_session():
    """serve=None and serve="device" are the one control plane: the
    same decisions, pops and state on the same trace."""
    from repro.core import Query, open_session
    rng = np.random.default_rng(4)
    hist = rng.uniform(0, 1, 64).astype(np.float32)
    a, b = (open_session(Query.single("red", fps=10.0), num_cameras=3,
                         train_utilities=hist, cdf_window=64, serve=serve)
            for serve in (None, "device"))
    for i in range(4):
        u = rng.uniform(0, 1, (3, 5)).astype(np.float32)
        for s in (a, b):
            s.report_backend_latency(0.03 * (i + 1))
        np.testing.assert_array_equal(a.step(utilities=u).decisions,
                                      b.step(utilities=u).decisions)
        assert a.next_frames(2) == b.next_frames(2)
    for k, v in a.state.as_dict().items():
        np.testing.assert_array_equal(v, b.state.as_dict()[k], err_msg=k)


def test_indivisible_camera_count_rejected():
    import jax
    from repro.core import fleet
    if len(jax.devices()) != 1:
        pytest.skip("needs the main process's single device")
    mesh = fleet.fleet_mesh(1)
    # 1 divides everything; build a fake 3-wide requirement via rules
    assert fleet.camera_axis(mesh, 5) == "camera"
    from jax.sharding import Mesh
    with pytest.raises(ValueError, match="no axis divides"):
        # a mesh whose only axis has size 1 but whose name is not in the
        # camera rules can never carry the camera dim
        fleet.camera_axis(Mesh(np.array(jax.devices()[:1]), ("model",)), 5)


def test_report_backend_latency_per_camera_lanes():
    """Scalar call broadcasts; cam= call updates one lane with the same
    asymmetric EWMA. The lanes reach the state at the next program that
    reads them (a tick here)."""
    from repro.core import Query, open_session
    s = open_session(Query.single("red", fps=10.0), num_cameras=3)
    s.report_backend_latency(0.2)
    s.tick()
    np.testing.assert_allclose(np.asarray(s.state.proc_q), 0.2)
    assert s.expected_proc() == pytest.approx(0.2)
    s.report_backend_latency(0.4, cam=1)     # up-move: alpha_up = 0.6
    s.tick()
    p = np.asarray(s.state.proc_q)
    assert p[0] == pytest.approx(0.2) and p[2] == pytest.approx(0.2)
    assert p[1] == pytest.approx(0.2 + 0.6 * 0.2)
    assert s.expected_proc(cam=1) == pytest.approx(0.32)
    assert s.expected_proc() == pytest.approx(0.32)    # worst lane
    # first per-camera report lands raw (proc_seen gating)
    s2 = open_session(Query.single("red", fps=10.0), num_cameras=2)
    s2.report_backend_latency(0.5, cam=0)
    s2.tick()
    p = np.asarray(s2.state.proc_q)
    assert p[0] == pytest.approx(0.5) and p[1] == 0.0
    assert bool(np.asarray(s2.state.proc_seen)[0])
    assert not bool(np.asarray(s2.state.proc_seen)[1])


# ---------------------------------------------------------------------------
# 8-device subprocess tests
# ---------------------------------------------------------------------------

def test_sharded_step_bit_parity_8dev():
    """(a) shard_map step over 8 devices == single-device step, bitwise,
    on a seeded utilities trace and on the fused frames path."""
    out = run_py(r"""
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core import Query, open_session

rng = np.random.default_rng(0)
C, T, W = 16, 8, 256
hist = rng.uniform(0, 1, 300).astype(np.float32)
q = Query.single("red", latency_bound=1.0, fps=10.0)
kw = dict(num_cameras=C, train_utilities=hist, queue_size=4,
          queue_capacity=16, cdf_window=W)
ref = open_session(q, **kw)
fl = open_session(q, shard_cameras=True, fleet_aggregate=True, **kw)
assert fl.mesh.shape["camera"] == 8
for s in range(6):
    lat = float(rng.uniform(0.7, 2.0) / (C * 10.0))
    ref.report_backend_latency(lat)
    fl.report_backend_latency(lat)
    u = rng.uniform(0, 1, (C, T)).astype(np.float32)
    r1 = ref.step(utilities=u, tick=True)
    r2 = fl.step(utilities=u, tick=True)
    assert np.array_equal(r1.decisions, r2.decisions), s
    assert np.array_equal(r1.pushed_seq, r2.pushed_seq), s
    assert np.array_equal(np.asarray(ref.state.threshold),
                          np.asarray(fl.state.threshold)), s
    assert np.array_equal(np.asarray(ref.state.q_seq),
                          np.asarray(fl.state.q_seq)), s
    assert np.array_equal(np.asarray(ref.state.cdf_buf),
                          np.asarray(fl.state.cdf_buf)), s

# fused frames path: ingest kernel inside shard_map, carried bg lanes
from repro.data.synthetic import generate_dataset
from repro.data.pipeline import scenario_records
from repro.core.colors import COLORS
scs = list(generate_dataset(range(2), num_frames=30, height=24, width=32))
recs = [r for i, s in enumerate(scs)
        for r in scenario_records(s, i, [COLORS["red"]], fps=10.0)]
pfs = np.stack([r.pf for r in recs])
labels = np.array([r.label for r in recs])
ref2 = open_session(q, num_cameras=8, frame_shape=(24, 32))
model = ref2.fit(pfs, labels)
fl2 = open_session(q, num_cameras=8, shard_cameras=True, model=model,
                   frame_shape=(24, 32))
fl2.seed_cdf(np.asarray(ref2.state.cdf_buf[0, :int(ref2.state.cdf_len[0])]))
frames = rng.uniform(0, 255, (8, 4, 24, 32, 3)).astype(np.float32)
for s in range(3):
    ref2.report_backend_latency(0.02)
    fl2.report_backend_latency(0.02)
    r1 = ref2.step(frames=frames, tick=True)
    r2 = fl2.step(frames=frames, tick=True)
    assert np.array_equal(r1.decisions, r2.decisions), s
    assert np.array_equal(np.asarray(ref2.state.bg),
                          np.asarray(fl2.state.bg)), s
    assert np.array_equal(np.asarray(ref2.state.gain),
                          np.asarray(fl2.state.gain)), s
print("PARITY-OK")
""")
    assert "PARITY-OK" in out


def test_sharded_checkpoint_elastic_restore():
    """(b) checkpoint a session sharded over 8 devices, restore onto a
    2-device mesh AND an unsharded device session; identical lanes and
    identical subsequent decisions."""
    out = run_py(r"""
import numpy as np, jax, tempfile
from repro.core import Query, fleet, open_session

rng = np.random.default_rng(1)
C, T, W = 16, 8, 256
hist = rng.uniform(0, 1, 300).astype(np.float32)
q = Query.single("red", latency_bound=1.0, fps=10.0)
kw = dict(num_cameras=C, train_utilities=hist, queue_size=4,
          queue_capacity=16, cdf_window=W)
fl8 = open_session(q, shard_cameras=True, **kw)
fl8.report_backend_latency(0.015)
for _ in range(4):
    fl8.step(utilities=rng.uniform(0, 1, (C, T)).astype(np.float32),
             tick=True)
d = tempfile.mkdtemp()
fl8.checkpoint(d, step=7)

fl2 = open_session(q, mesh=fleet.fleet_mesh(2), **kw)
step, meta = fl2.restore(d)
assert step == 7 and meta["num_cameras"] == C
dev = open_session(q, **kw)
dev.restore(d)
for k, v in fl8.state.as_dict().items():
    assert np.array_equal(v, np.asarray(getattr(fl2.state, k))), k
    assert np.array_equal(v, np.asarray(getattr(dev.state, k))), k
assert len(fl2.state.threshold.sharding.device_set) == 2

u = rng.uniform(0, 1, (C, T)).astype(np.float32)
r8 = fl8.step(utilities=u, tick=True)
r2 = fl2.step(utilities=u, tick=True)
rd = dev.step(utilities=u, tick=True)
assert np.array_equal(r8.decisions, r2.decisions)
assert np.array_equal(r8.decisions, rd.decisions)
assert np.array_equal(np.asarray(fl8.state.threshold),
                      np.asarray(fl2.state.threshold))
print("ELASTIC-OK")
""")
    assert "ELASTIC-OK" in out


def test_fleet_psum_aggregates_match_numpy():
    """(c) the one collective: psum aggregates == NumPy reductions over
    the gathered per-camera lanes."""
    out = run_py(r"""
import numpy as np, jax
from repro.core import Query, open_session
from repro.core.session import ADMIT

rng = np.random.default_rng(2)
C, T, W = 24, 8, 256
hist = rng.uniform(0, 1, 300).astype(np.float32)
fl = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                  num_cameras=C, train_utilities=hist, queue_size=4,
                  queue_capacity=16, cdf_window=W, shard_cameras=True,
                  fleet_aggregate=True)
fl.report_backend_latency(0.012)
u = rng.uniform(0, 1, (C, T)).astype(np.float32)
res = fl.step(utilities=u, tick=True)
st = fl.state
agg = fl.last_fleet_stats
assert agg["offered"] == int((res.decisions >= 0).sum())
assert agg["admitted"] == int((res.decisions == ADMIT).sum())
assert agg["shed"] == int((res.decisions > ADMIT).sum())
assert agg["queue_depth"] == int((np.asarray(st.q_seq) >= 0).sum())
assert agg["cdf_fill"] == int(np.asarray(st.cdf_len).sum())
np.testing.assert_allclose(agg["proc_q_mean"],
                           np.asarray(st.proc_q).mean(), rtol=1e-6)
th = np.asarray(st.threshold)
np.testing.assert_allclose(agg["threshold_mean"],
                           th[np.isfinite(th)].mean(), rtol=1e-6)
standalone = fl.fleet_stats()
assert standalone["queue_depth"] == agg["queue_depth"]
np.testing.assert_allclose(standalone["proc_q_mean"],
                           agg["proc_q_mean"], rtol=1e-6)
print("AGG-OK")
""")
    assert "AGG-OK" in out


# ---------------------------------------------------------------------------
# Sharded staging of the frames window (4 fake devices, one subprocess for
# every case below)
# ---------------------------------------------------------------------------

STAGING_CASES = [(dtype, bg) for dtype in ("uint8", "float32")
                 for bg in ("fresh", "carried")]


@pytest.fixture(scope="module")
def staged_runs():
    """Step a fleet session over 4 devices and a one-device session
    through the same windows, once for each staging case; the child
    prints one line per case that passed."""
    return run_py(r"""
import numpy as np, jax
from repro.core import Query, RED, fleet, open_session, train_utility_model
from repro.kernels.hsv_features.ops import IngestState

assert len(jax.devices()) == 4
C, T, H, W, STEPS = 8, 4, 24, 40, 3
rng = np.random.default_rng(0)
pfs = rng.random((40, 1, 8, 8)).astype(np.float32)
model = train_utility_model(pfs, rng.random(40) < 0.5, [RED])
hist = rng.uniform(0, 1, 64).astype(np.float32)
wins = [rng.integers(0, 256, (C, T, H, W, 3), dtype=np.uint8)
        for _ in range(STEPS)]
carried = IngestState(
    bg=rng.uniform(0, 255, (C, H * W)).astype(np.float32),
    gain=rng.uniform(0.8, 1.2, C).astype(np.float32))

staged = []
serve_step = fleet.serve_step

def spy(state, frames, *a, **k):
    # no array holding every camera's frames may live on one device
    for x in jax.live_arrays():
        if (x.shape[:2] == (C, T) and x.ndim >= 4
                and len(x.sharding.device_set) == 1):
            raise AssertionError(f"whole window on one device: {x.shape}")
    staged.append(frames)
    return serve_step(state, frames, *a, **k)

fleet.serve_step = spy

def session(bg, **kw):
    s = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                     num_cameras=C, model=model, train_utilities=hist,
                     queue_size=3, cdf_window=64, impl="jnp",
                     frame_shape=(H, W) if bg == "carried" else None, **kw)
    if bg == "carried":
        s.set_ingest_state(carried)
    return s

def same_state(a, b, where):
    for name, x in a.state.as_dict().items():
        assert np.array_equal(x, b.state.as_dict()[name]), (where, name)

for dtype in ("uint8", "float32"):
    for bg in ("fresh", "carried"):
        mesh = fleet.fleet_mesh(4)
        fl, one = session(bg, mesh=mesh), session(bg)
        want = fleet.frame_sharding(mesh)
        staged.clear()
        shed = 0
        for s, win in enumerate(wins):
            win = win.astype(dtype)
            for sess in (fl, one):
                sess.report_backend_latency(2.0 / (C * 10.0))
            r1 = fl.step(frames=win, tick=True)
            r2 = one.step(frames=win, tick=True)
            assert np.array_equal(r1.decisions, r2.decisions), s
            assert np.array_equal(r1.target_drop_rate, r2.target_drop_rate)
            shed += int((r1.decisions != 0).sum())
            same_state(fl, one, f"step {s}")
            x = staged[-1]
            assert x.dtype == win.dtype and x.shape == win.shape
            assert x.sharding.is_equivalent_to(want, x.ndim), x.sharding
            devs = []
            for sh in x.addressable_shards:
                i = list(mesh.devices.flat).index(sh.device)
                assert sh.index[0] == slice(2 * i, 2 * i + 2), sh.index
                assert np.array_equal(np.asarray(sh.data),
                                      win[2 * i:2 * i + 2])
                devs.append(i)
            assert sorted(devs) == [0, 1, 2, 3]
            p1, p2 = fl.next_frames(C), one.next_frames(C)
            assert p1 == p2 and p1, s
            same_state(fl, one, f"pop {s}")
        assert shed > 0 and len(staged) == STEPS
        print("STAGED-OK", dtype, bg)
""", ndev=4)


@pytest.mark.parametrize("dtype,background", STAGING_CASES,
                         ids=[f"{d}-{b}" for d, b in STAGING_CASES])
def test_fleet_stages_each_camera_on_its_own_device(staged_runs, dtype,
                                                    background):
    """A fleet ``step(frames=...)`` puts each camera's frames on the
    device that holds its lanes, in the camera's dtype, with no window
    gathered on one device; decisions, pops and the whole state after
    every step equal the one-device session's, bit for bit, from a
    fresh and from a carried background."""
    assert f"STAGED-OK {dtype} {background}" in staged_runs
