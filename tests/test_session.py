"""Unified multi-camera session API (ISSUE 4): Query spec, fused
camera-array ingest parity vs independent single-camera runs (oracle
bit-for-bit, kernel interpret-mode within tolerance, state carried
across chunk boundaries), vectorized admission, and SessionState
checkpoint round-trips."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import RED, YELLOW, Query, open_session
from repro.core.session import ADMIT, SHED_ADMISSION, SessionState, ShedSession
from repro.core.shedder import LoadShedder
from repro.core.threshold import UtilityCDF
from repro.core.control import ControlLoop
from repro.core.utility import UtilityModel
from repro.kernels.hsv_features.kernel import ingest_batch
from repro.kernels.hsv_features.ops import ingest_pipeline
from repro.kernels.hsv_features.ref import ingest_batch_ref

HR2 = (tuple(RED.hue_ranges), tuple(YELLOW.hue_ranges))


def _toy_model(rng, colors, op="or"):
    nc = len(colors)
    M = rng.uniform(0, 1, (nc, 8, 8)).astype(np.float32)
    return UtilityModel(tuple(colors), M, np.zeros_like(M),
                        rng.uniform(0.3, 1.0, nc).astype(np.float32), op)


# ---------------------------------------------------------------------------
# Query spec
# ---------------------------------------------------------------------------

def test_query_resolves_names_and_ops():
    q = Query.any_of("red", YELLOW, latency_bound=0.5, fps=30.0)
    assert q.colors == (RED, YELLOW) and q.op == "or"
    assert Query.all_of("red", "yellow").op == "and"
    assert Query.single("red").op == "single"
    # multi-color "single" silently promotes to OR (Eq. 15 default)
    assert Query(colors=(RED, YELLOW)).op == "or"
    with pytest.raises(ValueError):
        Query(colors=(RED,), op="xor")
    with pytest.raises(KeyError):
        Query.single("mauve")


# ---------------------------------------------------------------------------
# Multi-camera ingest parity (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_multicam_oracle_matches_independent_runs_bitwise(rng):
    """Batched (C, T, N, 3) oracle == C independent single-camera runs,
    bit-for-bit, including carried (bg, gain) state across batches."""
    C, T, n = 3, 5, 700
    rgb = jnp.asarray(rng.uniform(0, 255, (2 * T, C, n, 3)), jnp.float32)
    rgb = jnp.moveaxis(rgb, 1, 0)                       # (C, 2T, n, 3)
    bg0 = jnp.asarray(rng.uniform(0, 255, (C, n)), jnp.float32)
    gain0 = jnp.asarray(rng.uniform(0.8, 1.2, (C,)), jnp.float32)
    M = jnp.asarray(rng.uniform(0, 1, (2, 64)), jnp.float32)
    norm = jnp.asarray([0.5, 0.8], jnp.float32)

    # batched, chunked in two with carried state lanes
    outs = []
    b, g = bg0, gain0
    for i in (0, T):
        *out, b, g = ingest_batch_ref(rgb[:, i:i + T], b, g, M, norm, HR2)
        outs.append(out)
    for c in range(C):
        bc, gc = bg0[c], gain0[c]
        for chunk, i in zip(outs, (0, T)):
            *single, bc, gc = ingest_batch_ref(rgb[c, i:i + T], bc, gc,
                                               M, norm, HR2)
            for name, a, s in zip(("counts", "totals", "fgtot", "util"),
                                  chunk, single):
                np.testing.assert_array_equal(
                    np.asarray(a)[c], np.asarray(s), err_msg=f"cam{c} {name}")
        np.testing.assert_array_equal(np.asarray(b)[c], np.asarray(bc))
        np.testing.assert_array_equal(np.asarray(g)[c], np.asarray(gc))


def test_multicam_kernel_interpret_matches_independent_runs(rng):
    """Batched camera-array kernel (interpret mode) == C independent
    single-camera kernel runs within float tolerance, state carried."""
    C, T, n = 2, 3, 500
    rgb = jnp.asarray(rng.uniform(0, 255, (C, 2 * T, n, 3)), jnp.float32)
    bg0 = jnp.asarray(rng.uniform(0, 255, (C, n)), jnp.float32)
    gain0 = jnp.asarray([1.0, 1.1], jnp.float32)
    M = jnp.asarray(rng.uniform(0, 1, (2, 64)), jnp.float32)
    norm = jnp.asarray([0.5, 0.8], jnp.float32)

    outs = []
    b, g = bg0, gain0
    for i in (0, T):
        *out, b, g = ingest_batch(rgb[:, i:i + T], b, g, M, norm, HR2,
                                  interpret=True)
        outs.append(out)
    for c in range(C):
        bc, gc = bg0[c], gain0[c]
        for chunk, i in zip(outs, (0, T)):
            *single, bc, gc = ingest_batch(rgb[c, i:i + T], bc, gc, M, norm,
                                           HR2, interpret=True)
            for name, a, s in zip(("counts", "totals", "fgtot", "util"),
                                  chunk, single):
                np.testing.assert_allclose(
                    np.asarray(a)[c], np.asarray(s), atol=1e-4, rtol=1e-5,
                    err_msg=f"cam{c} {name}")
        np.testing.assert_allclose(np.asarray(b)[c], np.asarray(bc),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(g)[c], np.asarray(gc),
                                   atol=1e-5)


def test_multicam_kernel_matches_oracle(rng):
    """Camera-array kernel (interpret) vs camera-array oracle."""
    C, T, n = 3, 4, 4096 + 33          # non-multiple-of-BLOCK padding edge
    rgb = jnp.asarray(rng.uniform(0, 255, (C, T, n, 3)), jnp.float32)
    bg0 = jnp.asarray(rng.uniform(0, 255, (C, n)), jnp.float32)
    gain0 = jnp.asarray(rng.uniform(0.9, 1.1, (C,)), jnp.float32)
    M = jnp.asarray(rng.uniform(0, 1, (2, 64)), jnp.float32)
    norm = jnp.asarray([0.5, 0.8], jnp.float32)
    k = ingest_batch(rgb, bg0, gain0, M, norm, HR2, interpret=True)
    r = ingest_batch_ref(rgb, bg0, gain0, M, norm, HR2)
    for name, a, b in zip(("counts", "totals", "fgtot", "util", "bg",
                           "gain"), k, r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_session_ingest_matches_single_camera_sessions(impl, rng):
    """C-camera session.ingest (chunked) == C single-camera sessions."""
    C, T = 3, 10
    frames = rng.uniform(0, 255, (C, T, 16, 24, 3)).astype(np.float32)
    model = _toy_model(rng, [RED, YELLOW], "and")
    q = Query.all_of("red", "yellow")
    interp = True if impl == "pallas" else None

    sess = open_session(q, num_cameras=C, model=model, impl=impl,
                        interpret=interp)
    chunks = [sess.ingest(frames[:, i:i + 4]) for i in range(0, T, 4)]
    pf = np.concatenate([c.pf for c in chunks], axis=1)
    util = np.concatenate([c.utility for c in chunks], axis=1)

    for c in range(C):
        s1 = open_session(q, num_cameras=1, model=model, impl=impl,
                          interpret=interp)
        res = [s1.ingest(frames[c, i:i + 4]) for i in range(0, T, 4)]
        pf1 = np.concatenate([r.pf[0] for r in res], axis=0)
        u1 = np.concatenate([r.utility[0] for r in res], axis=0)
        if impl == "jnp":
            np.testing.assert_array_equal(pf[c], pf1)
            np.testing.assert_array_equal(util[c], u1)
        else:
            np.testing.assert_allclose(pf[c], pf1, atol=1e-5)
            np.testing.assert_allclose(util[c], u1, atol=1e-4)


# ---------------------------------------------------------------------------
# Vectorized admission + control parity with the scalar LoadShedder
# ---------------------------------------------------------------------------

def test_admit_matches_scalar_shedder_decisions(rng):
    """Per-camera vectorized admission reproduces the scalar LoadShedder
    admission layer (same CDF history, same control inputs)."""
    hist = rng.uniform(0, 1, 256)
    us = rng.uniform(0, 1, (2, 40))

    sess = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                        num_cameras=2, train_utilities=hist,
                        exact_tick=True)
    sess.report_backend_latency(0.2)                    # ST=5 -> r=0.5... per
    # lane: share = (1/0.2)/2 = 2.5 -> r = 1 - 2.5/10 = 0.75
    sess.tick()
    decisions = sess.admit(us)

    ref = LoadShedder(None, UtilityCDF(hist),
                      ControlLoop(1.0, 10.0), queue_size=8)
    ref.control.report_backend_latency(0.2)
    # emulate the per-camera share of the backend: 2 cameras -> each lane
    # sees half the supported throughput
    r = max(0.0, 1.0 - (ref.control.supported_throughput() / 2) / 10.0)
    ref.threshold = ref.cdf.threshold_for_drop_rate(r)
    for cam in range(2):
        want = us[cam] >= ref.threshold
        got = decisions[cam] != SHED_ADMISSION
        np.testing.assert_array_equal(got, want)


def test_admit_queue_eviction_and_next_frame(rng):
    from repro.core.session import SHED_QUEUE
    sess = open_session(Query.single("red"), num_cameras=2, queue_size=2)
    u = np.array([[0.5, 0.6, 0.9], [0.1, 0.2, 0.3]])
    d = sess.admit(u, items=[["a0", "a1", "a2"], ["b0", "b1", "b2"]])
    # no thresholds yet -> everything clears admission, but the queue
    # (size 2) evicts the worst same-batch frame per camera, which is
    # reported retroactively on the *evicted* frame
    np.testing.assert_array_equal(d, [[SHED_QUEUE, ADMIT, ADMIT],
                                      [SHED_QUEUE, ADMIT, ADMIT]])
    assert sess.stats.dropped_queue == 2    # one eviction per camera
    np.testing.assert_array_equal(sess.per_camera_dropped, [1, 1])
    # transmission pops globally best first
    assert sess.next_frame() == "a2"
    assert sess.next_frame() == "a1"
    assert sess.next_frame() == "b2"
    assert len(sess) == 1


def test_offer_lane_mapping_and_limit():
    sess = open_session(Query.single("red"), num_cameras=2)

    class F:
        def __init__(self, cid):
            self.cam_id = cid

    assert sess.offer(F(42), 0.9) == "queued"       # lane 0
    assert sess.offer(F(7), 0.8) == "queued"        # lane 1
    with pytest.raises(ValueError):
        sess.offer(F(99), 0.5)                      # third distinct id


# ---------------------------------------------------------------------------
# The backend-latency feed: a float32 host fold, uploaded at dispatch
# ---------------------------------------------------------------------------

def _ref_tick(control, procs):
    """The reference's tick with lane ``c`` on its own estimate
    ``procs[c]`` (the reference keeps one shared estimate)."""
    rates = np.empty(len(procs), np.float32)
    for c, p in enumerate(procs):
        control.proc = p
        rates[c] = control.rates()[c]
        control.threshold[c] = control._threshold(c, rates[c])
        control.cap[c] = control.queue_cap()
    for c in range(len(procs)):
        control.queue[c] = control._keep(control.queue[c], control.cap[c])
    return rates


@pytest.mark.parametrize("mode,C", [("device", 4), ("mesh", 4),
                                    ("device", 1), ("mesh", 1)],
                         ids=["device", "mesh", "device1", "mesh1"])
def test_latency_feed_matches_reference_fold_bitwise(mode, C):
    """200 reports, scalar and per camera mixed, with ticking steps
    between them: every lane's estimate is the reference's float32 fold
    bit for bit, both while reports are pending (``expected_proc``) and
    as uploaded (the state), and so are every tick's decisions, rates,
    thresholds and queue caps. ``mesh`` is a one-device camera mesh;
    ``C`` cameras share the backend."""
    from bench import reference as ref
    from repro.core.fleet import fleet_mesh
    T, W, K = 6, 64, 8
    rng = np.random.default_rng(21)
    hist = rng.uniform(0, 1, W).astype(np.float32)
    where = {"mesh": fleet_mesh(1)} if mode == "mesh" else {}
    sess = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                        num_cameras=C, train_utilities=hist,
                        cdf_window=W, queue_size=3, queue_capacity=K,
                        **where)
    cfg = {"cdf_window": W, "queue_capacity": K, "quantile_bins": 256,
           "quantile_range": [0.0, 1.0], "latency_bound_s": 1.0,
           "queue_size": 3}
    control = ref.Control(C, cfg, hist, fps=10.0)
    folds = [ref.Control(1, cfg, hist, fps=10.0) for _ in range(C)]
    items = [[(c, t) for t in range(T)] for c in range(C)]
    for i in range(200):
        # latencies around Eq. 20's budget, repeats (x == q keeps the
        # down rate) and zeros (clamped to min_proc)
        x = float(rng.choice([0.0, 0.05, rng.uniform(0.01, 0.4)]))
        cam = None if rng.random() < 0.4 else int(rng.integers(C))
        sess.report_backend_latency(x, cam=cam)
        for c in (range(C) if cam is None else [cam]):
            folds[c].report_latency(x)
        procs = np.array([f.proc for f in folds], np.float32)
        for c in range(C):
            assert sess.expected_proc(c) == float(procs[c])
        assert sess.expected_proc() == float(procs.max())
        if i % 10 == 9:
            u = rng.uniform(0, 1, (C, T)).astype(np.float32)
            res = sess.step(utilities=u, items=items, tick=True)
            want = control.step(u, items, tick=False)
            rates = _ref_tick(control, procs)
            st = sess.state
            np.testing.assert_array_equal(
                np.asarray(st.proc_q).view(np.uint32), procs.view(np.uint32))
            np.testing.assert_array_equal(res.decisions, want)
            np.testing.assert_array_equal(res.target_drop_rate, rates)
            np.testing.assert_array_equal(np.asarray(st.threshold),
                                          control.threshold)
            np.testing.assert_array_equal(np.asarray(st.queue_cap),
                                          control.cap)


# ---------------------------------------------------------------------------
# Checkpoint round-trip (serve-path state)
# ---------------------------------------------------------------------------

def test_session_state_is_pytree():
    st = SessionState.fresh(3, 10)
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == 23          # incl. queue/churn/floor + s2 lanes
    #                                   + the (C, bins) quantile counts
    st2 = jax.tree_util.tree_map(lambda x: x, st)
    assert isinstance(st2, SessionState)
    assert st2.bg.shape == (3, 10)
    assert st2.q_util.shape == st2.q_seq.shape == (3, 64)


def test_session_checkpoint_roundtrip(tmp_path, rng):
    q = Query.any_of("red", "yellow", latency_bound=1.0, fps=10.0)
    sess = open_session(q, num_cameras=2, frame_shape=(12, 20))
    frames = rng.uniform(0, 255, (2, 6, 12, 20, 3)).astype(np.float32)
    res = sess.ingest(frames)
    sess.fit(res.pf.reshape(-1, 2, 8, 8), rng.random(12) < 0.5)
    res2 = sess.ingest(frames)
    sess.report_backend_latency(0.15)
    sess.report_ingress_fps(24.0)
    sess.tick()
    sess.admit(res2.utility)
    sess.report_backend_latency(0.3, cam=1)     # not yet read by a tick
    sess.checkpoint(tmp_path, step=3)

    fresh = open_session(q, num_cameras=2, frame_shape=(12, 20))
    step, meta = fresh.restore(tmp_path)
    assert step == 3
    assert meta["colors"] == ["red", "yellow"] and meta["num_cameras"] == 2
    assert fresh.expected_proc(cam=1) == sess.expected_proc(cam=1)
    for k, v in sess.state.as_dict().items():
        np.testing.assert_array_equal(v, fresh.state.as_dict()[k],
                                      err_msg=k)
    # the trained model travels with the checkpoint; continued streams
    # score identically from either session
    a, b = sess.ingest(frames), fresh.ingest(frames)
    np.testing.assert_array_equal(a.pf, b.pf)
    np.testing.assert_array_equal(a.utility, b.utility)


def test_session_restore_requires_allocated_lanes(tmp_path, rng):
    q = Query.single("red")
    sess = open_session(q, num_cameras=1, frame_shape=(8, 8))
    sess.ingest(rng.uniform(0, 255, (1, 2, 8, 8, 3)).astype(np.float32))
    sess.checkpoint(tmp_path, step=1)
    other = open_session(q, num_cameras=1)      # no frame_shape -> (1, 0) bg
    with pytest.raises(ValueError):
        other.restore(tmp_path)


# ---------------------------------------------------------------------------
# ingest_pipeline camera lane (the layer sessions build on)
# ---------------------------------------------------------------------------

def test_ingest_pipeline_camera_lane_shapes(rng):
    rgb = rng.uniform(0, 255, (2, 3, 10, 12, 3)).astype(np.float32)
    pf, hf, util, st = ingest_pipeline(rgb, [RED], impl="jnp")
    assert pf.shape == (2, 3, 1, 8, 8) and hf.shape == (2, 3, 1)
    assert util is None
    assert st.bg.shape == (2, 120) and st.gain.shape == (2,)
    assert st.num_cameras == 2
    # chunk continuation through the camera-lane state
    pf2, _, _, st2 = ingest_pipeline(rgb, [RED], state=st, impl="jnp")
    assert st2.bg.shape == (2, 120)
