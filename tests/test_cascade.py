"""Semantic cascade: stage-2 scoring between admission and queue
insertion.

Covers the four acceptance surfaces: (1) a session without ``cascade=``
is bit-identical to the single-stage pipeline (including the algebraic
reduction ``gate_fraction=1.0`` -> stage 2 inert); (2) the stage-2
threshold converges to the conditional quantile of the Eq. 19 rate
split and the combined realized rate tracks the target; (3) cascade
sessions checkpoint/restore exactly (s2 lanes included); (4) the
ingest kernel's foreground-bbox rider matches ``ingest_batch_ref``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.cascade import Cascade, CallableScorer, MLPScorer, fit_scorer
from repro.cascade.scorer import extract_rois, roi_geometry
from repro.core import RED, Query
from repro.core.session import (
    ADMIT,
    SHED_ADMISSION,
    SHED_CASCADE,
    ShedSession,
)
from repro.kernels.hsv_features.kernel import ingest_batch
from repro.kernels.hsv_features.ref import foreground_bbox, ingest_batch_ref

from host_loop_reference import HostLoopCascade

HR1 = (tuple(RED.hue_ranges),)

# camera counts of the cascade sessions (the cascade runs on one device;
# camera sharding does not support it)
CAMERAS = {"device": 2, "device-3cams": 3}


def _sess(C=2, cascade=None, **kw):
    return ShedSession(Query.single(RED, latency_bound=1.0, fps=10.0), C,
                       cascade=cascade, **kw)


def _warm(sess, p=0.2, fps=10.0):
    sess.report_backend_latency(p)
    for c in range(sess.num_cameras):   # per-lane fps (cam=None splits)
        sess.report_ingress_fps(fps, cam=c)
    sess.tick()


def _gate_shed(decisions) -> int:
    """Frames shed by either GATE (not queue-pressure evictions — no
    backend drains the queue in these tests, so SHED_QUEUE reflects
    queue occupancy, not the Eq. 19 rate split)."""
    return int(((decisions == SHED_ADMISSION)
                | (decisions == SHED_CASCADE)).sum())


# ---------------------------------------------------------------------------
# 1. no-cascade bit-parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("placement", ["device", "mesh"])
def test_no_cascade_sessions_are_single_stage(placement, rng):
    """cascade=None leaves every decision identical run-to-run, the s2
    lanes untouched, and the snapshot free of cascade keys, on one
    device and on a one-device camera mesh."""
    runs = []
    for _ in range(2):
        sess = _sess(shard_cameras=placement == "mesh")
        _warm(sess)
        decs = []
        for i in range(12):
            u = rng_from(i).uniform(0, 1, (2, 8)).astype(np.float32)
            decs.append(sess.step(utilities=u, tick=(i % 3 == 0)).decisions)
        runs.append(np.concatenate(decs, axis=1))
        st = sess.state
        assert int(np.asarray(st.s2_len).sum()) == 0
        assert np.all(np.isinf(np.asarray(st.s2_threshold)))
        assert "s2_threshold" not in sess.tick()
    np.testing.assert_array_equal(runs[0], runs[1])


def rng_from(i):
    return np.random.default_rng(1000 + i)


def test_gate_fraction_one_reduces_to_single_stage(rng):
    """r1 = r, r2 = 0: a cascade with the whole rate on stage 1 and the
    color utilities as stage-2 scores makes the SAME decisions as the
    plain single-stage session (stage 2 inert, same queue ordering)."""
    plain = _sess()
    casc = _sess(cascade=Cascade(CallableScorer(lambda f, b: None),
                                 gate_fraction=1.0, window=64))
    _warm(plain)
    _warm(casc)
    a_all, b_all = [], []
    for i in range(15):
        u = rng_from(i).uniform(0, 1, (2, 8)).astype(np.float32)
        tick = i % 2 == 0
        a = plain.step(utilities=u, tick=tick)
        b = casc.step(utilities=u, s2_utilities=u, tick=tick)
        a_all.append(a.decisions)
        b_all.append(b.decisions)
        np.testing.assert_array_equal(a.pushed_seq, b.pushed_seq)
    np.testing.assert_array_equal(np.concatenate(a_all, 1),
                                  np.concatenate(b_all, 1))
    assert casc.stats.dropped_cascade == 0


def test_cascade_rejects_sharding_and_bad_inputs():
    with pytest.raises(ValueError):
        _sess(cascade=Cascade(CallableScorer(lambda f, b: None)),
              shard_cameras=True)
    sess = _sess()
    with pytest.raises(ValueError):
        sess.step(utilities=np.zeros((2, 4), np.float32),
                  s2_utilities=np.zeros((2, 4), np.float32))


# ---------------------------------------------------------------------------
# 2. stage-2 threshold control convergence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C", list(CAMERAS.values()),
                         ids=list(CAMERAS))
def test_stage2_threshold_converges_to_conditional_quantile(C):
    """Uniform [0,1] utilities and s2 scores, p*C*fps = 4 -> combined
    target r = 0.75. With gate_fraction g = 0.5: stage 1 thresholds at
    the 0.375-quantile, stage 2 at the conditional 0.6-quantile of the
    survivors, and the realized combined shed rate tracks 0.75."""
    T = 16
    sess = _sess(C=C,
                 cascade=Cascade(CallableScorer(lambda f, b: None),
                                 gate_fraction=0.5, window=2048))
    _warm(sess, p=0.4 / C)
    rng = np.random.default_rng(7)
    shed = off = 0
    for i in range(60):
        u = rng.uniform(0, 1, (C, T)).astype(np.float32)
        s2 = rng.uniform(0, 1, (C, T)).astype(np.float32)
        res = sess.step(utilities=u, s2_utilities=s2, tick=True)
        if i >= 20:                      # let the rings fill first
            off += res.decisions.size
            shed += _gate_shed(res.decisions)
    st = sess.state
    th1 = np.asarray(st.threshold, np.float32)
    th2 = np.asarray(st.s2_threshold, np.float32)
    # r1 = 0.375 of uniform stage-1 scores; r2 = 0.6 of uniform s2
    np.testing.assert_allclose(th1, 0.375, atol=0.06)
    np.testing.assert_allclose(th2, 0.6, atol=0.08)
    assert abs(shed / off - 0.75) < 0.08
    assert sess.stats.dropped_cascade > 0


@pytest.mark.parametrize("C", list(CAMERAS.values()),
                         ids=list(CAMERAS))
def test_degraded_floor_bounds_combined_rate(C):
    """set_rate_floor applies to the COMBINED rate before the split, so
    both stages together shed at least the floor."""
    T = 16
    sess = _sess(C=C,
                 cascade=Cascade(CallableScorer(lambda f, b: None),
                                 gate_fraction=0.5, window=1024))
    # a lightly loaded backend (p*C*fps = 0.8): target rate would be 0
    # without the floor
    _warm(sess, p=0.08 / C)
    sess.set_rate_floor(0.5)
    rng = np.random.default_rng(11)
    shed = off = 0
    for i in range(50):
        u = rng.uniform(0, 1, (C, T)).astype(np.float32)
        s2 = rng.uniform(0, 1, (C, T)).astype(np.float32)
        res = sess.step(utilities=u, s2_utilities=s2, tick=True)
        if i >= 20:
            off += res.decisions.size
            shed += _gate_shed(res.decisions)
    assert shed / off > 0.40
    assert sess.stats.dropped_admission > 0
    assert sess.stats.dropped_cascade > 0


def test_cascade_matches_host_loop_reference():
    """The jitted cascade phases make the scalar two-stage loop's
    decisions and converge its thresholds, bit for bit (exact sort
    ticks), with latency reports pending at some of the steps."""
    C, T, W2 = 3, 8, 256
    sess = _sess(C=C, exact_tick=True,
                 cascade=Cascade(CallableScorer(lambda f, b: None),
                                 gate_fraction=0.4, window=W2))
    ref = HostLoopCascade(C, gate_fraction=0.4, s2_window=W2)
    _warm(sess, p=0.15)
    ref.report_backend_latency(0.15)
    ref.tick()
    rng = np.random.default_rng(3)
    n_cascade = 0
    for i in range(40):
        u = rng.uniform(0, 1, (C, T)).astype(np.float32)
        s2 = rng.uniform(0, 1, (C, T)).astype(np.float32)
        tick = i % 2 == 1
        if i % 3 == 0:
            lat, cam = 0.1 + 0.01 * (i % 7), (None if i % 2 else i % C)
            sess.report_backend_latency(lat, cam=cam)
            ref.report_backend_latency(lat, cam=cam)
        a = sess.step(utilities=u, s2_utilities=s2, tick=tick)
        want = ref.admit(u, s2)
        n_cascade += int((want == SHED_CASCADE).sum())
        if tick:
            ref.tick()
        np.testing.assert_array_equal(a.decisions, want, err_msg=str(i))
        np.testing.assert_array_equal(
            np.asarray(sess.state.s2_threshold), ref.s2_threshold)
        np.testing.assert_array_equal(np.asarray(sess.state.threshold),
                                      ref.threshold)
    np.testing.assert_array_equal(np.asarray(sess.state.proc_q),
                                  ref.proc_q)
    np.testing.assert_array_equal(np.asarray(sess.state.queue_cap),
                                  ref.queue_cap)
    assert sess.stats.dropped_cascade == n_cascade > 0


# ---------------------------------------------------------------------------
# 3. checkpoint / restore round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C", list(CAMERAS.values()),
                         ids=list(CAMERAS))
def test_cascade_checkpoint_restore_roundtrip(C, tmp_path):
    mk = lambda: _sess(
        C=C,
        cascade=Cascade(CallableScorer(lambda f, b: None),
                        gate_fraction=0.5, window=128))
    live = mk()
    _warm(live, p=0.2)
    rng = np.random.default_rng(5)
    seg1 = [(rng.uniform(0, 1, (C, 8)).astype(np.float32),
             rng.uniform(0, 1, (C, 8)).astype(np.float32))
            for _ in range(10)]
    seg2 = [(rng.uniform(0, 1, (C, 8)).astype(np.float32),
             rng.uniform(0, 1, (C, 8)).astype(np.float32))
            for _ in range(10)]
    for u, s2 in seg1:
        live.step(utilities=u, s2_utilities=s2, tick=True)
    live.checkpoint(tmp_path / "ck", step=1)

    resumed = mk()
    resumed.restore(tmp_path / "ck")
    np.testing.assert_array_equal(
        np.asarray(live.state.s2_buf), np.asarray(resumed.state.s2_buf))
    np.testing.assert_array_equal(
        np.asarray(live.state.s2_threshold),
        np.asarray(resumed.state.s2_threshold))
    for u, s2 in seg2:
        a = live.step(utilities=u, s2_utilities=s2, tick=True)
        b = resumed.step(utilities=u, s2_utilities=s2, tick=True)
        np.testing.assert_array_equal(a.decisions, b.decisions)
        np.testing.assert_array_equal(a.pushed_seq, b.pushed_seq)


def test_mlp_scorer_checkpoint_roundtrip(tmp_path, rng):
    scorer = MLPScorer.init(3, roi_size=8, hidden=4)
    scorer.save(tmp_path / "sc", step=2)
    back = MLPScorer.from_checkpoint(tmp_path / "sc", roi_size=8, hidden=4)
    frames = rng.uniform(0, 255, (5, 24, 32, 3)).astype(np.float32)
    bbox = np.array([[2, 10, 3, 20]] * 5, np.int32)
    np.testing.assert_array_equal(scorer.score(frames, bbox),
                                  back.score(frames, bbox))


def test_fit_scorer_learns_synthetic_labels(tmp_path):
    from repro.data.synthetic import generate_scenario
    scs = [generate_scenario(s, num_frames=40, height=32, width=48,
                             target_colors=("red",),
                             color_mix={"red": 1.0}, vehicle_rate=0.08)
           for s in range(2)]
    scorer, metrics = fit_scorer(scs, [RED], op="or", roi_size=8, hidden=8,
                                 steps=60, seed=0,
                                 checkpoint_dir=tmp_path / "fit")
    assert metrics["examples"] == 80
    assert metrics["loss_final"] < metrics["loss_first"]
    back = MLPScorer.from_checkpoint(tmp_path / "fit", roi_size=8, hidden=8)
    fr = scs[0].frames_rgb().astype(np.float32)[:4]
    bb = np.full((4, 4), -1, np.int32)
    np.testing.assert_array_equal(scorer.score(fr, bb), back.score(fr, bb))


# ---------------------------------------------------------------------------
# 4. foreground-bbox: kernel vs reference
# ---------------------------------------------------------------------------

def _bbox_args(rng, T, H, W, nc=1):
    rgb = rng.uniform(0, 255, (T, H * W, 3)).astype(np.float32)
    bg0 = rng.uniform(0, 255, (H * W,)).astype(np.float32)
    M = np.zeros((nc, 64), np.float32)
    norm = np.ones((nc,), np.float32)
    return rgb, bg0, np.float32(1.0), M, norm


@pytest.mark.parametrize("hw", [(8, 16), (13, 24)])
def test_bbox_kernel_matches_ref(hw, rng):
    H, W = hw
    rgb, bg0, g0, M, norm = _bbox_args(rng, 6, H, W)
    out_k = ingest_batch(rgb, bg0, g0, M, norm, HR1, interpret=True,
                         width=W)
    out_r = ingest_batch_ref(rgb, bg0, g0, M, norm, HR1, width=W)
    assert len(out_k) == 7 and len(out_r) == 7
    np.testing.assert_array_equal(np.asarray(out_k[6]),
                                  np.asarray(out_r[6]))


def test_bbox_empty_and_full(rng):
    H, W = 8, 16
    rgb, bg0, g0, M, norm = _bbox_args(rng, 4, H, W)
    # identical frame and background -> no foreground -> all -1
    flat = np.tile(bg0[None, :, None], (4, 1, 3)).astype(np.float32)
    out = ingest_batch_ref(flat, bg0, g0, M, norm, HR1, width=W)
    assert np.all(np.asarray(out[6]) == -1)
    # direct oracle: a known blob
    fgf = np.zeros((2, H * W), bool)
    fgf[0, 2 * W + 3] = fgf[0, 5 * W + 10] = True
    bb = np.asarray(foreground_bbox(fgf, W))
    np.testing.assert_array_equal(bb[0], [2, 5, 3, 10])
    np.testing.assert_array_equal(bb[1], [-1, -1, -1, -1])


def test_ingest_batch_without_width_unchanged(rng):
    rgb, bg0, g0, M, norm = _bbox_args(rng, 3, 8, 16)
    assert len(ingest_batch_ref(rgb, bg0, g0, M, norm, HR1)) == 6
    assert len(ingest_batch(rgb, bg0, g0, M, norm, HR1,
                            interpret=True)) == 6


# ---------------------------------------------------------------------------
# ROI extraction
# ---------------------------------------------------------------------------

def test_extract_rois_shapes_and_fallback(rng):
    frames = rng.uniform(0, 255, (3, 20, 30, 3)).astype(np.float32)
    bboxes = np.array([[0, 19, 0, 29], [5, 5, 7, 7], [-1, -1, -1, -1]],
                      np.int32)
    rois = np.asarray(extract_rois(jnp.asarray(frames),
                                   jnp.asarray(bboxes), 4))
    assert rois.shape == (3, 4, 4, 3)
    # single-pixel bbox -> constant crop
    assert np.all(rois[1] == frames[1, 5, 7])
    # empty bbox falls back to the full frame (same as full-frame bbox
    # on the same frame content)
    full = np.asarray(extract_rois(frames[2:3],
                                   np.array([[0, 19, 0, 29]], np.int32), 4))
    np.testing.assert_array_equal(rois[2], full[0])


def test_roi_geometry_features():
    bb = np.array([[0, 9, 0, 19], [-1, -1, -1, -1]], np.int32)
    geo = np.asarray(roi_geometry(jnp.asarray(bb), 20, 40))
    np.testing.assert_allclose(geo[0], [0.5, 0.5, 0.25, 1.0], atol=1e-6)
    np.testing.assert_array_equal(geo[1], [0.0, 0.0, 0.0, 0.0])
