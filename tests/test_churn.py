"""Camera churn on a live session: attach/detach lane remapping,
masking of detached lanes, parity with a fresh session on the
surviving cameras, and checkpoint/restore of the lane map + mid-run
resume to bit-identical decisions.
"""
from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np
import pytest

from repro.core import Query, RED, open_session

FPS = 10.0


@dataclass(frozen=True)
class Rec:
    cam_id: str
    frame_idx: int
    t_gen: float = 0.0
    busy: bool = False


def _session(C=2, **kw):
    # no train_utilities: churn parity needs online-only CDFs (a reset
    # lane must equal a never-seeded fresh lane)
    return open_session(Query.single(RED, latency_bound=1.0, fps=FPS),
                        num_cameras=C, **kw)


def _feed(sess, cam_ids, utils):
    """Round-robin one utility stream across cameras; return codes."""
    codes = []
    for i, u in enumerate(utils):
        cam = cam_ids[i % len(cam_ids)]
        codes.append(sess.offer(Rec(cam, i), float(u)))
    return codes


def _snap(sess):
    t = sess.tick()
    return json.dumps({k: t[k] for k in
                       ("target_drop_rate", "threshold", "queue_size",
                        "per_camera")}, sort_keys=True)


# -- detach semantics --------------------------------------------------------

def test_detach_drains_queue_and_masks_lane():
    sess = _session(C=3)
    rng = np.random.default_rng(0)
    _feed(sess, ["a", "b", "c"], rng.random(30))
    depths = sess.queue_depths()
    assert depths[1] > 0
    dropped0 = sess.stats.dropped_queue
    drained = sess.detach_camera("b")
    assert len(drained) == depths[1]
    assert all(r.cam_id == "b" for r in drained)     # real payloads back
    assert sess.queue_depths()[1] == 0
    assert sess.stats.dropped_queue == dropped0 + len(drained)
    assert sess.num_active == 2
    assert np.asarray(sess.state.threshold)[1] == np.inf
    # the mask survives control ticks: lane 1 stays +inf, and the
    # aggregate drop rate is computed over active lanes only
    sess.report_backend_latency(0.05)
    sess.report_ingress_fps(30.0)
    snap = sess.tick()
    assert snap["per_camera"]["threshold"][1] == np.inf
    assert sess.offer(Rec("a", 99), 0.99) in ("queued", "shed_queue")


def test_attach_reuses_freed_lane_with_fresh_state():
    sess = _session(C=2)
    rng = np.random.default_rng(1)
    _feed(sess, ["a", "b"], rng.random(20))
    sess.detach_camera("b")
    lane = sess.attach_camera("c")
    assert lane == 1                       # lowest freed lane reclaimed
    assert sess.num_active == 2
    st = sess.state
    assert np.asarray(st.threshold)[1] == -np.inf    # admit-all again
    assert int(np.asarray(st.cdf_len)[1]) == 0       # history wiped
    assert sess.queue_depths()[1] == 0
    assert sess.offer(Rec("c", 0), 0.5) == "queued"


def test_churn_api_errors():
    sess = _session(C=2)
    sess.lane("a")
    sess.lane("b")
    with pytest.raises(ValueError):
        sess.attach_camera("a")            # duplicate id
    with pytest.raises(ValueError):
        sess.detach_camera("nope")         # unknown id
    with pytest.raises(ValueError):
        sess.lane("c")                     # no free lane
    sess.detach_camera("a")
    assert sess.attach_camera("c") == 0    # freed lane is claimable


# -- parity: detach+attach == fresh session on the survivors -----------------

def test_churned_session_matches_fresh_session_on_survivors():
    """After detaching 'b' and attaching 'c', the session must be
    indistinguishable — decisions, thresholds, pops — from a fresh
    session that only ever saw 'a' (with the same history) and 'c'."""
    rng = np.random.default_rng(7)
    pre = rng.random(40)                   # history seen by a (and b)
    post = rng.random(60)                  # stream seen by a and c

    churned = _session(C=2)
    _feed(churned, ["a", "b"], pre)
    churned.detach_camera("b")
    churned.attach_camera("c")

    fresh = _session(C=2)
    # replicate exactly a's slice of the history (lanes are row-local)
    for i, u in enumerate(pre):
        if i % 2 == 0:
            fresh.offer(Rec("a", i), float(u))
    assert fresh.lane("c") == 1            # same lane as in `churned`

    outs = []
    for sess in (churned, fresh):
        sess.report_backend_latency(0.05)
        sess.report_ingress_fps(30.0)
        snap1 = _snap(sess)
        codes = _feed(sess, ["a", "c"], post)
        snap2 = _snap(sess)
        pops = []
        for _ in range(6):
            item = sess.next_frame()
            pops.append(None if item is None
                        else (item.cam_id, item.frame_idx))
        outs.append((snap1, codes, snap2, pops,
                     sess.queue_depths().tolist()))
    assert outs[0] == outs[1]


# where a session's lanes live: one device, or a one-device camera mesh
PLACEMENTS = {"device": {}, "mesh": {"shard_cameras": True}}


def test_detach_attach_same_camera_equals_fresh_lane():
    for where, placement in PLACEMENTS.items():
        rng = np.random.default_rng(3)
        sess = _session(C=2, **placement)
        _feed(sess, ["a", "b"], rng.random(30))
        sess.report_backend_latency(0.05)
        sess.report_ingress_fps(30.0)
        sess.tick()
        before = np.asarray(sess.state.threshold)[1]
        assert np.isfinite(before)             # b had built real state
        # latency reports still pending when b cycles:
        # lane 0 keeps them, lane 1 starts unseen, as if each report
        # had reached the state at once
        sess.report_backend_latency(0.08, cam=1)
        sess.report_backend_latency(0.07)
        sess.detach_camera("b")
        sess.attach_camera("b")                # same id, cycled
        st = sess.state
        assert np.asarray(st.threshold)[1] == -np.inf
        assert int(np.asarray(st.cdf_len)[1]) == 0
        assert int(np.asarray(st.q_next_seq)[1]) == 0
        assert bool(np.asarray(st.active)[1])
        p0 = np.float32(0.05)
        p0 = p0 + np.float32(0.6) * (np.float32(0.07) - p0)
        assert sess.expected_proc(cam=0) == float(p0), where
        assert sess.expected_proc(cam=1) == 0.0, where
        sess.tick()
        np.testing.assert_array_equal(np.asarray(sess.state.proc_q),
                                      [p0, 0.0], err_msg=where)
        np.testing.assert_array_equal(np.asarray(sess.state.proc_seen),
                                      [True, False], err_msg=where)


# -- checkpoint / restore ----------------------------------------------------

def test_checkpoint_roundtrips_lane_map_and_active_mask(tmp_path):
    sess = _session(C=3)
    rng = np.random.default_rng(5)
    _feed(sess, ["x", "y", "z"], rng.random(30))
    sess.detach_camera("y")
    sess.set_rate_floor(0.25)
    sess.checkpoint(tmp_path / "ckpt", step=4)

    other = _session(C=3)
    step, meta = other.restore(tmp_path / "ckpt")
    assert step == 4
    assert meta["lane_map"] == [["x", 0], ["z", 2]]
    assert other.num_active == 2
    assert not bool(np.asarray(other.state.active)[1])
    assert other.rate_floor == 0.25
    assert other.lane("x") == 0 and other.lane("z") == 2
    assert other.attach_camera("w") == 1   # the freed lane, reactivated
    assert other.num_active == 3


def test_midrun_checkpoint_restore_is_bit_identical(tmp_path):
    """Segment 1 -> checkpoint -> segment 2 must equal restoring the
    checkpoint into a fresh session and replaying segment 2: identical
    admission codes, tick snapshots and state lanes, on one device and
    on a one-device camera mesh. A latency report still pending at the
    checkpoint is in it."""
    rng = np.random.default_rng(11)
    seg1, seg2 = rng.random(40), rng.random(50)

    def segment2(sess):
        sess.report_backend_latency(0.04)
        sess.report_ingress_fps(25.0)
        codes = _feed(sess, ["a", "b"], seg2)
        snap = _snap(sess)
        return codes, snap

    for where, placement in PLACEMENTS.items():
        live = _session(C=2, **placement)
        _feed(live, ["a", "b"], seg1)
        live.report_backend_latency(0.06)
        live.report_ingress_fps(30.0)
        live.tick()
        live.report_backend_latency(0.09, cam=1)
        live.checkpoint(tmp_path / where, step=1)

        resumed = _session(C=2, **placement)
        resumed.restore(tmp_path / where)
        assert resumed.expected_proc(cam=1) == live.expected_proc(cam=1)
        out_live = segment2(live)
        out_resumed = segment2(resumed)

        assert out_live == out_resumed, where
        for leaf in ("threshold", "q_util", "q_seq", "queue_cap",
                     "cdf_len", "cdf_pos", "proc_q", "fps_obs", "active",
                     "rate_floor"):
            a = np.asarray(getattr(live.state, leaf))
            b = np.asarray(getattr(resumed.state, leaf))
            assert np.array_equal(a, b), (where, leaf)
