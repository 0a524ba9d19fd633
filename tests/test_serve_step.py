"""Device-resident serve step: array-backed queue lanes vs the heapq
``UtilityQueue`` reference (push/evict/resize/pop_best with FIFO
tiebreaks), lane thresholds vs the scalar ``threshold_from_sorted``
across cdf_len edge cases, fused ``step()`` parity against the scalar
host loop (``host_loop_reference``) on one device and on a one-device
camera mesh, the float32 admission-boundary regression, and simulator
batched arrivals.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import Query, RED, open_session, train_utility_model
from repro.core import shed_queue as sq
from repro.core.session import ADMIT, SHED_ADMISSION, SHED_QUEUE
from repro.core.shed_queue import UtilityQueue
from repro.core.threshold import (
    threshold_from_sorted,
    thresholds_from_lanes_dev,
)

from host_loop_reference import HostLoopShedder

# where a session's lanes live: one device, or a one-device camera mesh
# (the sharded programs, one shard)
PLACEMENTS = {"device": {}, "mesh": {"shard_cameras": True}}


# ---------------------------------------------------------------------------
# Array queue lanes vs the heapq reference
# ---------------------------------------------------------------------------

def _ref_multiset(q: UtilityQueue):
    return sorted((e.utility, e.seq) for e in q._min if not e.dropped)


def _lane_multiset(util, seq, c):
    u, s = np.asarray(util)[c], np.asarray(seq)[c]
    return sorted((float(a), int(b)) for a, b in zip(u[s >= 0], s[s >= 0]))


def _run_mixed_ops(rng, C=3, K=6, T=5, rounds=6, utilities=None):
    """Drive heapq references and device lanes through the same mixed
    op sequence; assert multiset parity vs heapq after every op, and
    that every eviction and pop is the one the reference makes."""
    cap = rng.integers(1, K + 1, C).astype(np.int32)
    refs = [UtilityQueue(int(cap[c])) for c in range(C)]
    du, ds, dn = sq.make_lanes(C, K)
    pool = utilities or [0.1, 0.2, 0.5, 0.5, 0.5, 0.9]

    for rnd in range(rounds):
        kind = int(rng.integers(0, 4))
        if kind == 0:       # batch push
            u = rng.choice(pool, (C, T)).astype(np.float32)
            admit = rng.random((C, T)) < 0.8
            n0 = np.asarray(dn)
            ref_ev = [[] for _ in range(C)]
            for c in range(C):
                for t in range(T):
                    if admit[c, t]:
                        e = refs[c].push(("f", rnd, c, t), float(u[c, t]))
                        if e is not None:
                            ref_ev[c].append(e)
            du, ds, dn, dp, des, deb = sq.push_batch_dev(
                du, ds, dn, jnp.asarray(u), jnp.asarray(admit),
                jnp.asarray(cap))
            # seqs count up from next_seq over the admitted slots
            want = np.where(admit, n0[:, None]
                            + np.cumsum(admit, axis=1) - 1, -1)
            np.testing.assert_array_equal(np.asarray(dp), want)
            des, deb = np.asarray(des), np.asarray(deb)
            for c in range(C):
                assert int((des[c] >= 0).sum()) == len(ref_ev[c])
                # this batch's own evicted frames, by batch column
                assert sorted(deb[c][deb[c] >= 0].tolist()) == sorted(
                    e[3] for e in ref_ev[c] if e[:3] == ("f", rnd, c))
        elif kind == 1:     # single push
            u = rng.choice(pool, C).astype(np.float32)
            do = rng.random(C) < 0.7
            ref_evicted = {}
            for c in range(C):
                if do[c]:
                    ref_evicted[c] = refs[c].push(("s", rnd, c),
                                                  float(u[c]))
            n0 = np.asarray(dn)
            du, ds, dn, dp, des, die = sq.push_one_dev(
                du, ds, dn, jnp.asarray(u), jnp.asarray(do),
                jnp.asarray(cap))
            np.testing.assert_array_equal(np.asarray(dp),
                                          np.where(do, n0, -1))
            des, die = np.asarray(des), np.asarray(die)
            for c in range(C):      # eviction iff the reference evicted
                ev = ref_evicted.get(c)
                assert (des[c] >= 0) == (ev is not None)
                # the incoming frame itself lost iff the reference
                # handed it straight back
                assert bool(die[c]) == (ev == ("s", rnd, c))
        elif kind == 2:     # resize
            cap = rng.integers(1, K + 1, C).astype(np.int32)
            n_ev = [len(refs[c].resize(int(cap[c]))) for c in range(C)]
            du, ds, des = sq.resize_dev(du, ds, jnp.asarray(cap))
            np.testing.assert_array_equal(
                (np.asarray(des) >= 0).sum(axis=1), n_ev)
        else:               # pop best across the array
            bc, bu = -1, -np.inf
            for c, q in enumerate(refs):
                pu = q.peek_best_utility()
                if pu is not None and pu > bu:
                    bc, bu = c, pu
            ref_item = refs[bc].pop_best() if bc >= 0 else None
            du, ds, dcam, dseq = sq.pop_best_dev(du, ds)
            assert (ref_item is None) == (int(dseq) < 0)
            if ref_item is not None:
                assert ref_item[2] == int(dcam)    # same camera

        for c in range(C):
            assert _lane_multiset(du, ds, c) == _ref_multiset(refs[c]), c
        assert not np.any(np.signbit(np.asarray(du)[np.asarray(ds) >= 0]))


def test_queue_lanes_match_heapq_reference(rng):
    for trial in range(30):
        _run_mixed_ops(np.random.default_rng(trial))


@settings(deadline=None, max_examples=25)
@given(st.lists(st.floats(0, 1, allow_nan=False, width=32),
                min_size=2, max_size=12),
       st.integers(0, 2**31 - 1))
def test_queue_lanes_property_parity(pool, seed):
    """Property form: arbitrary float32 utility pools (duplicates and
    boundary values included) keep the lanes in lockstep with heapq."""
    _run_mixed_ops(np.random.default_rng(seed),
                   utilities=[np.float32(x) for x in pool])


SUBNORMAL_POOL = [0.0, 5.6e-45, -0.0, 2.0**-127, 2.0**-126, 0.5]


@pytest.mark.parametrize("seed", range(8))
def test_queue_lanes_subnormal_utilities(seed):
    """Subnormal utilities enter every queue as 0.0: the device lanes
    (whose float compares flush subnormals to zero) stay bitwise equal
    to the host lanes and in step with the heapq reference."""
    _run_mixed_ops(np.random.default_rng(seed),
                   utilities=[np.float32(x) for x in SUBNORMAL_POOL])


def test_subnormal_utilities_match_host_loop_reference(rng):
    """``step(utilities=...)`` with subnormals: the session makes the
    scalar host loop's decisions and thresholds, and its CDF rings and
    queues hold the same values, with every subnormal stored as +0.0."""
    C, T, W = 3, 8, 16
    sess = open_session(Query.single("red", fps=10.0), num_cameras=C,
                        cdf_window=W, exact_tick=True)
    ref = HostLoopShedder(C, cdf_window=W)
    for s in (sess, ref):
        s.report_backend_latency(0.2)
    for _ in range(5):
        u = rng.choice(np.array(SUBNORMAL_POOL, np.float32), (C, T))
        res = sess.step(utilities=u, tick=True)
        np.testing.assert_array_equal(res.decisions, ref.step(u))
        np.testing.assert_array_equal(np.asarray(sess.state.threshold),
                                      ref.threshold)
        np.testing.assert_array_equal(np.asarray(sess.state.cdf_buf),
                                      ref.cdf.buf)
        for c in range(C):
            assert _lane_multiset(sess.state.q_util, sess.state.q_seq,
                                  c) == _ref_multiset(ref.queues[c])
    assert not np.any(np.signbit(np.asarray(sess.state.cdf_buf)))


def test_queue_fifo_tiebreaks():
    """Equal utilities: eviction removes the OLDEST (min seq); pop_best
    returns the oldest of the best; any-camera pop prefers the lowest
    camera index on utility ties — all matching the heapq reference."""
    C, K = 2, 4
    du, ds, dn = sq.make_lanes(C, K)
    cap = jnp.array([2, 2], jnp.int32)
    u = np.array([[0.5, 0.5, 0.5], [0.7, 0.9, 0.9]], np.float32)
    admit = np.ones((C, 3), bool)
    du, ds, dn, pushed, ev_s, ev_b = sq.push_batch_dev(
        du, ds, dn, jnp.asarray(u), jnp.asarray(admit), cap)
    ev_s = np.asarray(ev_s)
    # camera 0: three 0.5s into cap 2 -> seq 0 (oldest) evicted
    assert ev_s[0][ev_s[0] >= 0].tolist() == [0]
    # camera 1: 0.7 evicted (lowest utility), not an equal-utility entry
    assert ev_s[1][ev_s[1] >= 0].tolist() == [0]
    refs = [UtilityQueue(2) for _ in range(C)]
    for c in range(C):
        for t in range(3):
            refs[c].push((c, t), float(u[c, t]))
    # pop_best any-camera: best utility 0.9 on camera 1, oldest first;
    # then the tie between the remaining 0.9 (cam 1) and 0.5 (cam 0);
    # then the equal 0.5s on camera 0, oldest surviving seq first
    for want, ref_item in (((1, 1), refs[1].pop_best()),
                           ((1, 2), refs[1].pop_best()),
                           ((0, 1), refs[0].pop_best())):
        du, ds, cam, seq = sq.pop_best_dev(du, ds)
        assert (int(cam), int(seq)) == want
        assert ref_item == want


def test_batch_push_equals_sequential_single_pushes(rng):
    """One push_batch == T push_one calls (same final lanes multiset,
    same eviction set) — the top-cap selection is order-free — and both
    hold what T heapq pushes leave."""
    C, K, T = 2, 5, 7
    cap = jnp.array([3, 5], jnp.int32)
    u = rng.choice([0.1, 0.4, 0.4, 0.8], (C, T)).astype(np.float32)
    admit = rng.random((C, T)) < 0.85

    bu_, bs_, bn_ = sq.make_lanes(C, K)
    bu_, bs_, *_ = sq.push_batch_dev(bu_, bs_, bn_, jnp.asarray(u),
                                     jnp.asarray(admit), cap)

    su_, ss_, sn_ = sq.make_lanes(C, K)
    for t in range(T):
        su_, ss_, sn_, *_ = sq.push_one_dev(
            su_, ss_, sn_, jnp.asarray(u[:, t]), jnp.asarray(admit[:, t]),
            cap)
    for c in range(C):
        ref = UtilityQueue(int(cap[c]))
        for t in np.flatnonzero(admit[c]):
            ref.push(t, float(u[c, t]))
        assert _lane_multiset(bu_, bs_, c) == _lane_multiset(su_, ss_, c)
        assert _lane_multiset(bu_, bs_, c) == _ref_multiset(ref)


# ---------------------------------------------------------------------------
# Threshold lanes: device vs host vs scalar, cdf_len edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lens", [(0, 0, 0), (0, 1, 17), (64, 64, 64),
                                  (3, 64, 40)])
def test_threshold_lanes_parity_edge_cases(lens, rng):
    """Empty, single-entry, partially filled and full windows: each row
    of the device lanes equals the scalar ``threshold_from_sorted``
    reference."""
    C, W = len(lens), 64
    buf = np.zeros((C, W), np.float32)
    n = np.asarray(lens, np.int32)
    for c in range(C):
        buf[c, :n[c]] = rng.choice(
            [0.0, 0.25, 0.5, 0.5, 0.77, 1.0], n[c]).astype(np.float32)
    for r in (0.0, 1e-3, 0.33, 0.5, 0.999, 1.0):
        rates = np.full((C,), r, np.float32)
        d = np.asarray(thresholds_from_lanes_dev(
            jnp.asarray(buf), jnp.asarray(n), jnp.asarray(rates)))
        for c in range(C):
            ref = threshold_from_sorted(np.sort(buf[c, :n[c]]), float(r))
            assert d[c] == np.float32(ref)


def test_threshold_through_wrapped_ring_matches_host_loop_reference(rng):
    """Session CDF rings that wrapped (len == W, pos mid-buffer) give the
    scalar host loop's ring positions and thresholds."""
    C, W = 2, 32
    sess = open_session(Query.single("red", fps=10.0), num_cameras=C,
                        cdf_window=W, exact_tick=True)
    ref = HostLoopShedder(C, cdf_window=W)
    for s in (sess, ref):
        s.report_backend_latency(0.2)
    for k in range(7):                        # 7*10 > 2*W: wraps twice
        u = rng.uniform(0, 1, (C, 10)).astype(np.float32)
        sess.step(utilities=u, tick=True)
        ref.step(u)
        np.testing.assert_array_equal(np.asarray(sess.state.cdf_pos),
                                      ref.cdf.pos)
        np.testing.assert_array_equal(np.asarray(sess.state.threshold),
                                      ref.threshold)
    assert int(np.asarray(sess.state.cdf_len)[0]) == W     # wrapped
    assert int(np.asarray(sess.state.cdf_pos)[0]) not in (0,)


# ---------------------------------------------------------------------------
# Fused step() vs the seed-style host loop (the acceptance contract)
# ---------------------------------------------------------------------------

def test_step_matches_host_loop_reference():
    rng = np.random.default_rng(11)
    C, T, W = 4, 12, 128
    hist = rng.uniform(0, 1, W + 16).astype(np.float32)
    ref = HostLoopShedder(C, cdf_window=W)
    ref.seed_cdf(hist)
    sessions = {
        where: open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                            num_cameras=C, train_utilities=hist,
                            cdf_window=W, exact_tick=True, **kw)
        for where, kw in PLACEMENTS.items()}
    for step in range(6):
        lat = float(rng.uniform(0.5, 2.0) / (C * 10.0))
        ref.report_backend_latency(lat)
        for s in sessions.values():
            s.report_backend_latency(lat)
        u = rng.uniform(0, 1, (C, T)).astype(np.float32)
        d_ref = ref.step(u)
        for where, s in sessions.items():
            res = s.step(utilities=u, tick=True)
            np.testing.assert_array_equal(res.decisions, d_ref,
                                          err_msg=f"{where} step {step}")
            np.testing.assert_array_equal(np.asarray(s.state.threshold),
                                          ref.threshold,
                                          err_msg=f"{where} step {step}")
            np.testing.assert_array_equal(np.asarray(s.state.queue_cap),
                                          ref.queue_cap)


def test_step_frames_fused_equals_split_pipeline(rng):
    """step(frames=...) — the ONE-dispatch device program — produces
    the same decisions and state as ingest() + admit() + tick()."""
    C, T = 2, 5
    frames = rng.uniform(0, 255, (3, C, T, 10, 12, 3)).astype(np.float32)
    pfs = rng.random((40, 1, 8, 8)).astype(np.float32)
    model = train_utility_model(pfs, rng.random(40) < 0.5, [RED])
    hist = rng.uniform(0, 1, 64).astype(np.float32)

    def mk(**kw):
        s = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                         num_cameras=C, model=model, train_utilities=hist,
                         queue_size=3, cdf_window=64, **kw)
        s.report_backend_latency(0.21)
        return s

    fused, fused_mesh, split = mk(), mk(shard_cameras=True), mk()
    for b in range(3):
        rd = fused.step(frames=frames[b])
        rm = fused_mesh.step(frames=frames[b])
        dec = split.admit(split.ingest(frames[b]).utility)
        split.tick()
        np.testing.assert_array_equal(rd.decisions, rm.decisions)
        np.testing.assert_array_equal(rd.decisions, dec)
        for k, v in split.state.as_dict().items():
            np.testing.assert_array_equal(fused.state.as_dict()[k], v,
                                          err_msg=k)
            np.testing.assert_array_equal(fused_mesh.state.as_dict()[k], v,
                                          err_msg=k)
    assert fused.stats.__dict__ == split.stats.__dict__


def test_step_requires_exactly_one_input(rng):
    s = open_session(Query.single("red"), num_cameras=1)
    with pytest.raises(ValueError):
        s.step()
    with pytest.raises(ValueError):
        s.step(utilities=np.zeros((1, 0), np.float32))
    with pytest.raises(ValueError):
        s.step(frames=rng.uniform(0, 255, (1, 2, 4, 4, 3)))   # no model


# ---------------------------------------------------------------------------
# float32 admission boundary (satellite regression)
# ---------------------------------------------------------------------------

def test_admission_float32_boundary_consistency():
    """Batch admit() and frame-at-a-time offer() agree on utilities at
    the float32 threshold boundary. (The seed compared float64
    utilities against float32 thresholds, so a float64 value strictly
    inside the threshold's last float32 ulp was shed by the comparison
    even though its stored float32 CDF entry was not below the
    threshold; float32 end-to-end removes the drift.)"""
    hist = np.array([0.2] * 49 + [0.5] * 51, np.float32)
    th32 = np.nextafter(np.float32(0.5), np.float32(np.inf))

    def mk():
        # exact_tick: the boundary value below is constructed from the
        # exact sort quantile's nextafter threshold
        s = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                         num_cameras=1, train_utilities=hist, cdf_window=128,
                         exact_tick=True)
        s.report_backend_latency(0.2)       # r = 0.5 -> threshold at 0.5
        s.tick()
        assert np.asarray(s.state.threshold)[0] == th32
        return s

    # a float64 utility strictly between 0.5 and the float32 threshold:
    # float64 comparison sheds it; float32 rounds it onto the threshold
    # and admits — batch and offer paths must agree (both admit)
    u_boundary = float(th32) - 1e-12
    assert np.float32(0.5) < u_boundary < float(th32)
    batch = mk().admit(np.array([[u_boundary]]))
    assert batch[0, 0] == ADMIT
    assert mk().offer("frame", u_boundary) == "queued"
    # well below the boundary both paths shed
    assert mk().admit(np.array([[0.4]]))[0, 0] == SHED_ADMISSION
    assert mk().offer("frame", 0.4) == "shed_admission"


# ---------------------------------------------------------------------------
# Batched arrivals (offer_batch + simulator coalescing)
# ---------------------------------------------------------------------------

class _Frame:
    def __init__(self, cam_id, i):
        self.cam_id, self.i = cam_id, i


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_offer_batch_matches_sequential_offers(placement, rng):
    C = 3
    hist = rng.uniform(0, 1, 100).astype(np.float32)

    def mk(**kw):
        sess = open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                            num_cameras=C, train_utilities=hist,
                            queue_size=2, cdf_window=128, **kw)
        sess.report_backend_latency(0.15)
        sess.tick()
        return sess

    seq_s, bat_s = mk(), mk(**PLACEMENTS[placement])
    items = [_Frame(i % C, i) for i in range(11)]
    us = rng.uniform(0, 1, len(items))
    codes_seq = [seq_s.offer(f, float(u)) for f, u in zip(items, us)]
    codes_bat = bat_s.offer_batch(items, us)
    assert codes_seq == codes_bat
    assert seq_s.stats.__dict__ == bat_s.stats.__dict__
    # identical queue contents -> identical transmission order
    for _ in range(4):
        a, b = seq_s.next_frame(), bat_s.next_frame()
        assert (a is None) == (b is None)
        if a is not None:
            assert a.i == b.i


def test_simulator_batch_arrivals_equivalence(rng):
    from repro.data.pipeline import interleave_streams, scenario_records
    from repro.data.synthetic import generate_dataset
    from repro.serve.simulator import BackendProfile, PipelineSimulator

    ds = generate_dataset(range(3), num_frames=80, height=32, width=48)
    train = [r for i, s in enumerate(ds[:2])
             for r in scenario_records(s, i, [RED])]
    model = train_utility_model(np.stack([r.pf for r in train]),
                                np.array([r.label for r in train]), [RED])
    train_us = [float(model.score(r.pf)) for r in train]
    streams = [scenario_records(ds[2], i, [RED], fps=10.0) for i in range(2)]
    recs = interleave_streams(streams)
    us = [float(model.score(r.pf)) for r in recs]

    def run(batch):
        sh = open_session(Query.single(RED, latency_bound=1.0, fps=10.0),
                          num_cameras=2, model=model,
                          train_utilities=train_us)
        return PipelineSimulator(sh, BackendProfile(), tokens=1, seed=3,
                                 batch_arrivals=batch).run(recs, us)

    a, b = run(False), run(True)
    assert a.kept_mask == b.kept_mask
    assert a.stats["offered"] == b.stats["offered"]
    assert a.stats["processed"] == b.stats["processed"]
    assert a.violations == b.violations


def test_restore_clears_stale_payloads(tmp_path):
    """Seq numbers restart across checkpoints: a restored session must
    not serve its pre-restore payloads for restored queue entries."""
    q = Query.single("red")
    a = open_session(q, num_cameras=1, frame_shape=(4, 4))
    assert a.offer("frame_A", 0.9) == "queued"
    a.checkpoint(tmp_path, step=1)
    b = open_session(q, num_cameras=1, frame_shape=(4, 4))
    assert b.offer("frame_B", 0.5) == "queued"      # also seq 0
    b.restore(tmp_path)
    assert b.next_frame() == (0, 0)                 # fallback, not frame_B


def test_simulator_fps_window_parameter(rng):
    """fps_window is honored: a shorter window sees the same ingress
    rate (uniform arrivals) — the parameter plumbs through without
    changing steady-state control decisions."""
    from repro.serve.simulator import PipelineSimulator
    s = open_session(Query.single("red"), num_cameras=1)
    sim = PipelineSimulator(s, fps_window=1.0)
    assert sim.fps_window == 1.0
    sim2 = PipelineSimulator(s)
    assert sim2.fps_window == 2.0
