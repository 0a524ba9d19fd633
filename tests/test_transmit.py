"""Device-side transmission control: batched top-k pops + bucket ticks.

Covered contracts:
  * ``pop_topk_dev`` reproduces the exact frame sequence of repeated
    ``pop_best_dev`` calls and of a sorted (utility desc, camera, seq)
    reference — FIFO tiebreaks included — on fuzzed lanes with
    deliberate utility ties and churned (emptied) rows;
  * ``ShedSession.next_frames(k)`` == k ``next_frame()`` calls on a
    twin session (payloads, order, stats), incl. the ``cams=`` mask,
    on one device and on a camera mesh;
  * the incremental ``(C, bins)`` bucket counts always equal a recount
    of the CDF ring (property test over random push/wrap sequences),
    and bucket-tick thresholds sit within one bucket width above the
    exact sort quantile;
  * ``exact_tick=True`` keeps ticks bit-identical to the lanes sort;
  * the cached queue depths equal a recount of the queue lanes through
    offer/admit/pop/tick/detach churn, and checkpoint->restore carries
    the counts leaves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import Query, open_session
from repro.core import shed_queue as sq
from repro.core.threshold import (
    threshold_from_sorted,
    thresholds_from_counts_dev,
)

# where a session's lanes live: one device, or a one-device camera mesh
PLACEMENTS = {"device": {}, "mesh": {"shard_cameras": True}}

_pop_best = jax.jit(sq.pop_best_dev)
_pop_topk_dev = jax.jit(sq.pop_topk_dev, static_argnums=2)


@pytest.fixture
def rng():
    return np.random.default_rng(7)


# ---------------------------------------------------------------------------
# pop_topk vs sequential pop_best (the order contract)
# ---------------------------------------------------------------------------

def _fuzz_lanes(rng, C, K, fill=0.6, ties=True):
    util = np.full((C, K), -np.inf, np.float32)
    seq = np.full((C, K), -1, np.int32)
    nxt = 0
    for c in range(C):
        for s in range(K):
            if rng.random() < fill:
                # coarse grid -> frequent exact utility ties across
                # cameras AND within a camera (FIFO tiebreak coverage)
                u = (np.float32(rng.integers(0, 8) / 8.0) if ties
                     else np.float32(rng.random()))
                util[c, s] = u
                seq[c, s] = nxt
                nxt += 1
    return util, seq


def _sequential_pops(util, seq, k, cam_mask=None):
    """Ground truth: ``k`` device ``pop_best_dev(cam=None)`` calls, one
    at a time. Returns the popped (cams, seqs) and the lanes left."""
    u, s = jnp.asarray(util), jnp.asarray(seq)
    if cam_mask is not None:
        # restrict by blanking the excluded rows on the reference copy
        u = jnp.where(cam_mask[:, None], u, -jnp.inf)
        s = jnp.where(cam_mask[:, None], s, -1)
    cams, seqs = [], []
    for _ in range(k):
        u, s, c, v = _pop_best(u, s)
        cams.append(int(c))
        seqs.append(int(v))
        if int(v) < 0:
            break
    return cams, seqs, np.asarray(u), np.asarray(s)


def _pop_topk(util, seq, k, rows=None):
    u, s, c, v = _pop_topk_dev(jnp.asarray(util), jnp.asarray(seq), k,
                               None if rows is None else jnp.asarray(rows))
    return np.asarray(c), np.asarray(v), np.asarray(u), np.asarray(s)


@pytest.mark.parametrize("ties", [True, False])
def test_pop_topk_dev_matches_sequential(rng, ties):
    for trial in range(20):
        C = int(rng.integers(1, 7))
        K = int(rng.integers(1, 9))
        util, seq = _fuzz_lanes(rng, C, K, fill=float(rng.uniform(0, 1)),
                                ties=ties)
        k = int(rng.integers(1, C * K + 4))
        got_c, got_s, gu, gs = _pop_topk(util, seq, k)
        kk = len(got_c)
        want_c, want_s, wu, ws = _sequential_pops(util, seq, kk)
        for i in range(kk):
            wc = want_c[i] if i < len(want_c) else -1
            w_s = want_s[i] if i < len(want_s) else -1
            if w_s < 0:
                assert got_s[i] == -1
            else:
                assert (got_c[i], got_s[i]) == (wc, w_s), (
                    f"trial {trial} pop {i}")
        # popped slots cleared exactly like sequential pops
        np.testing.assert_array_equal(gs, ws)
        np.testing.assert_array_equal(gu, wu)


def test_pop_topk_dev_matches_sorted_reference(rng):
    """The pop order is (utility desc, camera asc, seq asc) over the live
    entries: a Python sort of the lanes' entries gives the same pops,
    and the popped slots are the ones emptied."""
    for _ in range(10):
        C = int(rng.integers(1, 6))
        K = int(rng.integers(1, 8))
        util, seq = _fuzz_lanes(rng, C, K, fill=0.7)
        k = int(rng.integers(1, C * K + 2))
        live = sorted((-float(util[c, j]), c, int(seq[c, j]), j)
                      for c in range(C) for j in range(K) if seq[c, j] >= 0)
        top = live[:k]
        got_c, got_s, gu, gs = _pop_topk(util, seq, k)
        m = len(top)
        assert got_c[:m].tolist() == [c for _, c, _, _ in top]
        assert got_s[:m].tolist() == [v for _, _, v, _ in top]
        assert np.all(got_c[m:] == -1) and np.all(got_s[m:] == -1)
        wu, ws = util.copy(), seq.copy()
        for _, c, _, j in top:
            wu[c, j], ws[c, j] = -np.inf, -1
        np.testing.assert_array_equal(gu, wu)
        np.testing.assert_array_equal(gs, ws)


def test_pop_topk_signed_zero_tiebreak():
    """-0.0 and +0.0 utilities are the SAME rank (IEEE ==): the pop
    order between them must be FIFO, exactly like sequential pop_best."""
    util = np.array([[np.float32(-0.0)], [np.float32(0.0)]], np.float32)
    seq = np.array([[5], [2]], np.int32)
    want_c, want_s, _, _ = _sequential_pops(util, seq, 2)
    assert (want_c, want_s) == ([0, 1], [5, 2])
    got_c, got_s, _, _ = _pop_topk(util, seq, 2)
    assert list(got_c) == want_c and list(got_s) == want_s


def test_pop_topk_row_mask(rng):
    util, seq = _fuzz_lanes(rng, 5, 6, fill=0.8)
    rows = np.array([True, False, True, True, False])
    want_c, want_s, _, _ = _sequential_pops(util, seq, 30, cam_mask=rows)
    got_c, got_s, _, _ = _pop_topk(util, seq, 30, rows=rows)
    live = [i for i, s in enumerate(got_s) if s >= 0]
    assert [got_c[i] for i in live] == [c for c, s in
                                        zip(want_c, want_s) if s >= 0]
    assert not set(np.asarray(got_c)[live].tolist()) & {1, 4}


# ---------------------------------------------------------------------------
# Session-level next_frames vs next_frame (payloads + stats + depths)
# ---------------------------------------------------------------------------

def _mk_pair(placement, rng, C=3, **kw):
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    hist = rng.uniform(0, 1, 128).astype(np.float32)
    mk = lambda: open_session(q, num_cameras=C, train_utilities=hist,
                              queue_size=4, queue_capacity=16,
                              **PLACEMENTS[placement], **kw)
    return mk(), mk()


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_next_frames_matches_next_frame_loop(placement, rng):
    a, b = _mk_pair(placement, rng)
    u = rng.uniform(0, 1, (3, 10)).astype(np.float32)
    items = [[f"c{c}t{t}" for t in range(10)] for c in range(3)]
    a.admit(u, items=items)
    b.admit(u, items=items)
    for k in (1, 3, 5, 50):
        batched = a.next_frames(k)
        seqd = []
        for _ in range(k):
            it = b.next_frame()
            if it is None:
                break
            seqd.append(it)
        assert batched == seqd
        assert a.stats.sent == b.stats.sent
        assert len(a) == len(b)
        np.testing.assert_array_equal(a.queue_depths(), b.queue_depths())


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_next_frames_cams_mask(placement, rng):
    a, _ = _mk_pair(placement, rng)
    u = rng.uniform(0, 1, (3, 6)).astype(np.float32)
    items = [[(c, t) for t in range(6)] for c in range(3)]
    a.admit(u, items=items)
    got = a.next_frames(100, cams=[0, 2])
    assert got and all(it[0] in (0, 2) for it in got)
    # camera 1's frames are untouched and still poppable
    rest = a.next_frames(100)
    assert rest and all(it[0] == 1 for it in rest)
    assert len(a) == 0


def test_next_frames_after_detach_churn(rng):
    a, b = _mk_pair("device", rng)
    u = rng.uniform(0, 1, (3, 8)).astype(np.float32)
    items = [[(c, t) for t in range(8)] for c in range(3)]
    for s in (a, b):
        for c in range(3):
            s.lane(c)       # external id c -> lane c (first-seen order)
        s.admit(u, items=items)
        s.detach_camera(1)
    want = []
    while True:
        it = b.next_frame()
        if it is None:
            break
        want.append(it)
    got = a.next_frames(100)
    assert got == want
    assert all(it[0] != 1 for it in got)


def test_fleet_pop_topk_multi_shard():
    """8-device camera mesh (subprocess, test_fleet's pattern): sharded
    next_frames == the unsharded device session's, through churned
    lanes and a cams= mask."""
    from test_fleet import run_py
    out = run_py("""
import numpy as np
from repro.core import Query, open_session

rng = np.random.default_rng(3)
q = Query.single("red", latency_bound=1.0, fps=10.0)
hist = rng.uniform(0, 1, 128).astype(np.float32)
kw = dict(num_cameras=16, train_utilities=hist, queue_size=4,
          queue_capacity=16)
ref = open_session(q, **kw)
fl = open_session(q, shard_cameras=True, **kw)
assert len(fl.mesh.devices.ravel()) == 8
for step in range(3):
    u = rng.uniform(0, 1, (16, 6)).astype(np.float32)
    items = [[(step, c, t) for t in range(6)] for c in range(16)]
    ref.admit(u, items=items)
    fl.admit(u, items=items)
    for k in (1, 7, 200):
        assert fl.next_frames(k) == ref.next_frames(k), (step, k)
    assert len(fl) == len(ref)
cams = [0, 5, 9, 15]
assert fl.next_frames(50, cams=cams) == ref.next_frames(50, cams=cams)
print("MULTI_SHARD_POP_OK", len(fl))
""")
    assert "MULTI_SHARD_POP_OK" in out


def test_fleet_pop_topk_single_shard(rng):
    """1-device camera mesh: fleet pop_topk == unsharded device pops
    (same program, one shard; the 8-shard run is the subprocess test
    above)."""
    import jax
    if len(jax.devices()) != 1:
        pytest.skip("needs the main process's single device")
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    hist = rng.uniform(0, 1, 128).astype(np.float32)
    kw = dict(num_cameras=4, train_utilities=hist, queue_size=4,
              queue_capacity=16)
    ref = open_session(q, **kw)
    fl = open_session(q, shard_cameras=True, **kw)
    u = rng.uniform(0, 1, (4, 10)).astype(np.float32)
    items = [[(c, t) for t in range(10)] for c in range(4)]
    ref.admit(u, items=items)
    fl.admit(u, items=items)
    for k in (1, 5, 100):
        assert fl.next_frames(k) == ref.next_frames(k)
        assert len(fl) == len(ref)


# ---------------------------------------------------------------------------
# Bucket counts: incremental == recount; threshold within one bucket
# ---------------------------------------------------------------------------

def _recount(buf, ln, lo, inv_width, bins):
    """Recount each ring's live entries (slots ``[0, len)``) into
    ``(C, bins)`` bucket counts, binned in float32 by
    ``clip(floor((u - lo) * inv_width), 0, bins - 1)``."""
    buf, ln = np.asarray(buf), np.asarray(ln)
    counts = np.zeros((buf.shape[0], bins), np.int32)
    for c in range(buf.shape[0]):
        u = buf[c, :int(ln[c])]
        b = np.floor((u - np.float32(lo)) * np.float32(inv_width))
        np.add.at(counts[c], np.clip(b.astype(np.int32), 0, bins - 1), 1)
    return counts


def _exact(buf, ln, rates):
    """Eq. 17 per row, the scalar ``threshold_from_sorted`` (float32
    rows, so its threshold is the float32 one)."""
    buf, ln = np.asarray(buf), np.asarray(ln)
    return np.array([threshold_from_sorted(np.sort(buf[c, :int(ln[c])]),
                                           float(rates[c]))
                     for c in range(buf.shape[0])], np.float32)


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_counts_track_ring_and_threshold_drift(data):
    """Random push sequences through a small ring (wraps several
    times): the session's incremental counts always equal a recount of
    the live window, and the bucket threshold is >= the exact sort
    quantile by at most one bucket width."""
    C = data.draw(st.integers(1, 3))
    W = data.draw(st.integers(2, 6))
    bins = data.draw(st.integers(2, 16))
    n_steps = data.draw(st.integers(1, 8))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    sess = open_session(q, num_cameras=C, cdf_window=W,
                        quantile_bins=bins, queue_size=2)
    cfg = sess._tick_cfg
    for _ in range(n_steps):
        T = int(rng.integers(1, 2 * W))
        u = rng.uniform(0, 1, (C, T)).astype(np.float32)
        sess.admit(u)
        st_ = sess.state
        np.testing.assert_array_equal(
            np.asarray(st_.cdf_counts),
            _recount(st_.cdf_buf, st_.cdf_len, cfg.lo, cfg.inv_width, bins))
        rates = rng.uniform(0, 1, C).astype(np.float32)
        exact = _exact(st_.cdf_buf, st_.cdf_len, rates)
        bucket = np.asarray(thresholds_from_counts_dev(
            st_.cdf_counts, st_.cdf_len, jnp.asarray(rates), cfg.lo,
            cfg.width))
        live = np.isfinite(exact)
        np.testing.assert_array_equal(live, np.isfinite(bucket))
        assert np.all(bucket[live] >= exact[live] - 1e-7)
        assert np.all(bucket[live] - exact[live] <= cfg.width * 1.001)


def test_counts_thresholds_dev_match_order_statistic(rng):
    """The bucket threshold is the upper edge of the bucket that holds
    the rank-k entry of the window, k = ceil(r * n) in float32: expand
    the counts into the sorted list of bucket indices and read it."""
    C, B = 5, 32
    counts = rng.integers(0, 9, (C, B)).astype(np.int32)
    counts[1] = 0                                  # an empty window
    n = counts.sum(axis=1).astype(np.int32)
    rates = rng.uniform(0, 1.2, C).astype(np.float32)
    rates[2] = 0.0                                 # r <= 0 sheds nothing
    d = np.asarray(thresholds_from_counts_dev(
        jnp.asarray(counts), jnp.asarray(n), jnp.asarray(rates),
        0.0, 1.0 / B))
    for c in range(C):
        if n[c] == 0 or rates[c] <= 0:
            assert d[c] == -np.inf
            continue
        k = int(np.ceil(np.minimum(rates[c], np.float32(1.0))
                        * np.float32(n[c])))
        b = np.repeat(np.arange(B), counts[c])[min(max(k, 1), n[c]) - 1]
        assert d[c] == np.float32(0.0) + np.float32(b + 1) * np.float32(
            1.0 / B)


@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_exact_tick_matches_lanes_sort(placement, rng):
    """exact_tick=True: session thresholds == the scalar sort quantile
    of every ring — the pre-bucket behavior, bit for bit."""
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    hist = rng.uniform(0, 1, 64).astype(np.float32)
    sess = open_session(q, num_cameras=3, train_utilities=hist,
                        cdf_window=64, exact_tick=True,
                        **PLACEMENTS[placement])
    sess.report_backend_latency(0.05)
    sess.admit(rng.uniform(0, 1, (3, 12)).astype(np.float32))
    sess.tick()
    st_ = sess.state
    p = np.float32(np.asarray(st_.proc_q)[0])
    rate = np.float32(1.0) - np.float32(1.0) / (p * np.float32(3)
                                                * np.float32(10.0))
    want = _exact(st_.cdf_buf, st_.cdf_len, np.full((3,), rate))
    np.testing.assert_array_equal(np.asarray(st_.threshold), want)


def test_bucket_tick_within_one_bucket_of_exact(rng):
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    hist = rng.uniform(0, 1, 200).astype(np.float32)
    mk = lambda **kw: open_session(q, num_cameras=4, train_utilities=hist,
                                   cdf_window=128, **kw)
    se, sb = mk(exact_tick=True), mk()
    u = rng.uniform(0, 1, (4, 16)).astype(np.float32)
    for s in (se, sb):
        s.report_backend_latency(0.06)
        s.admit(u)
    # same admissions on both (thresholds still -inf before any tick)
    np.testing.assert_array_equal(np.asarray(se.state.cdf_buf),
                                  np.asarray(sb.state.cdf_buf))
    se.tick()
    sb.tick()
    e = np.asarray(se.state.threshold)
    b = np.asarray(sb.state.threshold)
    w = sb._tick_cfg.width
    assert np.all(b >= e - 1e-7) and np.all(b - e <= w * 1.001)


def test_counts_leaves_checkpoint_roundtrip(tmp_path, rng):
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    hist = rng.uniform(0, 1, 64).astype(np.float32)
    mk = lambda: open_session(q, num_cameras=2, train_utilities=hist,
                              cdf_window=32, frame_shape=(8, 8))
    a = mk()
    a.admit(rng.uniform(0, 1, (2, 10)).astype(np.float32))
    a.checkpoint(tmp_path / "ck")
    b = mk()
    b.restore(tmp_path / "ck")
    np.testing.assert_array_equal(np.asarray(a.state.cdf_counts),
                                  np.asarray(b.state.cdf_counts))
    np.testing.assert_array_equal(np.asarray(a.state.s2_counts),
                                  np.asarray(b.state.s2_counts))
    np.testing.assert_array_equal(a.queue_depths(), b.queue_depths())


# ---------------------------------------------------------------------------
# Depth cache: always equals a recount of the queue lanes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("placement", list(PLACEMENTS))
def test_queue_depths_cache_consistency(placement, rng):
    q = Query.single("red", latency_bound=1.0, fps=10.0)
    hist = rng.uniform(0, 1, 128).astype(np.float32)
    sess = open_session(q, num_cameras=3, train_utilities=hist,
                        queue_size=3, queue_capacity=8,
                        **PLACEMENTS[placement])

    def check():
        want = (np.asarray(sess.state.q_seq) >= 0).sum(axis=1)
        np.testing.assert_array_equal(sess.queue_depths(), want)
        assert len(sess) == int(want.sum())

    sess.report_backend_latency(0.05)
    for step in range(8):
        op = step % 4
        if op == 0:
            sess.admit(rng.uniform(0, 1, (3, 5)).astype(np.float32))
        elif op == 1:
            for _ in range(3):
                sess.offer(("it", step), float(rng.random()),
                           cam=int(rng.integers(0, 3)))
        elif op == 2:
            sess.next_frames(int(rng.integers(1, 6)))
            sess.next_frame()
        else:
            sess.tick()     # queue resize can evict
        check()
    for c in range(3):
        sess.lane(c)
    sess.detach_camera(1)
    check()
