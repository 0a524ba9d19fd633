"""Host spans of the serve path: ``MetricsRegistry.span`` and the
``session.*`` spans and counters a ``ShedSession`` with a registry
records, which must not change a single decision and reach the report
of a ``ServeService`` over the session."""
import contextlib

import jax
import numpy as np
import pytest

from repro.core import Query, RED, open_session, train_utility_model
from repro.serve import Arrival, MockBackend, ServeService, VirtualClock
from repro.serve.metrics import MetricsRegistry

STEP_PHASES = ["session.stage", "session.put", "session.dispatch",
               "session.readback", "session.absorb"]


def _span_counts(reg):
    return {k[len("span."):]: v["count"]
            for k, v in reg.snapshot()["histograms"].items()
            if k.startswith("span.")}


def test_span_times_a_block_into_its_histogram():
    reg = MetricsRegistry()
    with reg.span("outer"):
        with reg.span("inner"):
            pass
        with reg.span("inner"):
            pass
    hists = reg.snapshot()["histograms"]
    assert hists["span.outer"]["count"] == 1
    assert hists["span.inner"]["count"] == 2
    h_out, h_in = reg.histogram("span.outer"), reg.histogram("span.inner")
    assert 0.0 <= h_in.total <= h_out.total
    assert "span.inner" in reg.report()


def test_span_records_a_block_that_raises():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        with reg.span("failing"):
            raise KeyError("x")
    assert reg.histogram("span.failing").count == 1


# ---------------------------------------------------------------------------
# the session's spans and counters
# ---------------------------------------------------------------------------

C, T, H, W, STEPS = 3, 4, 48, 80, 3


def _model():
    rng = np.random.default_rng(0)
    pfs = rng.random((40, 1, 8, 8)).astype(np.float32)
    return train_utility_model(pfs, rng.random(40) < 0.5, [RED])


def _session(metrics=None, **kw):
    rng = np.random.default_rng(0)
    kw.setdefault("model", _model())
    return open_session(Query.single("red", latency_bound=1.0, fps=10.0),
                        num_cameras=C, frame_shape=(H, W),
                        train_utilities=rng.uniform(0, 1, 64)
                        .astype(np.float32),
                        queue_size=3, cdf_window=64, metrics=metrics, **kw)


def _drive(session):
    """Steps of uint8 windows with a tick, each followed by a pop of C
    frames and one latency report per popped frame. The reports run
    under a transfer guard that refuses any implicit transfer to the
    device: a report is a host fold that makes no device call."""
    rng = np.random.default_rng(1)
    out = []
    for k in range(STEPS):
        frames = rng.integers(0, 256, (C, T, H, W, 3), dtype=np.uint8)
        res = session.step(frames=frames, tick=True)
        popped = session.next_frames(C)
        with jax.transfer_guard("disallow"):
            for i, _ in enumerate(popped):
                session.report_backend_latency(0.05 + 0.01 * i)
        out.append((res.decisions.copy(), res.target_drop_rate.copy(),
                    popped))
    return out


def test_unmetered_session_shares_one_noop_span():
    s = open_session(Query.single("red"), num_cameras=1)
    assert s.metrics is None
    first = s._span("session.step")
    assert first is s._span("session.pop")
    assert first is s._span("session.report_latency")
    assert isinstance(first, contextlib.nullcontext)


def test_session_spans_and_counters_cover_every_call():
    reg = MetricsRegistry()
    runs = _drive(_session(reg))
    counts = _span_counts(reg)
    reports = sum(len(p) for _, _, p in runs)
    assert counts == {"session.step": STEPS, "session.pop": STEPS,
                      "session.report_latency": reports,
                      **{name: STEPS for name in STEP_PHASES}}
    # the phases run inside their step
    step_s = reg.histogram("span.session.step").total
    phases_s = sum(reg.histogram("span." + n).total for n in STEP_PHASES)
    assert 0.0 < phases_s <= step_s
    counters = reg.snapshot()["counters"]
    assert counters["session.steps"] == STEPS
    assert counters["session.frames"] == STEPS * C * T
    assert counters["session.staged_bytes"] == STEPS * C * T * H * W * 3 * 4
    report = reg.report()
    for name in counts:
        assert f"span.{name}" in report


def test_latency_reports_are_counted_and_uploaded_at_most_once_a_step():
    """The gauges ``session.latency_reports`` and
    ``session.latency_flushes`` total the reports and the uploads: the
    reports that come between two steps reach the device in one upload,
    when the next step dispatches."""
    reg = MetricsRegistry()
    s = _session(reg)
    runs = _drive(s)
    totals = lambda: {k: g["value"]
                      for k, g in reg.snapshot()["gauges"].items()}
    reports = sum(len(p) for _, _, p in runs)
    assert reports > STEPS
    # the last step's reports are still pending: one upload for each
    # step that followed reports
    assert totals() == {"session.latency_reports": reports,
                        "session.latency_flushes": STEPS - 1}
    pending = s.expected_proc()
    s.tick()
    assert totals()["session.latency_flushes"] == STEPS
    assert float(np.asarray(s.state.proc_q).max()) == pending
    s.tick()                        # nothing pending: no upload
    assert totals()["session.latency_flushes"] == STEPS
    # the registry's counters stay the per-step set
    assert set(reg.snapshot()["counters"]) == {
        "session.steps", "session.frames", "session.staged_bytes"}


def test_session_frames_count_every_step_and_offer_batch_has_no_phases():
    """A utilities step counts its frames; neither it nor offer_batch
    (which is no step) opens the frames path's phase spans."""
    reg = MetricsRegistry()
    s = _session(reg)
    util = np.random.default_rng(2).uniform(0, 1, (C, T)).astype(np.float32)
    s.step(utilities=util)
    s.offer_batch([("x", i) for i in range(4)], [0.9, 0.8, 0.7, 0.6],
                  cams=[0, 0, 1, 2])
    assert _span_counts(reg) == {"session.step": 1}
    counters = reg.snapshot()["counters"]
    assert counters["session.steps"] == 1
    assert counters["session.frames"] == C * T
    assert "session.staged_bytes" not in counters


def test_session_results_identical_with_and_without_metrics():
    plain, metered = _session(), _session(MetricsRegistry())
    a, b = _drive(plain), _drive(metered)
    for (da, ra, pa), (db, rb, pb) in zip(a, b):
        np.testing.assert_array_equal(da, db)
        np.testing.assert_array_equal(ra, rb)
        assert pa == pb
    sa, sb = plain.state.as_dict(), metered.state.as_dict()
    for k in sa:
        np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
    assert plain.stats.__dict__ == metered.stats.__dict__
    np.testing.assert_array_equal(plain.queue_depths(),
                                  metered.queue_depths())


def test_service_reports_into_the_sessions_registry():
    """A service over a metered session adopts its registry: the
    operator's one report carries the session's spans beside the
    service's own metrics."""
    reg = MetricsRegistry()
    session = _session(reg)
    rng = np.random.default_rng(3)
    arrivals = []
    for i in range(2 * T):
        for c in range(C):
            arrivals.append(Arrival(
                t=i / 10.0, cam=c, record=(c, i),
                frame=rng.integers(0, 256, (H, W, 3), dtype=np.uint8)))
    service = ServeService(session, MockBackend(seed=0),
                           clock=VirtualClock(), max_batch=8, max_wait=0.05)
    assert service.metrics is reg
    res = service.run(arrivals)
    assert res.metrics["counters"]["dispatch.fused"] > 0
    report = service.metrics.report()
    assert "span.session.step" in report
    assert "span.session.report_latency" in report
    assert "ingest.offered" in report


# ---------------------------------------------------------------------------
# the camera-sharded fleet: the pop's spans and counter, the device scopes
# ---------------------------------------------------------------------------

FLEET_POP_SPANS = ["session.pop_gather", "session.pop_merge"]


def _fleet_session(metrics=None, **kw):
    from repro.core import fleet
    return _session(metrics, mesh=fleet.fleet_mesh(1), impl="jnp", **kw)


def test_fleet_pop_records_its_halves_and_counts_candidates():
    """One camera shard here: each pop reads back ``S * kk`` candidates,
    ``kk = min(k, cameras_per_shard * queue_capacity)``."""
    reg = MetricsRegistry()
    s = _fleet_session(reg)
    runs = _drive(s)
    counts = _span_counts(reg)
    assert {n: counts[n] for n in FLEET_POP_SPANS} == {
        n: STEPS for n in FLEET_POP_SPANS}
    pop_s = reg.histogram("span.session.pop").total
    halves_s = sum(reg.histogram("span." + n).total for n in FLEET_POP_SPANS)
    assert 0.0 < halves_s <= pop_s
    kk = min(C, C * s.queue_capacity)
    assert reg.snapshot()["counters"]["fleet.pop_candidates"] == STEPS * kk
    assert sum(len(p) for _, _, p in runs) > 0


def test_fleet_pop_counts_every_shards_candidates_on_four_devices():
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    code = r"""
import numpy as np, jax
from repro.core import fleet
from repro.serve.metrics import MetricsRegistry
import test_spans as t
assert len(jax.devices()) == 4
reg = MetricsRegistry()
s = t.open_session(t.Query.single("red", latency_bound=1.0, fps=10.0),
                   num_cameras=8, train_utilities=np.linspace(0, 1, 64),
                   queue_size=3, queue_capacity=16, cdf_window=64,
                   mesh=fleet.fleet_mesh(4), metrics=reg)
u = np.random.default_rng(0).uniform(0, 1, (8, 4)).astype(np.float32)
s.step(utilities=u)
assert len(s.next_frames(5)) == 5
assert len(s.next_frames(40)) > 0
snap = reg.snapshot()
# S * kk: 4 * min(5, 2 * 16), then 4 * min(40, 2 * 16)
assert snap["counters"]["fleet.pop_candidates"] == 4 * 5 + 4 * 32, snap
assert snap["histograms"]["span.session.pop_gather"]["count"] == 2
assert snap["histograms"]["span.session.pop_merge"]["count"] == 2
print("FLEET-POP-OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(repo / "src"),
                                           str(repo / "tests")]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FLEET-POP-OK" in out.stdout


def test_unmetered_fleet_pop_records_nothing_and_pops_the_same(monkeypatch):
    """Without a registry the session calls ``pop_topk`` with its plain
    keywords (no span, no counter), and a metered fleet session pops,
    decides and ends in the same state as an unmetered one."""
    from repro.core import fleet
    calls = []
    pop_topk = fleet.pop_topk

    def plain(state, *, mesh, axis, k, rows=None):
        calls.append(k)
        return pop_topk(state, mesh=mesh, axis=axis, k=k, rows=rows)

    monkeypatch.setattr(fleet, "pop_topk", plain)
    plain_run = _drive(_fleet_session())
    assert calls == [C] * STEPS
    monkeypatch.setattr(fleet, "pop_topk", pop_topk)
    reg = MetricsRegistry()
    metered, unmetered = _fleet_session(reg), _fleet_session()
    a, b = _drive(metered), _drive(unmetered)
    for (da, ra, pa), (db, rb, pb), (dc, _, pc) in zip(a, b, plain_run):
        np.testing.assert_array_equal(da, db)
        np.testing.assert_array_equal(ra, rb)
        np.testing.assert_array_equal(db, dc)
        assert pa == pb == pc
    sa, sb = metered.state.as_dict(), unmetered.state.as_dict()
    for k in sa:
        np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
    assert unmetered.metrics is None


def _op_names(compiled) -> set:
    import re
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_fleet_programs_name_their_device_scopes():
    """The sharded step's control under ``shed.control`` (its flatten
    under ``shed.stage``), both pop programs under ``shed.pop``."""
    import jax.numpy as jnp
    from repro.core import fleet
    s = _fleet_session()
    captured = {}
    serve = fleet._fleet_serve_step

    def spy(*a, **k):
        captured.update(args=a, kw=k)
        return serve(*a, **k)

    fleet._fleet_serve_step = spy
    try:
        s.step(frames=np.zeros((C, T, H, W, 3), np.uint8), tick=True)
    finally:
        fleet._fleet_serve_step = serve
    lowered = serve.lower(*captured["args"], **captured["kw"])
    assert any("/shed.control/" in n for n in _op_names(lowered.compile()))
    # the CPU compiler folds the flatten into a bitcast: read the scope
    # off the traced program
    assert "shed.stage/reshape" in lowered.as_text(debug_info=True)
    st, mesh, axis = s.state, s.mesh, s._cam_axis
    cand = _op_names(fleet._fleet_pop_candidates.lower(
        st.q_util, st.q_seq, jnp.ones((C,), bool), mesh=mesh, axis=axis,
        kk=C).compile())
    clear = _op_names(fleet._fleet_pop_clear.lower(
        st, jnp.zeros((C,), jnp.int32), jnp.zeros((C,), jnp.int32),
        mesh=mesh, axis=axis).compile())
    for names in (cand, clear):
        assert any("/shed.pop/" in n for n in names), sorted(names)[:5]
