"""Scenario stress harness: trace-driven robustness runs over the
streaming service (ISSUE: resilient serving).

Six seeded scenarios exercise the service end-to-end under a virtual
clock — each reports QoR, shed rate and a latency-violation curve, and
the robustness scenarios additionally record pass/fail acceptance
facts (breaker re-closed, bit-identical restart, ...) in ``derived``:

``baseline``   the plain streaming run every other scenario is judged
               against.
``drift``      diurnal illumination drift: a slow sinusoid modulates
               the utility scores, forcing the online CDF/threshold
               loop to track a moving distribution.
``burst``      heavy-tail (Pareto) inter-arrivals at the same mean
               rate: admission + queue eviction absorb the bursts.
``outage``     a backend outage covering ~10% of the runtime behind a
               ``FaultyBackend``: frames shed at the transport instead
               of deadlocking, the breaker re-closes after recovery,
               and delivered frames stay inside the E2E budget.
``churn``      cameras leave and join mid-run (``detach_camera`` /
               ``attach_camera``) across three segments of one live
               session.
``restart``    mid-run kill: checkpoint after segment 1, restore into
               a fresh session, replay segment 2 — decisions must be
               bit-identical to the uninterrupted service.
"""
from __future__ import annotations

import tempfile
from dataclasses import replace
from pathlib import Path

import numpy as np

from repro.core import Query, RED, open_session, overall_qor
from repro.data.pipeline import camera_array_records, interleave_streams
from repro.serve import (
    Arrival,
    BreakerConfig,
    FaultyBackend,
    MockBackend,
    ResilienceConfig,
    RetryPolicy,
    ServeService,
    VirtualClock,
)
from benchmarks.common import FPS, Timer, dataset, records, train_model

BENCH_SEED = 0
BOUND = 1.0


def _setup(quick: bool) -> dict:
    nvid, frames = (6, 100) if quick else (9, 300)
    ncam = nvid - 3
    streams = records(nvid, frames, ("red",))
    train_recs = [r for s in streams[:3] for r in s]
    model = train_model(train_recs, [RED])
    train_us = [float(model.score(r.pf)) for r in train_recs]
    scs = dataset(nvid, frames)
    cam_streams = camera_array_records(scs[3:], [RED], model=model, fps=FPS)
    recs = interleave_streams(cam_streams)
    return {
        "ncam": ncam,
        "recs": recs,
        "duration": frames / FPS,
        "query": Query.single(RED, latency_bound=BOUND, fps=FPS),
        "model": model,
        "train_us": train_us,
        # raw RGB per camera lane, for scenarios that perturb pixels
        # and rescore through the fused frame path (drift)
        "cam_rgb": [sc.frames_rgb().astype(np.float32) for sc in scs[3:]],
    }


def _session(su: dict, **kw):
    # exact_tick: this harness pins robustness acceptance facts
    # (restart bit-identity, outage/churn QoR curves) against the
    # exact-quantile reference trajectory. The closed control loop is
    # chaotic — one extra shed frame rewrites the latency feedback and
    # with it the whole trajectory — so the bucket tick's (bounded, see
    # tests/test_transmit.py) threshold drift would land these
    # scenarios on a different-but-equally-valid trajectory and make
    # the pinned curves meaningless as a regression signal.
    kw.setdefault("exact_tick", True)
    return open_session(su["query"], num_cameras=su["ncam"],
                        model=su["model"], train_utilities=su["train_us"],
                        **kw)


def _service(sess, backend, **kw):
    return ServeService(sess, backend, clock=VirtualClock(), tokens=1,
                        max_batch=8, max_wait=0.05, **kw)


def _arrivals(recs):
    return [Arrival(t=r.t_gen, cam=r.cam_id, record=r,
                    utility=float(r.utility)) for r in recs]


def _report(res) -> dict:
    """QoR + shed + the latency-violation curve for one scenario run."""
    e2e = res.e2e_latencies()
    curve = {f"{m:g}x": (round(float((e2e > m * BOUND).mean()), 4)
                         if e2e.size else 0.0)
             for m in (0.25, 0.5, 0.75, 1.0)}
    return {
        "offered": len(res.offered),
        "delivered": len(res.processed),
        "qor": round(overall_qor([r.objects for r in res.offered],
                                 res.kept_mask), 4),
        "shed_rate": round(res.metrics["derived"]["shed_rate"], 4),
        "violations": int(res.violations),
        "e2e_p50_ms": (round(float(np.percentile(e2e, 50)) * 1e3, 2)
                       if e2e.size else 0.0),
        "e2e_p99_ms": (round(float(np.percentile(e2e, 99)) * 1e3, 2)
                       if e2e.size else 0.0),
        "violation_curve": curve,   # fraction of delivered past m*bound
    }


# -- scenarios ---------------------------------------------------------------

def _baseline(su: dict) -> dict:
    svc = _service(_session(su), MockBackend(seed=BENCH_SEED))
    return _report(svc.run(_arrivals(su["recs"])))


def _drift(su: dict) -> dict:
    """Diurnal illumination drift: a slow sinusoid scales the PIXELS
    (bright noon -> dim dusk) and every frame is rescored through the
    fused in-dispatch path — RGB->HSV, background subtraction, PF
    features, utility and admission in one device program per window —
    so the admission threshold must track the moving distribution the
    real optics would produce, not a post-hoc scaling of cached
    scores."""
    period = su["duration"]
    arrs = []
    for r in su["recs"]:
        g = 0.75 + 0.35 * np.sin(2 * np.pi * r.t_gen / period)
        frame = np.clip(su["cam_rgb"][r.cam_id][r.frame_idx] * g,
                        0.0, 255.0).astype(np.float32)
        arrs.append(Arrival(t=r.t_gen, cam=r.cam_id, record=r, frame=frame))
    svc = _service(_session(su), MockBackend(seed=BENCH_SEED))
    res = svc.run(arrs)
    out = _report(res)
    ths = [s["threshold"] for s in res.trace if np.isfinite(s["threshold"])]
    out["threshold_span"] = (round(max(ths) - min(ths), 4) if ths else 0.0)
    return out


def _burst(su: dict) -> dict:
    """Heavy-tail arrivals: Pareto inter-arrival gaps (alpha=2, same
    mean rate) replace the metronome trace — bursts pile into the
    bounded queues and must be shed, not queued unboundedly."""
    rng = np.random.default_rng(BENCH_SEED)
    recs = sorted(su["recs"], key=lambda r: (r.t_gen, r.cam_id))
    mean_gap = su["duration"] / max(len(recs), 1)
    gaps = rng.pareto(2.0, len(recs)) * mean_gap   # pareto(2) has mean 1
    ts = np.cumsum(gaps)
    recs = [replace(r, t_gen=float(t)) for r, t in zip(recs, ts)]
    svc = _service(_session(su), MockBackend(seed=BENCH_SEED))
    res = svc.run(_arrivals(recs))
    out = _report(res)
    out["queue_depth_max"] = int(
        res.metrics["gauges"]["queue.depth"]["max"])
    return out


def _outage(su: dict) -> dict:
    """Backend outage over ~10% of the runtime, with the full
    resilience stack on: retries + breaker + degraded-mode floor. The
    window sits in the trace's early high-traffic phase (the synthetic
    scenes go busy later, where admission already sheds hard)."""
    start, dur = 0.15 * su["duration"], 0.1 * su["duration"]
    sess = _session(su)
    backend = FaultyBackend(MockBackend(seed=BENCH_SEED), seed=BENCH_SEED,
                            outages=((start, dur),))
    svc = _service(sess, backend, resilience=ResilienceConfig(
        retry=RetryPolicy(max_retries=2, backoff_base=0.05,
                          backoff_max=0.2, seed=1),
        breaker=BreakerConfig(failure_threshold=3, reset_timeout=0.1)))
    res = svc.run(_arrivals(su["recs"]))
    out = _report(res)
    c = res.metrics["counters"]
    breaker = res.metrics["states"]["breaker.state"]
    e2e = res.e2e_latencies()
    out.update({
        "transport_shed": int(c.get("sender.transport_shed", 0)),
        "retries": int(c.get("sender.retries", 0)),
        "breaker_opens": int(breaker["transitions"].get("open", 0)),
        "breaker_reclosed": breaker["value"] == "closed",
        "degraded_time_fraction":
            round(res.metrics["derived"]["degraded_time_fraction"], 4),
        "delivered_within_budget":
            bool(e2e.size and float(np.percentile(e2e, 99)) <= BOUND),
    })
    return out


def _churn(su: dict) -> dict:
    """Cameras leave and join a live session: three trace segments on
    ONE service — full array, then one camera detached, then a new
    camera attached onto the freed lane."""
    ncam, D = su["ncam"], su["duration"]
    leaver = ncam - 1
    bounds = (D / 3, 2 * D / 3)
    segs = ([], [], [])
    for r in su["recs"]:
        k = 0 if r.t_gen < bounds[0] else 1 if r.t_gen < bounds[1] else 2
        if k >= 1 and r.cam_id == leaver:
            if k == 1:
                continue               # leaver is gone in segment 2
            # segment 3: its stream returns as a NEW camera id
            segs[2].append(Arrival(t=r.t_gen, cam="joiner", record=r,
                                   utility=float(r.utility)))
            continue
        segs[k].append(Arrival(t=r.t_gen, cam=r.cam_id, record=r,
                               utility=float(r.utility)))

    sess = _session(su)
    svc = _service(sess, MockBackend(seed=BENCH_SEED))
    out = {}
    for k, seg in enumerate(segs):
        if k == 1:
            drained = sess.detach_camera(leaver)
            out["drained_on_detach"] = len(drained)
        if k == 2:
            out["lane_reused"] = sess.attach_camera("joiner") == leaver
        svc.reset()
        for a in seg:
            svc.submit(a)
        svc.drain()
        rep = _report(svc.finalize())
        out[f"seg{k + 1}"] = {key: rep[key] for key in
                              ("offered", "delivered", "shed_rate", "qor")}
    out["active_cameras"] = sess.num_active
    return out


def _restart(su: dict) -> dict:
    """Mid-run kill + resume: serve segment 1, checkpoint the session,
    serve segment 2; separately restore the checkpoint into a fresh
    session and replay segment 2 — admission decisions, delivered
    frames and control traces must match bit-for-bit. Deterministic
    backend (jitter=0) so both lives see identical latencies."""
    t_split = round(su["duration"] / 2)    # aligned to the control period
    seg1 = [a for a in _arrivals(su["recs"]) if a.t < t_split]
    seg2 = [a for a in _arrivals(su["recs"]) if a.t >= t_split]

    def backend():
        return MockBackend(jitter=0.0, seed=BENCH_SEED)

    with tempfile.TemporaryDirectory(prefix="bench_restart_") as td:
        ckpt = Path(td) / "mid"
        live_sess = _session(su)
        live = _service(live_sess, backend())
        live.reset()
        for a in seg1:
            live.submit(a)
        live.drain()
        live.finalize()
        live_sess.checkpoint(ckpt, step=1)

        live.reset()                       # the uninterrupted continuation
        for a in seg2:
            live.submit(a)
        live.drain()
        res_live = live.finalize()

        res_sess = _session(su)
        res_sess.restore(ckpt)
        resumed = _service(res_sess, backend())
        res_resumed = resumed.run(seg2)

    ids = lambda res: [(p.record.cam_id, p.record.frame_idx, p.t_sent,
                        p.t_done) for p in res.processed]
    identical = (res_live.kept_mask == res_resumed.kept_mask
                 and ids(res_live) == ids(res_resumed)
                 and res_live.trace == res_resumed.trace)
    out = _report(res_resumed)
    out["bit_identical_resume"] = bool(identical)
    return out


def run(quick=True):
    su = _setup(quick)
    scenarios = {}
    with Timer() as t:
        scenarios["baseline"] = _baseline(su)
    scenarios["drift"] = _drift(su)
    scenarios["burst"] = _burst(su)
    scenarios["outage"] = _outage(su)
    scenarios["churn"] = _churn(su)
    scenarios["restart"] = _restart(su)

    base, out_, ch, rs = (scenarios[k] for k in
                          ("baseline", "outage", "churn", "restart"))
    derived = {
        "qor_baseline": base["qor"],
        "qor_drift": scenarios["drift"]["qor"],
        "shed_burst": scenarios["burst"]["shed_rate"],
        "outage_transport_shed": out_["transport_shed"],
        "outage_breaker_reclosed": out_["breaker_reclosed"],
        "outage_within_budget": out_["delivered_within_budget"],
        "churn_lane_reused": ch["lane_reused"],
        "restart_bit_identical": rs["bit_identical_resume"],
    }
    return {
        "us_per_call": t.us / max(base["offered"], 1),
        "derived": derived,
        "scenarios": scenarios,
    }


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=2))
