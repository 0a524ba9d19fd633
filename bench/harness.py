"""What the cell drivers share: the device check, the compile counter,
the scene and utility model a run is built from, the session under
test, host spans, and the comparison with the plain reference that
decides ``correct``.

Nothing here is specific to one configuration or traffic mix; those
come in as the parsed ``bench/configs/<name>.json`` and
``bench/traffic/<mix>.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List

import numpy as np

from bench import reference as ref
from bench.traffic_gen import render_scene

# the limit of each compared number; see PERF.md for the readings each
# was set from
LIMITS = {
    "util_gap": 6e-2,          # largest |utility - reference| of any frame
    "bg_gap": 5e-2,            # largest |background - reference|, 0..255
    "gain_gap": 1e-4,          # largest |gain - reference|
    "rate_gap": 1e-5,          # largest |Eq. 19 drop rate - reference|
    "util_missing": 0,         # frames whose utility the state lacks
    "decision_mismatch": 0,    # admission codes unlike the reference's
    "pop_mismatch": 0,         # popped frames unlike the reference's
    "threshold_mismatch": 0,   # final thresholds unlike the reference's
    "queue_mismatch": 0,       # final queue entries unlike the reference's
    "cap_mismatch": 0,         # final queue sizes unlike the reference's
    "undecided": 0,            # offered frames without exactly one decision
    "delivered_unpopped": 0,   # delivered frames the queues never sent
    "unfused_windows": 0,      # windows served without the fused step
}


class NoChip(SystemExit):
    """Raised (exit code 3) when JAX finds no TPU or too few chips."""


def require_devices(chips: int):
    """The devices a cell runs on; refuses any platform but a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (platform "
                     f"{devices[0].platform!r}); nothing was measured")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.n += 1


class Spans:
    """Host spans on the ``time.perf_counter`` clock, as (start, end,
    name); the trace reduction maps them onto the device's clock."""

    def __init__(self) -> None:
        self.out: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.out.append((t0, time.perf_counter(), name))


SPANS = Spans()


def span(name: str):
    """A host span of the benchmark (``bench.*``), recorded in SPANS."""
    return SPANS(name)


class Order:
    """Which rendered stream each camera replays and where in the pool
    it starts. The lanes' (stream, offset) pairs come from the traffic
    file's ``scene_seed``: lane c replays stream c mod S, the lanes of
    one stream spaced evenly over the pool from a drawn start. A run's
    ``--seed`` only permutes the pairs over the cameras and seeds the
    mock backend's jitter, so every seed serves the same content and
    arrivals in another order."""

    def __init__(self, seed: int, cameras: int, streams: int, pool: int,
                 layout_seed: int) -> None:
        lay = np.random.default_rng(int(layout_seed))
        start = lay.integers(0, pool, streams)
        per = -(-cameras // streams)
        lanes = np.arange(cameras)
        stream = lanes % streams
        offset = (start[stream] + (lanes // streams) * pool // per) % pool
        rng = np.random.default_rng(int(seed) % 2 ** 64)
        perm = rng.permutation(cameras)
        self.stream, self.offset = stream[perm], offset[perm]
        self.backend_seed = int(rng.integers(0, 2 ** 31 - 1))


@dataclasses.dataclass
class Model:
    m_pos: np.ndarray      # (colours, bins)
    norm: np.ndarray       # (colours,)
    calib_utilities: np.ndarray


def _pf_matrices(seed: int, sizes: dict, config: dict, traffic: dict):
    """PF matrices and labels of every frame of low-resolution renders."""
    scene = render_scene(seed, sizes["streams"], sizes["frames"],
                         sizes["height"], sizes["width"], traffic["scene"])
    pfs = np.concatenate([ref.pf_matrices(f, config) for f in scene.frames])
    return pfs, scene.labels.reshape(-1, scene.labels.shape[-1])


def fit_model(seed: int, config: dict, traffic: dict) -> Model:
    """The query's utility model (Eq. 12 and the normaliser), fitted on
    renders of training scenes drawn from ``seed``. The utilities that
    seed the admission windows come from held-out renders
    (``calib_seed``): unseen frames score as live ones do, while the
    training frames' own top decile scores well above them."""
    colors = config["query"]["colors"]
    pfs, labels = _pf_matrices(seed, traffic["train"], config, traffic)
    m_pos, norm = ref.fit_model(pfs, labels[:, :len(colors)])
    held, _ = _pf_matrices(traffic["calib_seed"], traffic["calib"], config,
                           traffic)
    return Model(m_pos, norm, ref.score(held, m_pos, norm,
                                        config["query"]["op"]))


def open_session(config: dict, model: Model, mesh=None):
    """The session under test, through the program's public entry."""
    from repro.core import Query, open_session as _open
    from repro.core.utility import UtilityModel
    q = config["query"]
    bs, bv = config["bins"]
    query = Query(colors=tuple(q["colors"]), op=q["op"],
                  latency_bound=config["latency_bound_s"],
                  fps=config["camera_fps"], bs=bs, bv=bv,
                  alpha=config["background_alpha"],
                  threshold=config["fg_threshold"])
    m = model.m_pos.reshape(len(q["colors"]), bs, bv)
    um = UtilityModel(tuple(query.colors), m, np.zeros_like(m),
                      model.norm, q["op"])
    return _open(query, num_cameras=config["cameras"],
                 frame_shape=(config["height"], config["width"]), model=um,
                 train_utilities=model.calib_utilities,
                 queue_size=config["queue_size"],
                 queue_capacity=config["queue_capacity"],
                 cdf_window=config["cdf_window"],
                 quantile_bins=config["quantile_bins"],
                 quantile_range=tuple(config["quantile_range"]),
                 serve="device", mesh=mesh)


@dataclasses.dataclass(eq=False)
class Frame:
    """One camera frame as the cameras offer it. ``t_gen`` is its
    scheduled capture instant on the service clock; ``busy`` says
    whether the backend runs its DNN stage on it."""
    cam: int
    index: int           # the camera's frame counter
    stream: int
    pool: int            # the frame's index in its stream's pool
    busy: bool
    objects: tuple
    t_gen: float = 0.0

    @property
    def cam_id(self) -> int:
        return self.cam

    @property
    def key(self):
        return (self.cam, self.index)


class Log:
    """What the timed path produced, step by step, for the reference.

    ``steps``: per step the (C, T) pool indices and stream of each
    camera's frames, the items, the decisions and, after a tick, the
    program's Eq. 19 rates. ``events``: the ordered calls into the
    session's control surface (steps, ticks, latency and ingress
    reports, pops), which the reference replays."""

    def __init__(self, session) -> None:
        self.session = session
        self.events: List[tuple] = []
        self.steps: List[dict] = []
        self.utilities: List[np.ndarray] = []     # (C, pushes) chunks
        self._pos = self._read_pos()
        self._since = 0

    def _read_pos(self) -> np.ndarray:
        return np.asarray(self.session.state.cdf_pos).astype(np.int64)

    def snapshot(self) -> None:
        """Read back the utilities pushed into the session's CDF windows
        since the last snapshot. Taken before the window can wrap."""
        pos = self._read_pos()
        W = int(self.session.state.cdf_buf.shape[1])
        n = int(((pos - self._pos) % W)[0])
        if n:
            buf = np.asarray(self.session.state.cdf_buf)
            idx = (self._pos[:, None] + np.arange(n)[None, :]) % W
            self.utilities.append(np.take_along_axis(buf, idx, axis=1))
        self._pos = pos
        self._since = 0

    def step(self, frames_index, items, result, tick: bool) -> None:
        T = len(items[0])
        self.steps.append({"index": frames_index, "items": items,
                           "decisions": np.asarray(result.decisions),
                           "rates": (None if result.target_drop_rate is None
                                     else np.asarray(result.target_drop_rate,
                                                     np.float32)),
                           "T": T})
        self.events.append(("step", len(self.steps) - 1, tick))
        self._since += T
        W = int(self.session.state.cdf_buf.shape[1])
        if self._since + 16 > W // 2:
            self.snapshot()


def compare(config: dict, model: Model, log: Log, final: dict,
            frames_of: Callable[[dict], np.ndarray], xp_ingest,
            fps: float, control=None) -> Dict[str, float]:
    """Every number the check compares, from the program's log and
    final state against the plain reference.

    ``control``: the reference's ingest in a lower precision; when
    given, ``out["control"]`` holds the same ingest numbers with it in
    the program's place, and the admission decisions it leads to."""
    out: Dict[str, float] = {}
    # -- ingest: utilities of every frame and the carried state ------------
    prog_u = (np.concatenate(log.utilities, axis=1) if log.utilities
              else np.zeros((config["cameras"], 0), np.float32))
    total = sum(s["T"] for s in log.steps)
    out["util_missing"] = int(max(total - prog_u.shape[1], 0))
    per_step, ref_u, px, start = [], [], [], 0
    bg = gain = None
    seeded = False
    C = config["cameras"]
    for s in log.steps:
        u, bg, gain, step = xp_ingest(frames_of(s), bg, gain, seeded)
        seeded = True
        ref_u.append(np.asarray(u, np.float32))
        px.append(np.asarray(step, np.float64))
        per_step.append(prog_u[:, start:start + s["T"]]
                        if start + s["T"] <= prog_u.shape[1] else None)
        start += s["T"]
    if control is not None:
        out["control"] = _control_numbers(config, model, log, frames_of,
                                          control, ref_u, px, bg, gain, fps)
    gaps = [(float(np.max(np.abs(p - r))), float(np.max(np.abs(p - r) / q)))
            for p, r, q in zip(per_step, ref_u, px)
            if p is not None and p.size]
    out["util_gap"] = max(g[0] for g in gaps) if gaps else float("inf")
    out["util_gap_px"] = max(g[1] for g in gaps) if gaps else float("inf")
    out["bg_gap"] = float(np.max(np.abs(np.asarray(final["bg"], np.float64)
                                        - np.asarray(bg, np.float64))))
    out["gain_gap"] = float(np.max(np.abs(
        np.asarray(final["gain"], np.float64)
        - np.asarray(gain, np.float64))))
    # -- control plane, fed the program's utilities (compared above); its
    # drop rates, thresholds and queue sizes are the reference's own ------
    ctl = ref.Control(C, config, model.calib_utilities, fps)
    mism = pops = 0
    rate_gap = 0.0
    for ev in log.events:
        kind = ev[0]
        if kind == "step":
            s = log.steps[ev[1]]
            u = per_step[ev[1]]
            if u is None:
                continue
            own = ctl.rates()
            dec = ctl.step(u, s["items"], ev[2])
            mism += int((dec != s["decisions"]).sum())
            if ev[2] and s["rates"] is not None:
                rate_gap = max(rate_gap, float(np.max(np.abs(
                    own - s["rates"]))))
        elif kind == "tick":
            rate_gap = max(rate_gap, float(np.max(np.abs(
                ctl.tick() - ev[1]))))
        elif kind == "latency":
            ctl.report_latency(ev[1])
        elif kind == "fps":
            ctl.report_fps(ev[1])
        elif kind == "pop":
            want = ctl.pop(ev[1])
            got = ev[2]
            pops += sum(a is not b for a, b in zip(want, got))
            pops += abs(len(want) - len(got))
    out["decision_mismatch"] = mism
    out["pop_mismatch"] = pops
    out["rate_gap"] = rate_gap
    out["threshold_mismatch"] = int(np.sum(
        np.asarray(final["threshold"], np.float32) != ctl.threshold))
    K = int(config["queue_capacity"])
    out["cap_mismatch"] = int(np.sum(
        np.minimum(np.asarray(final["queue_cap"]), K)
        != np.minimum(ctl.cap, K)))
    qm = 0
    for c in range(C):
        qu, qs = final["q_util"][c], final["q_seq"][c]
        live = sorted(((float(u), int(s)) for u, s in zip(qu, qs) if s >= 0),
                      key=lambda e: (e[0], e[1]))
        want = sorted(((e[0], e[1]) for e in ctl.queue[c]),
                      key=lambda e: (e[0], e[1]))
        qm += int(live != want) * max(len(live), len(want), 1)
    out["queue_mismatch"] = qm
    return out


def _control_numbers(config, model, log, frames_of, control, ref_u, px,
                     bg, gain, fps) -> Dict[str, float]:
    """The lower-precision reference in the program's place, against
    the float32 reference: its utilities, carried state, and the
    decisions each one's own control plane makes from its utilities."""
    cu, cbg, cgain, seeded = [], None, None, False
    for s in log.steps:
        u, cbg, cgain, _ = control(frames_of(s), cbg, cgain, seeded)
        seeded = True
        cu.append(np.asarray(u, np.float32))
    C = config["cameras"]
    a = ref.Control(C, config, model.calib_utilities, fps)
    b = ref.Control(C, config, model.calib_utilities, fps)
    import ml_dtypes
    mism, rate_gap = 0, 0.0
    for ev in log.events:
        if ev[0] in ("step", "tick"):
            rate_gap = max(rate_gap, float(np.max(np.abs(
                a.rates(ml_dtypes.bfloat16) - a.rates()))))
        if ev[0] == "step":
            s = log.steps[ev[1]]
            mism += int((a.step(ref_u[ev[1]], s["items"], ev[2])
                         != b.step(cu[ev[1]], s["items"], ev[2])).sum())
        elif ev[0] == "tick":
            a.tick()
            b.tick()
        elif ev[0] == "latency":
            a.report_latency(ev[1])
            b.report_latency(ev[1])
        elif ev[0] == "fps":
            a.report_fps(ev[1])
            b.report_fps(ev[1])
        elif ev[0] == "pop":
            a.pop(ev[1])
            b.pop(ev[1])
    return {
        "util_gap": max(float(np.max(np.abs(c - r)))
                        for c, r in zip(cu, ref_u)),
        "util_gap_px": max(float(np.max(np.abs(c - r) / q))
                           for c, r, q in zip(cu, ref_u, px)),
        "bg_gap": float(np.max(np.abs(np.asarray(cbg, np.float64)
                                      - np.asarray(bg, np.float64)))),
        "gain_gap": float(np.max(np.abs(np.asarray(cgain, np.float64)
                                        - np.asarray(gain, np.float64)))),
        "decision_mismatch": mism,
        "rate_gap": rate_gap,
    }


def final_state(session) -> dict:
    st = session.state
    return {k: np.asarray(getattr(st, k)) for k in (
        "bg", "gain", "threshold", "queue_cap", "q_util", "q_seq")}


def judge(numbers: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS
            if k in numbers}


def correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())


def ingest_on_device(config: dict, model: Model, dtype=None,
                     block: int = 8):
    """The reference's ingest, jitted with jax.numpy at float32 or
    ``dtype``, over blocks of ``block`` cameras so that it fits beside
    what the process already holds."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.float32 if dtype is None else dtype
    m_pos = jnp.asarray(model.m_pos)
    norm = jnp.asarray(model.norm)

    @jax.jit
    def seeded_fn(frames, bg, gain):
        return ref.utilities(frames, bg, gain, True, config, m_pos, norm,
                             jnp, dtype)

    @jax.jit
    def fresh_fn(frames):
        C = frames.shape[0]
        return ref.utilities(frames, None, jnp.ones((C,), dtype), False,
                             config, m_pos, norm, jnp, dtype)

    def run(frames, bg, gain, seeded):
        us, bgs, gains, steps = [], [], [], []
        for lo in range(0, frames.shape[0], block):
            x = jnp.asarray(frames[lo:lo + block])
            if seeded:
                u, b, g, st = seeded_fn(x, bg[lo:lo + block],
                                        gain[lo:lo + block])
            else:
                u, b, g, st = fresh_fn(x)
            us.append(u)
            bgs.append(b)
            gains.append(g)
            steps.append(st)
        return (np.concatenate([np.asarray(u) for u in us]),
                jnp.concatenate(bgs), jnp.concatenate(gains),
                np.concatenate([np.asarray(st) for st in steps]))

    return run


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
