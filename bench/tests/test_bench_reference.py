"""The plain reference against the session at 48x80 on the CPU: the
same utilities, background state and admission decisions; and a
bfloat16 run of the reference, the control, fails the comparison."""
from __future__ import annotations

import json

import numpy as np
import pytest

from tiny import ROOT  # noqa: F401  (sets JAX_PLATFORMS, paths)


@pytest.fixture(scope="module")
def run():
    from bench import harness as h, reference as ref
    from bench.traffic_gen import render_scene
    config = json.loads((ROOT / "bench/configs/detrac24_540p.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/saturate.json").read_text())
    config.update(height=48, width=80, cameras=4)
    traffic["train"].update(frames=40)
    traffic["calib"].update(frames=40)
    model = h.fit_model(5, config, traffic)
    scene = render_scene(6, 4, 24, 48, 80, traffic["scene"])
    session = h.open_session(config, model)
    wins = [scene.frames[:, k * 8:(k + 1) * 8] for k in range(3)]
    items = [[[(c, k, t) for t in range(8)] for c in range(4)]
             for k in range(3)]
    decisions = []
    for k in range(3):
        res = session.step(frames=wins[k], items=items[k], tick=True)
        decisions.append((np.asarray(res.decisions),
                          np.asarray(res.target_drop_rate)))
        session.report_backend_latency(0.15)
    st = session.state
    W = st.cdf_buf.shape[1]
    n0 = model.calib_utilities.size
    idx = (n0 + np.arange(24)) % W
    prog_u = np.asarray(st.cdf_buf)[:, idx]
    return dict(config=config, model=model, wins=wins, items=items,
                decisions=decisions, prog_u=prog_u,
                bg=np.asarray(st.bg), gain=np.asarray(st.gain), ref=ref)


def _ref_ingest(r, dtype):
    import jax.numpy as jnp
    ref, out, bg, gain = r["ref"], [], None, jnp.ones((4,), dtype)
    for k, w in enumerate(r["wins"]):
        u, bg, gain, _ = ref.utilities(jnp.asarray(w), bg, gain, k > 0,
                                    r["config"], jnp.asarray(r["model"].m_pos),
                                    jnp.asarray(r["model"].norm), jnp, dtype)
        out.append(np.asarray(u, np.float32))
    return np.concatenate(out, axis=1), np.asarray(bg, np.float32)


def test_utilities_and_background_agree(run):
    import jax.numpy as jnp
    from bench.harness import LIMITS
    u, bg = _ref_ingest(run, jnp.float32)
    assert np.max(np.abs(u - run["prog_u"])) <= 1e-4
    assert np.max(np.abs(bg - run["bg"])) <= LIMITS["bg_gap"]
    assert np.isfinite(u).all() and (u > 0).any()


def test_numpy_reference_matches_jax_reference(run):
    import jax.numpy as jnp
    ref = run["ref"]
    u_np, bg, gain, _ = ref.utilities(run["wins"][0], None, np.ones(4, np.float32),
                                   False, run["config"], run["model"].m_pos,
                                   run["model"].norm, np, np.float32)
    u_j, _ = _ref_ingest(dict(run, wins=run["wins"][:1]), jnp.float32)
    np.testing.assert_allclose(u_np, u_j, atol=1e-6)


def test_decisions_agree(run):
    ref = run["ref"]
    ctl = ref.Control(4, run["config"], run["model"].calib_utilities,
                      run["config"]["camera_fps"])
    for k in range(3):
        dec, rates = run["decisions"][k]
        own = ctl.rates()
        mine = ctl.step(run["prog_u"][:, k * 8:(k + 1) * 8], run["items"][k],
                        True)
        np.testing.assert_array_equal(mine, dec)
        np.testing.assert_allclose(own, rates, atol=1e-6)
        ctl.report_latency(0.15)
    assert (np.concatenate([d for d, _ in run["decisions"]]) != 0).any()


def test_bfloat16_control_fails(run):
    import jax.numpy as jnp
    from bench.harness import LIMITS
    u, bg = _ref_ingest(run, jnp.bfloat16)
    u32, bg32 = _ref_ingest(run, jnp.float32)
    assert (np.max(np.abs(u - u32)) > 1e-2
            or np.max(np.abs(bg - bg32)) > LIMITS["bg_gap"])


def test_limits_tool_reads_sound_and_control(capsys):
    """The readings tool at 48x80 on one seed: the program's numbers sit
    under every limit, and the bfloat16 control's ingest numbers do not."""
    import jax
    from bench import limits, run as R
    from tiny import tiny_cell
    spec, cell, config, traffic = tiny_cell("detrac24_540p.saturate", cameras=4)
    orig = R.load_cell
    R.load_cell = lambda name: (spec, cell, config, traffic)
    try:
        limits.main(["--workload", "detrac24_540p.saturate", "--seeds", "7",
                     "--seconds", "1", "--control-seeds", "1"],
                    device_check=lambda chips: jax.devices())
    finally:
        R.load_cell = orig
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    from bench.harness import LIMITS
    assert all(v <= LIMITS[k] for k, v in last["lower"].items()
               if k in LIMITS)
    assert any(v > LIMITS[k] for k, v in last["upper"].items()
               if k in LIMITS)
