"""Fused batched ingest kernel vs. jnp oracle, plus the state-carry and
memory-lean-oracle contracts (ISSUE 3 acceptance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.colors import BLUE, RED, YELLOW, hue_mask
from repro.core.utility import (
    UtilityModel,
    batch_utilities,
    pixel_fraction_matrix,
)
from repro.kernels.hsv_features.kernel import BLOCK, ingest_batch
from repro.kernels.hsv_features.ops import IngestState, ingest_pipeline
from repro.kernels.hsv_features.ref import (
    ema_background_scan,
    ingest_batch_ref,
    pf_from_counts,
)

HR2 = (tuple(RED.hue_ranges), tuple(YELLOW.hue_ranges))


def _toy_model(rng, colors, op="or"):
    nc = len(colors)
    M = rng.uniform(0, 1, (nc, 8, 8)).astype(np.float32)
    return UtilityModel(tuple(colors), M, np.zeros_like(M),
                        rng.uniform(0.3, 1.0, nc).astype(np.float32), op)


@pytest.mark.parametrize("T", [1, 3, 8])
@pytest.mark.parametrize("n", [257, BLOCK, BLOCK + 100, 2 * BLOCK + 17])
def test_ingest_kernel_matches_oracle(T, n, rng):
    """Batch sizes x non-multiple-of-BLOCK pixel counts (padding edge)."""
    rgb = jnp.asarray(rng.uniform(0, 255, (T, n, 3)), jnp.float32)
    bg0 = jnp.asarray(rng.uniform(0, 255, n), jnp.float32)
    M = jnp.asarray(rng.uniform(0, 1, (2, 64)), jnp.float32)
    norm = jnp.asarray([0.5, 0.8], jnp.float32)
    k = ingest_batch(rgb, bg0, 1.1, M, norm, HR2, interpret=True)
    r = ingest_batch_ref(rgb, bg0, 1.1, M, norm, HR2)
    for name, a, b in zip(("counts", "totals", "fgtot", "util", "bg",
                           "gain"), k, r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-5, err_msg=name)


def test_ingest_kernel_gain_is_the_jnp_row_sum_ratio(rng):
    """Each frame's gain is sum(v) / sum(background) summed as a plain
    jnp row sum, bit for bit, over frames with a carried background:
    frames of many tiles, whose sums' last bits move with the order of
    the additions."""
    C, T, n = 2, 3, 24 * BLOCK
    rgb = jnp.asarray(rng.integers(0, 256, (C, T, n, 3)), jnp.uint8)
    bg0 = jnp.asarray(rng.uniform(0, 255, (C, n)) * 2.0 ** rng.integers(
        -3, 2, (C, n)), jnp.float32)
    M = jnp.zeros((1, 64), jnp.float32)
    norm = jnp.ones((1,), jnp.float32)
    *_, bg, gain = ingest_batch(rgb, bg0, 1.0, M, norm,
                                (tuple(RED.hue_ranges),), interpret=True)

    @jax.jit
    def plain(rgb, bg):
        g = jnp.ones((C,), jnp.float32)
        for t in range(T):
            v = jnp.max(rgb[:, t].astype(jnp.float32), axis=-1)
            comp = v / g[:, None]
            g = jnp.clip(v.sum(axis=1) / jnp.maximum(bg.sum(axis=1), 1e-6),
                         0.25, 4.0)
            bg = 0.95 * bg + 0.05 * comp
        return bg, g

    want_bg, want_gain = plain(rgb, bg0)
    assert np.all((want_gain > 0.25) & (want_gain < 4.0))
    np.testing.assert_array_equal(np.asarray(gain), np.asarray(want_gain))
    np.testing.assert_array_equal(np.asarray(bg), np.asarray(want_bg))


@pytest.mark.parametrize("bg_valid", [False, True])
@pytest.mark.parametrize("use_fg", [True, False])
def test_ingest_kernel_fg_and_fresh_state(bg_valid, use_fg, rng):
    rgb = jnp.asarray(rng.uniform(0, 255, (4, 900, 3)), jnp.float32)
    bg0 = jnp.asarray(rng.uniform(0, 255, 900), jnp.float32)
    M = jnp.asarray(rng.uniform(0, 1, (1, 64)), jnp.float32)
    norm = jnp.ones((1,), jnp.float32)
    hr = (tuple(RED.hue_ranges),)
    k = ingest_batch(rgb, bg0, 1.0, M, norm, hr, use_fg=use_fg,
                     bg_valid=bg_valid, interpret=True)
    r = ingest_batch_ref(rgb, bg0, 1.0, M, norm, hr, use_fg=use_fg,
                         bg_valid=bg_valid)
    for name, a, b in zip(("counts", "totals", "fgtot", "util", "bg",
                           "gain"), k, r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("op", ["or", "and"])
def test_ingest_multicolor_composition(op, rng):
    """OR -> max, AND -> min over per-color normalized utilities."""
    colors = [RED, YELLOW, BLUE]
    model = _toy_model(rng, colors, op)
    rgb = rng.uniform(0, 255, (6, 24, 40, 3)).astype(np.float32)
    pf, hf, util, _ = ingest_pipeline(rgb, colors, model, impl="pallas",
                                      interpret=True)
    pf_j, hf_j, util_j, _ = ingest_pipeline(rgb, colors, model, impl="jnp")
    np.testing.assert_allclose(np.asarray(pf), np.asarray(pf_j), atol=1e-4)
    np.testing.assert_allclose(np.asarray(util), np.asarray(util_j),
                               atol=1e-4)
    # in-kernel utility == host-side batched scoring of the same PFs
    np.testing.assert_allclose(np.asarray(util),
                               batch_utilities(model, np.asarray(pf)),
                               atol=1e-4)
    # a conflicting caller-supplied op must not override the model's op
    _, _, util_c, _ = ingest_pipeline(rgb, colors, model,
                                      op=("or" if op == "and" else "and"),
                                      impl="jnp")
    np.testing.assert_allclose(np.asarray(util_c), np.asarray(util_j),
                               atol=1e-4)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_ingest_state_carry_across_batches(impl, rng):
    """Chunked ingest with carried (bg, gain) == one long batch."""
    colors = [RED]
    rgb = rng.uniform(0, 255, (10, 30, 50, 3)).astype(np.float32)
    interp = True if impl == "pallas" else None
    p_all, h_all, _, _ = ingest_pipeline(rgb, colors, impl=impl,
                                         interpret=interp)
    state = None
    chunks = []
    for i in range(0, 10, 4):        # uneven final chunk on purpose
        p, h, _, state = ingest_pipeline(rgb[i:i + 4], colors, state=state,
                                         impl=impl, interpret=interp)
        chunks.append(np.asarray(p))
    np.testing.assert_allclose(np.concatenate(chunks), np.asarray(p_all),
                               atol=1e-4)
    assert isinstance(state, IngestState)
    assert state.bg.shape == (30 * 50,)


def test_ema_background_matches_host_model(rng):
    """The oracle scan == the host-side EMABackground mirror."""
    from repro.data.background import EMABackground
    frames = rng.uniform(0, 255, (6, 12, 20, 3)).astype(np.float32)
    host = EMABackground()
    host_fg = np.stack([host(f) for f in frames])
    v = jnp.asarray(frames[..., 2].reshape(6, -1))
    fg, bg, gain = ema_background_scan(v, jnp.zeros(240), 1.0,
                                       bg_valid=False)
    np.testing.assert_array_equal(np.asarray(fg).reshape(6, 12, 20), host_fg)
    np.testing.assert_allclose(np.asarray(bg).reshape(12, 20),
                               host.state[0], rtol=1e-5)
    assert host.state[1] == pytest.approx(float(gain), rel=1e-5)


def test_pixel_fraction_matrix_memory_lean_parity(rng):
    """Segment-sum formulation == explicit one-hot math, incl. batch dims."""
    hsv = jnp.asarray(rng.uniform(0, 255, (3, 16, 24, 3)), jnp.float32)
    hsv = hsv.at[..., 0].multiply(180.0 / 255.0)
    fg = jnp.asarray(rng.random((3, 16, 24)) < 0.7)
    got = pixel_fraction_matrix(hsv, RED, fg)
    # explicit dense reference
    h, s, v = hsv[..., 0], hsv[..., 1], hsv[..., 2]
    m = (hue_mask(h, RED) & fg).astype(np.float32)
    sb = np.clip(np.asarray(s, np.int32) // 32, 0, 7)
    vb = np.clip(np.asarray(v, np.int32) // 32, 0, 7)
    want = np.zeros((3, 8, 8), np.float32)
    for b in range(3):
        for y in range(16):
            for x in range(24):
                want[b, sb[b, y, x], vb[b, y, x]] += m[b, y, x]
    want /= np.maximum(want.sum(axis=(1, 2), keepdims=True), 1.0)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)


def test_scenario_records_fused_utilities(rng):
    """scenario_records with a model fills record.utility in-pipeline."""
    from repro.data.pipeline import scenario_records
    from repro.data.synthetic import generate_scenario
    sc = generate_scenario(0, num_frames=40, height=24, width=40)
    model = _toy_model(np.random.default_rng(1), [RED], "or")
    recs = scenario_records(sc, 0, [RED], model=model, batch=16)
    us = np.array([r.utility for r in recs])
    assert np.isfinite(us).all()
    np.testing.assert_allclose(
        us, batch_utilities(model, np.stack([r.pf for r in recs])),
        atol=1e-4)
