"""Compile-only checks against a described TPU v5e topology.

The TPU compiler is installed even where no chip is attached: these
tests lower the fused ingest kernel, the one-chip device serve step and
the four-chip fleet serve step at camera resolution (8 cameras x 8
frames x 1280x720; the serve steps also with the camera's uint8
frames, the one-chip step at 24 x 8 x 960x540, the fleet step at 40 x
8 x 1920x1080) and compile them for the chip. Nothing runs, so they
say nothing about results or speed; they catch what interpret mode
cannot — tiling violations, VMEM overruns, programs that do not fit.

The topology is described inside module-scoped fixtures only: one
process at a time may load the TPU library, so describing it at import
would make pytest-xdist workers collect different tests.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

C, T, H, W = 8, 8, 720, 1280
NPIX = H * W


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def query():
    from repro.core import Query
    return Query.any_of("red", "yellow", latency_bound=0.5, fps=30.0)


def _ingest_kw(query):
    return dict(hue_ranges=query.hue_ranges, bs=query.bs, bv=query.bv,
                alpha=query.alpha, fg_threshold=query.threshold,
                use_fg=query.use_foreground, bg_valid=True, op="or",
                impl="pallas", interpret=False)


def _control_kw(num_total):
    from repro.core.control_plane import DEFAULT_TICK_CONFIG
    return dict(update_cdf=True, do_tick=True, min_proc=1e-6, budget=0.4,
                num_total=num_total, tick_cfg=DEFAULT_TICK_CONFIG)


def _state_shapes(num_cameras, sharding_of, npix=NPIX):
    """SessionState of ShapeDtypeStructs; ``sharding_of(name)`` places
    each leaf."""
    from repro.core.control_plane import SessionState
    st = jax.eval_shape(lambda: SessionState.fresh(num_cameras, npix))
    return SessionState(**{
        f.name: jax.ShapeDtypeStruct(getattr(st, f.name).shape,
                                     getattr(st, f.name).dtype,
                                     sharding=sharding_of(f.name))
        for f in dataclasses.fields(st)})


@pytest.mark.parametrize("with_bbox", [False, True])
def test_ingest_batch_compiles_at_720p(one_chip, query, with_bbox):
    from repro.kernels.hsv_features.kernel import ingest_batch
    nc = query.num_colors
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                           sharding=one_chip)

    def f(rgb, bg, gain, m, norm):
        return ingest_batch(rgb, bg, gain, m, norm, query.hue_ranges,
                            interpret=False, width=W if with_bbox else 0)

    compiled = jax.jit(f).lower(
        S((C, T, NPIX, 3)), S((C, NPIX)), S((C,)),
        S((nc, query.bs * query.bv)), S((nc,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_serve_step_compiles_at_720p(one_chip, query):
    from repro.core.control_plane import _serve_step_dev
    nc = query.num_colors
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                           sharding=one_chip)
    state = _state_shapes(C, lambda _: one_chip)
    compiled = _serve_step_dev.lower(
        state, S((C, T, NPIX, 3)), S((nc, query.bs * query.bv)), S((nc,)),
        **_ingest_kw(query), **_control_kw(C)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_device_serve_step_compiles_with_uint8_frames_at_540p(one_chip,
                                                               query):
    """The camera's uint8 frames as the benchmark's 24 cameras x 8
    frames x 960x540 window hands them over: the first relayout, then
    the serve step that converts them to float32 on the chip."""
    from repro.core.control_plane import _flatten_frames, _serve_step_dev
    cams, frames, h, w = 24, 8, 540, 960
    nc = query.num_colors
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    flat = _flatten_frames.lower(
        S((cams, frames, h, w, 3), jnp.uint8)).compile()
    assert flat.memory_analysis().output_size_in_bytes == (
        cams * frames * h * w * 3)
    state = _state_shapes(cams, lambda _: one_chip, npix=h * w)
    compiled = _serve_step_dev.lower(
        state, S((cams, frames, h * w, 3), jnp.uint8),
        S((nc, query.bs * query.bv)), S((nc,)),
        **_ingest_kw(query), **_control_kw(cams)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


# (cameras, frames per step, height, width, dtype, as the window comes):
# 32 x 8 x 720p pre-flattened, and the cityflow40_1080p deployment's window,
# 40 cameras x 8 frames x 1920x1080 uint8, as the session stages it
FLEET_WINDOWS = {
    "float32": (4 * C, T, H, W, jnp.float32, False),
    "uint8": (4 * C, T, H, W, jnp.uint8, False),
    "cityflow40_1080p": (40, 8, 1080, 1920, jnp.uint8, True),
}


@pytest.mark.parametrize("window", list(FLEET_WINDOWS.values()),
                         ids=list(FLEET_WINDOWS))
def test_fleet_serve_step_compiles_on_four_chips(topo, query, window):
    from jax.sharding import Mesh
    from repro.core import fleet
    from repro.core.control_plane import SessionState
    cams, frames_per_step, h, w, dtype, whole_frames = window
    mesh = Mesh(np.array(topo.devices[:4]), (fleet.CAMERA_AXIS,))
    axis = fleet.CAMERA_AXIS
    specs = fleet.state_pspecs(SessionState, axis)
    state = _state_shapes(
        cams, lambda name: NamedSharding(mesh, getattr(specs, name)),
        npix=h * w)
    nc = query.num_colors
    rep = NamedSharding(mesh, P())
    shape = ((cams, frames_per_step, h, w, 3) if whole_frames
             else (cams, frames_per_step, h * w, 3))
    frames = jax.ShapeDtypeStruct(shape, dtype,
                                  sharding=fleet.frame_sharding(mesh, axis))
    compiled = fleet._fleet_serve_step.lower(
        state, frames,
        jax.ShapeDtypeStruct((nc, query.bs * query.bv), jnp.float32,
                             sharding=rep),
        jax.ShapeDtypeStruct((nc,), jnp.float32, sharding=rep),
        mesh=mesh, axis=axis, aggregate=True,
        **_ingest_kw(query), **_control_kw(cams)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the psum aggregate tree is the step's only collective: no chip
    # gathers another's frames
    assert "all-reduce" in text
    assert "all-gather" not in text and "all-to-all" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_ingest_vmem_estimate_at_1080p_fits_the_cap():
    """1920x1080 is the first frame whose resident background lane asks
    for more than ``VMEM_FLOOR``; it stays under ``VMEM_CAP``."""
    from repro.kernels.hsv_features.kernel import (
        BLOCK, SUB, VMEM_CAP, VMEM_FLOOR, _ingest_vmem_bytes, _round_up)
    from repro.core import Query
    q = Query.any_of("red", "yellow")
    vmem = _ingest_vmem_bytes(_round_up(1920 * 1080, BLOCK),
                              _round_up(q.num_colors, SUB), q.bs * q.bv)
    assert VMEM_FLOOR < vmem < VMEM_CAP
