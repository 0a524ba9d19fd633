"""``bench/copy_probe.py`` at a small window on the CPU."""
from __future__ import annotations

from tiny import tiny_cell  # noqa: F401  (sets JAX_PLATFORMS, paths)


def test_copy_probe_times_every_layout():
    from bench.copy_probe import LAYOUTS, measure
    out = measure((2, 3, 4, 6, 3), repeats=2)
    n = 2 * 3 * 4 * 6 * 3
    assert out["u8_5d_bytes"] == out["u8_flat_pixels_bytes"] == n
    assert out["f32_5d_convert_included_bytes"] == 4 * n
    for name in LAYOUTS:
        assert len(out[name + "_ms"]) == 2
        assert all(t > 0 for t in out[name + "_ms"])


def test_copy_probe_exits_3_without_a_chip(capsys):
    from bench.copy_probe import main
    assert main([]) == 3
    assert "no TPU" in capsys.readouterr().err
