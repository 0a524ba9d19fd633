"""``bench/session_split.py``: the device split of hand-made
``hlo_stats`` rows, and the host split of the saturate cell's run at
48x80 on the CPU."""
from __future__ import annotations

import math

import pytest

from tiny import tiny_cell  # noqa: F401  (sets JAX_PLATFORMS, paths)

STEP_PHASES = ["session.stage", "session.put", "session.dispatch",
               "session.readback", "session.absorb"]


def _row(program, hlo, tf_op, us, n=2):
    return {"program_id": program, "hlo_op_name": hlo, "tf_op_name": tf_op,
            "occurrences": n, "total_self_time": us}


def test_device_split_by_scope_and_program():
    from bench.session_split import device_split
    serve = "jit(_serve_step_dev)/jit(ingest_batch)"
    rows = [
        _row("1", "ingest_batch.1", serve + "/shed.score/ingest_batch/"
             "pallas_call:", 20000.0),
        _row("1", "copy.82", serve + "/shed.stage/reshape", 7000.0),
        _row("1", "copy.110", "", 300.0),
        _row("1", "fusion.3", "jit(_serve_step_dev)/shed.control/sort",
             100.0),
        _row("2", "reshape.1", "jit(_flatten_frames)/shed.stage/reshape",
             9000.0),
        _row("2", "copy.1", "frames:", 8000.0),
        _row("3", "sort.16", "jit(_pop_topk_dev)/sort:", 40.0),
        _row("4", "add.1", "jit(add)/add:", 60.0),
    ]
    out = device_split(rows, steps=2)
    ms = out["device_ms"]
    assert ms["shed.stage"] == pytest.approx((7000 + 9000 + 8000) / 2e3)
    assert ms["shed.score"] == pytest.approx(10.0)
    assert ms["shed.control"] == pytest.approx(0.05)
    assert ms["program:_serve_step_dev"] == pytest.approx(0.15)
    assert ms["program:_pop_topk_dev"] == pytest.approx(0.02)
    assert ms["program:add"] == pytest.approx(0.03)
    assert list(ms)[0] == "shed.stage"
    total = sum(r["total_self_time"] for r in rows) / 2e3
    assert out["device_total_ms"] == pytest.approx(total)
    assert out["scoped_share"] == pytest.approx(
        (total - 0.15 - 0.03) / total)
    assert out["ingest_calls_per_step"] == 1.0


def test_device_split_of_an_empty_table():
    from bench.session_split import device_split
    out = device_split([], steps=3)
    assert out["device_ms"] == {} and out["scoped_share"] is None


def test_measure_host_split_on_the_tiny_cell():
    from bench.session_split import measure
    _, _, cfg, traffic = tiny_cell("detrac24_540p.saturate", cameras=4)
    steps = 2
    out = measure(cfg, traffic, 2 ** 31 + 7, steps)
    C, T = cfg["cameras"], traffic["frames_per_step"]
    host = out["host_ms"]
    assert set(host) == {"session.step", "session.pop",
                         "session.report_latency", *STEP_PHASES}
    assert all(v > 0 for v in host.values())
    assert sum(host[k] for k in STEP_PHASES) <= host["session.step"]
    assert out["counters"] == {
        "session.steps": 1.0, "session.frames": C * T,
        "session.staged_bytes": C * T * cfg["height"] * cfg["width"] * 12}
    assert 0.0 <= out["step_self_share"] < 1.0
    assert set(out["step_ms"]) == {"plain", "metered"}
    assert math.isfinite(out["tracing_cost"])
    assert "device_ms" not in out
