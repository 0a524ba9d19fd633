"""Entry points: the serving launcher's ``main(argv)``, the compile-cache
location, and ``chip_smoke.py``'s refusal to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(args, env_extra=None, cwd=REPO, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=cwd)


def test_launcher_main_serves_at_requested_size(tmp_path, monkeypatch):
    """main(argv) renders at --height/--width and returns the result."""
    from repro.launch import serve
    # tests keep the persistent compile cache off
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    out = tmp_path / "m.json"
    res = serve.main(["--cams", "2", "--frames", "16", "--height", "24",
                      "--width", "40", "--metrics-out", str(out)])
    assert len(res.offered) == 32
    assert len(res.processed) > 0
    assert res.metrics["counters"]["dispatch.fused"] > 0
    assert json.loads(out.read_text())["counters"]["ingest.offered"] == 32


def test_compile_cache_location(tmp_path):
    code = ("from repro.launch.jax_cache import enable_compile_cache;"
            "import jax; d = enable_compile_cache();"
            "print(d); print(jax.config.jax_compilation_cache_dir)")
    own = _run(["-c", code], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert own.returncode == 0, own.stderr[-2000:]
    assert own.stdout.split() == [str(tmp_path), str(tmp_path)]
    fixed = _run(["-c", code])
    assert fixed.returncode == 0, fixed.stderr[-2000:]
    assert fixed.stdout.split() == [str(REPO / ".jax_cache")] * 2


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """No TPU (CPU only), in the repo and alone in a directory: non-zero
    exit and no result line."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text((REPO / "chip_smoke.py").read_text())
    for script, cwd in ((REPO / "chip_smoke.py", REPO),
                        (alone, tmp_path)):
        out = _run([str(script)], cwd=cwd)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout
