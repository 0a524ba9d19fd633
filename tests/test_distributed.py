"""Multi-device tests (8 fake CPU devices, run in subprocesses so the
main pytest process keeps a single device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def run_py(code: str, ndev: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={ndev}").strip()
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Loss on a (2 data x 2 model) mesh == single-device loss."""
    out = run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import lm_specs, lm_loss
from repro.sharding.api import materialize, spec_shardings, auto_mesh
cfg = get_smoke_config('smollm-135m')
specs = lm_specs(cfg)
params = materialize(specs, jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (4, 33), 0, cfg.vocab_size)
batch = {'tokens': toks[:, :-1], 'labels': toks[:, 1:]}
l1, _ = jax.jit(lambda p, b: lm_loss(cfg, p, b))(params, batch)

mesh = auto_mesh((2, 2), ('data', 'model'))
sh = spec_shardings(specs, mesh)
with jax.set_mesh(mesh):
    ps = jax.device_put(params, sh)
    bs = {k: jax.device_put(v, NamedSharding(mesh, P('data', None)))
          for k, v in batch.items()}
    l2, _ = jax.jit(lambda p, b: lm_loss(cfg, p, b))(ps, bs)
print('LOSSES', float(l1), float(l2))
assert abs(float(l1) - float(l2)) < 5e-3, (float(l1), float(l2))
""")
    assert "LOSSES" in out


def test_pipeline_parallel_matches_unpipelined():
    out = run_py(r"""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_smoke_config, scaled
from repro.models import lm_specs, lm_loss
from repro.sharding.api import materialize, auto_mesh
from repro.train.pipeline_parallel import make_pp_loss
cfg = scaled(get_smoke_config('smollm-135m'), num_layers=4, remat='none')
specs = lm_specs(cfg)
params = materialize(specs, jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (8, 17), 0, cfg.vocab_size)
batch = {'tokens': toks[:, :-1], 'labels': toks[:, 1:]}
ref, _ = jax.jit(lambda p, b: lm_loss(cfg, p, b))(params, batch)

mesh = auto_mesh((4,), ('stage',))
pp_loss = make_pp_loss(cfg, mesh, num_microbatches=4)
with jax.set_mesh(mesh):
    lp = jax.jit(pp_loss)(params, batch)
print('PP', float(ref), float(lp))
assert abs(float(ref) - float(lp)) < 5e-3, (float(ref), float(lp))

# gradients flow through all stages
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(pp_loss))(params, batch)
gn = [float(jnp.sum(jnp.abs(x))) for x in jax.tree_util.tree_leaves(g['blocks'])]
assert all(v > 0 for v in gn), gn
print('PP-GRADS-OK')
""")
    assert "PP-GRADS-OK" in out


def test_dp_compressed_training_converges():
    out = run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config, scaled
from repro.models import lm_specs, lm_loss
from repro.sharding.api import materialize, auto_mesh
from repro.train.compression import make_dp_compressed_train_step
from repro.train.optimizer import AdamW, constant_lr
from repro.data.pipeline import BigramStream

cfg = scaled(get_smoke_config('smollm-135m'), num_layers=2)
params = materialize(lm_specs(cfg), jax.random.key(0))
opt = AdamW(lr=constant_lr(1e-2), weight_decay=0.0)
mesh = auto_mesh((4,), ('pod',))
loss_fn = lambda p, b: lm_loss(cfg, p, b)
step, init_ef = make_dp_compressed_train_step(loss_fn, opt, mesh, axis='pod',
                                              method='int8')
ef = init_ef(params)
opt_state = opt.init(params)
stream = BigramStream(cfg.vocab_size, seed=0)
rng = np.random.default_rng(0)
losses = []
with jax.set_mesh(mesh):
    jstep = jax.jit(step)
    for i in range(60):
        toks = stream.sample(rng, 8, 32)
        batch = {'tokens': jnp.asarray(toks[:, :-1]), 'labels': jnp.asarray(toks[:, 1:])}
        params, opt_state, ef, m = jstep(params, opt_state, ef, batch)
        losses.append(float(m['loss']))
print('FIRST', losses[0], 'LAST', losses[-1])
assert losses[-1] < losses[0] - 0.5, losses
""")
    assert "LAST" in out


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore onto 2-device and single-device."""
    out = run_py(r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import lm_specs
from repro.sharding.api import materialize, spec_shardings, spec_shapes, auto_mesh
from repro.train import checkpoint as ckpt
import tempfile, numpy as np

cfg = get_smoke_config('qwen2.5-32b')
specs = lm_specs(cfg)
mesh4 = auto_mesh((2, 2), ('data', 'model'))
sh4 = spec_shardings(specs, mesh4)
params = jax.device_put(materialize(specs, jax.random.key(0)), sh4)
d = tempfile.mkdtemp()
ckpt.save(d, 11, params)

mesh2 = auto_mesh((1, 2), ('data', 'model'))
sh2 = spec_shardings(specs, mesh2)
out2, step, _ = ckpt.restore(d, spec_shapes(specs), shardings=sh2)
assert step == 11
for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(out2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('ELASTIC-OK')
""")
    assert "ELASTIC-OK" in out
