"""Training launcher: end-to-end driver on whatever devices exist.

Wires together: config -> param init (sharded) -> AdamW -> fault-tolerant
driver (checkpoint/restart/straggler) -> token pipeline.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 200 --batch 16 --seq 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config, scaled
from repro.data.pipeline import BigramStream
from repro.launch.mesh import make_host_mesh
from repro.models import lm_specs
from repro.sharding.api import (
    materialize,
    num_params,
    spec_partition_specs,
    spec_shardings,
)
from repro.train.fault import FaultConfig, FaultInjector, run_training
from repro.train.optimizer import AdamW, warmup_cosine
from repro.train.step import make_train_step


def build(arch: str, smoke: bool, batch: int, seq: int, steps: int,
          data_axis: int = 1, model_axis: int = 1, lr: float = 3e-4):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    mesh = make_host_mesh(data_axis, model_axis)
    specs = lm_specs(cfg)
    shardings = spec_shardings(specs, mesh)
    pspecs = spec_partition_specs(specs, mesh)
    opt = AdamW(lr=warmup_cosine(lr, max(10, steps // 20), steps))

    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: materialize(specs, k),
                         out_shardings=shardings)(jax.random.key(0))
        opt_state = jax.jit(opt.init, out_shardings={
            "m": shardings, "v": shardings,
            "step": NamedSharding(mesh, P())})(params)
        step = make_train_step(cfg, opt)
        bspec = NamedSharding(mesh, P("data", None))
        jstep = jax.jit(step, donate_argnums=(0, 1))
    return cfg, mesh, params, opt_state, jstep, bspec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints/train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-fault-at", type=int, default=None)
    args = ap.parse_args()

    cfg, mesh, params, opt_state, jstep, bspec = build(
        args.arch, args.smoke, args.batch, args.seq, args.steps, lr=args.lr)
    from repro.sharding.api import num_params as npar
    from repro.models import lm_specs as _sp
    print(f"arch={cfg.name} params={num_params(_sp(cfg)):,} "
          f"devices={len(jax.devices())}")

    stream = BigramStream(cfg.vocab_size, seed=0)

    def batch_fn(step_idx):
        rng = np.random.default_rng(1000 + step_idx)   # replay-deterministic
        toks = stream.sample(rng, args.batch, args.seq)
        return {
            "tokens": jax.device_put(toks[:, :-1], bspec),
            "labels": jax.device_put(toks[:, 1:], bspec),
        }

    state = {"params": params, "opt_state": opt_state}

    def step_fn(state, batch):
        with jax.set_mesh(mesh):
            p, o, m = jstep(state["params"], state["opt_state"], batch)
        return {"params": p, "opt_state": o}, m

    injector = (FaultInjector([args.inject_fault_at])
                if args.inject_fault_at is not None else None)
    fcfg = FaultConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)

    def cb(step_idx, metrics, dt):
        if step_idx % 10 == 0 or step_idx == args.steps - 1:
            print(f"step {step_idx:5d} loss={metrics['loss']:.4f} "
                  f"gnorm={metrics['grad_norm']:.3f} {dt*1e3:.0f}ms", flush=True)

    report = run_training(step_fn, state, batch_fn, args.steps, fcfg,
                          injector=injector, metrics_cb=cb)
    print(f"done: steps={report.steps_run} restarts={report.restarts} "
          f"stragglers={report.stragglers} "
          f"final_loss={report.last_metrics.get('loss'):.4f}")
    return report


if __name__ == "__main__":
    main()
