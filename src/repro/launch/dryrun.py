import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the sharding config is coherent (no GSPMD
errors), records memory_analysis (fits per chip?), cost_analysis
(FLOPs/bytes) and the per-device collective bytes parsed from the
partitioned HLO — the inputs to the roofline analysis (EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_cells, get_config
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models import init_caches, lm_specs, padded_vocab
from repro.sharding.api import (
    DEFAULT_RULES,
    num_params,
    spec_partition_specs,
    spec_shapes,
)
from repro.sharding.caches import cache_partition_specs
from repro.train.optimizer import AdamW, constant_lr
from repro.train.step import make_decode_step, make_prefill_step, make_train_step

FSDP_RULES = {**DEFAULT_RULES, "embed": ("data",)}


def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def input_specs(cfg, shape, mesh):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    B, S = shape.global_batch, shape.seq_len
    dp = _dp_axes(mesh)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    batch_spec = P(dp if B > 1 else None, None)
    if shape.kind == "train":
        batch = {"tokens": tok, "labels": tok}
        specs = {"tokens": batch_spec, "labels": batch_spec}
        if cfg.is_encoder_decoder:
            batch["audio_embed"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
            specs["audio_embed"] = P(dp if B > 1 else None, None, None)
        return batch, specs
    if shape.kind == "prefill":
        batch = {"tokens": tok}
        specs = {"tokens": batch_spec}
        if cfg.is_encoder_decoder:
            batch["audio_embed"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
            specs["audio_embed"] = P(dp if B > 1 else None, None, None)
        return batch, specs
    # decode
    tok1 = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    return {"tokens": tok1, "pos": jax.ShapeDtypeStruct((), jnp.int32)}, \
        {"tokens": P(dp if B > 1 else None, None), "pos": P()}


def lower_cell(arch: str, shape_name: str, mesh, *, fsdp: bool = True,
               unroll: bool = False, opts: tuple = ()):
    import dataclasses as _dc
    cfg = get_config(arch)
    if unroll:
        cfg = _dc.replace(cfg, scan_layers=False)
    if opts:
        cfg = _dc.replace(cfg, **{f"opt_{o}": True for o in opts})
    shape = SHAPES[shape_name]
    rules = FSDP_RULES if (fsdp and shape.kind == "train") else DEFAULT_RULES
    specs = lm_specs(cfg)
    pdtype = "float32" if shape.kind == "train" else "bfloat16"
    param_shapes = spec_shapes(specs, dtype_override=pdtype)
    param_pspecs = spec_partition_specs(specs, mesh, rules)
    n_params = num_params(specs)
    batch, batch_pspecs = input_specs(cfg, shape, mesh)

    def shard(tree_pspecs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree_pspecs,
            is_leaf=lambda x: isinstance(x, P))

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = AdamW(lr=constant_lr(3e-4))
            opt_shapes = jax.eval_shape(opt.init, param_shapes)
            opt_pspecs = {"m": param_pspecs, "v": param_pspecs, "step": P()}
            step = make_train_step(cfg, opt)
            jitted = jax.jit(
                step,
                in_shardings=(shard(param_pspecs), shard(opt_pspecs),
                              shard(batch_pspecs)),
                out_shardings=(shard(param_pspecs), shard(opt_pspecs), None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(param_shapes, opt_shapes, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, max_seq=shape.seq_len)
            jitted = jax.jit(step, in_shardings=(shard(param_pspecs),
                                                 shard(batch_pspecs)))
            lowered = jitted.lower(param_shapes, batch)
        else:
            cache_shapes = jax.eval_shape(
                lambda: init_caches(cfg, shape.global_batch, shape.seq_len))
            cache_pspecs = cache_partition_specs(cache_shapes, mesh,
                                                 shape.global_batch)
            step = make_decode_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(shard(param_pspecs), shard(cache_pspecs),
                              shard(batch_pspecs["tokens"]),
                              shard(batch_pspecs["pos"])),
                donate_argnums=(1,))
            lowered = jitted.lower(param_shapes, cache_shapes,
                                   batch["tokens"], batch["pos"])
    return lowered, n_params, cfg


def analyse_cell(arch: str, shape_name: str, *, multi_pod: bool,
                 fsdp: bool = True, want_hlo: bool = True,
                 cost_mode: str = "unroll", opts: tuple = ()) -> dict:
    """Compile the scanned program (deployment form: memory proof) and,
    for the roofline cost terms, an unrolled-layers variant — XLA's
    cost_analysis counts while-loop bodies once, so the scanned program
    under-reports FLOPs/bytes/collectives by ~pattern_repeats."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, n_params, cfg = lower_cell(arch, shape_name, mesh, fsdp=fsdp,
                                        opts=opts)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text() if want_hlo else ""
    coll = collective_bytes(hlo)
    cost_source = "scan"
    if cost_mode == "unroll":
        try:
            lowered_u, _, _ = lower_cell(arch, shape_name, mesh, fsdp=fsdp,
                                         unroll=True, opts=opts)
            compiled_u = lowered_u.compile()
            cost = compiled_u.cost_analysis()
            coll = collective_bytes(compiled_u.as_text())
            cost_source = "unroll"
        except Exception as e:  # noqa: BLE001 — fall back to scan counts
            cost_source = f"scan (unroll failed: {type(e).__name__})"

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    from repro.configs.base import active_param_fraction
    n_active = n_params * active_param_fraction(cfg, n_params)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_active * tokens
    chips = int(np.prod(list(mesh.shape.values())))
    terms = roofline_terms(flops, bytes_acc, coll["total"])
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "chips": chips, "fsdp": fsdp,
        "n_params": n_params,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_est": int(mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes),
        },
        "cost": {"flops_per_device": flops,
                 "bytes_per_device": bytes_acc,
                 "cost_source": cost_source},
        "collectives": coll,
        "model_flops_global": model_flops,
        "model_flops_per_device": model_flops / chips,
        "useful_flops_ratio": (model_flops / chips) / flops if flops else 0.0,
        "roofline": terms,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", action="append", default=[],
                    help="enable beyond-paper levers: head_nofsdp, "
                         "decode_carry, seq_shard, attn_remat")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    for arch, shape, skip in all_cells():
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape.name != args.shape:
            continue
        cells.append((arch, shape.name, skip))
    if not cells:
        raise SystemExit("no cells matched")

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for arch, shape_name, skip in cells:
        for multi in meshes:
            tagpart = f"--{args.tag}" if args.tag else ""
            name = f"{arch}--{shape_name}--{'multi' if multi else 'single'}{tagpart}.json"
            path = outdir / name
            if path.exists() and not args.force:
                print(f"[skip-existing] {name}")
                continue
            if skip:
                path.write_text(json.dumps(
                    {"arch": arch, "shape": shape_name,
                     "mesh": "multi" if multi else "single",
                     "skipped": skip}, indent=2))
                print(f"[skipped] {arch} {shape_name}: {skip}")
                continue
            print(f"[dryrun] {arch} {shape_name} multi_pod={multi} ...",
                  flush=True)
            try:
                res = analyse_cell(arch, shape_name, multi_pod=multi,
                                   fsdp=not args.no_fsdp,
                                   opts=tuple(args.opt))
                res["opts"] = list(args.opt)
                path.write_text(json.dumps(res, indent=2))
                r = res["roofline"]
                print(f"  ok: compile={res['compile_s']}s "
                      f"peak={res['memory']['peak_bytes_est']/2**30:.2f}GiB/dev "
                      f"compute={r['compute_s']:.4f}s mem={r['memory_s']:.4f}s "
                      f"coll={r['collective_s']:.4f}s dom={r['dominant']} "
                      f"frac={r['roofline_fraction']:.3f}", flush=True)
            except Exception as e:
                err = {"arch": arch, "shape": shape_name,
                       "mesh": "multi" if multi else "single",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                path.with_suffix(".error.json").write_text(json.dumps(err, indent=2))
                print(f"  FAILED: {type(e).__name__}: {str(e)[:400]}", flush=True)


if __name__ == "__main__":
    main()
