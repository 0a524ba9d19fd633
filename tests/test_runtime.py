"""Runtime substrate: checkpoint roundtrip + fault-tolerant driver +
gradient compression + straggler-guarded pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import BigramStream, TokenPipeline
from repro.train import checkpoint as ckpt
from repro.train.compression import (
    compress,
    ef_compressed_psum,
    int8_dequantize,
    int8_quantize,
    topk_mask,
)
from repro.train.fault import FaultConfig, FaultInjector, run_training
from repro.train.optimizer import AdamW, constant_lr, global_norm, warmup_cosine


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree(rng):
    return {"a": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
            "b": {"c": jnp.arange(7, dtype=jnp.int32),
                  "d": (jnp.ones((2,), jnp.bfloat16),)}}


def test_checkpoint_roundtrip(tmp_path, rng):
    tree = _tree(rng)
    ckpt.save(tmp_path, 7, tree, metadata={"note": "x"})
    out, step, meta = ckpt.restore(tmp_path, jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
    assert step == 7 and meta["note"] == "x"
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_prune(tmp_path, rng):
    tree = _tree(rng)
    for s in (1, 5, 9, 13):
        ckpt.save(tmp_path, s, tree)
    assert ckpt.latest_step(tmp_path) == 13
    ckpt.prune(tmp_path, keep=2)
    assert ckpt.latest_step(tmp_path) == 13
    assert len(list(tmp_path.glob("*.ckpt"))) == 2


def test_checkpoint_async(tmp_path, rng):
    tree = _tree(rng)
    t = ckpt.save(tmp_path, 3, tree, async_=True)
    t.join()
    assert ckpt.latest_step(tmp_path) == 3


def test_checkpoint_shape_mismatch_raises(tmp_path, rng):
    ckpt.save(tmp_path, 1, {"a": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        ckpt.restore(tmp_path, {"a": jax.ShapeDtypeStruct((3, 3), jnp.float32)})


# ---------------------------------------------------------------------------
# Fault-tolerant driver
# ---------------------------------------------------------------------------

def _toy_problem(tmp_path, fail_at=(), max_restarts=3, steps=20, every=5):
    opt = AdamW(lr=constant_lr(0.1), weight_decay=0.0)
    params = {"w": jnp.asarray([1.0, 2.0])}
    state = {"params": params, "opt_state": opt.init(params)}

    def step_fn(state, batch):
        def loss_fn(p):
            return jnp.sum((p["w"] - batch) ** 2), {}
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(state["params"])
        p, o, m = opt.update(g, state["opt_state"], state["params"])
        return {"params": p, "opt_state": o}, {"loss": l, **m}

    def batch_fn(i):
        return jnp.asarray([0.0, 0.0]) + 0.01 * i

    fcfg = FaultConfig(ckpt_dir=str(tmp_path), ckpt_every=every,
                       max_restarts=max_restarts, async_checkpoint=False)
    inj = FaultInjector(fail_at)
    return step_fn, state, batch_fn, steps, fcfg, inj


def test_training_completes_and_checkpoints(tmp_path):
    step_fn, state, batch_fn, steps, fcfg, inj = _toy_problem(tmp_path)
    rep = run_training(step_fn, state, batch_fn, steps, fcfg)
    assert rep.steps_run == steps
    assert ckpt.latest_step(tmp_path) == steps


def test_recovers_from_injected_fault(tmp_path):
    step_fn, state, batch_fn, steps, fcfg, inj = _toy_problem(
        tmp_path, fail_at=(7,))
    rep = run_training(step_fn, state, batch_fn, steps, fcfg, injector=inj)
    assert rep.restarts == 1
    assert rep.steps_run >= steps - 5      # replayed steps re-counted
    assert ckpt.latest_step(tmp_path) == steps


def test_gives_up_after_max_restarts(tmp_path):
    step_fn, state, batch_fn, steps, fcfg, inj = _toy_problem(
        tmp_path, max_restarts=1)

    class AlwaysFail(FaultInjector):
        def maybe_fail(self, step):
            raise RuntimeError("persistent failure")

    with pytest.raises(RuntimeError):
        run_training(step_fn, state, batch_fn, steps, fcfg,
                     injector=AlwaysFail())


def test_resume_from_existing_checkpoint(tmp_path):
    step_fn, state, batch_fn, steps, fcfg, _ = _toy_problem(tmp_path, steps=10)
    run_training(step_fn, state, batch_fn, 10, fcfg)
    rep2 = run_training(step_fn, state, batch_fn, 15, fcfg)
    assert rep2.steps_run == 5             # resumed at step 10


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantize_bounds(rng):
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = int8_quantize(x)
    err = jnp.abs(int8_dequantize(q, s) - x)
    assert float(jnp.max(err)) <= float(s) / 2 + 1e-6


def test_topk_keeps_largest(rng):
    x = jnp.asarray(rng.standard_normal(100), jnp.float32)
    y = topk_mask(x, 0.1)
    nz = int(jnp.sum(y != 0))
    assert nz == 10
    kept = np.abs(np.asarray(x))[np.asarray(y) != 0].min()
    dropped = np.abs(np.asarray(x))[np.asarray(y) == 0].max()
    assert kept >= dropped


def test_ef_accumulates_to_exact_sum(rng):
    """Error feedback: sum over steps of compressed psum == sum of true
    gradients (within quantization of the final residual)."""
    if len(jax.devices()) < 1:
        pytest.skip("needs a device")
    from jax.sharding import PartitionSpec as P
    from repro.sharding.api import auto_mesh
    mesh = auto_mesh((1,), ("pod",))
    g_seq = [jnp.asarray(rng.standard_normal(64), jnp.float32) * 0.01
             for _ in range(20)]
    ef = {"g": jnp.zeros(64)}
    total_true = jnp.zeros(64)
    total_comp = jnp.zeros(64)

    def step(g, e):
        return ef_compressed_psum({"g": g}, e, "pod", "int8")

    smapped = jax.shard_map(step, mesh=mesh, in_specs=(P(), {"g": P()}),
                            out_specs=({"g": P()}, {"g": P()}),
                            check_vma=False)
    jstep = jax.jit(smapped)
    for g in g_seq:
        red, ef = jstep(g, ef)
        total_true += g
        total_comp += red["g"]
    resid = float(jnp.max(jnp.abs(total_true - (total_comp + ef["g"]))))
    assert resid < 1e-5


# ---------------------------------------------------------------------------
# Token pipeline
# ---------------------------------------------------------------------------

def test_bigram_stream_learnable_structure():
    s = BigramStream(64, seed=0)
    r = np.random.default_rng(0)
    toks = s.sample(r, 8, 100)
    assert toks.shape == (8, 101)
    assert toks.min() >= 0 and toks.max() < 64
    # chain property: most transitions follow the successor table
    hits = 0
    for b in range(8):
        for t in range(100):
            hits += int(toks[b, t + 1] in s.succ[toks[b, t]])
    assert hits / 800 > 0.7


def test_token_pipeline_prefetch():
    p = TokenPipeline(vocab=32, batch=2, seq=8, prefetch=2)
    try:
        b1 = next(p)
        b2 = next(p)
        assert b1["tokens"].shape == (2, 8)
        assert not np.array_equal(b1["tokens"], b2["tokens"])
    finally:
        p.close()
